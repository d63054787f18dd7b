"""Logical-axis sharding: rules with divisibility fallback.

JAX rejects uneven shardings (verified in the de-risk prototype), and the
assigned architectures have head/expert counts that don't divide the 16-way
model axis (gemma3: 8 q-heads, mixtral: 8 experts, xlstm: 4 heads). So each
parameter/activation dim carries a *logical* name and the mesh mapping is a
prioritized rule list; a rule is skipped when the dim isn't divisible by the
target mesh axes, falling through to the next rule (MaxText-style).

``lc(x, names)`` applies a sharding constraint inside jitted code when a mesh
context is active; it is a no-op on a single device so model code runs
unchanged in CPU tests.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Priority-ordered candidate mesh axes per logical axis name. The first
# candidate whose size divides the dim (and isn't already used by another dim
# of the same tensor) wins; otherwise the dim is replicated.
DEFAULT_RULES: dict[str, tuple[tuple[str, ...], ...]] = {
    "batch": (("pod", "data"), ("data",)),
    "vocab": (("model",),),
    "embed": (("data",),),  # FSDP-style weight sharding over the data axis
    "embed_tp": (("model",),),
    "mlp": (("model",),),
    "q_heads": (("model",),),
    "kv_heads": (("model",),),
    "heads_flat": (("model",),),
    "experts": (("model",),),
    "mamba_inner": (("model",),),
    "expert_mlp": (("model",),),
    "capacity": (("model",),),  # MoE buffer fallback when experts % model != 0
    "kv_seq": (("model", "data"), ("model",)),  # decode-cache sequence sharding
    "seq": (),  # sequence dim: replicated by default (SP is a perf knob)
    "layers": (),
    "conv": (),
    "state": (),
    # -- MCMC-ensemble axes (repro.core.ensemble 2-d chains x data meshes).
    # The (K,) chain axis spreads whole chains; "subsample" is the m axis of
    # a sequential-test round's (K, m) mini-batch, sharded over the data
    # axis so each device gathers+scores its slice of the drawn sections.
    # Both are no-ops on model-training meshes (no "chains" axis there) and
    # fall through to replicated when the dim isn't divisible.
    "ensemble_chains": (("chains",),),
    "subsample": (("data",),),
}


class _Ctx(threading.local):
    mesh: Mesh | None = None
    rules: dict[str, tuple[tuple[str, ...], ...]] | None = None


_CTX = _Ctx()


@contextlib.contextmanager
def logical_axis_rules(mesh: Mesh, rules: dict | None = None):
    """Activate a mesh + rule set; model code's ``lc`` calls start applying
    real sharding constraints."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    _CTX.rules = dict(DEFAULT_RULES, **(rules or {}))
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def _mesh_axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size


def resolve_spec(
    shape: Sequence[int], logical: Sequence[str | None], mesh: Mesh, rules: dict
) -> P:
    """Map logical axis names to a PartitionSpec honoring divisibility and
    one-mesh-axis-per-tensor uniqueness."""
    used: set[str] = set()
    parts: list = []
    for dim, name in zip(shape, logical):
        assigned = None
        if name is not None:
            for cand in rules.get(name, ()):
                cand_eff = tuple(a for a in cand if a in mesh.shape and a not in used)
                if not cand_eff:
                    continue
                if dim % _mesh_axis_size(mesh, cand_eff) == 0:
                    assigned = cand_eff if len(cand_eff) > 1 else cand_eff[0]
                    used.update(cand_eff)
                    break
        parts.append(assigned)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def lc(x: jax.Array, logical: Sequence[str | None]) -> jax.Array:
    """Logical sharding constraint; no-op without an active mesh context."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or len(mesh.devices.reshape(-1)) <= 1:
        return x
    spec = resolve_spec(x.shape, logical, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_local(fn, args: Sequence[jax.Array], in_logical: Sequence[Sequence[str | None]],
                out_shape: Sequence[int], out_logical: Sequence[str | None]) -> jax.Array:
    """``fn(*args)``, run on each device's shard when a mesh context is active.

    A Pallas TPU kernel is a Mosaic custom call, which the compiler does not
    partition: inside a program sharded through :func:`lc` constraints it is
    refused. Under an active mesh this wraps ``fn`` in ``jax.shard_map``,
    resolving every argument's and the result's spec from logical axis names
    exactly as :func:`lc` does, so the kernel sees its local block. A plain
    call otherwise.
    """
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or len(mesh.devices.reshape(-1)) <= 1:
        return fn(*args)
    in_specs = tuple(resolve_spec(a.shape, names, mesh, rules)
                     for a, names in zip(args, in_logical))
    out_spec = resolve_spec(out_shape, out_logical, mesh, rules)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
                         check_vma=False)(*args)


def named_sharding(mesh: Mesh, shape: Sequence[int], logical: Sequence[str | None],
                   rules: dict | None = None) -> NamedSharding:
    rules = dict(DEFAULT_RULES, **(rules or {}))
    return NamedSharding(mesh, resolve_spec(shape, logical, mesh, rules))


def tree_shardings(mesh: Mesh, specs: dict, rules: dict | None = None):
    """Map a {path: ParamSpec} dict to {path: NamedSharding}."""
    return {
        k: named_sharding(mesh, v.shape, v.logical, rules) for k, v in specs.items()
    }


def count_bytes(specs: dict) -> int:
    total = 0
    for v in specs.values():
        total += int(np.prod(v.shape)) * jax.dtypes.canonicalize_dtype(v.dtype).itemsize
    return total
