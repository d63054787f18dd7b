"""Compile-only guards for the TPU: the Pallas kernels and the fused refresh
program, built for a described (not attached) v5e chip.

Interpret mode on the CPU checks what a kernel computes, not whether the
chip's compiler accepts its block layout; these tests check the latter at
the main path's widths, and that ``tpu_custom_call`` (the Mosaic kernel)
is in what was compiled. Nothing runs, so they say nothing about results
or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import autotune, ops
from repro.kernels.batched_loglik import batched_logit_delta
from repro.kernels.fused_ce import batched_fused_ce, fused_ce
from repro.kernels.gaussian_ar1 import batched_gaussian_ar1_delta
from repro.kernels.logit_loglik import logit_delta


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A described-chip compile is written to the persistent cache but cannot
    # be read back without the chip: keep the cache off around these tests.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the ops wrappers' kernels for Mosaic instead of the interpreter
    (the dispatch asks the backend, which is the CPU here)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setenv(autotune.ENV_VAR, "0")


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# Main-path widths: BayesLR rounds (K=8 chains, m=500, D=50), the paper's
# full-data BayesLR pass (N=12,214, D=50), the stochvol AR(1) rounds
# (K=8, m=512), and the LM likelihood (K=4, T=256, D=256, V=8192).
def _kernel_args(family, sds, dt):
    f32 = jnp.float32
    if family == "batched_loglik":
        return (sds((8, 500, 50), dt), sds((8, 500), f32), sds((8, 50), dt), sds((8, 50), dt))
    if family == "logit_delta":
        return (sds((12214, 50), dt), sds((12214,), f32), sds((50,), dt), sds((50,), dt))
    if family == "gaussian_ar1":
        return (sds((8, 512), dt), sds((8, 512), dt)) + (sds((8,), f32),) * 4
    if family == "fused_ce":
        return (sds((256, 256), dt), sds((8192, 256), dt), sds((256,), jnp.int32))
    return (sds((4, 256, 256), dt), sds((8192, 256), dt), sds((4, 256), jnp.int32))


KERNELS = {
    "batched_loglik": batched_logit_delta,
    "logit_delta": logit_delta,
    "gaussian_ar1": batched_gaussian_ar1_delta,
    "fused_ce": fused_ce,
    "batched_fused_ce": batched_fused_ce,
}


@pytest.mark.parametrize("family", sorted(KERNELS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
def test_kernel_compiles_for_v5e(family, dtype, one_chip):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(KERNELS[family], *_kernel_args(family, sds, dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("family", sorted(KERNELS))
def test_every_autotune_candidate_compiles_for_v5e(family, one_chip):
    """The tuner races only tiles the chip accepts, defaults included."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = _kernel_args(family, sds, jnp.float32)
    cands = autotune.CANDIDATES[family]
    assert autotune.DEFAULT_TILES[family] in cands
    for cand in cands:
        text = _compiled_text(lambda *a: KERNELS[family](*a, **cand), *args)
        assert "tpu_custom_call" in text, cand


def _bayeslr_ensemble(**kw):
    import dataclasses

    from repro.experiments import bayeslr

    ens = bayeslr.make_serving_workload(num_chains=8, n_train=12214, d=50,
                                        batch_size=500).ensemble
    return dataclasses.replace(ens, **kw)


def _refresh_shapes(ens, sharding, steps):
    """(state, step_keys) of a BayesLR refresh as shapes on ``sharding``."""
    state = ens.init(jnp.zeros(50))
    sk = ens.step_keys(jax.random.key(0), 0, steps)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        (state, sk),
    )


def test_fused_refresh_program_compiles_for_v5e(one_chip, mosaic):
    """The serving refresh at the paper's BayesLR shape carries the kernel."""
    ens = _bayeslr_ensemble()
    state, sk = _refresh_shapes(ens, one_chip, steps=4)
    text = ens.lower(state, 4, step_keys=sk).compile().as_text()
    assert "tpu_custom_call" in text


def test_2d_mesh_refresh_compiles_for_v5e(topo, mosaic):
    """Under the chains x data mesh the kernel runs per device (shard_map):
    a Mosaic call left to the partitioner is refused."""
    from repro.distributed.sharding import logical_axis_rules

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("chains", "data"))
    ens = _bayeslr_ensemble(shard={"chains": 2, "data": 2})
    (theta, sampler, _), sk = _refresh_shapes(ens, NamedSharding(mesh, P()), steps=4)
    with logical_axis_rules(mesh):  # what run() activates on a 4-device host
        lowered = ens._run_lockstep_fused_jit.lower(sk, theta, sampler, None,
                                                    num_steps=4)
    assert "tpu_custom_call" in lowered.compile().as_text()
