"""Target construction layer: one kernel-family registry for every workload.

The paper's central claim is that *one* implementation of edge-subsampled MH
serves all three applications (Sec. 4). This module is where that claim
lives at the tensor level: a target declares its local-likelihood *family*
— the shape of its per-section factor — and the builder attaches

  * ``log_local``          the (m,) pair-delta used by single chains,
  * ``log_local_ensemble`` the (K, m) multi-chain round, backed by the
                           matching fused kernel in :mod:`repro.kernels.ops`
                           (Pallas on TPU, interpret/ref twin elsewhere),
  * ``log_density``        prior + full local sum, for diagnostics,

so BayesLR, the joint DP mixture's expert weights, the stochastic-volatility
parameter moves, and PPL-compiled programs all ride the same construction
path instead of hand-wiring their kernel hookups.

Registered families:

  ``logit``         Logit(y | x·w) observation factors (BayesLR, DPM experts)
                    data = (x (N, D), y (N,)), params = w
  ``gaussian_ar1``  N(x_t | phi x_{t-1}, sigma^2) transition factors
                    (stochastic volatility), data = (x_t, x_prev) each (N,),
                    params = (phi, sigma2)
  ``ce``            softmax cross-entropy token factors (the LM likelihood),
                    data = (h (N, D), targets (N,)), params = table (V, D)

``data`` may also be a callable ``theta -> data`` for sections that are
functions of latent state (stochvol's transition factors depend on the
current particle-Gibbs paths ``theta["h"]``); it must only read leaves the
MH proposal does not move, since both sides of the delta share it. In the
ensemble forms every params leaf carries a leading (K,) chain axis and the
data pools may be shared ``(N, ...)`` or per-chain ``(K, N, ...)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..distributed.sharding import lc, shard_local
from .target import PartitionedTarget

Params = Any

_LOG2PI = 1.8378770664093453


def _gather(arr: jax.Array, idx: jax.Array, section_ndim: int) -> jax.Array:
    """Gather sections: shared pool (N, ...) with any idx shape, or per-chain
    pool (K, N, ...) with (K, m) idx. Every family's gather (and
    :func:`_gather_sharded`'s) runs under the named scope ``gather``, which
    marks its device ops in the compiled program's metadata."""
    with jax.named_scope("gather"):
        if arr.ndim == section_ndim + 1:
            return arr[idx]
        return jax.vmap(lambda a, i: a[i])(arr, idx)


def _gather_sharded(arr: jax.Array, idx: jax.Array, section_ndim: int) -> jax.Array:
    """Ensemble-round gather with the chains x data sharding constraint: the
    (K, m, ...) block is split over the mesh data axis (when a 2-d ensemble
    mesh is active — see :mod:`repro.distributed.sharding`; a no-op
    otherwise), so each device materializes and scores only its slice of the
    drawn sections."""
    out = _gather(arr, idx, section_ndim)
    logical = ("ensemble_chains", "subsample") + (None,) * (out.ndim - 2)
    return lc(out, logical)


def _shard_round_idx(idx: jax.Array) -> jax.Array:
    return lc(idx, ("ensemble_chains", "subsample"))


def _replicate_round(out: jax.Array) -> jax.Array:
    # Re-replicate the (K, m) deltas along m before they reach the Welford
    # reduction — keeps sharded and unsharded reduction order identical
    # (the bit-for-bit contract of the 2-d ensemble mesh).
    return lc(out, ("ensemble_chains", None))


@dataclasses.dataclass(frozen=True)
class TargetSpec:
    """The re-buildable recipe behind a builder-constructed target.

    Everything :func:`build_target` needs to assemble the target again:
    the family name, the section-pool arrays (axis 0 = sections), the
    prior, and ``prior_scale`` — the tempering exponent on the prior,
    ``p(theta)^(1/P)`` for a P-way subposterior partition (Scott et al.
    consensus Monte Carlo; "Patterns of Scalable Bayesian Inference"), so
    the product of the P subposteriors is the full posterior.

    A spec is only attached when ``data`` is concrete arrays (latent-
    dependent callable pools cannot be sliced/appended structurally).
    """

    family: str
    data: Any  # pytree of (N, ...) arrays, sections along axis 0
    num_sections: int
    prior_logpdf: Callable[[Params], jax.Array]
    params_fn: Callable[[Params], Any] | None = None
    prior_scale: float = 1.0


def spec_of(target: PartitionedTarget) -> TargetSpec:
    """The target's construction recipe, or a clear error for targets that
    were hand-wired (no family / callable data / explicit log_global)."""
    if target.spec is None:
        raise ValueError(
            "target carries no TargetSpec (hand-wired log_global/log_local, "
            "callable data, or family=None) — partitioning and streaming "
            "append need a build_target(...) construction with concrete "
            "data arrays and prior_logpdf"
        )
    return target.spec


def build_from_spec(spec: TargetSpec) -> PartitionedTarget:
    """Re-run the builder on a (possibly sliced/appended/tempered) spec."""
    return build_target(
        spec.family,
        spec.data,
        spec.num_sections,
        prior_logpdf=spec.prior_logpdf,
        params_fn=spec.params_fn,
        prior_scale=spec.prior_scale,
    )


def _section_count(data: Any) -> int:
    leaves = jax.tree.leaves(data)
    if not leaves:
        return 0
    counts = {int(leaf.shape[0]) for leaf in leaves}
    if len(counts) != 1:
        raise ValueError(
            f"data leaves disagree on the section axis: {sorted(counts)}"
        )
    return counts.pop()


def append_observations(target: PartitionedTarget, new_data: Any) -> PartitionedTarget:
    """A new target whose section pool is ``concat([old, new], axis=0)``.

    The streaming append-only primitive: scoring functions are rebuilt by
    the same builder path, so the result is *identical* to building the
    target on the concatenated data from scratch (regression-tested
    property). An empty append (zero new sections) returns ``target``
    itself — a bit-for-bit no-op.
    """
    spec = spec_of(target)
    n_new = _section_count(new_data)
    if n_new == 0:
        return target
    old_leaves = jax.tree.structure(spec.data)
    new_leaves = jax.tree.structure(new_data)
    if old_leaves != new_leaves:
        raise ValueError(
            f"appended data structure {new_leaves} != target data "
            f"structure {old_leaves}"
        )
    def cat(a, b):
        a, b = jnp.asarray(a), jnp.asarray(b)
        if a.shape[1:] != b.shape[1:]:
            raise ValueError(
                f"appended section shape {b.shape[1:]} != existing "
                f"{a.shape[1:]}"
            )
        return jnp.concatenate([a, b.astype(a.dtype)], axis=0)

    merged = jax.tree.map(cat, spec.data, new_data)
    return build_from_spec(
        dataclasses.replace(
            spec, data=merged, num_sections=spec.num_sections + n_new
        )
    )


@dataclasses.dataclass(frozen=True)
class KernelFamily:
    """A local-likelihood family: reference scoring + fused ensemble delta.

    ``loglik(data, params, idx) -> (m,)`` per-section log-likelihoods,
    ``delta(data, params, params_p, idx) -> (m,)`` the pair-delta a single
    chain's sequential-test round evaluates, and
    ``ensemble_delta(data, params, params_p, idx) -> (K, m)`` the multi-chain
    round routed through the :mod:`repro.kernels.ops` dispatch.
    """

    name: str
    loglik: Callable[[Any, Any, jax.Array], jax.Array]
    delta: Callable[[Any, Any, Any, jax.Array], jax.Array]
    ensemble_delta: Callable[[Any, Any, Any, jax.Array], jax.Array]


_FAMILIES: dict[str, KernelFamily] = {}


def register_family(family: KernelFamily) -> KernelFamily:
    """Add a family to the registry (overwrites an existing name)."""
    _FAMILIES[family.name] = family
    return family


def get_family(name: str) -> KernelFamily:
    if name not in _FAMILIES:
        raise KeyError(
            f"unknown kernel family {name!r}; registered: {sorted(_FAMILIES)}"
        )
    return _FAMILIES[name]


def registered_families() -> tuple[str, ...]:
    return tuple(sorted(_FAMILIES))


# ---------------------------------------------------------------------------
# Built-in families
# ---------------------------------------------------------------------------


def _logit_loglik(data, w, idx):
    from ..kernels import ref

    x, y = data
    return ref.logit_loglik(w, _gather(x, idx, 1), _gather(y, idx, 0))


def _logit_delta(data, w, w_p, idx):
    from ..kernels import ref

    x, y = data
    return ref.logit_delta_ref(_gather(x, idx, 1), _gather(y, idx, 0), w, w_p)


_CHAINS = ("ensemble_chains",)
_ROUND = ("ensemble_chains", "subsample")


def _local_round(fn, args, logical):
    """Evaluate a (K, m) round kernel on each device's (chains, subsample)
    block — a Pallas kernel does not partition itself under the 2-d mesh."""
    k, m = args[0].shape[:2]
    return shard_local(fn, args, logical, (k, m), _ROUND)


def _logit_ensemble_delta(data, w, w_p, idx):
    from ..kernels import ops

    x, y = data
    idx = _shard_round_idx(idx)
    args = (_gather_sharded(x, idx, 1), _gather_sharded(y, idx, 0), w, w_p)
    logical = (_ROUND + (None,), _ROUND, _CHAINS + (None,), _CHAINS + (None,))
    return _replicate_round(_local_round(ops.batched_logit_delta, args, logical))


def _ar1_loglik(data, params, idx):
    phi, s2 = params
    xt, xp = (_gather(a, idx, 0) for a in data)
    s2c = jnp.clip(s2, 1e-12, None)
    z2 = (xt - phi * xp) ** 2 / s2c
    return -0.5 * (z2 + jnp.log(s2c) + _LOG2PI)


def _ar1_delta(data, params, params_p, idx):
    from ..kernels import ref

    xt, xp = (_gather(a, idx, 0) for a in data)
    return ref.gaussian_ar1_delta_ref(xt, xp, *params, *params_p)


def _ar1_ensemble_delta(data, params, params_p, idx):
    from ..kernels import ops

    idx = _shard_round_idx(idx)
    xt, xp = (_gather_sharded(a, idx, 0) for a in data)
    args = (xt, xp, *params, *params_p)
    logical = (_ROUND, _ROUND) + (_CHAINS,) * 4
    return _replicate_round(
        _local_round(ops.batched_gaussian_ar1_delta, args, logical)
    )


def _ce_loglik(data, table, idx):
    from ..kernels import ops

    h, targets = data
    return ops.fused_ce(_gather(h, idx, 1), table, _gather(targets, idx, 0))


def _ce_delta(data, table, table_p, idx):
    return _ce_loglik(data, table_p, idx) - _ce_loglik(data, table, idx)


def _ce_ensemble_delta(data, table, table_p, idx):
    # Two kernel passes, not a pair-fused one: unlike the logit pair (one
    # matmul against a stacked (D, 2) weight pair), the CE sides score
    # against two *different* vocab tables, so both table streams are
    # irreducible — pair fusion would only share the (m, D) activation reads
    # and one launch, a second-order saving at V >> D. The gathers are hoisted
    # so they happen once for both sides.
    from ..kernels import ops

    h, targets = data
    idx = _shard_round_idx(idx)
    hg, tg = _gather_sharded(h, idx, 1), _gather_sharded(targets, idx, 0)
    tab = (None, None) if table.ndim == 2 else _CHAINS + (None, None)
    return _replicate_round(_local_round(
        lambda hg, tg, t, t_p: ops.batched_fused_ce(hg, t_p, tg)
        - ops.batched_fused_ce(hg, t, tg),
        (hg, tg, table, table_p), (_ROUND + (None,), _ROUND, tab, tab),
    ))


def _gm_loglik(data, theta, idx):
    xg = _gather(data, idx, 1)  # (m, D) or (K, m, D)
    return -0.5 * jnp.sum((xg - theta[..., None, :]) ** 2, axis=-1)


def _gm_delta(data, theta, theta_p, idx):
    xg = _gather(data, idx, 1)
    return 0.5 * (
        jnp.sum((xg - theta[..., None, :]) ** 2, axis=-1)
        - jnp.sum((xg - theta_p[..., None, :]) ** 2, axis=-1)
    )


def _gm_ensemble_delta(data, theta, theta_p, idx):
    idx = _shard_round_idx(idx)
    xg = _gather_sharded(data, idx, 1)  # (K, m, D)
    out = 0.5 * (
        jnp.sum((xg - theta[:, None, :]) ** 2, axis=-1)
        - jnp.sum((xg - theta_p[:, None, :]) ** 2, axis=-1)
    )
    return _replicate_round(out)


register_family(KernelFamily("logit", _logit_loglik, _logit_delta, _logit_ensemble_delta))
register_family(KernelFamily("gaussian_ar1", _ar1_loglik, _ar1_delta, _ar1_ensemble_delta))
register_family(KernelFamily("ce", _ce_loglik, _ce_delta, _ce_ensemble_delta))
# Unit-variance Gaussian mean model: data = x (N, D), params = theta (D,),
# per-section factor N(x_i | theta, I) up to the additive constant (only
# deltas and relative densities matter to MH and the diagnostics). This is
# the conjugate family the subposterior ground-truth harness runs on: prior
# N(0, I) gives the closed-form posterior N(n xbar / (n+1), I / (n+1)).
register_family(KernelFamily("gaussian_mean", _gm_loglik, _gm_delta, _gm_ensemble_delta))


# ---------------------------------------------------------------------------
# The builder
# ---------------------------------------------------------------------------


def build_target(
    family: str | None,
    data: Any = None,
    num_sections: int | None = None,
    *,
    prior_logpdf: Callable[[Params], jax.Array] | None = None,
    log_global: Callable[[Params, Params], jax.Array] | None = None,
    log_local: Callable[[Params, Params, jax.Array], jax.Array] | None = None,
    log_density: Callable[[Params], jax.Array] | None = None,
    params_fn: Callable[[Params], Any] | None = None,
    prior_scale: float = 1.0,
) -> PartitionedTarget:
    """Construct a :class:`~repro.core.target.PartitionedTarget` from a
    registered kernel family.

    ``data`` is the family's section pool (arrays, or ``theta -> arrays`` for
    latent-dependent sections); ``params_fn`` maps the chain's ``theta`` to
    the family's canonical parameters (default: identity). The global section
    comes from ``prior_logpdf`` (pairs are differenced) or an explicit
    ``log_global``. With ``family=None`` an explicit ``log_local`` is
    required and no ensemble evaluation is attached — the pass-through for
    targets whose local score matches no registered family.

    ``prior_scale`` tempers the prior to ``prior_scale * log p(theta)`` —
    the ``p(theta)^(1/P)`` subposterior construction, where each of P
    data-partition workers carries 1/P of the prior mass so the product of
    the P subposteriors is the full posterior (:mod:`repro.partition`).
    With ``prior_scale == 1.0`` (the default) the built closures are
    *exactly* the untempered ones — no wrapper — which is what keeps the
    P=1 fleet configuration bit-for-bit identical to the unpartitioned
    path.

    When ``data`` is concrete arrays and the prior is given, the target
    carries a :class:`TargetSpec` recipe (``target.spec``) so it can be
    re-built on a data slice (:func:`repro.partition.partition_target`) or
    on appended observations (:func:`append_observations`).

    Example — the BayesLR target in one call::

        >>> import jax, jax.numpy as jnp
        >>> from repro.core import build_target
        >>> x = jax.random.normal(jax.random.key(0), (100, 3))
        >>> y = jnp.where(jax.random.bernoulli(jax.random.key(1), 0.5, (100,)), 1.0, -1.0)
        >>> t = build_target("logit", (x, y), 100,
        ...                  prior_logpdf=lambda w: -5.0 * jnp.sum(w**2))
        >>> t.family, t.num_sections, t.log_local_ensemble is not None
        ('logit', 100, True)
        >>> w0, w1 = jnp.zeros(3), jnp.full((3,), 0.1)
        >>> t.log_local(w0, w1, jnp.arange(8, dtype=jnp.int32)).shape
        (8,)
    """
    if num_sections is None:
        raise ValueError("num_sections is required")
    user_prior = prior_logpdf
    if prior_logpdf is not None and prior_scale != 1.0:
        scale = float(prior_scale)
        base_prior = prior_logpdf
        prior_logpdf = lambda theta: scale * base_prior(theta)
    elif prior_scale != 1.0:
        raise ValueError("prior_scale tempering requires prior_logpdf")
    if log_global is None:
        if prior_logpdf is None:
            raise ValueError("pass prior_logpdf or an explicit log_global")

        def log_global(theta, theta_p):
            return prior_logpdf(theta_p) - prior_logpdf(theta)

    if family is None:
        if log_local is None:
            raise ValueError("family=None requires an explicit log_local")
        return PartitionedTarget(
            num_sections=num_sections,
            log_global=log_global,
            log_local=log_local,
            log_density=log_density,
        )

    fam = get_family(family)
    spec = None
    if not callable(data) and data is not None and user_prior is not None:
        # Recipe for partitioning / streaming append: the *untempered* prior
        # plus the exponent, so re-tempering composes instead of stacking.
        spec = TargetSpec(
            family=family,
            data=data,
            num_sections=num_sections,
            prior_logpdf=user_prior,
            params_fn=params_fn,
            prior_scale=float(prior_scale),
        )
    data_fn = data if callable(data) else (lambda theta: data)
    params_fn = params_fn or (lambda theta: theta)

    if log_local is None:

        def log_local(theta, theta_p, idx):
            return fam.delta(data_fn(theta), params_fn(theta), params_fn(theta_p), idx)

    def log_local_ensemble(theta, theta_p, idx):
        return fam.ensemble_delta(
            data_fn(theta), params_fn(theta), params_fn(theta_p), idx
        )

    if log_density is None and prior_logpdf is not None:

        def log_density(theta):
            idx = jnp.arange(num_sections, dtype=jnp.int32)
            local = fam.loglik(data_fn(theta), params_fn(theta), idx)
            return prior_logpdf(theta) + local.sum()

    return PartitionedTarget(
        num_sections=num_sections,
        log_global=log_global,
        log_local=log_local,
        log_density=log_density,
        log_local_ensemble=log_local_ensemble,
        family=fam.name,
        spec=spec,
    )
