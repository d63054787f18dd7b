"""Vectorized multi-chain execution: K independent MH chains in one program.

The paper's sublinear bound is *per transition*; aggregate throughput comes
from running many chains at once (the ensemble / parallel-chain pattern of
Angelino et al., *Patterns of Scalable Bayesian Inference*). ``ChainEnsemble``
lifts the single-chain kernels in this package over a leading chain axis:

  * ``jax.vmap`` over :func:`repro.core.subsampled_mh.subsampled_mh_step`
    (or the exact :func:`repro.core.mh.mh_step`) — batched PRNG keys,
    batched theta pytrees, batched Fisher–Yates sampler states — so K
    transitions compile to ONE jitted program and every mini-batch
    evaluation is a (K, m) block instead of K separate (m,) calls,
  * per-chain semantics are preserved exactly: chain k of the ensemble,
    seeded with key k, produces the same trajectory as a sequential
    :func:`repro.core.chain.run_chain` call with that key,
  * an optional ``shard_map`` fan-out over a chain mesh axis spreads the
    ensemble across devices; a 2-d ``shard=("chains", "data")`` mesh
    additionally shards each sequential-test round's (K, m) mini-batch over
    the data axis through the logical-axis rules of
    :mod:`repro.distributed.sharding` (the per-round deltas are computed on
    device slices, then re-replicated before the test statistics reduce, so
    sharded runs stay bit-for-bit). On one device both are skipped entirely.

Two stepping modes control how the K sequential tests share the vmapped row:

  ``lockstep``
    transitions advance in sync; within a transition the batched while_loop
    runs every round until the *slowest* chain's test stops, so one hard
    accept/reject decision stalls the whole row (its per-row cost is
    ``max_k rounds_k`` per transition).

  ``masked``
    the masked-continuation superstep: one while_loop over *rounds*, where a
    chain whose test finishes immediately commits its transition and begins
    the next proposal inside the same compiled loop — per-chain progress
    counters instead of lock-step rounds. Total row count drops from
    ``sum_t max_k rounds_{k,t}`` to ``max_k sum_t rounds_{k,t}``, which is
    what restores the amortized speedup at large K. With adaptation
    disabled the mode reproduces ``lockstep`` results bit for bit (the
    stepping order of every chain's draws/merges/splits is identical).

An optional :class:`repro.core.schedule.ScheduleConfig` attaches the
adaptive per-chain controller: each chain's trailing ``rounds`` /
``n_evaluated`` / acceptance statistics tune its ``batch_size`` (within a
compile-time bucket set) and ``epsilon`` between transitions, in either
stepping mode.

Downstream, :func:`repro.core.stats.split_rhat` /
:func:`repro.core.stats.ensemble_summary` consume the (K, T) outputs for
cross-chain convergence diagnostics; when the target carries a fused
``log_local_ensemble`` (attached by :mod:`repro.core.target_builder`, e.g.
:func:`repro.kernels.ops.batched_logit_delta`) and the dispatch selects
Pallas, BOTH stepping modes route each (K, m) round through it instead of
vmapping ``log_local`` — the masked superstep natively, the lock-step scan
via the batched-transition form of the same round loop.

The serving layer (:mod:`repro.serving`) keeps ensembles *resident*: the
:meth:`ChainEnsemble.step_keys` schedule (``fold_in(chain_key, t)``) makes
chunked ``run``/``run_timed(start_step=)`` calls resume one logical run bit
for bit, which is what lets a background refresh loop — and a checkpoint
restore — continue exactly the trajectory an offline ``run`` would produce.

Composite programs — the paper's ``(cycle (...))`` inference expressions —
run through ``transition=cycle([...])``: per-variable
:class:`repro.core.composite.SubsampledMHOp` kernels (each with its own
target/proposal/config, fused rounds when available) interleaved with
opaque vmapped :class:`repro.core.composite.SweepOp` sweeps (Gibbs scans,
particle Gibbs). That is how stochvol and jointdpm ride this engine; see
:mod:`repro.experiments.stochvol` / :mod:`repro.experiments.jointdpm`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import lc, logical_axis_rules
from .composite import CycleOp, SubsampledMHOp, SweepOp, init_cycle_samplers
from .mh import mh_step
from .schedule import ScheduleConfig, controller_init, controller_params, controller_update
from .sequential_test import test_round_decision
from .stats import Welford
from .subsampled_mh import (
    SubsampledMHConfig,
    SubsampledMHInfo,
    adaptive_max_rounds,
    make_kernel,
    propose_and_mu0,
)
from .samplers import make_bounded_draw, make_sampler
from .target import PartitionedTarget, Window

Params = Any


class EnsembleState(NamedTuple):
    """Per-chain carried state; every leaf has a leading (K,) chain axis.

    ``controller`` is ``None`` without a schedule, otherwise the batched
    :class:`repro.core.schedule.ControllerState` pytree."""

    theta: Params
    sampler_state: Any  # batched sampler pytree ("exact" kernel: dummy zeros)
    controller: Any = None

    @property
    def num_chains(self) -> int:
        return jax.tree.leaves(self.theta)[0].shape[0]


def _broadcast_chain_axis(tree: Params, num_chains: int) -> Params:
    """Tile every leaf with a leading chain axis (identical initial chains)."""

    def tile(leaf):
        leaf = jnp.asarray(leaf)
        return jnp.broadcast_to(leaf[None], (num_chains,) + leaf.shape)

    return jax.tree.map(tile, tree)


def _bselect(pred: jax.Array, on_true: Params, on_false: Params) -> Params:
    """Tree-select with a (K,) predicate broadcast over trailing leaf dims."""

    def sel(a, b):
        p = pred.reshape(pred.shape + (1,) * (a.ndim - pred.ndim))
        return jnp.where(p, a, b)

    return jax.tree.map(sel, on_true, on_false)


def _scatter_at(buf: jax.Array, pos: jax.Array, val: jax.Array, do: jax.Array) -> jax.Array:
    """Per-chain write ``buf[pos] = val where do`` (buf: (T, ...), scalars pos/do)."""
    cur = jax.lax.dynamic_index_in_dim(buf, pos, axis=0, keepdims=False)
    new = jnp.where(do, val, cur)
    return jax.lax.dynamic_update_index_in_dim(buf, new, pos, 0)


def _lc_chains(tree: Params) -> Params:
    """Constrain every (K, ...) leaf to the mesh chain axis (no-op without an
    active :func:`repro.distributed.sharding.logical_axis_rules` context)."""
    return jax.tree.map(
        lambda l: lc(l, ("ensemble_chains",) + (None,) * (l.ndim - 1)), tree
    )


def _lc_round(idx: jax.Array) -> jax.Array:
    """Shard a round's (K, m) index block chains x data."""
    return lc(idx, ("ensemble_chains", "subsample"))


def _lc_replicate_round(l: jax.Array) -> jax.Array:
    """Re-replicate a round's (K, m) deltas along m. The sharded gather +
    delta evaluation is elementwise per section, so each element's bits match
    the unsharded run; all-gathering *before* the Welford merge keeps the
    test-statistic reduction order identical too — the bit-for-bit contract
    of the 2-d mesh."""
    return lc(l, ("ensemble_chains", None))


def _takes_window(target: PartitionedTarget, sampler: str, m_max: int,
                  use_fused: bool) -> bool:
    """Does a round read its sections as a :class:`~repro.core.target.Window`?
    Only the ``stream`` sampler draws contiguous sections, and only the
    builder's family closures read a window: ``log_local_ensemble`` always,
    ``log_local`` where ``target.local_window`` says so."""
    return (sampler == "stream" and m_max <= target.num_sections
            and (use_fused or target.local_window))


def _eval_round(evaluate, idx: jax.Array, pos: jax.Array, active: jax.Array,
                m_max: int, n_total: int, window: bool) -> jax.Array:
    """One round's (K, m) deltas, ``evaluate`` applied to the drawn (K, m)
    index block or, when ``window``, to each chain's run [pos, pos + m_max)
    (``pos`` the stream sampler's pre-draw position). Decided once per round,
    outside any vmap (a vmapped cond runs both branches): the window when
    every active chain's lies inside the pool, where the draw's clamp is a
    no-op and the run holds exactly the drawn rows; else the index gather,
    in the round where a chain reaches the pool's end. Inactive lanes'
    deltas are discarded, so their runs may be clamped."""
    if not window:
        return evaluate(idx)
    fits = jnp.all((pos + m_max <= n_total) | ~active)
    start = lc(pos, ("ensemble_chains",))
    return jax.lax.cond(fits, lambda: evaluate(Window(start, m_max)),
                        lambda: evaluate(idx))


def _lockstep_gather_rounds(rounds, batch_eff, m_max: int, n_total: int):
    """``(gather, total)`` round-loop iterations of a lock-step block from its
    (K, T) infos: step t's loop runs its slowest chain's rounds, and
    iteration r took :func:`_eval_round`'s gather branch when a chain still
    active there (``rounds > r``) had a run past the pool's end (the stream
    position after r rounds is ``min(r * batch_eff, N)``)."""
    rounds, b = np.asarray(rounds), np.asarray(batch_eff)
    r = np.arange(int(rounds.max(initial=0)))[:, None, None]
    crosses = (r < rounds) & (r * b + m_max > n_total)
    return int(crosses.any(axis=1).sum()), int(rounds.max(axis=0, initial=0).sum())


def _masked_gather_rounds(rounds, batch_eff, m_max: int, n_total: int):
    """The same for a masked superstep block: superstep s is round s of every
    chain whose transitions are not all committed, each chain's transitions
    back to back, so a chain's within-transition round at s follows from the
    prefix sums of its ``rounds``."""
    rounds, b = np.asarray(rounds), np.asarray(batch_eff)
    totals = rounds.sum(axis=1)
    hit = np.zeros(int(totals.max(initial=0)), bool)
    for rk, bk, total in zip(rounds, b, totals):
        t = np.repeat(np.arange(rk.size), rk)  # transition of each round
        r = np.arange(total) - (np.cumsum(rk) - rk)[t]
        hit[:total] |= r * bk[t] + m_max > n_total
    return int(hit.sum()), hit.size


def _make_batched_transition(
    target: PartitionedTarget,
    proposal,
    config: SubsampledMHConfig,
    num_chains: int,
    use_fused: bool,
    *,
    adaptive: bool = False,
    batch_max: int | None = None,
    max_rounds: int,
):
    """One *batched* subsampled-MH transition for K chains: vmapped
    propose/reset, then a single while_loop over sequential-test rounds where
    each round evaluates one (K, m) block — through
    ``target.log_local_ensemble`` when ``use_fused`` (the fused lock-step
    route), through ``vmap(target.log_local)`` otherwise.

    Round-for-round this reproduces ``vmap(subsampled_mh_step)`` (the same
    key-splitting, draw, Welford-merge, and ``test_round_decision`` order;
    finished lanes keep their whole state, exactly as XLA's batched
    while_loop does) — it exists so the lock-step scan and composite cycles
    can route rounds through the fused kernels, which a vmapped scalar step
    cannot express.

    Returns ``transition(keys (K,), theta, sampler, epsilon (K,),
    batch_eff (K,), prop_scale=None) -> (theta', sampler', info)`` where the
    optional ``prop_scale`` is a (K,) per-chain proposal-sigma multiplier
    (the adaptive-proposal knob; ``None`` keeps the static proposal call).
    """
    _, reset_fn, draw_fn = make_sampler(config.sampler, target.num_sections)
    draw_bounded = make_bounded_draw(config.sampler) if adaptive else None
    m_max = batch_max if batch_max is not None else config.batch_size
    n_total = target.num_sections
    K = num_chains
    evaluate = target.log_local_ensemble if use_fused else jax.vmap(target.log_local)
    window = _takes_window(target, config.sampler, m_max, use_fused)

    def transition(keys, theta, sampler, epsilon, batch_eff, prop_scale=None):
        if prop_scale is None:
            th_p, mu0, log_u, ktest = jax.vmap(
                lambda k, t: propose_and_mu0(k, t, target, proposal)
            )(keys, theta)
        else:
            th_p, mu0, log_u, ktest = jax.vmap(
                lambda k, t, s: propose_and_mu0(k, t, target, proposal, s)
            )(keys, theta, prop_scale)
        init = (
            ktest,
            jax.vmap(reset_fn)(sampler),
            Welford(*(jnp.zeros((K,), jnp.float32) for _ in range(3))),
            jnp.zeros((K,), jnp.int32),  # rounds
            jnp.zeros((K,), bool),  # done
            jnp.zeros((K,), bool),  # decision
            jnp.ones((K,), jnp.float32),  # pvalue
        )

        def cond(c):
            return jnp.any(~c[4])

        def body(c):
            tk, smp, w, rounds, done, decision, pval = c
            active = ~done
            pairs = jax.vmap(jax.random.split)(tk)
            tkey, sub = pairs[:, 0], pairs[:, 1]
            with jax.named_scope("draw"):
                if adaptive:
                    smp2, idx, valid = jax.vmap(
                        lambda k, s, m: draw_bounded(k, s, m_max, m)
                    )(sub, smp, batch_eff)
                else:
                    smp2, idx, valid = jax.vmap(
                        lambda k, s: draw_fn(k, s, m_max)
                    )(sub, smp)
            idx = _lc_round(idx)
            with jax.named_scope("delta"):
                l = _eval_round(functools.partial(evaluate, theta, th_p), idx,
                                smp.pos, active, m_max, n_total, window)
            l = _lc_replicate_round(l)
            with jax.named_scope("seq_test"):
                w2 = jax.vmap(Welford.merge_batch)(w, l, valid)
                dec, pv, test_ok, exhausted = jax.vmap(
                    lambda w_, m_, e: test_round_decision(w_, m_, n_total, e)
                )(w2, mu0, epsilon)
            rounds2 = rounds + 1
            fin = test_ok | exhausted | (rounds2 >= max_rounds)
            return (
                jnp.where(active, tkey, tk),
                _bselect(active, smp2, smp),
                _bselect(active, w2, w),
                jnp.where(active, rounds2, rounds),
                done | fin,
                jnp.where(active, dec, decision),
                jnp.where(active, pv, pval),
            )

        _, sampler2, w, rounds, _, decision, pval = jax.lax.while_loop(cond, body, init)
        theta_new = _bselect(decision, th_p, theta)
        info = SubsampledMHInfo(
            accepted=decision,
            n_evaluated=w.count.astype(jnp.int32),
            rounds=rounds,
            mu_hat=w.mean,
            mu0=mu0,
            pvalue=pval,
            log_u=log_u,
            epsilon=jnp.asarray(epsilon, jnp.float32),
            batch_eff=jnp.asarray(batch_eff, jnp.int32),
        )
        return theta_new, sampler2, info

    return transition


class _MaskedCarry(NamedTuple):
    """Superstep state of the masked-continuation loop (all leaves (K, ...))."""

    test_key: jax.Array  # per-chain sequential-test key
    theta: Params  # current sample
    theta_prop: Params  # proposal under test
    log_u: jax.Array
    mu0: jax.Array
    welford: Welford
    sampler: Any
    controller: Any
    epsilon: jax.Array  # knobs frozen at each transition's start
    batch_eff: jax.Array
    steps_done: jax.Array  # i32: transitions committed per chain
    rounds: jax.Array  # i32: rounds inside the current transition
    fresh: jax.Array  # bool: chain must start a new proposal this superstep
    samples: Params  # (K, T, ...) output buffers
    infos: SubsampledMHInfo  # (K, T) leaves
    supersteps: jax.Array  # scalar i32 safety counter


@dataclasses.dataclass(frozen=True)
class ChainEnsemble:
    """K independent MH chains advanced inside one jitted program.

    Usage::

        ens = ChainEnsemble(target, RandomWalk(0.05), num_chains=16)
        state = ens.init(theta0)                      # broadcast K chains
        state, samples, infos = ens.run(key, state, num_steps=1000)
        # samples: (K, num_steps, ...); infos leaves: (K, num_steps)

    ``run`` splits ``key`` into one key per chain and, per chain, into one
    key per step exactly like :func:`repro.core.chain.run_chain` does — so
    passing per-chain keys (a ``(K,)`` key array) reproduces K sequential
    ``run_chain`` calls bit-for-bit on elementwise targets.

    ``stepping="masked"`` (subsampled kernel only) switches to the
    masked-continuation superstep — chains that finish their sequential test
    early begin their next transition inside the same compiled loop instead
    of waiting for the row's slowest test. ``schedule=ScheduleConfig(...)``
    attaches the per-chain adaptive controller (works in both modes).

    With multiple devices visible (and ``shard="auto"`` or ``True``), the
    lock-step vmapped step is wrapped in ``shard_map`` over a 1-d chain
    mesh, so each device advances ``K / n_devices`` chains with zero
    cross-device traffic. ``shard=("chains", "data")`` (or
    ``{"chains": c, "data": d}`` with explicit sizes) instead builds a 2-d
    mesh: chains spread over the first axis while each sequential-test
    round's (K, m) mini-batch — the gather plus the per-section delta
    evaluation, fused or vmapped — shards its m rows over the second, via
    the logical-axis rules in :mod:`repro.distributed.sharding`. The deltas
    are re-replicated before the test statistics reduce, so a 2-d-sharded
    run is bit-for-bit the unsharded run (regression-tested at 4 forced
    host devices); the 2-d form also covers the masked superstep.

    Doctest — four subsampled chains, then the masked + adaptive form::

        >>> import jax, jax.numpy as jnp
        >>> from repro.core import (ChainEnsemble, RandomWalk, ScheduleConfig,
        ...                         SubsampledMHConfig, from_iid_loglik)
        >>> x = 0.5 + jax.random.normal(jax.random.key(0), (300,))
        >>> target = from_iid_loglik(lambda th: -0.5 * th**2,
        ...                          lambda th, idx: -0.5 * (x[idx] - th) ** 2,
        ...                          None, 300)
        >>> cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05)
        >>> ens = ChainEnsemble(target, RandomWalk(0.1), num_chains=4, config=cfg)
        >>> state, samples, infos = ens.run(jax.random.key(1),
        ...                                 ens.init(jnp.zeros(())), 20)
        >>> samples.shape, infos.n_evaluated.shape
        ((4, 20), (4, 20))
        >>> fast = ChainEnsemble(target, RandomWalk(0.1), num_chains=4, config=cfg,
        ...                      stepping="masked", schedule=ScheduleConfig())
        >>> state, samples, infos = fast.run(jax.random.key(1),
        ...                                  fast.init(jnp.zeros(())), 20)
        >>> samples.shape, bool(jnp.all(infos.epsilon >= cfg.epsilon))
        ((4, 20), True)
    """

    target: PartitionedTarget | None = None
    proposal: Any = None
    num_chains: int = 1
    kernel: str = "subsampled"  # "subsampled" | "exact"
    config: SubsampledMHConfig | None = None
    chunk_size: int | None = None  # exact kernel: lax.map chunking
    collect: Callable[[Params], Any] | None = None
    # "auto" | True | False — shard_map over a 1-d chain mesh; or a 2-d
    # chains x data request: ("chains", "data") / {"chains": c, "data": d}
    shard: Any = "auto"
    chain_axis: str = "chains"
    data_axis: str = "data"
    stepping: str = "lockstep"  # "lockstep" | "masked" (subsampled only)
    schedule: ScheduleConfig | None = None  # adaptive per-chain controller
    fused_kernels: str = "auto"  # "auto" | "always" | "never" — (K, m) Pallas path
    transition: CycleOp | None = None  # composite cycle (replaces target+proposal)

    def __post_init__(self):
        if self.kernel not in ("subsampled", "exact"):
            raise ValueError(f"unknown kernel {self.kernel!r}")
        if self.stepping not in ("lockstep", "masked"):
            raise ValueError(f"unknown stepping {self.stepping!r}")
        if self.fused_kernels not in ("auto", "always", "never"):
            raise ValueError(f"unknown fused_kernels {self.fused_kernels!r}")
        if self.num_chains < 1:
            raise ValueError(f"num_chains must be >= 1, got {self.num_chains}")
        if self._shard_2d_request is not None:
            if self.transition is not None:
                raise ValueError(
                    "composite transitions run unsharded; the 2-d "
                    "shard=(chains, data) mesh supports single-kernel "
                    "ensembles only"
                )
            if self.kernel != "subsampled":
                raise ValueError(
                    "the 2-d shard=(chains, data) mesh requires the "
                    "subsampled kernel — only its sequential-test rounds "
                    "have a data axis to shard"
                )
        if self.transition is not None:
            if self.target is not None or self.proposal is not None:
                raise ValueError(
                    "pass either (target, proposal) or transition=cycle(...), not both"
                )
            if self.kernel != "subsampled" or self.config is not None or \
                    self.chunk_size is not None:
                raise ValueError(
                    "composite transitions take kernel/config per component "
                    "(SubsampledMHOp(..., config=)); the ensemble-level "
                    "kernel=, config=, and chunk_size= knobs do not apply"
                )
            if self.stepping != "lockstep":
                raise ValueError(
                    "composite transitions run on the lock-step scan; the masked "
                    "superstep supports single-kernel ensembles only"
                )
            if self.schedule is not None:
                raise ValueError(
                    "adaptive scheduling is not supported with composite "
                    "transitions yet (the controller assumes one target)"
                )
            if self.shard is True:
                raise ValueError(
                    "composite transitions run unsharded; use shard='auto' or False"
                )
            if self.fused_kernels == "always":
                names = self.transition.names
                missing = [names[i] for i, op in self.transition.mh_ops
                           if op.target.log_local_ensemble is None]
                if missing:
                    raise ValueError(
                        f"fused_kernels='always' but composite MH components "
                        f"{missing} carry no log_local_ensemble (build their "
                        "targets via repro.core.build_target)"
                    )
            return
        if self.target is None or self.proposal is None:
            raise ValueError("target and proposal are required without transition=")
        if self.kernel == "exact" and (self.stepping == "masked" or self.schedule):
            raise ValueError(
                "masked stepping / adaptive scheduling require the subsampled "
                "kernel (the exact kernel has no sequential test to overlap)"
            )
        if self.schedule is not None and self.schedule.adapt_proposal:
            import inspect

            try:
                params = inspect.signature(self.proposal).parameters
                takes_scale = len(params) >= 3 or any(
                    p.kind is inspect.Parameter.VAR_POSITIONAL
                    or p.kind is inspect.Parameter.VAR_KEYWORD
                    for p in params.values()
                )
            except (TypeError, ValueError):  # builtins etc: trust the caller
                takes_scale = True
            if not takes_scale:
                raise ValueError(
                    "schedule.adapt_proposal=True needs a proposal accepting "
                    "a third `scale` argument (e.g. repro.core.RandomWalk)"
                )
        if self.stepping == "masked" and self.shard is True:
            raise ValueError("masked stepping runs unsharded; use shard='auto' or False")
        if self.fused_kernels == "always" and self.kernel == "exact":
            raise ValueError(
                "fused_kernels='always' requires the subsampled kernel — only "
                "its sequential-test rounds route through log_local_ensemble"
            )
        if self.fused_kernels == "always" and self.target.log_local_ensemble is None:
            raise ValueError(
                "fused_kernels='always' but the target carries no "
                "log_local_ensemble (build it via repro.core.build_target)"
            )
        if self.fused_kernels == "always" and self.shard is True:
            raise ValueError(
                "fused_kernels='always' runs the (K, m) rounds unsharded; "
                "use shard='auto' or False"
            )

    # -- derived static config -------------------------------------------

    @functools.cached_property
    def _shard_2d_request(self):
        """Normalized 2-d mesh request: ``(chains_size | None, data_size |
        None)`` when ``shard`` asks for a chains x data mesh, else None."""
        s = self.shard
        if isinstance(s, (tuple, list)):
            if tuple(s) != (self.chain_axis, self.data_axis):
                raise ValueError(
                    f"tuple shard= must name the mesh axes "
                    f"({self.chain_axis!r}, {self.data_axis!r}), got {tuple(s)!r}"
                )
            return (None, None)
        if isinstance(s, dict):
            extra = set(s) - {self.chain_axis, self.data_axis}
            if extra:
                raise ValueError(
                    f"dict shard= keys must be a subset of "
                    f"{{{self.chain_axis!r}, {self.data_axis!r}}}, got extra {sorted(extra)}"
                )
            return (s.get(self.chain_axis), s.get(self.data_axis))
        if s not in ("auto", True, False):
            raise ValueError(
                f"shard must be 'auto', True, False, a "
                f"({self.chain_axis!r}, {self.data_axis!r}) tuple, or a dict "
                f"of axis sizes; got {s!r}"
            )
        return None

    @functools.cached_property
    def _mesh_2d(self):
        """The chains x data mesh for a 2-d ``shard=`` request (None on a
        single device — the unsharded program is identical there)."""
        req = self._shard_2d_request
        if req is None:
            return None
        devices = jax.devices()
        n = len(devices)
        if n <= 1:
            return None
        c, d = req
        if c is None and d is not None:
            if n % d:
                raise ValueError(f"data axis size {d} must divide device count {n}")
            c = n // d
        if c is not None:
            d = d if d is not None else n // c
            if c * d != n:
                raise ValueError(
                    f"mesh {self.chain_axis}={c} x {self.data_axis}={d} != "
                    f"device count {n}"
                )
        else:
            # Balanced default: the divisor of n nearest sqrt(n) that also
            # divides num_chains (c=1, a pure data mesh, always qualifies).
            cands = [k for k in range(1, n + 1)
                     if n % k == 0 and self.num_chains % k == 0]
            c = min(cands, key=lambda k: (abs(k - math.sqrt(n)), -k))
            d = n // c
        if self.num_chains % c:
            raise ValueError(
                f"num_chains ({self.num_chains}) must be divisible by the "
                f"{self.chain_axis!r} mesh axis size ({c})"
            )
        from jax.sharding import Mesh

        return Mesh(np.asarray(devices).reshape(c, d),
                    (self.chain_axis, self.data_axis))

    @property
    def _config(self) -> SubsampledMHConfig:
        return self.config or SubsampledMHConfig()

    @functools.cached_property
    def _buckets(self) -> tuple[int, ...]:
        if self.schedule is None:
            return (self._config.batch_size,)
        return self.schedule.buckets_for(self._config, self.target.num_sections)

    @functools.cached_property
    def _max_rounds(self) -> int:
        return adaptive_max_rounds(self._config, self.target.num_sections, self._buckets)

    def _fused_for(self, target: PartitionedTarget) -> bool:
        """Does the fused (K, m) route apply to ``target`` under this
        ensemble's ``fused_kernels`` setting? One decision for the masked
        superstep, the fused lock-step scan, and composite MH components —
        delegating the "auto" case to :func:`repro.kernels.ops.use_kernel`
        (TPU, or the ``REPRO_FUSED`` environment default)."""
        if self.fused_kernels == "never" or target.log_local_ensemble is None:
            return False
        from ..kernels import ops

        return ops.use_kernel(self.fused_kernels)

    def _use_fused(self) -> bool:
        return self.target is not None and self._fused_for(self.target)

    # -- state ------------------------------------------------------------

    def init(self, theta0: Params, *, batched: bool = False) -> EnsembleState:
        """Build the batched initial state.

        ``theta0`` is a single pytree broadcast to all chains, or (with
        ``batched=True``) a pytree whose leaves already carry a leading
        (num_chains,) axis — e.g. overdispersed starting points for R-hat.

        Example::

            >>> import jax.numpy as jnp
            >>> from repro.core import ChainEnsemble, RandomWalk, from_iid_loglik
            >>> t = from_iid_loglik(lambda th: -0.5 * th**2,
            ...                     lambda th, idx: jnp.zeros(idx.shape), None, 10)
            >>> ens = ChainEnsemble(t, RandomWalk(0.1), num_chains=3)
            >>> ens.init(jnp.zeros(2)).theta.shape
            (3, 2)
        """
        theta = theta0 if batched else _broadcast_chain_axis(theta0, self.num_chains)
        lead = jax.tree.leaves(theta)[0].shape[0]
        if lead != self.num_chains:
            raise ValueError(f"theta leading axis {lead} != num_chains {self.num_chains}")
        if self.transition is not None:
            sampler = _broadcast_chain_axis(init_cycle_samplers(self.transition),
                                            self.num_chains)
            return EnsembleState(theta, sampler, None)
        if self.kernel == "subsampled":
            state0, _, _ = make_sampler(self._config.sampler, self.target.num_sections)
            sampler = _broadcast_chain_axis(state0, self.num_chains)
        else:
            sampler = jnp.zeros((self.num_chains,), jnp.int32)
        ctrl = None
        if self.schedule is not None:
            ctrl = controller_init(
                self.schedule, self._config, self.target.num_sections, self.num_chains
            )
        return EnsembleState(theta, sampler, ctrl)

    # -- single-chain step with a uniform (key, theta, state) signature ---

    def _make_step(self):
        if self.kernel == "subsampled":
            scheduled = self.schedule is not None
            _, step = make_kernel(
                self.target, self.proposal, self._config, scheduled=scheduled,
                batch_max=max(self._buckets) if scheduled else None,
            )
            return step

        def exact_step(key, theta, state):
            theta, info = mh_step(key, theta, self.target, self.proposal, chunk_size=self.chunk_size)
            return theta, state, info

        return exact_step

    def _per_chain_keys(self, key: jax.Array) -> jax.Array:
        key = jnp.asarray(key)
        typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
        # Per-chain keys are a (K,) typed-key array or (K, 2) legacy uint32
        # array; a bare legacy key of shape (2,) must NOT be mistaken for two
        # per-chain keys when num_chains == 2.
        batched = (key.ndim == 1 and typed) or (key.ndim == 2 and not typed)
        if batched and key.shape[0] == self.num_chains:
            return key
        return jax.random.split(key, self.num_chains)

    @functools.cached_property
    def _run_jit(self):
        step = self._make_step()
        collect = self.collect or (lambda t: t)
        sched = self.schedule
        buckets = self._buckets
        max_rounds = self._max_rounds
        n_total = self.target.num_sections
        eps_floor = sched.epsilon_floor(self._config) if sched else 0.0
        adapt_prop = sched is not None and sched.adapt_proposal

        def one_chain(keys, theta0, sampler0, ctrl0):
            # ``keys``: this chain's (num_steps,) per-step key row.
            if sched is None:

                def body(carry, k):
                    theta, sstate, ctrl = carry
                    theta, sstate, info = step(k, theta, sstate)
                    return (theta, sstate, ctrl), (collect(theta), info)

            else:

                def body(carry, k):
                    theta, sstate, ctrl = carry
                    eps, meff = controller_params(ctrl, buckets)
                    theta, sstate, info = step(
                        k, theta, sstate, eps, meff, max_rounds,
                        prop_scale=ctrl.sigma_scale if adapt_prop else None,
                    )
                    ctrl = controller_update(ctrl, info, sched, buckets, n_total, eps_floor)
                    return (theta, sstate, ctrl), (collect(theta), info)

            (theta, sstate, ctrl), (samples, infos) = jax.lax.scan(
                body, (theta0, sampler0, ctrl0), keys
            )
            return theta, sstate, ctrl, samples, infos

        def run_all(step_keys, theta, sampler, ctrl, num_steps):
            del num_steps  # static; implied by step_keys' trailing axis
            fn = jax.vmap(one_chain)
            mesh = self._chain_mesh()
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                spec = P(self.chain_axis)
                fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec, spec),
                                   out_specs=(spec,) * 5, check_vma=False)
            return fn(step_keys, theta, sampler, ctrl)

        return jax.jit(run_all, static_argnames=("num_steps",))

    # -- batched-transition lock-step scan --------------------------------

    def _make_run_batched(self, use_fused: bool):
        """Lock-step scan whose sequential-test rounds are (K, m) blocks —
        through ``target.log_local_ensemble`` when ``use_fused`` (the
        fused-kernel route; only the block evaluation's float order differs,
        parity-tested against ``fused_kernels="never"``), through
        ``vmap(target.log_local)`` otherwise (round-for-round AND bit-for-bit
        the vmapped scan — the route the 2-d chains x data mesh runs on).
        Chain semantics match the vmapped scan round for round."""
        config = self._config
        sched = self.schedule
        buckets = self._buckets
        collect = self.collect or (lambda t: t)
        K = self.num_chains
        n_total = self.target.num_sections
        eps_floor = sched.epsilon_floor(config) if sched else 0.0
        transition = _make_batched_transition(
            self.target, self.proposal, config, K, use_fused,
            adaptive=sched is not None,
            batch_max=max(buckets) if sched else None,
            max_rounds=self._max_rounds,
        )
        adapt_prop = sched is not None and sched.adapt_proposal

        def run_all(step_keys, theta, sampler, ctrl, num_steps):
            del num_steps
            step_keys = jnp.swapaxes(step_keys, 0, 1)  # (num_steps, K)

            def body(carry, keys_t):
                theta, sampler, ctrl = carry
                theta = _lc_chains(theta)
                sampler = _lc_chains(sampler)
                if sched is None:
                    eps = jnp.full((K,), config.epsilon, jnp.float32)
                    meff = jnp.full((K,), config.batch_size, jnp.int32)
                else:
                    eps, meff = jax.vmap(lambda c: controller_params(c, buckets))(ctrl)
                theta, sampler, info = transition(
                    keys_t, theta, sampler, eps, meff,
                    ctrl.sigma_scale if adapt_prop else None,
                )
                if sched is not None:
                    ctrl = jax.vmap(
                        lambda c, i: controller_update(c, i, sched, buckets, n_total, eps_floor)
                    )(ctrl, info)
                return (theta, sampler, ctrl), (jax.vmap(collect)(theta), info)

            (theta, sampler, ctrl), (samples, infos) = jax.lax.scan(
                body, (theta, sampler, ctrl), step_keys
            )
            swap = lambda t: jax.tree.map(lambda l: jnp.swapaxes(l, 0, 1), t)
            return theta, sampler, ctrl, swap(samples), swap(infos)

        return jax.jit(run_all, static_argnames=("num_steps",))

    @functools.cached_property
    def _run_lockstep_fused_jit(self):
        return self._make_run_batched(True)

    @functools.cached_property
    def _run_lockstep_batched_jit(self):
        return self._make_run_batched(False)

    # -- composite cycle --------------------------------------------------

    @functools.cached_property
    def _run_composite_jit(self):
        """Lock-step scan over a composite cycle: per engine transition each
        component applies once, in order — batched subsampled-MH transitions
        (fused (K, m) rounds when dispatch selects them) interleaved with
        vmapped opaque sweeps. Key discipline matches
        :func:`repro.core.composite.run_cycle_sequential` per chain."""
        cyc = self.transition
        names = cyc.names
        K = self.num_chains
        collect = self.collect or (lambda t: t)
        n_ops = len(cyc.ops)
        comps = []
        for op in cyc.ops:
            if isinstance(op, SubsampledMHOp):
                trans = _make_batched_transition(
                    op.target, op.proposal, op.cfg, K,
                    self._fused_for(op.target), max_rounds=op.max_rounds,
                )
                comps.append(("mh", trans, op.cfg))
            else:
                comps.append(("sweep", op.fn, op.has_info, op.batched_fn))

        def run_all(step_keys, theta, samplers, ctrl, num_steps):
            del ctrl, num_steps  # composite cycles run unscheduled
            step_keys = jnp.swapaxes(step_keys, 0, 1)  # (num_steps, K)

            def body(carry, keys_t):
                theta, samplers = carry
                # single-component cycles consume the step key directly
                # (mirrors run_cycle_sequential: cycle([op]) == bare kernel)
                if n_ops > 1:
                    subkeys = jax.vmap(lambda k: jax.random.split(k, n_ops))(keys_t)
                else:
                    subkeys = keys_t[:, None]
                infos = {}
                new_s = list(samplers)
                for i, comp in enumerate(comps):
                    k_i = subkeys[:, i]
                    if comp[0] == "mh":
                        _, trans, cfg = comp
                        eps = jnp.full((K,), cfg.epsilon, jnp.float32)
                        meff = jnp.full((K,), cfg.batch_size, jnp.int32)
                        theta, new_s[i], info = trans(k_i, theta, samplers[i], eps, meff)
                        infos[names[i]] = info
                    else:
                        _, fn, has_info, batched_fn = comp
                        # a natively chain-batched sweep (fused pgibbs scan)
                        # replaces the opaque per-chain vmap when provided
                        if batched_fn is not None:
                            out = batched_fn(k_i, theta)
                        else:
                            out = jax.vmap(fn)(k_i, theta)
                        if has_info:
                            theta, infos[names[i]] = out
                        else:
                            theta = out
                return (theta, tuple(new_s)), (jax.vmap(collect)(theta), infos)

            (theta, samplers), (samples, infos) = jax.lax.scan(
                body, (theta, samplers), step_keys
            )
            swap = lambda t: jax.tree.map(lambda l: jnp.swapaxes(l, 0, 1), t)
            return theta, samplers, None, swap(samples), swap(infos)

        return jax.jit(run_all, static_argnames=("num_steps",))

    # -- masked-continuation superstep ------------------------------------

    @functools.cached_property
    def _run_masked_jit(self):
        target = self.target
        proposal = self.proposal
        config = self._config
        sched = self.schedule
        collect = self.collect or (lambda t: t)
        buckets = self._buckets
        m_max = max(buckets)
        max_rounds = self._max_rounds
        n_total = target.num_sections
        eps_floor = sched.epsilon_floor(config) if sched else 0.0
        _, reset_fn, draw_fn = make_sampler(config.sampler, n_total)
        draw_bounded = make_bounded_draw(config.sampler)
        adaptive = sched is not None
        adapt_prop = adaptive and sched.adapt_proposal
        use_fused = self._use_fused()
        evaluate = target.log_local_ensemble if use_fused else jax.vmap(target.log_local)
        window = _takes_window(target, config.sampler, m_max, use_fused)
        K = self.num_chains

        def knobs(ctrl):
            if not adaptive:
                return (jnp.full((K,), config.epsilon, jnp.float32),
                        jnp.full((K,), config.batch_size, jnp.int32))
            return jax.vmap(lambda c: controller_params(c, buckets))(ctrl)

        def run_masked(step_keys, theta, sampler, ctrl, num_steps):
            keys = step_keys[:, 0]  # placeholder only; replaced at first start
            eps0, meff0 = knobs(ctrl)
            zero = jnp.zeros((K,), jnp.int32)
            sample_sd = jax.eval_shape(jax.vmap(collect), theta)
            samples0 = jax.tree.map(
                lambda s: jnp.zeros((K, num_steps) + s.shape[1:], s.dtype), sample_sd
            )
            infos0 = SubsampledMHInfo(
                accepted=jnp.zeros((K, num_steps), bool),
                n_evaluated=jnp.zeros((K, num_steps), jnp.int32),
                rounds=jnp.zeros((K, num_steps), jnp.int32),
                mu_hat=jnp.zeros((K, num_steps), jnp.float32),
                mu0=jnp.zeros((K, num_steps), jnp.float32),
                pvalue=jnp.zeros((K, num_steps), jnp.float32),
                log_u=jnp.zeros((K, num_steps), jnp.float32),
                epsilon=jnp.zeros((K, num_steps), jnp.float32),
                batch_eff=jnp.zeros((K, num_steps), jnp.int32),
            )
            carry0 = _MaskedCarry(
                test_key=keys,  # placeholder; replaced at each chain's first start
                theta=theta,
                theta_prop=theta,
                log_u=jnp.zeros((K,), jnp.float32),
                mu0=jnp.zeros((K,), jnp.float32),
                welford=Welford(*(jnp.zeros((K,), jnp.float32) for _ in range(3))),
                sampler=sampler,
                controller=ctrl,
                epsilon=eps0,
                batch_eff=meff0,
                steps_done=zero,
                rounds=zero,
                fresh=jnp.ones((K,), bool),
                samples=samples0,
                infos=infos0,
                supersteps=jnp.zeros((), jnp.int32),
            )
            cap = jnp.int32(num_steps * max_rounds + num_steps + 1)

            def cond(c: _MaskedCarry):
                return jnp.any(c.steps_done < num_steps) & (c.supersteps < cap)

            def body(c: _MaskedCarry):
                active = c.steps_done < num_steps
                start = c.fresh & active
                pos = jnp.minimum(c.steps_done, num_steps - 1)

                # --- transition start: propose, reset test state (Alg.3 2-6).
                # Guarded by a scalar cond: mid-test supersteps (no chain
                # starting) skip the proposal / log_global / reset work
                # entirely instead of computing and discarding it.
                def start_block(_):
                    k_step = jax.vmap(lambda ks, i: ks[i])(step_keys, pos)
                    if adapt_prop:
                        th_p, mu0_n, log_u_n, ktest_n = jax.vmap(
                            lambda k, t, s: propose_and_mu0(k, t, target, proposal, s)
                        )(k_step, c.theta, c.controller.sigma_scale)
                    else:
                        th_p, mu0_n, log_u_n, ktest_n = jax.vmap(
                            lambda k, t: propose_and_mu0(k, t, target, proposal)
                        )(k_step, c.theta)
                    eps_n, meff_n = knobs(c.controller)
                    return (
                        jnp.where(start, ktest_n, c.test_key),
                        _bselect(start, th_p, c.theta_prop),
                        jnp.where(start, mu0_n, c.mu0),
                        jnp.where(start, log_u_n, c.log_u),
                        jnp.where(start, eps_n, c.epsilon),
                        jnp.where(start, meff_n, c.batch_eff),
                        _bselect(
                            start,
                            Welford(*(jnp.zeros((K,), jnp.float32) for _ in range(3))),
                            c.welford,
                        ),
                        _bselect(start, jax.vmap(reset_fn)(c.sampler), c.sampler),
                        jnp.where(start, 0, c.rounds),
                    )

                def no_start(_):
                    return (c.test_key, c.theta_prop, c.mu0, c.log_u, c.epsilon,
                            c.batch_eff, c.welford, c.sampler, c.rounds)

                (test_key, theta_prop, mu0, log_u, epsilon, batch_eff, welford,
                 sampler, rounds) = jax.lax.cond(jnp.any(start), start_block, no_start, None)

                # --- one sequential-test round for every active chain
                theta_cur = _lc_chains(c.theta)
                theta_prop = _lc_chains(theta_prop)
                pairs = jax.vmap(jax.random.split)(test_key)
                tkey, sub = pairs[:, 0], pairs[:, 1]
                with jax.named_scope("draw"):
                    if adaptive:
                        sampler2, idx, valid = jax.vmap(
                            lambda k, s, m: draw_bounded(k, s, m_max, m)
                        )(sub, sampler, batch_eff)
                    else:
                        sampler2, idx, valid = jax.vmap(
                            lambda k, s: draw_fn(k, s, m_max)
                        )(sub, sampler)
                idx = _lc_round(idx)
                with jax.named_scope("delta"):
                    l = _eval_round(
                        functools.partial(evaluate, theta_cur, theta_prop), idx,
                        sampler.pos, active, m_max, n_total, window,
                    )
                l = _lc_replicate_round(l)
                with jax.named_scope("seq_test"):
                    w2 = jax.vmap(Welford.merge_batch)(welford, l, valid)
                    decision, pval, test_ok, exhausted = jax.vmap(
                        lambda w, m, e: test_round_decision(w, m, n_total, e)
                    )(w2, mu0, epsilon)
                rounds2 = rounds + 1
                done = active & (test_ok | exhausted | (rounds2 >= max_rounds))

                # --- commit finished transitions (Alg.3 15-19)
                theta_new = _bselect(done & decision, theta_prop, c.theta)
                info_now = SubsampledMHInfo(
                    accepted=decision,
                    n_evaluated=w2.count.astype(jnp.int32),
                    rounds=rounds2,
                    mu_hat=w2.mean,
                    mu0=mu0,
                    pvalue=pval,
                    log_u=log_u,
                    epsilon=epsilon,
                    batch_eff=batch_eff,
                )
                scatter = jax.vmap(_scatter_at)
                samples = jax.tree.map(
                    lambda buf, val: scatter(buf, pos, val, done),
                    c.samples, jax.vmap(collect)(theta_new),
                )
                infos = jax.tree.map(
                    lambda buf, val: scatter(buf, pos, val, done), c.infos, info_now
                )
                ctrl = c.controller
                if adaptive:
                    ctrl2 = jax.vmap(
                        lambda cs, i: controller_update(cs, i, sched, buckets, n_total, eps_floor)
                    )(ctrl, info_now)
                    ctrl = _bselect(done, ctrl2, ctrl)

                return _MaskedCarry(
                    test_key=jnp.where(active, tkey, test_key),
                    theta=theta_new,
                    theta_prop=theta_prop,
                    log_u=log_u,
                    mu0=mu0,
                    welford=_bselect(active, w2, welford),
                    sampler=_bselect(active, sampler2, sampler),
                    controller=ctrl,
                    epsilon=epsilon,
                    batch_eff=batch_eff,
                    steps_done=c.steps_done + done.astype(jnp.int32),
                    rounds=jnp.where(active, rounds2, rounds),
                    fresh=jnp.where(active, done, c.fresh),
                    samples=samples,
                    infos=infos,
                    supersteps=c.supersteps + 1,
                )

            end = jax.lax.while_loop(cond, body, carry0)
            return end.theta, end.sampler, end.controller, end.samples, end.infos

        return jax.jit(run_masked, static_argnames=("num_steps",))

    def _chain_mesh(self):
        if self.shard is False or self.stepping == "masked" or self.transition is not None:
            return None
        if self._shard_2d_request is not None:
            return None  # 2-d requests route through the batched runners
        devices = jax.devices()
        if len(devices) <= 1:
            return None  # single device: the plain vmap path is identical
        if self.num_chains % len(devices) != 0:
            if self.shard is True:
                raise ValueError(
                    f"shard=True needs num_chains ({self.num_chains}) divisible "
                    f"by the device count ({len(devices)})"
                )
            return None
        from jax.sharding import Mesh

        return Mesh(np.asarray(devices), (self.chain_axis,))

    # -- drivers ----------------------------------------------------------

    @functools.cached_property
    def _split_keys_jit(self):
        """(K,) per-chain keys -> (K, num_steps) step keys, exactly the split
        the scanned runners historically performed internally."""
        return jax.jit(
            lambda keys, num_steps: jax.vmap(
                lambda k: jax.random.split(k, num_steps)
            )(keys),
            static_argnames=("num_steps",),
        )

    @functools.cached_property
    def _fold_keys_jit(self):
        return jax.jit(
            lambda keys, start, num_steps: jax.vmap(
                lambda k: jax.vmap(
                    lambda t: jax.random.fold_in(k, t)
                )(start + jnp.arange(num_steps, dtype=jnp.uint32))
            )(keys),
            static_argnames=("num_steps",),
        )

    def step_keys(self, key: jax.Array, start: int, num_steps: int) -> jax.Array:
        """The canonical *resumable* step-key schedule: step ``t`` of chain
        ``c`` gets ``fold_in(chain_key_c, t)``, independent of how the run is
        chunked. ``ens.run(None, state, n, step_keys=ens.step_keys(key, o, n))``
        advanced in any block sizes reproduces one offline
        ``ens.run(None, state0, total, step_keys=ens.step_keys(key, 0, total))``
        bit for bit — the contract :class:`repro.serving.ResidentEnsemble`
        and :meth:`run_timed`'s ``start_step=`` resumption are built on.
        (The default :meth:`run` schedule splits ``key`` per step instead and
        is *not* resumable across chunk boundaries.)
        """
        keys = self._per_chain_keys(key)
        return self._fold_keys_jit(keys, jnp.uint32(start), num_steps=num_steps)

    def _runner(self):
        """The jitted program :meth:`run` dispatches to."""
        if self.transition is not None:
            return self._run_composite_jit
        if self.stepping == "masked":
            return self._run_masked_jit
        if self._shard_2d_request is not None:
            # 2-d chains x data requests run the batched-transition scan (the
            # only lock-step form whose rounds expose a shardable data axis);
            # on a single device the same runner executes unsharded —
            # bit-for-bit the vmapped scan when unfused.
            return (self._run_lockstep_fused_jit if self._use_fused()
                    else self._run_lockstep_batched_jit)
        if self._lockstep_fused():
            return self._run_lockstep_fused_jit
        return self._run_jit

    def _lockstep_fused(self) -> bool:
        # The fused lock-step scan runs unsharded. An explicit "always" wins
        # over the chain mesh (shard=True + "always" is rejected at
        # construction); under "auto" with a mesh present, the vmapped scan
        # keeps the multi-device fan-out instead.
        return (self.kernel == "subsampled" and self._use_fused()
                and (self.fused_kernels == "always" or self._chain_mesh() is None))

    @functools.cached_property
    def _window_loops(self) -> tuple:
        """``(info_name, m_max, n_total, masked)`` of each round loop of
        :meth:`run` whose rounds read windows (:func:`_takes_window`);
        ``info_name`` is a composite component's, else None."""
        if self.transition is not None:
            names = self.transition.names
            return tuple(
                (names[i], op.cfg.batch_size, op.target.num_sections, False)
                for i, op in self.transition.mh_ops
                if _takes_window(op.target, op.cfg.sampler, op.cfg.batch_size,
                                 self._fused_for(op.target))
            )
        batched = (self.stepping == "masked" or self._shard_2d_request is not None
                   or self._lockstep_fused())
        m_max = max(self._buckets)
        if batched and _takes_window(self.target, self._config.sampler, m_max,
                                     self._use_fused()):
            return ((None, m_max, self.target.num_sections,
                     self.stepping == "masked"),)
        return ()

    def section_tags(self, infos) -> dict:
        """How a block's rounds read their sections, from its infos:
        ``sections`` is ``"window"`` when :meth:`run`'s rounds read each
        chain's stream run as one slice, else ``"gather"``; for windows,
        ``gather_rounds`` of all ``rounds`` (round-loop iterations) took the
        index gather instead, because a chain's run passed the pool's end."""
        if not self._window_loops:
            return {"sections": "gather"}
        gather = total = 0
        for name, m_max, n_total, masked in self._window_loops:
            info = infos if name is None else infos[name]
            count = _masked_gather_rounds if masked else _lockstep_gather_rounds
            g, t = count(info.rounds, info.batch_eff, m_max, n_total)
            gather, total = gather + g, total + t
        return {"sections": "window", "gather_rounds": gather, "rounds": total}

    def run(self, key: jax.Array | None, state: EnsembleState, num_steps: int,
            *, step_keys: jax.Array | None = None):
        """Advance every chain ``num_steps`` transitions in one XLA program.

        Returns ``(state, samples, infos)`` with ``samples`` leaves shaped
        (K, num_steps, ...) and ``infos`` leaves (K, num_steps). ``key`` may
        be one key (split per chain) or a (K,) per-chain key array.

        ``step_keys`` (a (K, num_steps) key array, e.g. from
        :meth:`step_keys`) bypasses the internal per-chain splitting — the
        hook for resumable serving runs; ``key`` is then ignored and may be
        ``None``.
        """
        if step_keys is None:
            keys = self._per_chain_keys(key)
            step_keys = self._split_keys_jit(keys, num_steps=num_steps)
        else:
            lead = jnp.asarray(step_keys).shape[:2] if hasattr(step_keys, "shape") else None
            if lead != (self.num_chains, num_steps):
                raise ValueError(
                    f"step_keys must be a ({self.num_chains}, {num_steps}) key "
                    f"array, got leading shape {lead}"
                )
        with self._mesh_rules():
            theta, sampler, ctrl, samples, infos = self._runner()(
                step_keys, state.theta, state.sampler_state, state.controller,
                num_steps=num_steps
            )
        return EnsembleState(theta, sampler, ctrl), samples, infos

    def lower(self, state: EnsembleState, num_steps: int, *, step_keys: jax.Array):
        """Lower the program ``run(None, state, num_steps, step_keys=...)``
        executes, without running it — ``.compile().as_text()`` shows what
        the device runs (e.g. whether a Pallas kernel is in it)."""
        with self._mesh_rules():
            return self._runner().lower(
                step_keys, state.theta, state.sampler_state, state.controller,
                num_steps=num_steps
            )

    def _mesh_rules(self):
        # Activate the logical-axis rules while tracing/running so the lc
        # constraints in the round loop (and in the kernel-family registry's
        # gathers and kernels) bind to the 2-d mesh.
        mesh2 = self._mesh_2d
        return logical_axis_rules(mesh2) if mesh2 is not None else contextlib.nullcontext()

    def run_timed(self, key: jax.Array, state: EnsembleState, num_steps: int,
                  block_every: int = 1, *, start_step: int = 0, on_block=None):
        """Host-chunked loop recording wall clock, the multi-chain analog of
        :func:`repro.core.chain.run_chain_timed`. Compile time is excluded.

        Steps run on the **resumable** :meth:`step_keys` schedule: global
        step ``start_step + i`` of chain ``c`` is keyed by
        ``fold_in(chain_key_c, start_step + i)``, so consecutive calls with
        advancing ``start_step`` (and the returned state) continue one
        logical run bit for bit — the incremental-refresh contract of
        :class:`repro.serving.ResidentEnsemble`. ``on_block(state, samples,
        infos, steps_done)`` (optional) is invoked after every block inside
        the timed window — the collect hook a serving loop uses to stream
        draws out while the next block runs.

        Returns (state, dict) with ``transitions_per_sec`` aggregated over
        chains — the number ``benchmarks/multichain_bench.py`` reports —
        plus ``next_step`` (pass it back as ``start_step`` to resume).

        Example::

            >>> import jax, jax.numpy as jnp
            >>> from repro.core import ChainEnsemble, RandomWalk, from_iid_loglik
            >>> x = jax.random.normal(jax.random.key(0), (50,))
            >>> t = from_iid_loglik(lambda th: -0.5 * th**2,
            ...                     lambda th, idx: -0.5 * (x[idx] - th) ** 2,
            ...                     None, 50)
            >>> ens = ChainEnsemble(t, RandomWalk(0.1), num_chains=2)
            >>> state, out = ens.run_timed(jax.random.key(1),
            ...                            ens.init(jnp.zeros(())), 4, block_every=2)
            >>> out["samples"].shape, out["wall"] > 0, out["next_step"]
            ((2, 4), True, 4)
        """
        import time

        # All step keys for this window, computed (and warmed) up front so
        # neither key generation nor a ragged final block compiles inside
        # the timed region (num_steps is a static jit argument).
        all_keys = self.step_keys(key, start_step, num_steps)
        jax.block_until_ready(all_keys)
        block_sizes = {min(block_every, num_steps)}
        if num_steps % block_every:
            block_sizes.add(num_steps % block_every)
        for n in block_sizes:
            warm, _, _ = self.run(None, state, n, step_keys=all_keys[:, :n])
            jax.block_until_ready(warm.theta)
        samples_blocks, infos_blocks = [], []
        t0 = time.perf_counter()
        done = 0
        while done < num_steps:
            n = min(block_every, num_steps - done)
            state, samples, infos = self.run(
                None, state, n, step_keys=all_keys[:, done:done + n]
            )
            jax.block_until_ready(state.theta)
            samples_blocks.append(samples)
            infos_blocks.append(infos)
            done += n
            if on_block is not None:
                on_block(state, samples, infos, start_step + done)
        wall = time.perf_counter() - t0
        cat = lambda xs: jax.tree.map(lambda *ls: np.concatenate([np.asarray(l) for l in ls], axis=1), *xs)
        return state, {
            "samples": cat(samples_blocks),
            "infos": cat(infos_blocks),
            "wall": wall,
            "transitions_per_sec": self.num_chains * num_steps / max(wall, 1e-12),
            "next_step": start_step + num_steps,
        }


def run_ensemble(
    key: jax.Array,
    theta0: Params,
    target: PartitionedTarget,
    proposal,
    num_chains: int,
    num_steps: int,
    kernel: str = "subsampled",
    config: SubsampledMHConfig | None = None,
    **kw,
):
    """One-shot convenience wrapper: init + run. Returns (state, samples, infos).

    Extra keyword arguments reach :class:`ChainEnsemble` — e.g.
    ``stepping="masked"``, ``schedule=ScheduleConfig()`` for the adaptive
    masked-continuation engine.

    Example::

        >>> import jax, jax.numpy as jnp
        >>> from repro.core import RandomWalk, from_iid_loglik, run_ensemble
        >>> x = jax.random.normal(jax.random.key(0), (100,))
        >>> t = from_iid_loglik(lambda th: -0.5 * th**2,
        ...                     lambda th, idx: -0.5 * (x[idx] - th) ** 2, None, 100)
        >>> _, samples, infos = run_ensemble(jax.random.key(1), jnp.zeros(()),
        ...                                  t, RandomWalk(0.1), num_chains=2,
        ...                                  num_steps=10)
        >>> samples.shape
        (2, 10)
    """
    ens = ChainEnsemble(target, proposal, num_chains, kernel=kernel, config=config, **kw)
    state = ens.init(theta0)
    return ens.run(key, state, num_steps)
