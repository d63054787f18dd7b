"""The ensemble pool: configuration, freshness policy, and persistence.

An :class:`EnsemblePool` owns one :class:`~repro.serving.resident.ResidentEnsemble`
per registered workload and stands between requests and residents:

  * every query goes through :meth:`EnsemblePool.query`, which first runs
    the :class:`FreshnessPolicy` — a snapshot is only served if it is young
    enough (``max_staleness_s``), deep enough (``min_draws``), and (when
    configured) mixed enough (``min_ess``, cross-chain Geyer ESS of the
    window); a stale snapshot triggers synchronous refreshes until the
    policy admits one;
  * :meth:`save` / :meth:`restore` persist every resident's sampler state,
    controller, step counter, and posterior window through
    :mod:`repro.checkpoint.manager`, so a restarted pool resumes *warm* —
    no re-burn-in, and its next refresh continues the same key schedule the
    original process was on;
  * :meth:`start` / :meth:`stop` run the residents' background refresh
    threads for always-on serving.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from ..checkpoint import manager as ckpt
from ..core.stats import multichain_ess, split_rhat
from .resident import QuerySpec, ResidentEnsemble, Snapshot
from .workloads import ServingWorkload, build_serving_workload


@dataclasses.dataclass(frozen=True)
class FreshnessPolicy:
    """When is a snapshot servable?

    ``max_staleness_s``: newest draw must be younger than this;
    ``min_draws``: the window must hold at least this many cross-chain
    draws (K × window depth);
    ``min_ess``: optional floor on the window's total effective sample
    size, computed on a scalar functional of the draws (the first
    component of the first leaf);
    ``max_rhat``: optional online-convergence gate — the rolling window's
    cross-chain split-R̂ (:func:`repro.core.stats.split_rhat` on the same
    scalar functional) must sit at or below this before the snapshot
    serves. A window too short to split (fewer than 4 draws per chain)
    counts as stale, so the gate forces refreshes until the resident has
    both depth and mixing.

    Staleness is measured against the last *state change*, not only the
    last draw-refresh: a streaming data append
    (:meth:`ResidentEnsemble.append`) marks the window infinitely stale, so
    the ``max_staleness_s`` gate never serves a pre-append posterior as
    fresh no matter how recently it was refreshed.
    """

    max_staleness_s: float = 30.0
    min_draws: int = 64
    min_ess: float | None = None
    max_rhat: float | None = None

    def stale_reason(self, snap: Snapshot) -> str | None:
        """None if servable, else a human-readable refusal."""
        if snap.draws is None:
            return "no draws yet"
        if snap.num_draws < self.min_draws:
            return f"only {snap.num_draws}/{self.min_draws} draws"
        if snap.staleness_s > self.max_staleness_s:
            return f"stale by {snap.staleness_s:.3f}s > {self.max_staleness_s}s"
        if self.min_ess is not None:
            ess = snapshot_ess(snap)
            if ess < self.min_ess:
                return f"window ESS {ess:.1f} < {self.min_ess}"
        if self.max_rhat is not None:
            rhat = snapshot_rhat(snap)
            if rhat is None:
                return "window too short for split-R-hat (need >= 4 draws/chain)"
            if not rhat <= self.max_rhat:  # NaN R-hat must read as stale
                return f"window R-hat {rhat:.4f} > {self.max_rhat}"
        return None

    def is_fresh(self, snap: Snapshot) -> bool:
        return self.stale_reason(snap) is None


def snapshot_ess(snap: Snapshot) -> float:
    """Total cross-chain ESS of a scalar trace of the window draws."""
    leaf = np.asarray(jax.tree.leaves(snap.draws)[0], np.float64)
    k, w = leaf.shape[:2]
    if w < 4:
        return 0.0
    return multichain_ess(leaf.reshape(k, w, -1)[:, :, 0])


def snapshot_rhat(snap: Snapshot) -> float | None:
    """Rolling-window split-R̂ of the same scalar trace ``snapshot_ess``
    uses (the first component of the first draws leaf), or None when the
    window is too short to split into half-chains."""
    if snap.draws is None:
        return None
    leaf = np.asarray(jax.tree.leaves(snap.draws)[0], np.float64)
    k, w = leaf.shape[:2]
    if w < 4:
        return None
    return float(split_rhat(leaf.reshape(k, w, -1)[:, :, 0]))


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Pool-wide serving knobs (per-workload overrides go through
    ``add_workload(..., **build_kw)``)."""

    num_chains: int = 8
    refresh_steps: int = 32  # transitions per refresh block
    window: int = 64  # posterior draws retained per chain
    micro_batch: int = 64  # request rows per compiled evaluation
    max_batch: int = 16  # requests coalesced into one evaluation
    freshness: FreshnessPolicy = FreshnessPolicy()
    default_deadline_s: float = 1.0
    background_interval_s: float = 0.0  # pause between background refreshes
    max_refreshes_per_query: int = 64  # freshness-loop safety bound
    seed: int = 0


class EnsemblePool:
    """Named resident ensembles behind one freshness-enforcing query API."""

    def __init__(self, config: ServingConfig | None = None, tracer=None):
        self.config = config or ServingConfig()
        self._workloads: dict[str, ServingWorkload] = {}
        self._residents: dict[str, ResidentEnsemble] = {}
        self._tracer = tracer

    @property
    def tracer(self):
        """The :class:`repro.obs.trace.Tracer` every resident's refresh
        blocks report to (None: untraced). Setting it reaches every
        resident, present and future."""
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        self._tracer = tracer
        for resident in self._residents.values():
            resident.tracer = tracer

    # -- registration ------------------------------------------------------

    def add_workload(
        self, workload: str | ServingWorkload, *, key=None, **build_kw
    ) -> ResidentEnsemble:
        """Build (or adopt) a workload and give it a resident ensemble.

        ``key`` overrides the resident's base chain key (default
        ``jax.random.key(config.seed)``) — the hook the fleet layer uses to
        give each shard of one workload an independent chain trajectory
        over the same data.
        """
        cfg = self.config
        if isinstance(workload, str):
            build_kw.setdefault("num_chains", cfg.num_chains)
            build_kw.setdefault("seed", cfg.seed)
            workload = build_serving_workload(workload, **build_kw)
        name = workload.name
        if name in self._residents:
            raise ValueError(f"workload {name!r} already resident in this pool")
        resident = ResidentEnsemble(
            workload.ensemble,
            workload.theta0,
            key=jax.random.key(cfg.seed) if key is None else key,
            window=cfg.window,
            refresh_steps=cfg.refresh_steps,
            micro_batch=cfg.micro_batch,
            name=name,
            tracer=self._tracer,
        )
        self._workloads[name] = workload
        self._residents[name] = resident
        return resident

    def names(self) -> tuple[str, ...]:
        return tuple(sorted(self._residents))

    def resident(self, name: str) -> ResidentEnsemble:
        return self._residents[name]

    def workload(self, name: str) -> ServingWorkload:
        return self._workloads[name]

    def spec(self, name: str, query_class: str) -> QuerySpec:
        return self._workloads[name].query_specs[query_class]

    # -- freshness ---------------------------------------------------------

    def ensure_fresh(self, name: str) -> Snapshot:
        """Refresh ``name`` until its snapshot passes the freshness policy;
        returns the admitted snapshot."""
        return self._refresh_until_fresh(name, "sync")

    def _refresh_until_fresh(self, name: str, cause: str) -> Snapshot:
        resident = self._residents[name]
        policy = self.config.freshness
        snap = resident.snapshot()
        refreshes = 0
        while not policy.is_fresh(snap):
            if refreshes >= self.config.max_refreshes_per_query:
                raise RuntimeError(
                    f"freshness unreachable for {name!r} after {refreshes} "
                    f"refreshes: {policy.stale_reason(snap)}"
                )
            resident.refresh(cause=cause)
            refreshes += 1
            snap = resident.snapshot()
        return snap

    def warm(self) -> None:
        """Bring every resident to a servable snapshot (initial burn)."""
        for name in self.names():
            self._refresh_until_fresh(name, "warm")

    # -- streaming append --------------------------------------------------

    def append_observations(self, name: str, new_data) -> int:
        """Fold newly appended observations into ``name``'s running chains
        (see :meth:`ResidentEnsemble.append`). The resident's staleness
        clock resets to "never refreshed", so the next freshness-checked
        query refuses the pre-append window and refreshes against the grown
        posterior before serving. Returns the number of sections added."""
        return self._residents[name].append(new_data)

    # -- queries -----------------------------------------------------------

    def query(
        self,
        name: str,
        query_class: str,
        xs,
        *,
        snapshot: Snapshot | None = None,
        span_sink: list | None = None,
    ) -> tuple[np.ndarray, Snapshot]:
        """Freshness-checked posterior-functional evaluation.

        Returns ``(values, snapshot_used)``; pass an explicit ``snapshot``
        (e.g. pinned by the request queue for a whole batch) to skip the
        freshness round-trip. ``span_sink`` collects the evaluator's raw
        ``device_eval`` trace span when the caller is tracing.
        """
        spec = self.spec(name, query_class)
        if snapshot is None:
            snapshot = self.ensure_fresh(name)
        return self._residents[name].query(
            spec, xs, snapshot=snapshot, span_sink=span_sink
        )

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        for resident in self._residents.values():
            resident.start_background(self.config.background_interval_s)

    def stop(self) -> None:
        for resident in self._residents.values():
            resident.stop_background()

    # -- persistence -------------------------------------------------------

    def save(self, ckpt_dir: str, keep: int = 3) -> str:
        """Atomically persist every resident (state + posterior window)."""
        state = {
            "residents": {
                name: res.state_dict() for name, res in self._residents.items()
            }
        }
        step = max((r.steps_done for r in self._residents.values()), default=0)
        return ckpt.save(ckpt_dir, step, state, keep=keep)

    def restore(self, ckpt_dir: str, step: int | None = None) -> int:
        """Restore residents saved by :meth:`save` into this pool's
        (identically configured) residents. Returns the checkpoint step."""
        step_loaded, flat = ckpt.restore(ckpt_dir, step=step)
        for name, resident in self._residents.items():
            prefix = f"residents__{name}__"
            sub = {
                k[len(prefix):]: v for k, v in flat.items() if k.startswith(prefix)
            }
            if not sub:
                raise KeyError(
                    f"checkpoint {ckpt_dir} has no state for resident {name!r}"
                )
            resident.load_flat(sub)
        return step_loaded

    def slo_snapshot_report(self) -> dict:
        """Per-resident snapshot ages / depths (for dashboards and smoke)."""
        out = {}
        for name in self.names():
            snap = self._residents[name].snapshot()
            out[name] = {
                "staleness_s": snap.staleness_s,
                "num_draws": snap.num_draws,
                "steps_done": snap.steps_done,
                "fresh": self.config.freshness.is_fresh(snap),
            }
        return out
