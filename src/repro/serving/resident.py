"""Resident posterior ensembles: warm sampler state behind a query API.

The paper's pitch is that sublinear per-transition cost makes posterior
inference cheap enough to sit inside an application loop. This module is
the serving half of that claim: a :class:`ResidentEnsemble` keeps a
:class:`repro.core.ensemble.ChainEnsemble` *alive* across requests —
compiled step functions, per-chain sampler states, and (when scheduled)
controller state all stay warm — and interleaves

  * **refresh**: advance every chain a block of transitions on the
    ensemble's resumable :meth:`~repro.core.ensemble.ChainEnsemble.step_keys`
    schedule, appending the collected draws to a rolling per-chain window.
    Chunked refreshes reproduce one offline ``run`` of the same ensemble
    bit for bit (regression-tested in ``tests/test_serving.py``);
  * **snapshot**: the current cross-chain posterior window plus
    :func:`repro.core.stats.ensemble_summary` diagnostics and a staleness
    clock — the unit the freshness policy in :mod:`repro.serving.pool`
    admits or refuses to serve;
  * **query**: evaluate a posterior functional (a :class:`QuerySpec`) over
    the snapshot draws — vmapped over chains × window draws in one jitted
    program, micro-batched over request rows so arbitrarily large request
    batches run at a fixed compiled shape.

Background refresh runs on a daemon thread (`start_background`), so
queries always see *some* recent snapshot instead of waiting on MCMC.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import _flatten_names
from ..core.ensemble import ChainEnsemble, EnsembleState
from ..core.stats import ensemble_summary
from ..obs.trace import program_span

Params = Any


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One posterior-functional request class.

    ``fn(theta_draw, xs) -> (B,)`` scores a *single* posterior draw on B
    request rows; the resident vmaps it over every draw in the snapshot and
    aggregates:

      * ``aggregate="mean"``: the posterior mean of ``fn`` per row — e.g.
        BayesLR predictive probabilities ``E[sigmoid(x·w)]``;
      * ``aggregate="quantile"``: per-row posterior quantiles, where
        ``xs[b]`` is the quantile level for row ``b`` — e.g. stochvol
        stationary-volatility quantiles (``fn`` then typically broadcasts a
        scalar per-draw statistic to ``xs.shape``).

    ``make_queries(key, rows) -> xs`` generates representative request
    inputs (used by the serve front-end, benches, and smoke tests).
    """

    fn: Callable[[Params, jax.Array], jax.Array]
    aggregate: str = "mean"  # "mean" | "quantile"
    make_queries: Callable[[jax.Array, int], np.ndarray] | None = None
    name: str = ""

    def __post_init__(self):
        if self.aggregate not in ("mean", "quantile"):
            raise ValueError(f"unknown aggregate {self.aggregate!r}")


class Snapshot(NamedTuple):
    """An immutable view of a resident ensemble's posterior window."""

    draws: Params  # pytree, leaves (K, W, ...) host arrays
    num_draws: int  # K * W
    steps_done: int  # transitions committed per chain since init/restore
    staleness_s: float  # age of the newest draw at snapshot time
    summary: dict  # ensemble_summary of the last refresh's infos
    created_at: float  # time.monotonic() at construction


#: What a stage opens when nothing traces it: a shared no-op.
_NO_SPAN = contextlib.nullcontext()


def _stage(tracer, stage: str, parent: dict | None, **tags):
    """The tracer's program span over a refresh stage, or the no-op."""
    if tracer is None:
        return _NO_SPAN
    return tracer.program_span(stage, parent, **tags)


def _summarize_infos(infos) -> dict:
    """ensemble_summary over plain or composite (dict-keyed) infos."""
    if infos is None:
        return {}
    if hasattr(infos, "accepted"):
        return ensemble_summary(infos)
    if isinstance(infos, dict):
        return {
            name: ensemble_summary(v)
            for name, v in infos.items()
            if hasattr(v, "accepted")
        }
    return {}


def _window_append(window, block, limit: int):
    """Append a (K, n, ...) block to the (K, W, ...) host window, keep last
    ``limit`` draws per chain."""
    block = jax.tree.map(np.asarray, block)
    if window is None:
        merged = block
    else:
        merged = jax.tree.map(
            lambda a, b: np.concatenate([a, b], axis=1), window, block
        )
    return jax.tree.map(lambda a: a[:, -limit:], merged)


class SnapshotEvaluator:
    """Micro-batched posterior-functional evaluation against snapshots.

    Owns the two caches the query path lives on: per-:class:`QuerySpec`
    jitted evaluators, and a per-snapshot-generation device copy of the
    flattened (S, ...) window so a batch of queries against one snapshot
    uploads the draws once. Rows are processed in fixed ``micro_batch``-row
    chunks (the last chunk padded), so the compiled evaluation shape never
    depends on the request batch — the property that makes queue batching
    result-transparent.

    Shared by :class:`ResidentEnsemble` (writer-side queries) and the
    fleet's read replicas (:mod:`repro.fleet.replica`), which answer from a
    delta-streamed copy of the same window.
    """

    def __init__(self, micro_batch: int = 64):
        if micro_batch < 1:
            raise ValueError(f"micro_batch must be >= 1, got {micro_batch}")
        self.micro_batch = int(micro_batch)
        self._eval_cache: dict[Any, Any] = {}
        self._flat_cache: tuple[Any, Any] | None = None

    def invalidate(self) -> None:
        """Drop the device-side window cache (call when the window is
        replaced out-of-band, e.g. on checkpoint restore or replica resync —
        a stale cache could otherwise collide on the generation key)."""
        self._flat_cache = None

    def _evaluator(self, spec: QuerySpec):
        # Both aggregates reduce over the draw axis on device: only (mb,)
        # per chunk crosses to the host instead of the (S, mb) per-draw
        # matrix — the matrix is memory-bound host work that would otherwise
        # dominate a replica's serve path (for quantiles it was a python
        # loop of np.quantile calls per row on top of the transfer).
        # Per-row results are unchanged by padding or chunking (the compiled
        # reduction shape is fixed at (S, mb), and both reductions are
        # column-independent), so the exact-equality batching contracts hold.
        # The named scope ``query_eval`` marks the evaluator's device ops in
        # the compiled program's metadata (and so in a profiler trace).
        cache_key = (spec.fn, spec.aggregate)
        fn = self._eval_cache.get(cache_key)
        if fn is None:
            if spec.aggregate == "mean":

                def _mean(draws, xs):
                    with jax.named_scope("query_eval"):
                        return jax.vmap(spec.fn, in_axes=(0, None))(
                            draws, xs
                        ).mean(axis=0)

                fn = jax.jit(_mean)
            else:  # quantile: xs[b] carries the level for row b up front

                def _quantile(draws, xs):
                    with jax.named_scope("query_eval"):
                        per_draw = jax.vmap(spec.fn, in_axes=(0, None))(
                            draws, xs
                        )  # (S, mb)
                        levels = jnp.clip(
                            xs.reshape(xs.shape[0], -1)[:, 0], 0.0, 1.0
                        ).astype(per_draw.dtype)
                        return jax.vmap(jnp.quantile, in_axes=(1, 0))(
                            per_draw, levels
                        )

                fn = jax.jit(_quantile)
            self._eval_cache[cache_key] = fn
        return fn

    def evaluate(self, spec: QuerySpec, snap: Snapshot, xs,
                 span_sink: list | None = None) -> np.ndarray:
        """Evaluate ``spec`` over every draw of ``snap`` on request rows
        ``xs``; returns the aggregated (B,) values.

        ``span_sink``, when given, receives one raw ``device_eval`` trace
        span (a plain dict — no trace_id yet; the caller's Tracer adopts
        it) covering the device-side work, and its children
        ``device_eval.upload`` (the window's device copy, when this
        snapshot is not cached yet) and ``device_eval.run`` (every
        micro-batched evaluator call). Each is also a ``repro.<stage>``
        profiler annotation (:func:`repro.obs.trace.program_span`). Kept
        dependency-free on purpose: replica worker processes ship these
        dicts back over the pipe."""
        xs = np.asarray(xs)
        if xs.ndim == 0:
            xs = xs[None]
        if xs.shape[0] == 0:
            return np.zeros((0,), np.float64)
        if span_sink is None:
            return self._evaluate(spec, snap, xs, None, None)
        with program_span(span_sink.append, "device_eval",
                          f"device_eval:{spec.name or spec.aggregate}",
                          rows=int(xs.shape[0]),
                          draws=int(snap.num_draws)) as span:
            return self._evaluate(spec, snap, xs, span_sink, span)

    def _evaluate(self, spec, snap, xs, sink, span) -> np.ndarray:
        def stage(name):
            if span is None:
                return _NO_SPAN
            return program_span(sink.append, name, parent=span)

        gen = (snap.steps_done, snap.num_draws)
        cached = self._flat_cache
        if cached is not None and cached[0] == gen:
            flat = cached[1]
        else:
            with stage("device_eval.upload"):
                flat = jax.tree.map(
                    lambda a: jnp.asarray(a.reshape((-1,) + a.shape[2:])),
                    snap.draws,
                )  # (S, ...) with S = K * W
            self._flat_cache = (gen, flat)
        evaluator = self._evaluator(spec)
        b, mb = xs.shape[0], self.micro_batch
        vals = []
        with stage("device_eval.run"):
            for start in range(0, b, mb):
                chunk = xs[start:start + mb]
                pad = mb - chunk.shape[0]
                if pad:
                    chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, axis=0)])
                v = np.asarray(evaluator(flat, jnp.asarray(chunk)))  # (mb,)
                keep = slice(None, mb - pad) if pad else slice(None)
                vals.append(v[keep])
        return np.concatenate(vals, axis=0).astype(np.float64)


class ResidentEnsemble:
    """A warm :class:`~repro.core.ensemble.ChainEnsemble` serving queries.

    Thread-safe: refresh (foreground or background) and query/snapshot may
    interleave; state mutation happens under a lock and snapshots are
    immutable once taken.
    """

    def __init__(
        self,
        ensemble: ChainEnsemble,
        theta0: Params,
        *,
        key: jax.Array,
        window: int = 64,
        refresh_steps: int = 32,
        micro_batch: int = 64,
        name: str = "resident",
        batched_theta0: bool = False,
        tracer=None,
    ):
        if window < 1 or refresh_steps < 1 or micro_batch < 1:
            raise ValueError("window, refresh_steps, micro_batch must be >= 1")
        self.ensemble = ensemble
        self.name = name
        self.window = int(window)
        self.refresh_steps = int(refresh_steps)
        self.micro_batch = int(micro_batch)
        self._base_key = key
        self._state: EnsembleState = ensemble.init(theta0, batched=batched_theta0)
        self._steps_done = 0
        self._draws = None  # pytree of np arrays, leaves (K, W<=window, ...)
        self._last_infos = None
        self._last_refresh: float | None = None
        # _lock guards the committed state (snapshot/query reads, commits);
        # _refresh_lock serializes the *mutators* (refresh, load_flat) so the
        # long MCMC run happens outside _lock and never blocks snapshots.
        self._lock = threading.RLock()
        self._refresh_lock = threading.RLock()
        self._evaluator = SnapshotEvaluator(micro_batch)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # An obs.trace.Tracer, or None: when set, every refresh block emits
        # a "refresh" span with its stages as children (see refresh()).
        self.tracer = tracer

    # -- refresh -----------------------------------------------------------

    @property
    def steps_done(self) -> int:
        return self._steps_done

    @property
    def state(self) -> EnsembleState:
        return self._state

    def refresh(self, num_steps: int | None = None, *, cause: str = "call") -> int:
        """Advance every chain ``num_steps`` (default ``refresh_steps``)
        transitions and fold the collected draws into the window.

        Runs on the resumable step-key schedule, so any sequence of refresh
        calls equals one offline ``ensemble.run`` over the same total steps
        (same base key) bit for bit.

        With a tracer attached the block is a ``refresh`` span tagged
        ``cause`` (``background``, ``sync``, ``warm`` or ``call``) whose
        children time its host stages: ``refresh.keys`` (step keys),
        ``refresh.dispatch`` (the run's dispatch), ``refresh.wait`` (until
        the device is done), ``refresh.pull`` (draws and infos to the host)
        and ``refresh.commit``.
        """
        n = self.refresh_steps if num_steps is None else int(num_steps)
        if n < 1:
            raise ValueError(f"refresh needs num_steps >= 1, got {n}")
        tracer = self.tracer
        with self._refresh_lock, _stage(tracer, "refresh", None, cause=cause,
                                        workload=self.name, steps=n) as block:
            # Only mutators hold _refresh_lock, so these reads are stable;
            # the expensive run happens with _lock released and snapshots
            # keep serving the previous window meanwhile.
            with self._lock:
                state, steps_done = self._state, self._steps_done
            with _stage(tracer, "refresh.keys", block):
                sk = self.ensemble.step_keys(self._base_key, steps_done, n)
            with _stage(tracer, "refresh.dispatch", block):
                state, samples, infos = self.ensemble.run(
                    None, state, n, step_keys=sk
                )
            with _stage(tracer, "refresh.wait", block):
                jax.block_until_ready(state.theta)
            with _stage(tracer, "refresh.pull", block):
                draws = _window_append(self._draws, samples, self.window)
                last_infos = jax.tree.map(np.asarray, infos)
            with _stage(tracer, "refresh.commit", block), self._lock:
                self._draws = draws
                self._last_infos = last_infos
                self._state = state
                self._steps_done = steps_done + n
                self._last_refresh = time.monotonic()
        return n

    # -- streaming append --------------------------------------------------

    def append(self, new_data) -> int:
        """Fold newly appended observations into the *running* chains.

        The streaming append-only target mode: the ensemble's target is
        rebuilt on ``concat([old, new])`` via its
        :class:`~repro.core.target_builder.TargetSpec` recipe (identical to
        a from-scratch build on the concatenated pool — tested property),
        while ``theta`` and ``steps_done`` carry over, so the next
        :meth:`refresh` continues the *same* resumable step-key schedule
        against the grown posterior — no restart, no re-burn-in from
        ``theta0``. Returns the number of sections added.

        Sampler state and (when scheduled) controller state are shaped by
        ``num_sections``, so they are re-initialized for the grown pool
        (the controller re-adapts over the next refreshes). The pre-append
        window is kept servable but marked infinitely stale
        (``_last_refresh = None``): the freshness policy's
        ``max_staleness_s`` gate then refuses to serve pre-append
        posteriors as fresh until a refresh folds the new data in.

        An empty append is a bit-for-bit no-op: same target object, state,
        window, and staleness clock.
        """
        from ..core.target_builder import append_observations

        with self._refresh_lock:
            if self.ensemble.target is None:
                raise ValueError(
                    f"resident {self.name!r} runs a composite transition "
                    "with no single appendable target"
                )
            new_target = append_observations(self.ensemble.target, new_data)
            if new_target is self.ensemble.target:
                return 0
            added = new_target.num_sections - self.ensemble.target.num_sections
            new_ensemble = dataclasses.replace(self.ensemble, target=new_target)
            with self._lock:
                theta = self._state.theta
            fresh = new_ensemble.init(theta, batched=True)
            jax.block_until_ready(fresh.theta)
            with self._lock:
                self.ensemble = new_ensemble
                self._state = fresh
                self._last_refresh = None  # pre-append window is not fresh
        return int(added)

    # -- snapshots ---------------------------------------------------------

    def snapshot(self) -> Snapshot:
        """The current posterior window (empty draws before any refresh)."""
        with self._lock:
            # Clock read under the lock: a concurrent background refresh
            # advancing _last_refresh must not yield negative staleness.
            now = time.monotonic()
            draws = self._draws  # host arrays, replaced (never mutated) by refresh
            staleness = (
                float("inf") if self._last_refresh is None else now - self._last_refresh
            )
            num = 0
            if draws is not None:
                lead = jax.tree.leaves(draws)[0].shape
                num = int(lead[0] * lead[1])
            return Snapshot(
                draws=draws,
                num_draws=num,
                steps_done=self._steps_done,
                staleness_s=staleness,
                summary=_summarize_infos(self._last_infos),
                created_at=now,
            )

    # -- queries -----------------------------------------------------------

    def query(
        self,
        spec: QuerySpec,
        xs,
        *,
        snapshot: Snapshot | None = None,
        span_sink: list | None = None,
    ) -> tuple[np.ndarray, Snapshot]:
        """Evaluate ``spec`` on request rows ``xs`` against a snapshot.

        Returns ``(values (B,), snapshot_used)``; the evaluation itself is
        the shared :class:`SnapshotEvaluator` (fixed-shape micro-batching,
        per-snapshot device cache). ``span_sink`` collects the raw
        ``device_eval`` trace span when the caller is tracing.
        """
        snap = snapshot if snapshot is not None else self.snapshot()
        if snap.draws is None:
            raise RuntimeError(
                f"resident {self.name!r} has no draws yet; refresh() first "
                "(or serve through EnsemblePool, which enforces freshness)"
            )
        return self._evaluator.evaluate(spec, snap, xs, span_sink=span_sink), snap

    # -- background refresh ------------------------------------------------

    def start_background(self, interval_s: float = 0.0) -> None:
        """Refresh continuously (or every ``interval_s``) on a daemon thread."""
        with self._lock:
            if self._thread is not None:
                return
            self._stop.clear()

            def loop():
                while not self._stop.is_set():
                    self.refresh(cause="background")
                    if interval_s:
                        self._stop.wait(interval_s)

            self._thread = threading.Thread(
                target=loop, name=f"refresh-{self.name}", daemon=True
            )
            self._thread.start()

    def stop_background(self, timeout_s: float = 30.0) -> None:
        thread = self._thread
        if thread is None:
            return
        self._stop.set()
        thread.join(timeout=timeout_s)
        self._thread = None

    # -- persistence -------------------------------------------------------

    def state_dict(self) -> dict:
        """Host pytree for :mod:`repro.checkpoint.manager` (pure arrays)."""
        with self._lock:
            out = {
                "key_data": np.asarray(jax.random.key_data(self._base_key)),
                "steps_done": np.asarray(self._steps_done, np.int64),
                "theta": jax.tree.map(np.asarray, self._state.theta),
                "sampler": jax.tree.map(np.asarray, self._state.sampler_state),
            }
            if self._state.controller is not None:
                out["controller"] = jax.tree.map(np.asarray, self._state.controller)
            if self._draws is not None:
                out["draws"] = self._draws
            return out

    def load_flat(self, flat: dict) -> None:
        """Restore from the flattened-leaf dict a checkpoint ``restore``
        (without target) yields for this resident's subtree. Rebuilds the
        pytree structure from this resident's own (freshly-initialized)
        state, so only a pool with the same configuration can restore."""
        with self._refresh_lock, self._lock:
            # 0 placeholders keep key_data/steps_done as pytree *leaves*
            # (None would vanish from jax.tree.flatten and desync the names).
            core = {
                "key_data": 0,
                "steps_done": 0,
                "theta": self._state.theta,
                "sampler": self._state.sampler_state,
            }
            if self._state.controller is not None:
                core["controller"] = self._state.controller
            names = _flatten_names(core)
            missing = [n for n in names if n not in flat]
            if missing:
                raise KeyError(
                    f"checkpoint is missing leaves for resident "
                    f"{self.name!r}: {missing[:5]}"
                )
            leaves = [flat[n] for n in names]
            _, treedef = jax.tree.flatten(core)
            core = jax.tree.unflatten(treedef, leaves)
            self._base_key = jax.random.wrap_key_data(
                jnp.asarray(core["key_data"])
            )
            self._steps_done = int(core["steps_done"])
            def put_leaf(a, like):
                a = np.asarray(a)
                want = getattr(like, "shape", None)
                if want is not None and a.shape != tuple(want):
                    raise ValueError(
                        f"checkpoint leaf shape {a.shape} != resident shape "
                        f"{tuple(want)} for {self.name!r} — the pool must be "
                        "configured (num_chains, workload sizes, schedule) "
                        "exactly as when it was saved"
                    )
                return jnp.asarray(a, getattr(like, "dtype", None))

            put = lambda tree, like: jax.tree.map(put_leaf, tree, like)
            self._state = EnsembleState(
                put(core["theta"], self._state.theta),
                put(core["sampler"], self._state.sampler_state),
                None
                if self._state.controller is None
                else put(core["controller"], self._state.controller),
            )
            draw_keys = [k for k in flat if k == "draws" or k.startswith("draws__")]
            if draw_keys:
                tmpl = jax.eval_shape(
                    jax.vmap(self.ensemble.collect or (lambda t: t)),
                    self._state.theta,
                )
                dnames = _flatten_names({"draws": tmpl})
                leaves = [np.asarray(flat[n]) for n in dnames]
                _, dtreedef = jax.tree.flatten({"draws": tmpl})
                self._draws = jax.tree.unflatten(dtreedef, leaves)["draws"]
            self._last_infos = None
            self._last_refresh = None  # unknown age: freshness forces a refresh
            # The restored window replaces whatever was resident; a stale
            # device-side cache could otherwise collide on the
            # (steps_done, num_draws) generation key and serve old draws.
            self._evaluator.invalidate()
