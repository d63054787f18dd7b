"""Traffic kind ``open_loop``: posterior queries at a fixed Poisson rate
while the pool refreshes in the background.

``EnsemblePool.start()`` refreshes every resident continuously, as every
deployment of the pool does, and ``RequestQueue.start_worker()`` serves
the queries. The whole schedule (due times, request classes, rows) is
made from the seed with numpy before the window; the generator thread
only sleeps and submits. Each request is timed from its due time to its
result. The answers are compared afterwards with the reference over the
snapshot that served them, which a recording wrapper around the pool's
``ensure_fresh`` keeps. A request carries its snapshot's staleness, but
the host clock is coarse enough that two snapshots can share one, so a
request is matched to the snapshot with its staleness taken between its
submit and its result.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from .context import TRACE_SECONDS, RunContext, now
from .device import peak_bytes
from .loadgen import OpenLoop, poisson_schedule
from .stats import latency_from_due

#: How long past the window's close a request may still come back; one
#: that has not by then never came.
DRAIN_SECONDS = 60.0


def make_schedule(traffic: dict, seconds: float, n_rows: int,
                  rng: np.random.Generator):
    """Due offsets and ``(query_class, row_indices)`` payloads.

    Every seed gets the same multiset of request sizes (log-uniform over
    ``rows_min``..``rows_max``) and the same count of each class, in its
    own order; the rows themselves are drawn from the seed."""
    due = poisson_schedule(rng, traffic["rate_per_s"], seconds)
    n = due.size
    lo, hi = math.log(traffic["rows_min"]), math.log(traffic["rows_max"] + 1)
    sizes = np.floor(np.exp(lo + (np.arange(n) + 0.5) / n * (hi - lo))).astype(int)
    sizes = np.clip(sizes, traffic["rows_min"], traffic["rows_max"])
    rng.shuffle(sizes)
    classes = []
    for name, share in traffic["classes"].items():
        classes += [name] * int(round(share * n))
    classes = (classes + [next(iter(traffic["classes"]))] * n)[:n]
    rng.shuffle(classes)
    payloads = [(c, rng.integers(0, n_rows, size=int(k)))
                for c, k in zip(classes, sizes)]
    return due, payloads


def _snapshot_of(req, done_at: float, snapshots: dict):
    """The snapshot that served ``req``: its staleness, taken between the
    request's submit and its result; None where that is not one snapshot."""
    found = {snap.steps_done: snap for taken, snap in snapshots.get(req.staleness_s, ())
             if req.submitted_at <= taken <= done_at}
    return next(iter(found.values())) if len(found) == 1 else None


def run(ctx: RunContext) -> dict:
    from repro.obs.trace import Tracer
    from repro.serving import RequestQueue

    cfg, traffic = ctx.config, ctx.cell.traffic
    model = ctx.cell.model
    data = model.make_data(cfg, ctx.seeds["data"])
    x_test = np.asarray(data["x_test"])
    pool, name = model.build_pool(cfg, data, ctx.seeds["program"])
    resident = pool.resident(name)
    k = resident.ensemble.num_chains
    pool.warm()
    for cls in traffic["classes"]:  # compile each class's evaluator
        pool.query(name, cls, x_test[:1])
    tracer = Tracer() if ctx.trace else None
    queue = RequestQueue(pool, tracer=tracer)

    snapshots: dict[float, list] = {}  # staleness -> [(taken at, snapshot)]
    ensure_fresh = pool.ensure_fresh

    def recording_ensure_fresh(wl_name):
        snap = ensure_fresh(wl_name)
        snapshots.setdefault(snap.staleness_s, []).append((now(), snap))
        return snap

    pool.ensure_fresh = recording_ensure_fresh
    if ctx.trace:  # host spans that label the device's idle gaps
        refresh, query = resident.refresh, pool.query

        def spanned_refresh(*a, **kw):
            with ctx.span("refresh"):
                return refresh(*a, **kw)

        def spanned_query(*a, **kw):
            with ctx.span("query"):
                return query(*a, **kw)

        resident.refresh, pool.query = spanned_refresh, spanned_query
    due, payloads = make_schedule(traffic, ctx.seconds, x_test.shape[0],
                                  ctx.rng("schedule"))
    rows = {id(p): x_test[p[1]] for p in payloads}

    def submit(payload):
        return queue.submit(name, payload[0], rows[id(payload)])

    pool.start()
    queue.start_worker()
    try:
        # Warm the served path with refresh running: the queue, the
        # snapshot upload and both evaluators, before the clock starts.
        warm = [queue.submit(name, cls, x_test[:4])
                for cls in traffic["classes"] for _ in range(4)]
        for req in warm:
            req.result(timeout_s=120.0)
        setup_s = now() - ctx.t_process

        gen = OpenLoop(due, payloads, submit, span=ctx.span)
        profiler = ctx.profiler()
        profiler.start()
        t0 = now() + 0.01
        steps0 = resident.steps_done
        gen.start(t0)
        if profiler.active:
            time.sleep(max(t0 + TRACE_SECONDS - now(), 0.0))
            profiler.stop()
        gen.join(timeout_s=ctx.seconds + 60.0)
        close = max(now(), t0 + ctx.seconds)
        steps_close, t_steps = resident.steps_done, now()
        deadline = close + DRAIN_SECONDS
        for req in gen.handles:
            req.done.wait(timeout=max(deadline - now(), 0.0))
        peak = peak_bytes()
    finally:
        queue.stop_worker()
        pool.stop()

    reqs = gen.handles
    ok = [r.done.is_set() and r.error is None for r in reqs]
    done_at = [r.submitted_at + r.latency_s if o else None for r, o in zip(reqs, ok)]
    latency = latency_from_due(gen.due_abs(), done_at, ok)
    staleness = np.array([r.staleness_s for r, o in zip(reqs, ok) if o], np.float64)
    batch = np.array([r.batch_size for r, o in zip(reqs, ok) if o], np.float64)
    spans = tracer.spans() if tracer is not None else []
    used = [_snapshot_of(r, done, snapshots) if o else None
            for r, done, o in zip(reqs, done_at, ok)]
    served = [{
        "query_class": p[0], "xs": rows[id(p)],
        "values": r.values if o else None,
        "draws": None if snap is None else snap.draws.reshape(-1, x_test.shape[1]),
    } for p, r, o, snap in zip(payloads, reqs, ok, used)]
    windows = {snap.steps_done: snap.draws for snap in used if snap is not None}
    host_data = {key: np.asarray(v) for key, v in data.items()}
    del pool, resident, queue, data
    gc.collect()
    checks = model.check_serve(cfg, served)
    checks.update(model.check_chains(cfg, host_data, sorted(windows.items()),
                                     ctx.seeds["program"], ctx.rng("check")))
    return {
        "setup_s": setup_s,
        "window_s": close - t0,
        "attempted": len(reqs),
        "failed": int(len(reqs) - sum(ok)),
        "peak_bytes": peak,
        "checks": checks,
        "due_s": gen.due,
        "latency_s": latency,
        "staleness_s": staleness,
        "generator_lag_s": gen.lag_s(),
        "batch_requests": batch,
        "spans": spans,
        "refresh_transitions_per_s": k * (steps_close - steps0) / (t_steps - t0),
        # Printed to stderr: where a tail comes from, if one is long.
        "diagnostics": {
            "generator_lag_max_ms": 1e3 * float(np.nanmax(gen.lag_s())),
            "latency_max_ms": 1e3 * float(np.max(latency)),
            "requests_over_1s": int(np.sum(latency > 1.0)),
            "first_over_1s_due_s": (float(gen.due[np.argmax(latency > 1.0)])
                                    if np.any(latency > 1.0) else None),
        },
    }
