"""End-to-end request tracing: span lifecycle, the Tracer ring/stream,
Chrome/Perfetto export, queue-path propagation, the program's refresh and
evaluator spans with their profiler annotations, the named scopes of the
compiled programs, and (slow tier) the cross-process ReplicaProcess
round-trip — one trace_id spanning two OS processes on the shared
monotonic timeline.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from repro.obs import Recorder, Tracer, chrome_trace_events
from repro.obs.trace import (
    REFRESH_TID,
    STAGES,
    load_spans,
    main as trace_main,
    span_close,
    span_open,
)
from repro.serving import FreshnessPolicy, RequestQueue, ServingConfig
from repro.serving.pool import EnsemblePool

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Span + Tracer primitives
# ---------------------------------------------------------------------------


def test_span_open_close_contract():
    span = span_open("t1", "request:w.q", "request", workload="w")
    assert span["trace_id"] == "t1" and span["parent_id"] is None
    assert span["stage"] in STAGES and span["pid"] == os.getpid()
    assert "dur_s" not in span  # open
    child = span_open("t1", "queue_wait", "queue_wait",
                      parent_id=span["span_id"])
    assert child["parent_id"] == span["span_id"]
    span_close(child, rows=4)
    span_close(span)
    assert child["dur_s"] >= 0 and child["rows"] == 4
    # raw spans (no tracer available where they're produced) carry no ids
    raw = span_open(None, "device_eval", "device_eval")
    assert raw["trace_id"] is None


def test_tracer_ring_bounds_and_counts_drops():
    tracer = Tracer(max_spans=3)
    roots = []
    for i in range(5):
        roots.append(tracer.finish(tracer.new_trace(f"r{i}", idx=i)))
    kept = tracer.spans()
    assert len(kept) == 3 and tracer.dropped == 2
    assert [s["idx"] for s in kept] == [2, 3, 4]  # newest survive
    assert tracer.trace(roots[-1]["trace_id"]) == [kept[-1]]
    tracer.close()


def test_tracer_adopt_grafts_raw_spans_onto_trace():
    tracer = Tracer()
    root = tracer.new_trace("request:w.q")
    inner_parent = span_close(span_open(None, "replica_serve", "replica_serve"))
    inner_child = span_close(span_open(
        None, "device_eval", "device_eval", parent_id=inner_parent["span_id"]))
    wire = dict(inner_child)
    wire["span_id"] = None  # e.g. assigned on the far side of a pipe
    adopted = tracer.adopt([inner_parent, wire], root["trace_id"],
                           parent_id=root["span_id"])
    assert all(s["trace_id"] == root["trace_id"] for s in adopted)
    assert adopted[0]["parent_id"] == root["span_id"]  # unparented -> grafted
    assert adopted[1]["parent_id"] == inner_parent["span_id"]  # kept
    assert adopted[1]["span_id"] is not None
    tracer.finish(root)
    assert len(tracer.spans()) == 3
    tracer.close()


def test_tracer_tees_to_recorder_stream_and_jsonl(tmp_path):
    rec = Recorder()
    path = str(tmp_path / "t" / "spans.jsonl")
    tracer = Tracer(recorder=rec, jsonl_path=path)
    tracer.finish(tracer.new_trace("request:a.b"))
    tracer.finish(tracer.new_trace("request:a.b"))
    spans_stream = rec.rollup()["streams"]["spans"]
    assert spans_stream["count"] == 2
    assert spans_stream["fields"]["dur_s"]["count"] == 2
    tracer.close()
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2 and all(l["dur_s"] is not None for l in lines)
    assert load_spans(str(tmp_path / "t")) == lines  # dir resolution
    rec.close()


# ---------------------------------------------------------------------------
# Chrome/Perfetto export
# ---------------------------------------------------------------------------


def test_chrome_trace_events_shape():
    tracer = Tracer()
    root = tracer.new_trace("request:w.q", workload="w")
    child = tracer.start(root["trace_id"], "assembly", "assembly",
                         parent_id=root["span_id"])
    tracer.finish(child)
    tracer.finish(root)
    open_span = tracer.new_trace("dangling")  # never closed
    payload = chrome_trace_events(tracer.spans() + [open_span])
    events = payload["traceEvents"]
    assert len(events) == 2  # open spans are excluded, not fabricated
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)
    assert min(e["ts"] for e in events) == 0.0  # rebased to earliest span
    assert events[0]["cat"] == "request" and events[1]["cat"] == "assembly"
    assert events[0]["args"]["workload"] == "w"  # tags ride in args
    json.dumps(payload)  # JSON-serializable as-is
    tracer.close()


def test_export_cli_roundtrip(tmp_path, capsys):
    path = str(tmp_path / "spans.jsonl")
    tracer = Tracer(jsonl_path=path)
    keep = tracer.finish(tracer.new_trace("request:w.q"))
    tracer.finish(tracer.new_trace("request:w.q"))
    tracer.close()
    assert trace_main(["--export", str(tmp_path)]) == 0
    line = capsys.readouterr().out.strip()
    assert line.startswith("TRACE_EXPORT spans=2 traces=2")
    out = json.loads((tmp_path / "trace.json").read_text())
    assert len(out["traceEvents"]) == 2
    # --trace-id narrows the export to one request
    assert trace_main(["--export", path, "--trace-id", keep["trace_id"],
                       "--out", str(tmp_path / "one.json")]) == 0
    one = json.loads((tmp_path / "one.json").read_text())
    assert len(one["traceEvents"]) == 1
    assert one["traceEvents"][0]["args"]["trace_id"] == keep["trace_id"]


# ---------------------------------------------------------------------------
# Queue-path propagation: submit -> batch assembly -> device eval
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_pool():
    cfg = ServingConfig(
        num_chains=2, refresh_steps=8, window=16, micro_batch=8, max_batch=4,
        freshness=FreshnessPolicy(max_staleness_s=60.0, min_draws=16), seed=0,
    )
    pool = EnsemblePool(cfg)
    pool.add_workload("bayeslr", smoke=True, n_train=400, d=3, batch_size=50)
    pool.warm()
    return pool


def _contains(outer, inner, slack=1e-6):
    return (outer["start_s"] - slack <= inner["start_s"] and
            inner["start_s"] + inner["dur_s"]
            <= outer["start_s"] + outer["dur_s"] + slack)


def test_queue_serving_emits_nested_trace(traced_pool):
    tracer = Tracer()
    queue = RequestQueue(traced_pool, max_batch=4, tracer=tracer)
    spec = traced_pool.workload("bayeslr").query_specs["predictive"]
    reqs = [queue.submit("bayeslr", "predictive",
                         spec.make_queries(jax.random.key(i), 3))
            for i in range(3)]
    queue.drain()
    for req in reqs:
        assert req.trace_id is not None
        spans = tracer.trace(req.trace_id)
        stages = {s["stage"] for s in spans}
        # every request's journey carries its own root + queue_wait
        assert {"request", "queue_wait"} <= stages
        root = next(s for s in spans if s["stage"] == "request")
        assert root["parent_id"] is None
        assert root.get("deadline_met") is not None
        for s in spans:
            assert s.get("dur_s") is not None  # drain closed everything
            if s is not root:
                assert _contains(root, s)  # nesting-consistent timestamps
    # batch-level work (assembly + device eval) is attributed to the batch
    # head's trace — the full queue -> assembly -> device journey
    head_stages = {s["stage"] for s in tracer.trace(reqs[0].trace_id)}
    assert {"request", "queue_wait", "assembly", "device_eval"} <= head_stages
    # the batch-level spans are shared: 3 requests, one assembly span each
    # batch — with max_batch=4 all three rode together
    asm = [s for s in tracer.spans() if s["stage"] == "assembly"]
    assert len(asm) == 1 and asm[0]["batch_size"] == 3
    tracer.close()


def test_queue_error_path_still_closes_trace(traced_pool):
    tracer = Tracer()
    queue = RequestQueue(traced_pool, max_batch=2, tracer=tracer)
    req = queue.submit("bayeslr", "no_such_class", np.zeros((2, 3)))
    queue.drain()
    with pytest.raises(RuntimeError):
        req.result(timeout_s=5.0)
    spans = tracer.trace(req.trace_id)
    root = next(s for s in spans if s["stage"] == "request")
    assert root["dur_s"] is not None and root.get("error")
    assert all(s.get("dur_s") is not None for s in spans)
    tracer.close()


def test_untraced_queue_requests_carry_no_trace(traced_pool):
    queue = RequestQueue(traced_pool, max_batch=2)  # tracer off
    spec = traced_pool.workload("bayeslr").query_specs["predictive"]
    req = queue.submit("bayeslr", "predictive",
                       spec.make_queries(jax.random.key(0), 2))
    queue.drain()
    assert req.trace_id is None and req.trace is None
    assert req.values is not None


# ---------------------------------------------------------------------------
# Cross-process propagation (slow tier): ReplicaProcess round-trip
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_trace_crosses_replica_process_boundary():
    """One trace_id spans two OS processes: the root + serve spans come
    back from the replica worker with ITS pid, nest inside the parent's
    request span on the shared monotonic clock, and export as valid
    Perfetto X events across both pid tracks."""
    script = r"""
import json, os
import jax, numpy as np
from repro.fleet import Fleet, FleetConfig
from repro.obs import Tracer, chrome_trace_events
from repro.serving import FreshnessPolicy, ServingConfig

def main():
    cfg = FleetConfig(
        replicas=1, shards=1, transport="proc",
        serving=ServingConfig(num_chains=2, refresh_steps=8, window=16,
                              micro_batch=8,
                              freshness=FreshnessPolicy(max_staleness_s=1e9,
                                                        min_draws=8),
                              seed=0),
    )
    fleet = Fleet(cfg)
    fleet.add_workload("bayeslr", smoke=True, n_train=400, d=3, batch_size=50)
    fleet.warm(); fleet.pump()
    shard = fleet.shards("bayeslr")[0]
    spec = fleet.spec("bayeslr", "predictive")
    xs = spec.make_queries(jax.random.key(9), 4)

    tracer = Tracer()
    root = tracer.new_trace("request:bayeslr.predictive")
    values, staleness, spans = shard.replicas[0].serve(
        spec, "predictive", xs, trace=(root["trace_id"], root["span_id"]))
    tracer.adopt(spans, root["trace_id"], parent_id=root["span_id"])
    tracer.finish(root)
    all_spans = tracer.trace(root["trace_id"])
    rootc = next(s for s in all_spans if s["stage"] == "request")
    nested = all(
        rootc["start_s"] <= s["start_s"]
        and s["start_s"] + s["dur_s"] <= rootc["start_s"] + rootc["dur_s"]
        for s in all_spans if s is not rootc)
    events = chrome_trace_events(all_spans)["traceEvents"]
    fleet.close()
    print(json.dumps({
        "values_ok": bool(np.isfinite(np.asarray(values)).all()),
        "trace_ids": sorted({s["trace_id"] for s in all_spans}),
        "stages": sorted({s["stage"] for s in all_spans}),
        "pids": sorted({s["pid"] for s in all_spans}),
        "parent_pid": os.getpid(),
        "nested": nested,
        "events_ok": all(e["ph"] == "X" and e["dur"] >= 0 for e in events),
        "n_events": len(events),
    }))

if __name__ == "__main__":
    main()
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=_REPO, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["values_ok"] is True
    assert len(res["trace_ids"]) == 1  # ONE trace_id end to end
    assert {"request", "replica_serve", "device_eval"} <= set(res["stages"])
    assert len(res["pids"]) == 2  # parent + replica worker process
    assert res["parent_pid"] in res["pids"]
    assert res["nested"] is True  # monotone clock shared across processes
    assert res["events_ok"] is True and res["n_events"] >= 3


# ---------------------------------------------------------------------------
# Program spans: refresh blocks and the evaluator's stages
# ---------------------------------------------------------------------------

REFRESH_STAGES = ("refresh.keys", "refresh.dispatch", "refresh.wait",
                  "refresh.pull", "refresh.commit")


def _small_pool(tracer=None, **policy):
    cfg = ServingConfig(
        num_chains=2, refresh_steps=8, window=16, micro_batch=8, max_batch=4,
        freshness=FreshnessPolicy(max_staleness_s=60.0, min_draws=32), seed=0,
    )
    pool = EnsemblePool(cfg, tracer=tracer)
    pool.add_workload("bayeslr", smoke=True, n_train=400, d=3, batch_size=50)
    return pool


def _blocks(spans, cause=None):
    return [s for s in spans if s["stage"] == "refresh"
            and (cause is None or s["cause"] == cause)]


def _check_block(spans, block):
    kids = sorted((s for s in spans if s.get("parent_id") == block["span_id"]),
                  key=lambda s: s["start_s"])
    assert [s["stage"] for s in kids] == list(REFRESH_STAGES)
    for kid in kids:
        assert kid["trace_id"] == block["trace_id"]
        assert _contains(block, kid)
    for a, b in zip(kids, kids[1:]):  # stages run one after the other
        assert a["start_s"] + a["dur_s"] <= b["start_s"] + 1e-6


def test_traced_refresh_emits_one_block_with_its_five_stages(traced_pool):
    tracer = Tracer()
    traced_pool.tracer = tracer
    try:
        traced_pool.resident("bayeslr").refresh()
    finally:
        traced_pool.tracer = None
    spans = tracer.spans()
    (block,) = _blocks(spans)
    assert block["cause"] == "call" and block["parent_id"] is None
    assert block["workload"] == "bayeslr" and block["steps"] == 8
    assert len(spans) == 1 + len(REFRESH_STAGES)
    _check_block(spans, block)


def test_refresh_cause_tags_sync_warm_and_background():
    tracer = Tracer()
    synced = _small_pool(tracer)
    synced.ensure_fresh("bayeslr")  # two blocks reach min_draws=32
    assert [b["cause"] for b in _blocks(tracer.spans())] == ["sync", "sync"]

    tracer = Tracer()
    warmed = _small_pool()
    warmed.tracer = tracer  # attached after the residents exist
    warmed.warm()
    blocks = _blocks(tracer.spans())
    assert [b["cause"] for b in blocks] == ["warm", "warm"]
    for block in blocks:
        _check_block(tracer.spans(), block)

    warmed.start()
    try:
        deadline = time.monotonic() + 60.0
        while not _blocks(tracer.spans(), "background"):
            assert time.monotonic() < deadline, "no background block"
            time.sleep(0.01)
    finally:
        warmed.stop()
    for block in _blocks(tracer.spans(), "background"):
        _check_block(tracer.spans(), block)


def test_untraced_refresh_builds_no_span_and_opens_no_annotation(
        traced_pool, monkeypatch):
    import repro.obs.trace as trace_mod

    opened = []

    class Counting:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    real_open = trace_mod.span_open
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(trace_mod, "span_open",
                        lambda *a, **kw: opened.append(a) or real_open(*a, **kw))
    resident = traced_pool.resident("bayeslr")
    assert resident.tracer is None
    resident.refresh()
    assert opened == []
    # the same refresh, traced, opens the block and its five stages
    resident.tracer = Tracer()
    try:
        resident.refresh()
    finally:
        resident.tracer = None
    names = [o for o in opened if isinstance(o, str)]
    assert names == ["repro.refresh", *("repro." + s for s in REFRESH_STAGES)]


def test_evaluator_splits_device_eval_into_upload_and_run(traced_pool):
    from repro.serving.resident import SnapshotEvaluator

    resident = traced_pool.resident("bayeslr")
    spec = traced_pool.spec("bayeslr", "predictive")
    snap = resident.snapshot()
    xs = spec.make_queries(jax.random.key(0), 3)
    evaluator = SnapshotEvaluator(micro_batch=8)
    sink = []
    plain = evaluator.evaluate(spec, snap, xs)
    evaluator.invalidate()
    traced = evaluator.evaluate(spec, snap, xs, span_sink=sink)
    np.testing.assert_array_equal(plain, traced)
    (top,) = [s for s in sink if s["stage"] == "device_eval"]
    assert top["name"] == "device_eval:predictive" and top["rows"] == 3
    kids = {s["stage"]: s for s in sink if s is not top}
    assert set(kids) == {"device_eval.upload", "device_eval.run"}
    for kid in kids.values():
        assert kid["parent_id"] == top["span_id"] and _contains(top, kid)
    # the window is cached now: a second evaluation uploads nothing
    sink.clear()
    evaluator.evaluate(spec, snap, xs, span_sink=sink)
    assert sorted(s["stage"] for s in sink) == ["device_eval", "device_eval.run"]


def test_queue_adopts_the_evaluator_stages_under_device_eval(traced_pool):
    tracer = Tracer()
    queue = RequestQueue(traced_pool, max_batch=4, tracer=tracer)
    spec = traced_pool.workload("bayeslr").query_specs["predictive"]
    req = queue.submit("bayeslr", "predictive",
                       spec.make_queries(jax.random.key(5), 2))
    queue.drain()
    spans = tracer.trace(req.trace_id)
    (top,) = [s for s in spans if s["stage"] == "device_eval"]
    run = [s for s in spans if s["stage"] == "device_eval.run"]
    assert len(run) == 1 and run[0]["parent_id"] == top["span_id"]
    tracer.close()


# ---------------------------------------------------------------------------
# Named scopes in the compiled programs
# ---------------------------------------------------------------------------


def _scopes(lowered) -> set:
    """Named scopes in a lowered program's op names: every component of an
    op name but the last (the primitive), with ``vmap(...)``-style wrappers
    opened up."""
    import re

    text = lowered.as_text(debug_info=True)
    out = set()
    for op_name in re.findall(r'loc\("([^"]*/[^"]*)"', text):
        for part in op_name.split("/")[:-1]:
            out.update(p for p in re.split(r"[()]", part) if p)
    return out


@pytest.mark.parametrize("stepping,fused", [
    ("lockstep", "always"),  # the fused lock-step scan (the chip's route)
    ("lockstep", "never"),   # the vmapped scan of single-chain steps
    ("masked", "always"),    # the masked superstep
])
def test_refresh_program_carries_the_round_scopes(stepping, fused):
    import dataclasses

    from repro.serving.workloads import build_serving_workload

    wl = build_serving_workload("bayeslr", smoke=True, n_train=400, d=3,
                                batch_size=50, num_chains=2, seed=0)
    ens = dataclasses.replace(wl.ensemble, stepping=stepping,
                              fused_kernels=fused)
    state = ens.init(wl.theta0)
    sk = ens.step_keys(jax.random.key(0), 0, 4)
    scopes = _scopes(ens.lower(state, 4, step_keys=sk))
    assert {"draw", "gather", "delta", "seq_test", "propose"} <= scopes


def test_evaluator_program_carries_query_eval(traced_pool):
    resident = traced_pool.resident("bayeslr")
    spec = traced_pool.spec("bayeslr", "predictive")
    snap = resident.snapshot()
    flat = snap.draws.reshape((-1,) + snap.draws.shape[2:])
    xs = spec.make_queries(jax.random.key(1), 8)
    lowered = resident._evaluator._evaluator(spec).lower(flat, xs)
    assert "query_eval" in _scopes(lowered)


# ---------------------------------------------------------------------------
# The same spans on the profiler's clock
# ---------------------------------------------------------------------------


def test_refresh_annotations_nest_on_the_profiler_host_plane(traced_pool,
                                                             tmp_path):
    from jax.profiler import ProfileData

    resident = traced_pool.resident("bayeslr")
    tracer = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    resident.tracer = tracer
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            resident.refresh()
    finally:
        resident.tracer = None
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for p in ProfileData.from_file(path).planes
              if not p.name.startswith("/device:")
              for line in p.lines for e in line.events
              if e.name.startswith("repro.")]
    (outer,) = [e for e in events if e[0] == "repro.refresh"]
    inner = sorted((e for e in events if e is not outer), key=lambda e: e[1])
    # the annotations nest as the dict spans do: the block, then its
    # stages in order, each inside it
    spans = tracer.spans()
    (block,) = _blocks(spans)
    kids = sorted((s for s in spans if s["parent_id"] == block["span_id"]),
                  key=lambda s: s["start_s"])
    assert [e[0] for e in inner] == ["repro." + s["stage"] for s in kids]
    for _, a, b in inner:
        assert outer[1] <= a <= b <= outer[2]


def test_chrome_export_puts_refresh_blocks_on_their_own_track(traced_pool):
    tracer = Tracer()
    tracer.finish(tracer.new_trace("request:w.q"))
    resident = traced_pool.resident("bayeslr")
    resident.tracer = tracer
    try:
        resident.refresh()
    finally:
        resident.tracer = None
    events = chrome_trace_events(tracer.spans())["traceEvents"]
    tids = {e["cat"]: e["tid"] for e in events}
    assert tids["request"] == os.getpid()
    assert {tids["refresh"], tids["refresh.pull"]} == {REFRESH_TID}


def test_serve_profile_dir_captures_one_steady_traced_block(tmp_path, capsys):
    from jax.profiler import ProfileData

    from repro.launch import serve

    prof, spans_dir = tmp_path / "prof", tmp_path / "trace"
    serve.main(["--workload", "bayeslr", "--smoke",
                "--profile-dir", str(prof), "--trace-dir", str(spans_dir)])
    out = capsys.readouterr().out
    assert "SERVE_OK" in out
    assert f"profile: jax.profiler capture in {prof}" in out
    # the capture holds one refresh block, taken after warm-up, with the
    # program's annotations on the host plane
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(prof)
               for f in fs if f.endswith(".xplane.pb")]
    names = [e.name for p in ProfileData.from_file(path).planes
             if not p.name.startswith("/device:")
             for line in p.lines for e in line.events
             if e.name.startswith("repro.refresh")]
    assert names.count("repro.refresh") == 1
    assert {"repro." + s for s in REFRESH_STAGES} <= set(names)
    # --trace-dir's tracer reached the pool: the export holds the blocks
    blocks = _blocks(load_spans(str(spans_dir)))
    assert blocks and {b["cause"] for b in blocks} <= {"call", "sync"}
