#!/usr/bin/env python3
"""Per-layer readings from the program's own spans and named scopes, on
the chip.

    python bench/program_trace.py --workload <cell> --seeds 1,2,3 \\
        --seconds 10 --trace 1

Runs the cell once per seed in this one process, as ``bench/run.py``
does, with a program tracer (``repro.obs.trace.Tracer``) attached to the
cell's ``EnsemblePool`` from the start. With ``--trace 1`` the profile of
the window is reduced by the program's named scopes and ``repro.*``
annotations (``bench/harness/scopes.py``), and each metric reader under
``bench/metrics`` that declares ``SCOPES`` or ``SPANS`` for the cell's kind
(``*.refresh`` or ``*.serve``) reads it; the line also gives how much of
the device's idle time inside the benchmark's own ``refresh`` spans the
program's annotations cover. With ``--trace 0`` the profiler stays off and
the line holds the run's end-to-end metrics with the tracer attached,
which is what the tracer costs. One JSON line per run; none of this is a
benchmark result.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as bench_run  # noqa: E402
from bench.harness import scopes, spec  # noqa: E402
from bench.harness import trace as trace_mod  # noqa: E402
from bench.harness.context import TRACE_DIR  # noqa: E402
from bench.harness.stats import percentile  # noqa: E402

KIND_SUFFIX = {"refresh": ".refresh", "open_loop": ".serve"}
BLOCK = "repro.refresh"
STAGES = ("keys", "dispatch", "wait", "pull", "commit")
EVAL_STAGES = ("device_eval", "device_eval.upload", "device_eval.run")
#: Every named scope of the program, for the breakdown of the device time.
ROUND_SCOPES = ("propose", "draw", "gather", "delta", "seq_test", "query_eval")


def readers(kind: str) -> dict:
    """The metric readers of this kind that read the program's marks."""
    out = {}
    for path in sorted((spec.ROOT / "bench" / "metrics").glob("*.py")):
        if path.stem.endswith(KIND_SUFFIX[kind]):
            mod = spec._load_module(path, "metric")
            if hasattr(mod, "SCOPES") or hasattr(mod, "SPANS"):
                out[path.stem] = mod
    return out


def program_texts(resident) -> list[str]:
    """Compiled HLO of the refresh program and of the resident's query
    evaluators (a persistent-cache hit after the run), for the op names
    the trace's own stats may lack."""
    import jax

    ens, steps = resident.ensemble, resident.refresh_steps
    keys = ens.step_keys(jax.random.key(0), 0, steps)
    texts = [ens.lower(resident.state, steps, step_keys=keys).compile().as_text()]
    ev = resident._evaluator
    if ev._flat_cache is not None:
        flat = ev._flat_cache[1]
        rows = np.zeros((ev.micro_batch,) + tuple(flat.shape[1:]), np.float32)
        for fn in ev._eval_cache.values():
            texts.append(fn.lower(flat, rows).compile().as_text())
    return texts


def span_summary(spans: list[dict]) -> dict:
    """Counts of refresh blocks by cause, the median of each refresh
    stage's host time over the background and call blocks, and the median
    and 95th percentile of the evaluator's spans, in milliseconds."""
    blocks = {s["span_id"]: s for s in spans if s["stage"] == "refresh"}
    causes: dict[str, int] = {}
    for b in blocks.values():
        causes[b["cause"]] = causes.get(b["cause"], 0) + 1
    steady = {i for i, b in blocks.items() if b["cause"] in ("background", "call")}
    stage_ms = {}
    for stage in ("refresh",) + tuple("refresh." + s for s in STAGES):
        durs = [s["dur_s"] for s in spans if s["stage"] == stage
                and (s["span_id"] in steady or s.get("parent_id") in steady)]
        if durs:
            stage_ms[stage] = 1e3 * statistics.median(durs)
    eval_ms = {}
    for stage in EVAL_STAGES:
        durs = [s["dur_s"] for s in spans if s["stage"] == stage]
        if durs:
            eval_ms[stage] = {"n": len(durs), "p50": 1e3 * statistics.median(durs),
                              "p95": 1e3 * percentile(durs, 95)}
    return {"blocks_by_cause": causes, "stage_median_ms": stage_ms,
            "eval_ms": eval_ms}


def eval_waits(reduction: dict) -> dict:
    """For the evaluator's annotations: the delay from each one's start to
    the first ``query_eval`` operation (median, 90th percentile, largest,
    and how many exceed 50 ms, about half a refresh block), in
    milliseconds, and the device's idle seconds inside them."""
    out = {}
    for name in ("repro.device_eval", *(f"repro.{s}" for s in EVAL_STAGES[1:])):
        delays = [d for d in reduction["first_op_delay_s"].get(name, {})
                  .get("query_eval", ()) if d is not None]
        if delays:
            out[name] = {"n": len(delays),
                         "first_query_op_p50_ms": 1e3 * statistics.median(delays),
                         "first_query_op_p90_ms": 1e3 * percentile(delays, 90),
                         "first_query_op_max_ms": 1e3 * max(delays),
                         "over_50ms": sum(d > 0.05 for d in delays),
                         "device_idle_s": reduction["idle_in_span_s"][name]}
    return out


def coverage(reduction: dict) -> dict:
    """Device idle time inside the benchmark's ``refresh`` spans, and the
    shares of it inside the program's block annotation and its stages."""
    idle = reduction["idle_in_span_s"]
    bench = idle.get("refresh", 0.0)
    stages = sum(idle.get(f"{BLOCK}.{s}", 0.0) for s in STAGES)
    out = {"bench_refresh_idle_s": bench, "block_idle_s": idle.get(BLOCK, 0.0),
           "stages_idle_s": stages,
           "stage_idle_s": {s: idle.get(f"{BLOCK}.{s}", 0.0) for s in STAGES}}
    if bench > 0:
        out["block_share"] = idle.get(BLOCK, 0.0) / bench
        out["stages_share"] = stages / bench
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    from repro.obs.trace import Tracer

    cell = spec.load_cell(args.workload)
    marks = readers(cell.traffic["kind"])
    want_scopes, want_spans = scopes.declared(marks.values())
    extra = (BLOCK, *(f"{BLOCK}.{x}" for x in STAGES), "refresh",
             *(f"repro.{x}" for x in EVAL_STAGES))
    want_spans += tuple(s for s in extra if s not in want_spans)
    want_scopes += tuple(s for s in ROUND_SCOPES if s not in want_scopes)
    for seed in args.seeds.split(","):
        tracer = Tracer()
        out: dict = {}

        def hook(drive, tracer=tracer, out=out):
            def traced(ctx):
                model = ctx.cell.model
                build = model.build_pool

                def build_traced(*a, **kw):
                    pool, name = build(*a, **kw)
                    pool.tracer = tracer
                    out["resident"] = pool.resident(name)
                    return pool, name

                model.build_pool = build_traced
                try:
                    rec = drive(ctx)
                finally:
                    model.build_pool = build
                if ctx.trace and not args.rehearse:
                    # The profile is still on disk here: run_cell reduces
                    # and removes it once this function returns.
                    planes = trace_mod.load_planes(str(TRACE_DIR))
                    op_names = scopes.op_names_from_hlo(program_texts(out["resident"]))
                    out["reduction"] = scopes.reduce_program(
                        planes, want_scopes, want_spans, op_names)
                out["rec"] = rec
                del out["resident"]
                return rec
            return traced

        run_args = argparse.Namespace(workload=args.workload, seed=int(seed),
                                      seconds=args.seconds, trace=args.trace)
        result = bench_run.run_cell(run_args, rehearse=args.rehearse, driver_hook=hook)
        spans = tracer.spans() + list(out.get("rec", {}).get("spans", ()))
        line = {"workload": args.workload, "seed": int(seed), "trace": args.trace,
                "correct": result["correct"], "spans": span_summary(spans)}
        if "reduction" in out:
            red = out["reduction"]
            view = dict(out["rec"], trace=red)
            line["program_metrics"] = {name: r.read(view) for name, r in marks.items()}
            line["coverage"] = coverage(red)
            line["eval_waits"] = eval_waits(red)
            line["scope_s"] = red["scope_s"]
            line["span_count"] = red["span_count"]
            line["op_names_from"] = red["op_names_from"]
        line["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
        line["device"] = result["device"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
