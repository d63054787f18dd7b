"""CI perf-regression gate over the machine-readable bench artifacts.

Diffs this run's ``BENCH_<name>.json`` files (multichain, serving, fleet,
roofline — see ``multichain_bench.bench_json_path``) against the previous
CI run's artifact directory and fails on any metric that regressed by more
than the threshold (default 15%, ``--threshold`` / ``$REPRO_GATE_THRESHOLD``):
req/s down, latency tails up, steady-state transition throughput down.

    python -m benchmarks.gate --previous prev-artifacts --current bench-artifacts

Records are matched run-over-run on their identifying fields (bench name +
``kind``/``engine``/shape fields); metrics are compared per direction —
``qps``/``tps_*`` must not drop, ``p95_ms``/``us_per_call`` must not rise.
A machine-readable verdict lands in ``<current>/GATE_verdict.json``; the
process exits nonzero iff any comparison regressed. A missing previous
artifact passes with ``status: "no_baseline"`` (first run, expired cache)
unless ``--fail-on-missing`` is set.

``--trend`` replaces the single-run diff with the historical store
(:class:`repro.obs.history.HistoryStore`): the baseline for each metric is
the **median of the last K runs** (``--trend-window``, robust to one noisy
CI host), and a second detector flags **monotone drift** — a metric that
worsened on every one of the last ``--trend-window`` runs and lost more
than the threshold cumulatively, even though no single step tripped the
gate. On a passing (or no-baseline) verdict the current artifacts are
appended to the store, so the history maintains itself run-over-run:

    python -m benchmarks.gate --trend --history bench-history \
        --current bench-artifacts
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

BENCHES = ("multichain", "serving", "fleet", "roofline", "subposterior")

# Metric -> direction. HIGHER: a drop beyond the threshold regresses.
# LOWER: a rise beyond the threshold regresses. Anything not listed is
# informational and never gates.
HIGHER, LOWER = "higher", "lower"
METRIC_DIRECTIONS = {
    "qps": HIGHER,
    "tps_e2e": HIGHER,
    "tps_steady": HIGHER,
    "transitions_per_sec": HIGHER,
    "tps_mesh_2d": HIGHER,
    "gflops": HIGHER,
    "p50_ms": LOWER,
    "p95_ms": LOWER,
    "p99_ms": LOWER,
    "us_per_call": LOWER,
    "ratio": LOWER,  # delta-stream wire bytes vs full-snapshot bytes
}

# Fields that identify a record across runs (never compared as metrics).
ID_FIELDS = ("kind", "engine", "name", "kernel", "workload", "transport",
             "path", "backend", "shape", "N", "K", "steps", "replicas",
             "queries", "rows_per_query", "max_batch", "window", "mode",
             "P", "method", "precision")


def record_key(bench: str, rec: dict) -> str:
    parts = [bench] + [
        f"{f}={rec[f]}" for f in ID_FIELDS if rec.get(f) is not None
    ]
    return "/".join(parts)


def load_records(art_dir: str, bench: str) -> dict[str, dict] | None:
    """``{record_key: record}`` from one artifact file, or None when the
    file is absent (bench not run / first CI run)."""
    path = os.path.join(art_dir, f"BENCH_{bench}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        payload = json.load(f)
    out: dict[str, dict] = {}
    for rec in payload.get("records", []):
        key = record_key(bench, rec)
        if key in out:  # duplicate id fields: keep first, flag neither
            continue
        out[key] = rec
    return out


def compare(prev: dict, cur: dict, key: str, threshold: float) -> list[dict]:
    """Per-metric comparisons for one matched record pair."""
    rows = []
    for metric, direction in METRIC_DIRECTIONS.items():
        p, c = prev.get(metric), cur.get(metric)
        if not isinstance(p, (int, float)) or not isinstance(c, (int, float)):
            continue
        if abs(p) < 1e-12:  # degenerate baseline: nothing meaningful to diff
            continue
        if direction == HIGHER:
            change = (p - c) / abs(p)  # fraction LOST
        else:
            change = (c - p) / abs(p)  # fraction GAINED (latency up = bad)
        rows.append({
            "record": key,
            "metric": metric,
            "direction": direction,
            "previous": p,
            "current": c,
            "regression": change,
            "regressed": change > threshold,
        })
    return rows


def run_gate(previous_dir: str, current_dir: str, *,
             threshold: float = 0.15,
             benches: tuple[str, ...] = BENCHES,
             fail_on_missing: bool = False) -> dict:
    """The full verdict dict (``status`` in pass/fail/no_baseline)."""
    comparisons: list[dict] = []
    missing: list[dict] = []
    seen_baseline = False
    for bench in benches:
        cur = load_records(current_dir, bench)
        prev = load_records(previous_dir, bench)
        if cur is None:
            missing.append({"bench": bench, "side": "current"})
            continue
        if prev is None:
            missing.append({"bench": bench, "side": "previous"})
            continue
        seen_baseline = True
        for key, cur_rec in cur.items():
            prev_rec = prev.get(key)
            if prev_rec is None:
                missing.append({"bench": bench, "side": "previous",
                                "record": key})
                continue
            comparisons.extend(compare(prev_rec, cur_rec, key, threshold))
    regressions = [c for c in comparisons if c["regressed"]]
    if regressions:
        status = "fail"
    elif not seen_baseline:
        status = "fail" if fail_on_missing else "no_baseline"
    else:
        status = "fail" if (fail_on_missing and missing) else "pass"
    return {
        "status": status,
        "threshold": threshold,
        "benches": list(benches),
        "checked": len(comparisons),
        "regressions": regressions,
        "missing": missing,
    }


# ---------------------------------------------------------------------------
# Historical trend gating (--trend, over repro.obs.history.HistoryStore)
# ---------------------------------------------------------------------------


def _metric_series(history_records: list[dict[str, dict] | None],
                   key: str, metric: str) -> list[float]:
    """The metric's value in each historical run that has the record
    (oldest first)."""
    series = []
    for recs in history_records:
        if recs is None:
            continue
        rec = recs.get(key)
        if rec is None:
            continue
        v = rec.get(metric)
        if isinstance(v, (int, float)):
            series.append(float(v))
    return series


def _drift_row(series: list[float], current: float, key: str, metric: str,
               direction: str, threshold: float, window: int) -> dict | None:
    """Monotone-drift detector: every step over the trailing window moved
    the wrong way AND the cumulative move exceeds the threshold. Needs at
    least 3 historical points (4 values with the current run) so two noisy
    runs can't fake a trend."""
    values = series[-window:] + [current]
    if len(values) < 4:
        return None
    worse = (lambda a, b: b < a) if direction == HIGHER else (lambda a, b: b > a)
    if not all(worse(a, b) for a, b in zip(values, values[1:])):
        return None
    first = values[0]
    if abs(first) < 1e-12:
        return None
    if direction == HIGHER:
        change = (first - current) / abs(first)
    else:
        change = (current - first) / abs(first)
    if change <= threshold:
        return None
    return {
        "record": key,
        "metric": metric,
        "direction": direction,
        "kind": "drift",
        "previous": first,
        "current": current,
        "steps": len(values) - 1,
        "regression": change,
        "regressed": True,
    }


def run_trend_gate(history_dir: str, current_dir: str, *,
                   threshold: float = 0.15,
                   benches: tuple[str, ...] = BENCHES,
                   window: int = 5,
                   fail_on_missing: bool = False) -> dict:
    """Gate the current artifacts against the run history.

    Per matched metric, two detectors:

    * **median baseline** — the single-run ``compare`` formula against the
      median of the last ``window`` runs' values (robust to one outlier
      baseline run, unlike the previous-run-only diff);
    * **monotone drift** — see :func:`_drift_row` (slow regressions that
      never individually trip the threshold).

    On pass / no_baseline the current run is appended to the store, so the
    history is self-maintaining. Returns the verdict dict (adds
    ``mode: "trend"``, ``history_runs``, ``appended_run``).
    """
    from repro.obs.history import HistoryStore

    store = HistoryStore(history_dir)
    run_dirs = [store.run_dir(r["id"]) for r in store.last(window)]
    comparisons: list[dict] = []
    missing: list[dict] = []
    seen_baseline = False
    for bench in benches:
        cur = load_records(current_dir, bench)
        if cur is None:
            missing.append({"bench": bench, "side": "current"})
            continue
        history_records = [load_records(d, bench) for d in run_dirs]
        if not any(r is not None for r in history_records):
            missing.append({"bench": bench, "side": "history"})
            continue
        seen_baseline = True
        for key, cur_rec in cur.items():
            matched = False
            for metric, direction in METRIC_DIRECTIONS.items():
                c = cur_rec.get(metric)
                if not isinstance(c, (int, float)):
                    continue
                series = _metric_series(history_records, key, metric)
                if not series:
                    continue
                matched = True
                baseline = statistics.median(series)
                rows = compare({metric: baseline}, {metric: c}, key, threshold)
                for row in rows:
                    row["baseline_runs"] = len(series)
                comparisons.extend(rows)
                drift = _drift_row(series, float(c), key, metric,
                                   direction, threshold, window)
                if drift is not None:
                    comparisons.append(drift)
            if not matched:
                missing.append({"bench": bench, "side": "history",
                                "record": key})
    regressions = [c for c in comparisons if c["regressed"]]
    if regressions:
        status = "fail"
    elif not seen_baseline:
        status = "fail" if fail_on_missing else "no_baseline"
    else:
        status = "fail" if (fail_on_missing and missing) else "pass"
    verdict = {
        "status": status,
        "mode": "trend",
        "threshold": threshold,
        "window": window,
        "history_runs": len(store),
        "benches": list(benches),
        "checked": len(comparisons),
        "regressions": regressions,
        "missing": missing,
        "appended_run": None,
    }
    return verdict


def _append_history(history_dir: str, current_dir: str) -> str | None:
    """Fold the current artifacts into the store (post-verdict); a current
    dir with no BENCH artifacts appends nothing."""
    from repro.obs.history import HistoryStore

    try:
        return HistoryStore(history_dir).append(current_dir)
    except FileNotFoundError:
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--previous", default=None,
                    help="previous run's bench artifact directory "
                         "(single-run diff mode)")
    ap.add_argument("--current", default=os.environ.get("REPRO_BENCH_DIR", "."),
                    help="this run's bench artifact directory "
                         "(default: $REPRO_BENCH_DIR, else cwd)")
    ap.add_argument("--threshold", type=float,
                    default=float(os.environ.get("REPRO_GATE_THRESHOLD", 0.15)),
                    help="regression fraction that fails the gate "
                         "(default 0.15 = 15%%)")
    ap.add_argument("--benches", default=",".join(BENCHES),
                    help="comma list of bench artifacts to diff")
    ap.add_argument("--out", default=None,
                    help="verdict JSON path (default <current>/GATE_verdict.json)")
    ap.add_argument("--fail-on-missing", action="store_true",
                    help="also fail when a baseline artifact or record is "
                         "absent (default: pass with status no_baseline)")
    ap.add_argument("--trend", action="store_true",
                    help="gate against the run-history store instead of a "
                         "single previous run: median-of-last-K baseline + "
                         "monotone-drift detection; appends this run to the "
                         "store on pass")
    ap.add_argument("--history", default="bench-history",
                    help="HistoryStore root for --trend (default "
                         "bench-history; CI backs it with actions/cache)")
    ap.add_argument("--trend-window", type=int, default=5,
                    help="K: history runs in the median baseline / drift "
                         "window (default 5)")
    args = ap.parse_args(argv)

    benches = tuple(b for b in args.benches.split(",") if b)
    if args.trend:
        verdict = run_trend_gate(
            args.history, args.current,
            threshold=args.threshold,
            benches=benches,
            window=args.trend_window,
            fail_on_missing=args.fail_on_missing,
        )
    else:
        if args.previous is None:
            ap.error("--previous is required without --trend")
        verdict = run_gate(
            args.previous, args.current,
            threshold=args.threshold,
            benches=benches,
            fail_on_missing=args.fail_on_missing,
        )
    out = args.out or os.path.join(args.current, "GATE_verdict.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(verdict, f, indent=1)
    if args.trend and verdict["status"] in ("pass", "no_baseline"):
        # The verdict is written first so the stored run carries its own
        # GATE_verdict.json; a failing run is NOT appended (a regressed
        # run must not drag the median baseline down with it).
        verdict["appended_run"] = _append_history(args.history, args.current)
        with open(out, "w") as f:
            json.dump(verdict, f, indent=1)

    worst = sorted(verdict["regressions"],
                   key=lambda c: -c["regression"])[:10]
    for c in worst:
        kind = " (monotone drift)" if c.get("kind") == "drift" else ""
        print(f"GATE REGRESSION {c['record']} {c['metric']}: "
              f"{c['previous']:.4g} -> {c['current']:.4g} "
              f"({c['regression']:+.1%}, {c['direction']}-is-better){kind}")
    for m in verdict["missing"][:10]:
        print(f"gate: missing {m['side']} "
              f"{m.get('record', 'artifact for ' + m['bench'])}")
    trend_info = ""
    if args.trend:
        trend_info = (f" mode=trend history_runs={verdict['history_runs']} "
                      f"window={verdict['window']} "
                      f"appended={verdict['appended_run']}")
    print(f"GATE_{verdict['status'].upper()} checked={verdict['checked']} "
          f"regressions={len(verdict['regressions'])} "
          f"threshold={verdict['threshold']:.0%} verdict={out}{trend_info}")
    return 1 if verdict["status"] == "fail" else 0


if __name__ == "__main__":
    sys.exit(main())
