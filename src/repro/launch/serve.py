"""Posterior query serving front-end.

Serves posterior-functional queries from a pool of **resident ensembles**
(warm multi-chain sampler state, background refresh, request batching,
SLO-aware freshness — see :mod:`repro.serving` and docs/ARCHITECTURE.md):

    PYTHONPATH=src python -m repro.launch.serve --workload bayeslr --smoke
    PYTHONPATH=src python -m repro.launch.serve --workload stochvol \
        --queries 500 --max-batch 32 --deadline-ms 100
    PYTHONPATH=src python -m repro.launch.serve --workload bayeslr \
        --ckpt-dir /tmp/pool  # save on exit; restarts warm from the same dir

Per request class it reports p50/p95/p99 latency, deadline hit rate, and
snapshot staleness, then (always) cross-checks one served predictive
against the same functional computed offline from the identical snapshot
draws. ``--workload lm`` keeps the legacy LM decoding demo (batched
posterior-sample decoding with ``--arch`` / ``--prompt-len`` /
``--gen-len``; params restored from ``--ckpt-dir``).

``--fleet`` serves through the sharded fleet instead (:mod:`repro.fleet`):
writer resident ensembles per workload shard stream snapshot deltas to
``--replicas`` read replicas, and a priority-aware router with admission
control spreads requests across the replica lanes:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m repro.launch.serve --fleet --workload bayeslr --smoke --mesh 2d
    python -m repro.launch.serve --fleet --devices 4 --replicas 3 \
        --replica-transport proc --workload bayeslr

(``--devices N`` forces N virtual host devices before JAX initializes —
one process group hosting the writer mesh and the replicas.)

``--subposterior P`` turns the fleet data-parallel: the observation pool
is stride-partitioned into P shards, each with its own writer group
sampling the local slice under the ``p(theta)^(1/P)`` tempered prior, and
the router recombines the per-partition windows at query time
(``--combine consensus|product``). ``--stream`` demos the append-only
target mode: a fresh observation chunk is folded into the *running*
writers mid-serve (no restart) and the freshness gate refuses the
pre-append windows:

    python -m repro.launch.serve --subposterior 2 --smoke
    python -m repro.launch.serve --subposterior 4 --stream --workload bayeslr
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import ARCHS

POSTERIOR_WORKLOADS = ("bayeslr", "stochvol", "jointdpm", "ppl")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", default="bayeslr",
                    choices=POSTERIOR_WORKLOADS + ("lm",),
                    help="posterior workload to serve (or 'lm' for the "
                         "legacy decoding demo)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: small model, >=100 queries, parity check")
    ap.add_argument("--queries", type=int, default=None,
                    help="number of requests to serve (default: 120 smoke, 400 full)")
    ap.add_argument("--rows-per-query", type=int, default=8,
                    help="request rows (test points / quantile levels) per query")
    ap.add_argument("--chains", type=int, default=None,
                    help="resident chains K (default: 4 smoke, 8 full)")
    ap.add_argument("--refresh-steps", type=int, default=None,
                    help="transitions per refresh block (default: 16 smoke, 64 full)")
    ap.add_argument("--window", type=int, default=None,
                    help="posterior draws retained per chain (default: 32 smoke, 128 full)")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="requests coalesced into one evaluation")
    ap.add_argument("--micro-batch", type=int, default=64,
                    help="request rows per compiled evaluation chunk")
    ap.add_argument("--deadline-ms", type=float, default=250.0,
                    help="per-request latency SLO")
    ap.add_argument("--max-staleness-s", type=float, default=30.0,
                    help="freshness: oldest admissible snapshot age")
    ap.add_argument("--min-draws", type=int, default=None,
                    help="freshness: min cross-chain draws before serving "
                         "(default: chains * window / 2)")
    ap.add_argument("--background", action="store_true",
                    help="refresh on a background thread while serving "
                         "(default: refresh synchronously when stale)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="posterior pool: restore-if-present + save-on-exit; "
                         "lm: restore params (a posterior sample)")
    ap.add_argument("--seed", type=int, default=0)
    # -- sharded serving fleet (--fleet) -----------------------------------
    fl = ap.add_argument_group("sharded serving fleet (--fleet)")
    fl.add_argument("--fleet", action="store_true",
                    help="serve through the writer/replica fleet "
                         "(repro.fleet) instead of the single pool")
    fl.add_argument("--replicas", type=int, default=2,
                    help="read replicas per workload shard")
    fl.add_argument("--fleet-shards", type=int, default=1,
                    help="independent writer shards per workload")
    fl.add_argument("--replica-transport", default="inproc",
                    choices=("inproc", "proc"),
                    help="replica hosting: in-process objects or one OS "
                         "process per replica (the scaling configuration)")
    fl.add_argument("--mesh", default="auto", choices=("auto", "2d", "off"),
                    help="writer ensemble sharding: 'auto' (1-d chain mesh "
                         "when devices allow), '2d' (chains x data), 'off'")
    fl.add_argument("--devices", type=int, default=None,
                    help="force N virtual host devices (XLA_FLAGS) before "
                         "JAX initializes — the fleet's process group size")
    fl.add_argument("--max-depth", type=int, default=256,
                    help="admission: queue depth before shedding starts")
    fl.add_argument("--max-miss-rate", type=float, default=0.5,
                    help="admission: predicted deadline-miss rate threshold")
    fl.add_argument("--subposterior", type=int, default=1, metavar="P",
                    help="data-parallel subposterior MCMC: partition the "
                         "observations into P shards, run a writer group "
                         "per shard under the p(theta)^(1/P) tempered "
                         "prior, recombine draws at query time (implies "
                         "--fleet; P=1 is the unpartitioned fleet)")
    fl.add_argument("--combine", default="consensus",
                    choices=("consensus", "product"),
                    help="subposterior draw-combination rule: consensus "
                         "weighted averaging or Gaussian density-product")
    fl.add_argument("--autoscale", action="store_true",
                    help="closed-loop replica autoscaling: a control loop "
                         "over the recorded admission/SLO signals adds "
                         "replicas under overload and retires them after "
                         "quiesce (fleet/soak modes; implies --fleet)")
    fl.add_argument("--autoscale-max", type=int, default=None,
                    help="autoscaler replica ceiling per workload "
                         "(default: launch replicas + 2)")
    fl.add_argument("--autoscale-cooldown", type=float, default=2.0,
                    help="seconds between autoscaler actuations")
    fl.add_argument("--stream", action="store_true",
                    help="streaming append-only target demo: mid-serve, "
                         "append a fresh observation chunk into the running "
                         "writers (no restart) and prove the staleness "
                         "gate refuses pre-append windows (implies --fleet)")
    # -- observability (repro.obs) ------------------------------------------
    ob = ap.add_argument_group("observability")
    ob.add_argument("--stats-addr", default=None, metavar="HOST:PORT",
                    help="expose the live metric rollup as JSON over HTTP "
                         "(port 0 = ephemeral); prints a STATS_OK self-check")
    ob.add_argument("--obs-dir", default=os.environ.get("REPRO_OBS_DIR"),
                    help="write per-run JSONL metric streams + summary.json "
                         "under this directory (default: $REPRO_OBS_DIR, "
                         "else in-memory only)")
    ob.add_argument("--alerts", action="store_true",
                    help="evaluate the standard alert ruleset (threshold / "
                         "SLO burn-rate / anomaly rules with a pending-"
                         "firing-resolved state machine) over the live "
                         "rollup; transitions land on the 'alerts' stream, "
                         "/alerts + /health appear on --stats-addr, and an "
                         "ALERTS_OK self-check prints on exit")
    ob.add_argument("--soak", action="store_true",
                    help="chaos soak: sustained mixed-class load on the "
                         "fleet while one replica is killed and restarted "
                         "mid-load; prints SOAK_OK with recovery counters")
    ob.add_argument("--soak-seconds", type=float, default=None,
                    help="soak load duration (default: 6 smoke, 30 full)")
    ob.add_argument("--trace-dir", default=None,
                    help="end-to-end request tracing: tee every span to "
                         "<dir>/spans.jsonl and export a Chrome/Perfetto "
                         "<dir>/trace.json on exit (prints TRACE_OK)")
    ob.add_argument("--profile-dir", default=None,
                    help="capture one jax.profiler trace of one steady "
                         "writer refresh block, after warm-up, into this "
                         "directory, with the program's repro.* spans "
                         "(no-op when the profiler is unavailable)")
    # -- legacy LM decoding flags (only read under --workload lm) ----------
    lm = ap.add_argument_group("lm decoding demo (--workload lm)")
    lm.add_argument("--arch", default="xlstm-350m", choices=list(ARCHS))
    lm.add_argument("--reduced", action="store_true")
    lm.add_argument("--batch", type=int, default=8)
    lm.add_argument("--prompt-len", type=int, default=64)
    lm.add_argument("--gen-len", type=int, default=64)
    lm.add_argument("--model-parallel", type=int, default=1)
    return ap


# ---------------------------------------------------------------------------
# Observability wiring (repro.obs)
# ---------------------------------------------------------------------------


def _setup_obs(args, source=None):
    """Recorder + optional HTTP stats endpoint + SLO sampler + tracer for a
    serve run, or (None, None, None, None) when no observability flag is
    set."""
    if not (args.stats_addr is not None or args.obs_dir or args.soak
            or args.trace_dir or args.profile_dir or args.alerts
            or args.autoscale):
        return None, None, None, None
    from repro.obs import Recorder, SLOSampler, StatsServer, Tracer

    recorder = Recorder(
        args.obs_dir,
        meta={"workload": args.workload, "argv": sys.argv[1:]},
    )
    tracer = None
    if args.trace_dir:
        tracer = Tracer(
            recorder=recorder,
            jsonl_path=os.path.join(args.trace_dir, "spans.jsonl"),
        )
        print(f"trace: spans tee to {args.trace_dir}/spans.jsonl")
    server = None
    if args.stats_addr is not None:
        server = StatsServer(recorder, args.stats_addr, tracer=tracer)
        print(f"stats: live rollup at {server.url}")
    sampler = SLOSampler(recorder, source) if source is not None else None
    return recorder, server, sampler, tracer


def _setup_alerts(args, recorder, stats_server, workload, fleet=None):
    """AlertEngine over the run's recorder, wired into the stats endpoint
    (``/alerts`` and a component-health ``/health``), or None with
    ``--alerts`` off — the request path then never sees any of this."""
    if not args.alerts or recorder is None:
        return None
    from repro.obs import default_rules, health_report
    from repro.obs.alerts import AlertEngine

    rules = default_rules(
        args.workload, workload.default_class,
        deadline_ms=args.deadline_ms, max_depth=args.max_depth,
    )
    engine = AlertEngine(recorder, rules)
    if stats_server is not None:
        stats_server.alerts = engine
        stats_server.health = lambda: health_report(
            recorder.rollup(),
            fleet_report=fleet.report() if fleet is not None else None,
            alert_status=engine.status(),
            max_depth=args.max_depth if fleet is not None else None,
        )
        print(f"alerts: {len(rules)} rules over the live rollup; "
              f"/alerts and /health at {stats_server.url}")
    else:
        print(f"alerts: {len(rules)} rules over the live rollup")
    return engine


def _setup_autoscaler(args, fleet, router, recorder, engine):
    """The closed-loop actuator (``--autoscale``): scale between the launch
    replica count and ``--autoscale-max`` on the admission/SLO signals (and
    the overload alerts, when ``--alerts`` is also on)."""
    if not args.autoscale:
        return None
    from repro.fleet import AutoScaleConfig, AutoScaler

    launch = fleet.replica_count(args.workload)
    ceiling = args.autoscale_max
    if ceiling is None:
        ceiling = launch + 2
    config = AutoScaleConfig(
        min_replicas=launch,
        max_replicas=max(ceiling, launch),
        scale_up_depth=args.max_depth,
        scale_down_depth=max(args.max_depth // 16, 2),
        quiesce_ticks=2,
        cooldown_s=args.autoscale_cooldown,
    )
    scaler = AutoScaler(fleet, router, args.workload, config,
                        recorder=recorder, engine=engine)
    print(f"autoscale: replicas {launch}..{config.max_replicas}, "
          f"scale_up_depth={config.scale_up_depth} "
          f"scale_down_depth={config.scale_down_depth} "
          f"cooldown={config.cooldown_s}s")
    return scaler


def _alerts_selfcheck(engine, server) -> bool:
    """The ALERTS_OK line CI greps: the engine evaluated at least once and,
    when an endpoint is up, ``/alerts`` serves its live status."""
    ok = engine.evaluations >= 1
    if server is not None:
        import urllib.request

        import json as _json

        try:
            with urllib.request.urlopen(server.url.rstrip("/") + "/alerts",
                                        timeout=10) as resp:
                ok = ok and bool(_json.loads(resp.read()).get("available"))
        except Exception:  # noqa: BLE001 — an unreachable endpoint is a fail
            ok = False
    firing = ",".join(engine.firing()) or "-"
    line = "ALERTS_OK" if ok else "ALERTS_FAIL"
    print(f"{line} rules={len(engine.rules)} "
          f"evaluations={engine.evaluations} "
          f"transitions={engine.transitions} fired={engine.fired_total} "
          f"resolved={engine.resolved_total} firing={firing}")
    return ok


def _obs_num_sections(ensemble):
    """``num_sections`` of a serving ensemble's target(s), in the shape
    :func:`repro.obs.record_transition_cost` wants: an int for a
    builder-constructed single target, a per-op dict for a composite
    ``cycle()`` transition, None when nothing is subsampled."""
    if ensemble.target is not None:
        return int(ensemble.target.num_sections)
    transition = getattr(ensemble, "transition", None)
    if transition is not None and hasattr(transition, "mh_ops"):
        names = transition.names
        return {
            names[i]: int(op.target.num_sections)
            for i, op in transition.mh_ops
        }
    return None


def _record_transition_cost(recorder, workload_name, snap, num_sections):
    from repro.obs import record_transition_cost

    record_transition_cost(
        recorder, workload_name, snap.summary, num_sections=num_sections
    )


def _profile_block(recorder, args, pool, run_block) -> None:
    """``--profile-dir``: one ``jax.profiler`` capture of one steady
    refresh block (``run_block``), taken after warm-up, with a program
    tracer on ``pool`` so the capture holds the ``repro.refresh.*``
    annotations beside the device ops. Noted on the ``profile`` stream; a
    profiler that cannot start leaves serving untouched."""
    from repro.obs import Tracer

    try:
        jax.profiler.start_trace(args.profile_dir)
    except Exception as e:  # noqa: BLE001 — profiling must never break serving
        print(f"profile: jax.profiler unavailable ({type(e).__name__}: {e})")
        return
    own_tracer = pool.tracer is None
    if own_tracer:
        pool.tracer = Tracer()
    try:
        run_block()
    finally:
        jax.profiler.stop_trace()
        if own_tracer:
            pool.tracer = None
    recorder.record("profile", {
        "workload": args.workload,
        "capture_dir": args.profile_dir,
        "tool": "jax.profiler",
    })
    print(f"profile: jax.profiler capture in {args.profile_dir}")


def _stats_selfcheck(server) -> bool:
    """Fetch our own endpoint and print STATS_OK/STATS_FAIL — the CI-style
    proof that the rollup is reachable and carries the headline fields."""
    import urllib.request

    import json as _json

    with urllib.request.urlopen(server.url, timeout=10) as resp:
        roll = _json.loads(resp.read())
    streams = roll.get("streams", {})
    slo_last = streams.get("slo", {}).get("last", {})
    snap_last = streams.get("snapshot", {}).get("last", {})
    ok = (
        "req_per_s" in slo_last and "p95_ms" in slo_last
        and "shed" in slo_last and "staleness_s" in snap_last
    )
    sublinear = ""
    try:
        with urllib.request.urlopen(server.url.rstrip("/") + "/sublinear",
                                    timeout=10) as resp:
            sub = _json.loads(resp.read())
        frac = sub.get("frac_data_touched", {}).get("mean") \
            if isinstance(sub.get("frac_data_touched"), dict) else None
        if frac is not None:
            sublinear = f" frac_data_touched={frac:.4f}"
    except Exception:  # noqa: BLE001 — the sublinear view is informational
        pass
    line = "STATS_OK" if ok else "STATS_FAIL"
    print(f"{line} url={server.url} streams={sorted(streams)} "
          f"req_per_s={slo_last.get('req_per_s', float('nan')):.0f} "
          f"p95_ms={slo_last.get('p95_ms', float('nan')):.2f} "
          f"shed={slo_last.get('shed', 'n/a')} "
          f"staleness_s={snap_last.get('staleness_s', float('nan')):.3f}"
          f"{sublinear}")
    return ok


def _teardown_obs(recorder, server, tracer=None, trace_dir=None) -> None:
    if server is not None:
        server.close()
    if tracer is not None:
        if trace_dir:
            _export_trace(tracer, trace_dir)
        tracer.close()
    if recorder is not None:
        path = recorder.close()
        if path:
            print(f"obs: metric streams + summary in {recorder.dir}")


def _export_trace(tracer, trace_dir) -> None:
    """Write the Chrome/Perfetto export next to the spans tee and print the
    TRACE_OK line CI greps (and uploads as an artifact)."""
    from repro.obs.trace import export_chrome_trace

    spans = tracer.spans()
    out = export_chrome_trace(spans, os.path.join(trace_dir, "trace.json"))
    n_traces = len({s.get("trace_id") for s in spans if s.get("trace_id")})
    print(f"TRACE_OK spans={len(spans)} traces={n_traces} "
          f"dropped={tracer.dropped} export={out}")


# ---------------------------------------------------------------------------
# Posterior serving path
# ---------------------------------------------------------------------------


def _offline_reference(workload, spec, snap, xs) -> np.ndarray | None:
    """Recompute the served functional offline (numpy / per-draw loop) from
    the *same* snapshot draws — the acceptance cross-check. Returns None when
    the workload has no independent closed form wired up."""
    if workload.name in ("bayeslr", "ppl") and spec.name == "predictive":
        from repro.experiments import bayeslr

        w = np.asarray(jax.tree.leaves(snap.draws)[0])
        w = w.reshape(-1, w.shape[-1])  # (S, D)
        return bayeslr.predictive_mean_prob(w, np.asarray(xs))[-1]
    if workload.name == "stochvol" and spec.name == "vol_quantile":
        # Per-draw stationary log-vol scale in the draws' own float32 (as the
        # served functional computes it), then float64 numpy quantiles.
        phi = np.asarray(snap.draws["phi"]).reshape(-1)
        s2 = np.clip(np.asarray(snap.draws["sigma2"]).reshape(-1), 1e-12, None)
        vol = np.sqrt(s2 / np.clip(1.0 - phi ** 2, 1e-6, None)).astype(np.float64)
        return np.quantile(vol, np.clip(np.asarray(xs, np.float64), 0.0, 1.0))
    return None


def serve_posterior(args) -> int:
    from repro.serving import (
        EnsemblePool,
        FreshnessPolicy,
        RequestQueue,
        ServingConfig,
    )

    smoke = args.smoke
    dflt = lambda v, d: d if v is None else v
    chains = dflt(args.chains, 4 if smoke else 8)
    refresh_steps = dflt(args.refresh_steps, 16 if smoke else 64)
    window = dflt(args.window, 32 if smoke else 128)
    num_queries = dflt(args.queries, 120 if smoke else 400)
    # --min-draws 0 is meaningful (disable the draw-count freshness floor)
    min_draws = dflt(args.min_draws, max(chains * window // 2, chains))
    config = ServingConfig(
        num_chains=chains,
        refresh_steps=refresh_steps,
        window=window,
        micro_batch=args.micro_batch,
        max_batch=args.max_batch,
        freshness=FreshnessPolicy(
            max_staleness_s=args.max_staleness_s, min_draws=min_draws
        ),
        default_deadline_s=args.deadline_ms / 1e3,
        seed=args.seed,
    )
    print(f"pool: workload={args.workload} K={chains} refresh={refresh_steps} "
          f"window={window} min_draws={min_draws} "
          f"max_staleness={args.max_staleness_s}s")
    pool = EnsemblePool(config)
    pool.add_workload(args.workload, smoke=smoke, seed=args.seed)
    workload = pool.workload(args.workload)
    print(f"target: {workload.description}; request classes: "
          f"{sorted(workload.query_specs)}")

    restored = None
    if args.ckpt_dir:
        from repro.checkpoint.manager import latest_step

        if latest_step(args.ckpt_dir) is not None:
            restored = pool.restore(args.ckpt_dir)
            print(f"restored warm pool from {args.ckpt_dir} (step {restored})")

    t0 = time.perf_counter()
    pool.warm()
    warm_s = time.perf_counter() - t0
    resident = pool.resident(args.workload)
    print(f"warm in {warm_s:.1f}s: {resident.steps_done} transitions/chain "
          f"resident ({chains * resident.steps_done} total)")
    # Compile each request class's evaluator outside the measured window
    # (a cold query would otherwise charge XLA compile time to its batch).
    wkey = jax.random.key(args.seed + 2)
    for cls in sorted(workload.query_specs):
        wkey, sub = jax.random.split(wkey)
        pool.query(args.workload, cls,
                   workload.query_specs[cls].make_queries(sub, args.rows_per_query))
    queue = RequestQueue(pool, max_batch=args.max_batch,
                         default_deadline_s=args.deadline_ms / 1e3)
    recorder, stats_server, sampler, tracer = _setup_obs(args, source=queue)
    queue.tracer = tracer
    pool.tracer = tracer
    if args.profile_dir:
        _profile_block(recorder, args, pool, resident.refresh)
    if args.background:
        pool.start()
    engine = _setup_alerts(args, recorder, stats_server, workload)
    num_sections = _obs_num_sections(resident.ensemble)
    classes = sorted(workload.query_specs)
    qkey = jax.random.key(args.seed + 1)
    t0 = time.perf_counter()
    served = 0
    # Submit in bursts (1..max_batch) so the batcher actually coalesces.
    burst = max(2, args.max_batch // 2)
    for i in range(0, num_queries, burst):
        take = min(burst, num_queries - i)
        for j in range(take):
            cls = classes[(i + j) % len(classes)]
            qkey, sub = jax.random.split(qkey)
            xs = workload.query_specs[cls].make_queries(sub, args.rows_per_query)
            queue.submit(args.workload, cls, xs)
        served += len(queue.drain())
        if sampler is not None:
            sampler.sample()
            from repro.obs import record_snapshot

            snap_now = pool.resident(args.workload).snapshot()
            record_snapshot(recorder, args.workload, snap_now)
            _record_transition_cost(recorder, args.workload, snap_now,
                                    num_sections)
            if engine is not None:
                engine.evaluate()
    wall = time.perf_counter() - t0
    report = queue.slo_report()

    print(f"\nserved {served} requests "
          f"({args.rows_per_query} rows each) in {wall:.2f}s "
          f"({served / max(wall, 1e-9):.0f} req/s)")
    for cls, entry in report["classes"].items():
        if not entry.get("count"):
            print(f"  {cls:28s} ALL {entry['errors']} requests FAILED")
            continue
        print(f"  {cls:28s} p50={entry['p50_ms']:7.2f}ms "
              f"p95={entry['p95_ms']:7.2f}ms p99={entry['p99_ms']:7.2f}ms "
              f"deadline_hit={entry['deadline_hit_rate']:.1%} "
              f"batch~{entry['mean_batch_size']:.1f} "
              f"staleness~{entry.get('staleness_mean_s', float('nan')):.3f}s")
    if report["errors"]:
        print(f"  WARNING: {report['errors']} request(s) failed")
    snap_report = pool.slo_snapshot_report()[args.workload]
    print(f"  snapshot: staleness={snap_report['staleness_s']:.3f}s "
          f"draws={snap_report['num_draws']} "
          f"steps_done={snap_report['steps_done']} fresh={snap_report['fresh']}")

    # -- parity: a served predictive vs the same functional offline --------
    spec = workload.query_specs[workload.default_class]
    qkey, sub = jax.random.split(qkey)
    xs = spec.make_queries(sub, 16)
    snap = pool.ensure_fresh(args.workload)
    served_vals, snap = pool.query(
        args.workload, workload.default_class, xs, snapshot=snap
    )
    ref = _offline_reference(workload, spec, snap, xs)
    parity = "n/a"
    if ref is not None:
        err = float(np.max(np.abs(served_vals - ref)))
        if not np.allclose(served_vals, ref, rtol=1e-4, atol=1e-5):
            print(f"PARITY FAIL: served vs offline max|delta|={err:.3g}")
            return 1
        parity = f"ok(max|delta|={err:.2g})"
        print(f"  parity: served {workload.default_class} == offline "
              f"{spec.name} from the same draws ({parity})")

    if args.ckpt_dir:
        path = pool.save(args.ckpt_dir)
        print(f"saved warm pool to {path}")
    if args.background:
        pool.stop()

    stats_ok = alerts_ok = True
    if recorder is not None:
        from repro.obs import record_adaptation

        snap = pool.resident(args.workload).snapshot()
        record_adaptation(recorder, args.workload, snap.summary)
        _record_transition_cost(recorder, args.workload, snap, num_sections)
        if engine is not None:
            engine.evaluate()
            alerts_ok = _alerts_selfcheck(engine, stats_server)
        if stats_server is not None:
            stats_ok = _stats_selfcheck(stats_server)
        _teardown_obs(recorder, stats_server, tracer, args.trace_dir)

    first = next(
        (e for e in report["classes"].values() if e.get("count")), None
    )
    if first is None or report["errors"] or not stats_ok or not alerts_ok:
        print(f"SERVE_FAIL workload={args.workload} errors={report['errors']}")
        return 1
    # New fields go AFTER parity= so existing CI greps keep matching.
    print(f"SERVE_OK workload={args.workload} queries={served} "
          f"p50_ms={first['p50_ms']:.2f} p95_ms={first['p95_ms']:.2f} "
          f"deadline_hit={first['deadline_hit_rate']:.3f} "
          f"staleness_s={snap_report['staleness_s']:.3f} parity={parity}"
          + (f" alerts_fired={engine.fired_total}"
             if engine is not None else ""))
    if smoke:
        assert served >= 100, f"smoke must serve >=100 queries, served {served}"
    return 0


# ---------------------------------------------------------------------------
# Sharded serving fleet (--fleet)
# ---------------------------------------------------------------------------


def _build_fleet(args):
    """Config + fleet + workload registration shared by the fleet and soak
    paths; returns (fleet, workload, classes)."""
    from repro.fleet import Fleet, FleetConfig
    from repro.serving import FreshnessPolicy, ServingConfig

    smoke = args.smoke
    dflt = lambda v, d: d if v is None else v
    chains = dflt(args.chains, 4 if smoke else 8)
    refresh_steps = dflt(args.refresh_steps, 16 if smoke else 64)
    window = dflt(args.window, 32 if smoke else 128)
    min_draws = dflt(args.min_draws, max(chains * window // 2, chains))
    mesh = {"auto": "auto", "2d": ("chains", "data"), "off": False}[args.mesh]
    config = FleetConfig(
        replicas=args.replicas,
        shards=args.fleet_shards,
        transport=args.replica_transport,
        mesh=mesh,
        subposterior=args.subposterior,
        combine=args.combine,
        serving=ServingConfig(
            num_chains=chains,
            refresh_steps=refresh_steps,
            window=window,
            micro_batch=args.micro_batch,
            max_batch=args.max_batch,
            freshness=FreshnessPolicy(
                max_staleness_s=args.max_staleness_s, min_draws=min_draws
            ),
            default_deadline_s=args.deadline_ms / 1e3,
            seed=args.seed,
        ),
    )
    print(f"fleet: workload={args.workload} shards={args.fleet_shards} "
          f"replicas={args.replicas}/shard transport={args.replica_transport} "
          f"mesh={args.mesh} devices={len(jax.devices())} K={chains} "
          f"refresh={refresh_steps} window={window} "
          f"subposterior={args.subposterior} combine={args.combine}")
    fleet = Fleet(config)
    fleet.add_workload(args.workload, smoke=smoke, seed=args.seed)
    workload = fleet.workload(args.workload)
    classes = sorted(workload.query_specs)
    print(f"target: {workload.description}; request classes: {classes}")
    return fleet, workload, classes


def _build_router(args, fleet, workload):
    """Priority/admission router over a fleet: the default class outranks
    the rest, so under overload the low classes are shed first."""
    from repro.fleet import AdmissionConfig, FleetRouter

    priorities = {cls: 0 for cls in sorted(workload.query_specs)}
    priorities[workload.default_class] = 1
    return FleetRouter(
        fleet,
        priorities=priorities,
        admission=AdmissionConfig(
            max_depth=args.max_depth, max_miss_rate=args.max_miss_rate
        ),
        max_batch=args.max_batch,
        default_deadline_s=args.deadline_ms / 1e3,
    )


def _compile_lanes(args, fleet, workload, router=None):
    """Compile every replica lane's evaluators outside the measured window."""
    wkey = jax.random.key(args.seed + 2)
    for shard in fleet.shards(args.workload):
        for replica in shard.replicas:
            for cls in sorted(workload.query_specs):
                wkey, sub = jax.random.split(wkey)
                spec = workload.query_specs[cls]
                replica.serve(spec, cls, spec.make_queries(sub, args.rows_per_query))
    if router is not None and args.subposterior > 1:
        # Partitioned workloads serve through the router's combined window,
        # whose evaluator is distinct from the lanes' — warm it too so the
        # first measured query doesn't pay XLA compile + first combination.
        for cls in sorted(workload.query_specs):
            wkey, sub = jax.random.split(wkey)
            spec = workload.query_specs[cls]
            router._serve_combined(
                args.workload, cls, spec.make_queries(sub, args.rows_per_query)
            )


def _stream_append(args, fleet) -> int:
    """The --stream demo: append a bootstrap-resampled observation chunk
    into the running writers mid-serve, prove the staleness gate flipped
    (pre-append windows read as infinitely stale), then pump one
    refresh+broadcast round so serving continues against the grown
    posterior. Returns the number of appended rows."""
    from repro.core import spec_of

    base = fleet.workload(args.workload)
    if base.ensemble.target is None:
        raise RuntimeError(
            f"--stream needs a builder-constructed target; workload "
            f"{args.workload!r} runs a composite transition"
        )
    spec = spec_of(base.ensemble.target)
    rng = np.random.default_rng(args.seed + 7)
    n = int(spec.num_sections)
    k = max(8, n // 16)
    idx = rng.integers(0, n, size=k)
    chunk = jax.tree.map(lambda a: np.asarray(a)[idx], spec.data)
    added = fleet.append_observations(args.workload, chunk)
    stale = [
        s.writer.snapshot().staleness_s for s in fleet.shards(args.workload)
    ]
    grew = [s for s in stale if not np.isfinite(s)]
    fleet.pump(args.workload)  # fold the grown targets into fresh windows
    print(f"STREAM_OK appended={added} rows mid-serve; "
          f"{len(grew)}/{len(stale)} writer(s) marked stale by the append, "
          f"refreshed without restart")
    return added


def serve_fleet(args) -> int:
    smoke = args.smoke
    dflt = lambda v, d: d if v is None else v
    num_queries = dflt(args.queries, 120 if smoke else 400)
    fleet, workload, classes = _build_fleet(args)

    restored = None
    if args.ckpt_dir:
        from repro.checkpoint.manager import latest_step

        if latest_step(args.ckpt_dir) is not None:
            restored = fleet.restore(args.ckpt_dir)
            print(f"restored warm fleet from {args.ckpt_dir} (step {restored})")

    t0 = time.perf_counter()
    fleet.warm()
    warm_s = time.perf_counter() - t0
    shard0 = fleet.shards(args.workload)[0]
    print(f"warm in {warm_s:.1f}s: writers at "
          f"{[s.writer.steps_done for s in fleet.shards(args.workload)]} "
          f"transitions/chain, replicas synced to "
          f"{[r.version for r in shard0.replicas]}")

    router = _build_router(args, fleet, workload)
    recorder, stats_server, sampler, tracer = _setup_obs(args, source=router)
    router.tracer = tracer
    fleet.pool.tracer = tracer
    engine = _setup_alerts(args, recorder, stats_server, workload, fleet)
    scaler = _setup_autoscaler(args, fleet, router, recorder, engine)
    num_sections = _obs_num_sections(shard0.writer.ensemble)
    _compile_lanes(args, fleet, workload, router)
    if args.profile_dir:
        _profile_block(recorder, args, fleet.pool,
                       lambda: fleet.pump(args.workload))
    if args.background:
        fleet.start()
        router.start_workers()

    qkey = jax.random.key(args.seed + 1)
    burst = max(2, args.max_batch // 2)
    t0 = time.perf_counter()
    served = 0
    stream_rows = 0
    streamed = False
    pending = []
    for i in range(0, num_queries, burst):
        take = min(burst, num_queries - i)
        for j in range(take):
            cls = classes[(i + j) % len(classes)]
            qkey, sub = jax.random.split(qkey)
            xs = workload.query_specs[cls].make_queries(sub, args.rows_per_query)
            pending.append(router.submit(args.workload, cls, xs))
        if args.background:
            # done.wait, not result(): a shed/errored request must pace the
            # burst loop, not crash it (shedding is the feature under test).
            pending[-1].done.wait(timeout=60.0)
        else:
            served += len(router.drain())
            if (i // burst) % 8 == 7:
                fleet.pump(args.workload)  # stream fresh deltas mid-serve
        if args.stream and not streamed and i + burst >= num_queries // 2:
            stream_rows = _stream_append(args, fleet)
            streamed = True
        if sampler is not None and (i // burst) % 4 == 3:
            from repro.obs import record_fleet_sync

            sampler.sample()
            record_fleet_sync(recorder, fleet)
            _record_transition_cost(recorder, args.workload,
                                    shard0.writer.snapshot(), num_sections)
            if engine is not None:
                engine.evaluate()
            if scaler is not None:
                scaler.tick()
    if args.background:
        for req in pending:
            req.done.wait(timeout=60.0)
        # Shed requests complete instantly with error="shed: ..." — they
        # must not inflate the served count (the sync path's drain() never
        # sees them, so both modes now agree).
        served = len([
            r for r in pending
            if r.done.is_set() and not (r.error or "").startswith("shed")
        ])
    wall = time.perf_counter() - t0
    stats_ok = alerts_ok = True
    if sampler is not None:
        from repro.obs import record_adaptation, record_fleet_sync, record_snapshot

        sampler.sample()
        record_fleet_sync(recorder, fleet)
        snap = shard0.writer.snapshot()
        record_snapshot(recorder, args.workload, snap)
        record_adaptation(recorder, args.workload, snap.summary)
        _record_transition_cost(recorder, args.workload, snap, num_sections)
        if engine is not None:
            engine.evaluate()
            alerts_ok = _alerts_selfcheck(engine, stats_server)
        if stats_server is not None:
            stats_ok = _stats_selfcheck(stats_server)
    report = router.slo_report()

    print(f"\nserved {served} requests ({args.rows_per_query} rows each) in "
          f"{wall:.2f}s ({served / max(wall, 1e-9):.0f} req/s) across "
          f"{args.fleet_shards * args.replicas} replica lane(s)")
    for cls, entry in report["classes"].items():
        if not entry.get("count"):
            print(f"  {cls:28s} admitted={entry.get('admitted', 0)} "
                  f"shed={entry.get('shed', 0)} (nothing served)")
            continue
        print(f"  {cls:28s} p50={entry['p50_ms']:7.2f}ms "
              f"p95={entry['p95_ms']:7.2f}ms p99={entry['p99_ms']:7.2f}ms "
              f"deadline_hit={entry['deadline_hit_rate']:.1%} "
              f"prio={entry['priority']} admitted={entry['admitted']} "
              f"shed={entry['shed']} "
              f"staleness~{entry.get('staleness_mean_s', float('nan')):.3f}s")
    adm = report["admission"]
    print(f"  admission: depth={adm['depth']} "
          f"predicted_miss={adm['predicted_miss_rate']:.3f} "
          f"shed_floor={adm['shed_floor']} total_shed={report['shed']}")
    sync = fleet.sync_stats
    ratio = sync["delta_wire_bytes"] / max(sync["full_wire_bytes"], 1)
    print(f"  delta stream: {sync['syncs']} syncs, "
          f"{sync['delta_wire_bytes']} delta bytes vs "
          f"{sync['full_wire_bytes']} full-snapshot bytes "
          f"({ratio:.2f}x)")

    if args.background:
        router.stop_workers()
        fleet.stop()

    # -- parity: a replica's answer vs the writer's from the same version --
    fleet.sync_all()  # replicas now mirror the writers exactly
    spec = workload.query_specs[workload.default_class]
    qkey, sub = jax.random.split(qkey)
    xs = spec.make_queries(sub, 16)
    w_vals, w_snap = shard0.writer.query(spec, xs)
    r_vals, _ = shard0.replicas[0].serve(spec, workload.default_class, xs)
    err = float(np.max(np.abs(np.asarray(w_vals) - np.asarray(r_vals)))) if len(xs) else 0.0
    if not np.array_equal(np.asarray(w_vals), np.asarray(r_vals)):
        print(f"PARITY FAIL: replica vs writer max|delta|={err:.3g} "
              f"(writer v{w_snap.steps_done}, replica v{shard0.replicas[0].version})")
        _teardown_obs(recorder, stats_server, tracer, args.trace_dir)
        fleet.close()
        return 1
    parity = "ok(bitexact)"
    print(f"  parity: replica {workload.default_class} == writer from the "
          f"same delta-streamed window ({parity})")

    if args.ckpt_dir:
        path = fleet.save(args.ckpt_dir)
        print(f"saved warm fleet to {path}")
    _teardown_obs(recorder, stats_server, tracer, args.trace_dir)
    fleet.close()

    first = next((e for e in report["classes"].values() if e.get("count")), None)
    if (first is None or report["errors"] or (smoke and served < 100)
            or not stats_ok or not alerts_ok):
        # The smoke floor gates BEFORE SERVE_OK: CI greps the log, so a
        # failed smoke must never have printed the success line.
        print(f"SERVE_FAIL workload={args.workload} fleet=1 "
              f"errors={report['errors']} served={served}")
        return 1
    # New fields go AFTER parity= so existing CI greps keep matching.
    print(f"SERVE_OK workload={args.workload} fleet=1 "
          f"shards={args.fleet_shards} replicas={args.replicas} "
          f"queries={served} p50_ms={first['p50_ms']:.2f} "
          f"p95_ms={first['p95_ms']:.2f} "
          f"deadline_hit={first['deadline_hit_rate']:.3f} "
          f"shed={report['shed']} delta_ratio={ratio:.2f} parity={parity} "
          f"subposterior={args.subposterior} combine={args.combine}"
          + (f" stream_rows={stream_rows}" if args.stream else "")
          + (f" alerts_fired={engine.fired_total}"
             if engine is not None else "")
          + (f" scale_up={scaler.events['scale_up']} "
             f"scale_down={scaler.events['scale_down']}"
             if scaler is not None else ""))
    return 0


# ---------------------------------------------------------------------------
# Chaos soak (--soak)
# ---------------------------------------------------------------------------


def serve_soak(args) -> int:
    """Sustained mixed-class load against the multi-replica fleet while one
    replica is SIGKILLed mid-load and later restarted: proves the router
    reroutes around the dead lane without dropping top-class requests and
    that the revived replica full-resyncs to bit-exact parity with the warm
    writer. Prints ``SOAK_OK``/``SOAK_FAIL`` with the recovery counters."""
    from repro.obs import record_fleet_sync, record_snapshot

    smoke = args.smoke
    soak_s = args.soak_seconds or (6.0 if smoke else 30.0)
    # Killing a replica must leave a live lane in its shard.
    args.replicas = max(args.replicas, 2)
    fleet, workload, classes = _build_fleet(args)
    fleet.warm()
    shard0 = fleet.shards(args.workload)[0]
    victim = shard0.replicas[-1]
    router = _build_router(args, fleet, workload)
    recorder, stats_server, sampler, tracer = _setup_obs(args, source=router)
    router.tracer = tracer
    fleet.pool.tracer = tracer
    engine = _setup_alerts(args, recorder, stats_server, workload, fleet)
    scaler = _setup_autoscaler(args, fleet, router, recorder, engine)
    num_sections = _obs_num_sections(shard0.writer.ensemble)
    _compile_lanes(args, fleet, workload)
    if args.profile_dir:
        _profile_block(recorder, args, fleet.pool,
                       lambda: fleet.pump(args.workload))
    top = workload.default_class
    print(f"soak: {soak_s:.0f}s mixed-class load "
          f"({', '.join(classes)}; top class {top!r}), "
          f"kill {victim.name} at ~35%, restart at ~65%")

    fleet.start()          # background refresh + delta sync
    router.start_workers()  # one worker thread per replica lane

    t0 = time.perf_counter()
    end = t0 + soak_s
    kill_at = t0 + 0.35 * soak_s
    recover_at = t0 + 0.65 * soak_s
    killed = recovered = False
    full_before = 0
    pending: list = []
    qkey = jax.random.key(args.seed + 1)
    i = 0
    last_sample = t0
    while True:
        now = time.perf_counter()
        if now >= end and recovered:
            break
        if not killed and now >= kill_at:
            recorder.record("chaos", {"event": "kill", "replica": victim.name})
            victim.kill()
            killed = True
            print(f"chaos: killed {victim.name} at t+{now - t0:.1f}s "
                  f"(pending={router.pending_count})")
        if killed and not recovered and now >= recover_at and (
                router.dead_lanes >= 1 or now >= end):
            full_before = fleet.sync_stats["full_deltas"]
            victim.restart()
            fleet.sync_shard(shard0)  # version 0 -> full snapshot resync
            revived = router.revive()
            recovered = True
            recorder.record("chaos", {
                "event": "restart", "replica": victim.name,
                "revived_lanes": revived,
                "replica_version": victim.version,
            })
            print(f"chaos: restarted {victim.name} at t+{now - t0:.1f}s "
                  f"(revived {revived} lane(s), replica v{victim.version})")
        if router.pending_count > 4 * args.max_depth:
            time.sleep(0.01)  # backpressure: let the lane workers catch up
        else:
            cls = classes[i % len(classes)]
            qkey, sub = jax.random.split(qkey)
            xs = workload.query_specs[cls].make_queries(sub, args.rows_per_query)
            pending.append(router.submit(args.workload, cls, xs))
            i += 1
            if i % 8 == 0:
                time.sleep(0.002)  # yield to the worker threads
        if sampler is not None and now - last_sample >= max(soak_s / 12, 0.25):
            sampler.sample()
            record_fleet_sync(recorder, fleet)
            snap_now = shard0.writer.snapshot()
            record_snapshot(recorder, args.workload, snap_now)
            _record_transition_cost(recorder, args.workload, snap_now,
                                    num_sections)
            if engine is not None:
                engine.evaluate()
            # The scaler deliberately does NOT tick during the kill/restart
            # window: the choreography below is the deterministic
            # scale-up-under-pressure / scale-down-after-quiesce proof, and
            # a mid-chaos actuation would spend the replica headroom first.
            last_sample = now

    # -- closed-loop overload burst (--autoscale) --------------------------
    # Drive submissions past the admission shed point and hold them there
    # until the loop closes: the sampler records the active shed floor, the
    # admission_overload rule fires, and the scaler actuates a scale-up.
    burst_submitted = burst_shed = 0
    if scaler is not None:
        low = next((c for c in classes if c != top), top)
        up_before = scaler.events["scale_up"]
        fired_before = engine.fired_total if engine is not None else 0
        burst_done = lambda: (
            scaler.events["scale_up"] > up_before
            and (engine is None or engine.fired_total > fired_before)
        )
        burst_deadline = time.perf_counter() + 60.0
        while not burst_done() and time.perf_counter() < burst_deadline:
            while router.pending_count < args.max_depth + 8:
                qkey, sub = jax.random.split(qkey)
                xs = workload.query_specs[top].make_queries(
                    sub, args.rows_per_query)
                pending.append(router.submit(args.workload, top, xs))
                burst_submitted += 1
            # With the floor up, a low-class submission is refused — the
            # shed that proves the overload point was actually crossed.
            qkey, sub = jax.random.split(qkey)
            shed_probe = router.submit(
                args.workload, low,
                workload.query_specs[low].make_queries(sub, args.rows_per_query))
            burst_shed += int((shed_probe.error or "").startswith("shed"))
            if sampler is not None:
                sampler.sample()
            if engine is not None:
                engine.evaluate()
            scaler.tick()
            time.sleep(0.05)
        print(f"chaos: overload burst submitted {burst_submitted} top-class "
              f"requests (depth {router.pending_count}), "
              f"{burst_shed} low-class shed, "
              f"scale_up={scaler.events['scale_up']}")

    for req in pending:
        req.done.wait(timeout=120.0)

    # -- quiesce: the backlog is drained; tick the scaler until it has
    # retired every replica it added (calm depth -> scale-down events).
    if scaler is not None:
        scaler.observe()  # absorb the burst's shed counters: not fresh pressure
        quiesce_deadline = time.perf_counter() + 60.0
        while scaler.outstanding and time.perf_counter() < quiesce_deadline:
            if sampler is not None:
                sampler.sample()
            if engine is not None:
                engine.evaluate()
            scaler.tick()
            time.sleep(max(args.autoscale_cooldown / 4, 0.05))
        print(f"chaos: quiesce done, scale_down={scaler.events['scale_down']} "
              f"replicas={fleet.replica_count(args.workload)}")
    wall = time.perf_counter() - t0
    stats_ok = alerts_ok = True
    if sampler is not None:
        sampler.sample()
        record_fleet_sync(recorder, fleet)
        snap_final = shard0.writer.snapshot()
        record_snapshot(recorder, args.workload, snap_final)
        _record_transition_cost(recorder, args.workload, snap_final,
                                num_sections)
        if engine is not None:
            engine.evaluate()
            alerts_ok = _alerts_selfcheck(engine, stats_server)
        if stats_server is not None:
            stats_ok = _stats_selfcheck(stats_server)
    report = router.slo_report()
    router.stop_workers()
    fleet.stop()

    # -- post-chaos parity: EVERY current replica (the revived victim and
    # any autoscaler survivors) vs the warm writer, bit-exact ---------------
    fleet.sync_all()
    resyncs = fleet.sync_stats["full_deltas"] - full_before
    spec = workload.query_specs[top]
    qkey, sub = jax.random.split(qkey)
    xs = spec.make_queries(sub, 16)
    # Re-read shard0: runtime add/remove_replica swapped the shard entry,
    # so the launch-time NamedTuple's replica tuple is stale.
    shard0 = fleet.shards(args.workload)[0]
    w_vals, w_snap = shard0.writer.query(spec, xs)
    parity_bad = []
    for replica in shard0.replicas:
        r_vals, _ = replica.serve(spec, top, xs)
        if not np.array_equal(np.asarray(w_vals), np.asarray(r_vals)):
            err = float(np.max(np.abs(np.asarray(w_vals) - np.asarray(r_vals))))
            parity_bad.append(f"{replica.name} max|delta|={err:.3g} "
                              f"v{replica.version}")
    parity_ok = not parity_bad

    served = len([
        r for r in pending
        if r.done.is_set() and not (r.error or "").startswith("shed")
    ])
    recovery = report["recovery"]
    top_entry = report["classes"].get(f"{args.workload}.{top}", {})
    top_reqs = [r for r in pending if r.query_class == top]
    dropped = [r for r in top_reqs if not r.done.is_set()]
    print(f"\nsoak: {served} served / {len(pending)} submitted in {wall:.1f}s "
          f"({served / max(wall, 1e-9):.0f} req/s), shed={report['shed']}, "
          f"lane_deaths={recovery['lane_deaths']}, "
          f"rerouted={recovery['rerouted']}, "
          f"dead_lanes={recovery['dead_lanes']}, resyncs={resyncs}")

    failures = []
    if not top_entry.get("count"):
        failures.append(f"no completed top-class ({top!r}) requests in report")
    if not killed or not recovered:
        failures.append("kill/restart never fired (soak too short?)")
    if recovery["lane_deaths"] < 1:
        failures.append("victim lane never died under load")
    if recovery["dead_lanes"]:
        failures.append(f"{recovery['dead_lanes']} lane(s) still dead after revive")
    if dropped:
        failures.append(f"{len(dropped)} top-class request(s) never completed")
    if top_entry.get("errors", 0):
        failures.append(f"top-class errors={top_entry['errors']}")
    if top_entry.get("shed", 0):
        failures.append(f"top-class shed={top_entry['shed']}")
    if resyncs < 1:
        failures.append("restarted replica never full-resynced")
    if not parity_ok:
        failures.append(
            f"parity vs writer v{w_snap.steps_done}: " + "; ".join(parity_bad))
    if not stats_ok:
        failures.append("stats endpoint self-check failed")
    if not alerts_ok:
        failures.append("alert engine self-check failed")
    if scaler is not None:
        if scaler.events["scale_up"] < 1:
            failures.append("autoscaler never scaled up under overload")
        if scaler.events["scale_down"] < 1:
            failures.append("autoscaler never scaled down after quiesce")
        if burst_shed < 1:
            failures.append("overload burst never crossed the shed point")
        if engine is not None and engine.fired_total < 1:
            failures.append("no alert fired during the overload burst")

    _teardown_obs(recorder, stats_server, tracer, args.trace_dir)
    fleet.close()
    if failures:
        print(f"SOAK_FAIL workload={args.workload} " + "; ".join(failures))
        return 1
    # New fields go AFTER parity= so existing CI greps keep matching.
    print(f"SOAK_OK workload={args.workload} soak_s={wall:.1f} "
          f"served={served} kills=1 recovered=1 resyncs={resyncs} "
          f"reroutes={recovery['rerouted']} "
          f"lane_deaths={recovery['lane_deaths']} shed={report['shed']} "
          f"top_class_errors=0 "
          f"p95_ms={top_entry.get('p95_ms') or float('nan'):.2f} "
          f"parity=ok(bitexact)"
          + (f" alerts_fired={engine.fired_total}"
             if engine is not None else "")
          + (f" scale_up={scaler.events['scale_up']} "
             f"scale_down={scaler.events['scale_down']}"
             if scaler is not None else ""))
    return 0


# ---------------------------------------------------------------------------
# Legacy LM decoding demo (--workload lm)
# ---------------------------------------------------------------------------


def serve_lm(args) -> int:
    from repro.checkpoint import manager as ckpt
    from repro.configs import reduce_config
    from repro.distributed.sharding import logical_axis_rules
    from repro.models import decode_step, init_params, prefill

    from .mesh import make_mesh_for_devices

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduce_config(cfg)
    mesh = make_mesh_for_devices(model_parallel=args.model_parallel)
    with logical_axis_rules(mesh), mesh:
        params = init_params(jax.random.key(0), cfg)
        if args.ckpt_dir:
            _, params = ckpt.restore(args.ckpt_dir, target=params)
            print(f"restored posterior sample from {args.ckpt_dir}")
        prompts = jax.random.randint(
            jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab
        )
        extra = None
        if cfg.family == "audio":
            extra = {"frames": 0.1 * jax.random.normal(
                jax.random.key(2), (args.batch, cfg.n_audio_frames, cfg.d_model),
                jnp.bfloat16)}
        max_len = args.prompt_len + args.gen_len + 8
        jprefill = jax.jit(lambda p, t: prefill(p, t, cfg, max_len, extra))
        jdecode = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))

        t0 = time.perf_counter()
        cache, logits = jprefill(params, prompts)
        jax.block_until_ready(logits)
        t_pre = time.perf_counter() - t0
        tok = jnp.argmax(logits, -1)[:, None]
        key = jax.random.key(3)
        t0 = time.perf_counter()
        for _ in range(args.gen_len):
            key, sub = jax.random.split(key)
            cache, logits = jdecode(params, cache, tok)
            tok = jax.random.categorical(sub, logits, axis=-1)[:, None]
        jax.block_until_ready(logits)
        t_dec = time.perf_counter() - t0
    print(f"prefill {args.batch}x{args.prompt_len}: {t_pre:.2f}s "
          f"({args.batch * args.prompt_len / t_pre:.0f} tok/s)")
    print(f"decode {args.gen_len} steps: {t_dec:.2f}s "
          f"({args.batch * args.gen_len / t_dec:.0f} tok/s)")
    return 0


_LM_ONLY_FLAGS = ("arch", "reduced", "batch", "prompt_len", "gen_len",
                  "model_parallel")


def main(argv=None) -> None:
    parser = build_parser()
    args = parser.parse_args(argv)
    compile_cache.enable()
    if args.fleet and args.workload == "lm":
        parser.error("--fleet serves posterior workloads, not the lm demo")
    if args.subposterior > 1 or args.stream:
        if args.workload == "lm":
            parser.error("--subposterior/--stream serve posterior "
                         "workloads through the fleet, not the lm demo")
        args.fleet = True  # both modes live in the fleet serve path
    if args.autoscale:
        if args.workload == "lm":
            parser.error("--autoscale scales the replica fleet, not the "
                         "lm demo")
        args.fleet = True  # the actuator needs replica lanes to scale
    if args.alerts and args.workload == "lm":
        parser.error("--alerts applies to posterior serving, not the lm demo")
    if args.fleet and args.devices:
        # Must land before JAX initializes its backends (importing jax is
        # fine; creating the first array is not) — hence a fresh
        # `python -m repro.launch.serve` process, not a long-lived session.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={args.devices}".strip()
            )
        # The flag only multiplies *host* devices; on a TPU it would be
        # ignored and the fleet would run on the chips it finds instead.
        if jax.default_backend() == "tpu":
            parser.error("--devices forces virtual CPU devices and has no "
                         "effect on a TPU; drop it to use the chips present")
    if args.workload != "lm":
        # Guard legacy invocations: the pre-serving CLI was LM-only and had
        # no --workload flag, so `serve --arch ... --batch 8` must not be
        # silently rewired onto the bayeslr posterior service.
        drifted = [f"--{name.replace('_', '-')}" for name in _LM_ONLY_FLAGS
                   if getattr(args, name) != parser.get_default(name)]
        if drifted:
            parser.error(
                f"{', '.join(drifted)} only apply to the LM decoding demo; "
                "add --workload lm (posterior serving ignores them)"
            )
    if args.soak and (args.workload == "lm" or not args.fleet):
        parser.error("--soak drives the replica fleet: add --fleet "
                     "(and a posterior --workload)")
    if args.workload == "lm":
        code = serve_lm(args)
    elif args.soak:
        code = serve_soak(args)
    elif args.fleet:
        code = serve_fleet(args)
    else:
        code = serve_posterior(args)
    if code:
        sys.exit(code)


if __name__ == "__main__":
    main()
