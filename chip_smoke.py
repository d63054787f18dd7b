#!/usr/bin/env python3
"""Chip smoke test: the posterior-serving path, end to end, on a TPU.

Runs, in this one process, the calls the serve front-end makes
(``EnsemblePool`` -> ``warm`` -> ``RequestQueue`` -> served-vs-offline
parity, as in :func:`repro.launch.serve.serve_posterior`):

* ``bayeslr``: the paper's BayesLR shape (N = 12,214 train rows, D = 50),
  m = 500, K = 8; then N = 1,048,576, D = 50 — a ~210 MB section pool on
  the device, so the sublinear path runs at a size users would call real.
* ``stochvol``: the stochastic-volatility serving workload at its full
  defaults (200 series, T = 10, 25 particles): the ``gaussian_ar1`` kernel
  and the fused particle-Gibbs scan.

Each phase warms the pool, serves a few dozen queries of both request
classes, checks the served default class against the same functional
computed offline from the same draws, and fails unless the compiled
refresh program contains the Pallas kernel (``tpu_custom_call``).

``--multichip`` runs only the four-chip phase: BayesLR at the paper shape,
K = 8, unsharded on one chip vs the 1-d chain mesh vs the 2-d chains x data
mesh, all from the same step keys. It fails unless each mesh reproduces
its unsharded route bit for bit, and reports how closely the fused
(Pallas) and vmapped routes agree.

    python chip_smoke.py               # one chip
    python chip_smoke.py --multichip   # four chips

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The figures printed before it are single-run smoke figures, not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

REFRESH_STEPS, WINDOW, CHAINS, BATCH = 64, 128, 8, 500
QUERIES, ROWS = 48, 8  # requests per phase (both classes), rows each
PAPER_N, PAPER_D, LARGE_N = 12_214, 50, 1_048_576


def _device() -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _require_kernel(text: str, what: str) -> None:
    if "tpu_custom_call" not in text:
        raise RuntimeError(f"{what}: the compiled refresh program holds no "
                           "Pallas kernel (tpu_custom_call) — the fused path "
                           "fell back to the reference")
    print(f"  {what}: tpu_custom_call in the compiled refresh program")


class _Record:
    """A recorder for :func:`repro.obs.record_transition_cost` that hands
    the record back instead of storing it."""

    def record(self, stream: str, rec: dict) -> dict:
        return rec


def serve_phase(name: str, workload: str, **build_kw) -> dict:
    """Warm a pool, serve both request classes, check parity; one phase."""
    from repro.launch.serve import _obs_num_sections, _offline_reference
    from repro.obs import record_transition_cost
    from repro.serving import EnsemblePool, FreshnessPolicy, RequestQueue, ServingConfig

    print(f"\n[{name}] workload={workload} {build_kw}")
    config = ServingConfig(
        num_chains=CHAINS, refresh_steps=REFRESH_STEPS, window=WINDOW,
        freshness=FreshnessPolicy(max_staleness_s=30.0,
                                  min_draws=CHAINS * WINDOW // 2),
        default_deadline_s=0.25, seed=0,
    )
    t0 = time.perf_counter()
    pool = EnsemblePool(config)
    pool.add_workload(workload, seed=0, **build_kw)
    wl = pool.workload(workload)
    resident = pool.resident(workload)
    ens = resident.ensemble
    jax.block_until_ready(resident.state.theta)
    build_s = time.perf_counter() - t0
    print(f"  target: {wl.description}")

    t0 = time.perf_counter()
    sk = ens.step_keys(jax.random.key(0), 0, REFRESH_STEPS)
    text = ens.lower(resident.state, REFRESH_STEPS, step_keys=sk).compile().as_text()
    compile_s = time.perf_counter() - t0  # trace (autotune included) + compile
    _require_kernel(text, name)
    t0 = time.perf_counter()
    pool.warm()  # first refresh block (same program as compiled above)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    resident.refresh()  # steady state: one compiled refresh block
    refresh_s = time.perf_counter() - t0
    tps = CHAINS * REFRESH_STEPS / refresh_s
    # sections evaluated per transition over N (mean over a cycle's MH ops)
    frac = record_transition_cost(
        _Record(), workload, resident.snapshot().summary,
        num_sections=_obs_num_sections(ens),
    )["frac_data_touched"]

    classes = sorted(wl.query_specs)
    qkey = jax.random.key(1)
    for cls in classes:  # compile each evaluator outside the served window
        qkey, sub = jax.random.split(qkey)
        pool.query(workload, cls, wl.query_specs[cls].make_queries(sub, ROWS))
    queue = RequestQueue(pool, max_batch=config.max_batch,
                         default_deadline_s=config.default_deadline_s)
    for i in range(QUERIES):
        cls = classes[i % len(classes)]
        qkey, sub = jax.random.split(qkey)
        queue.submit(workload, cls, wl.query_specs[cls].make_queries(sub, ROWS))
        if i % 8 == 7:
            queue.drain()
    queue.drain()
    report = queue.slo_report()
    if report["errors"]:
        raise RuntimeError(f"{name}: {report['errors']} request(s) failed")
    served = {c: e for c, e in report["classes"].items() if e.get("count")}
    if len(served) != len(classes):
        raise RuntimeError(f"{name}: served classes {sorted(served)} != {classes}")

    spec = wl.query_specs[wl.default_class]
    qkey, sub = jax.random.split(qkey)
    xs = spec.make_queries(sub, 16)
    snap = pool.ensure_fresh(workload)
    got, snap = pool.query(workload, wl.default_class, xs, snapshot=snap)
    want = _offline_reference(wl, spec, snap, xs)
    if want is None:
        raise RuntimeError(f"{name}: no offline reference for {wl.default_class}")
    err = float(np.max(np.abs(got - want)))
    if not (np.all(np.isfinite(got)) and got.shape == want.shape
            and np.allclose(got, want, rtol=1e-4, atol=1e-5)):
        raise RuntimeError(f"{name}: parity FAIL served vs offline "
                           f"max|delta|={err:.3g}")

    out = {
        "build_s": build_s, "compile_s": compile_s, "warm_s": warm_s,
        "transitions_per_s": tps, "frac_data_touched": frac,
        "parity_max_abs_delta": err, "peak_bytes_in_use": _peak_bytes(),
    }
    for cls, e in sorted(served.items()):
        out[f"{cls}.p50_ms"], out[f"{cls}.p99_ms"] = e["p50_ms"], e["p99_ms"]
    print(f"  build {build_s:.1f}s, compile {compile_s:.1f}s, "
          f"warm (first refresh) {warm_s:.1f}s, "
          f"refresh {refresh_s * 1e3:.1f} ms = {tps:.0f} transitions/s, "
          f"frac_data_touched={frac:.5f}")
    for cls, e in sorted(served.items()):
        print(f"  {cls:14s} n={e['count']} p50={e['p50_ms']:.2f} ms "
              f"p99={e['p99_ms']:.2f} ms")
    print(f"  parity ok: served {wl.default_class} == offline, "
          f"max|delta|={err:.3g}; peak_bytes_in_use={out['peak_bytes_in_use']}")
    return out


def one_chip() -> dict:
    return {
        "bayeslr": serve_phase("bayeslr", "bayeslr", n_train=PAPER_N,
                               d=PAPER_D, batch_size=BATCH),
        "bayeslr_1m": serve_phase("bayeslr_1m", "bayeslr", n_train=LARGE_N,
                                  d=PAPER_D, batch_size=BATCH),
        "stochvol": serve_phase("stochvol", "stochvol"),
    }


def multichip(steps: int = 64) -> dict:
    """Unsharded vs 1-d chain mesh vs 2-d chains x data mesh, same keys."""
    from repro.experiments import bayeslr

    if len(jax.devices()) != 4:
        raise RuntimeError(f"--multichip needs 4 devices, found {len(jax.devices())}")
    base = bayeslr.make_serving_workload(num_chains=CHAINS, n_train=PAPER_N,
                                         d=PAPER_D, batch_size=BATCH).ensemble
    runs = {
        # the fused (Pallas) route on one chip, and what the 2-d mesh shards
        "unsharded": dict(shard=False),
        # the vmapped reference route on one chip: what the 1-d mesh shards
        "unsharded_unfused": dict(shard=False, fused_kernels="never"),
        "mesh_1d": dict(shard=True),
        "mesh_2d": dict(shard={"chains": 2, "data": 2}),
    }
    key = jax.random.key(3)
    draws = {}
    for name, kw in runs.items():
        ens = dataclasses.replace(base, **kw)
        state = ens.init(jnp.zeros(PAPER_D))
        sk = ens.step_keys(key, 0, steps)
        text = ens.lower(state, steps, step_keys=sk).compile().as_text()
        if name in ("unsharded", "mesh_2d"):
            _require_kernel(text, name)
        t0 = time.perf_counter()
        _, samples, infos = ens.run(None, state, steps, step_keys=sk)
        samples = np.asarray(samples)
        secs = time.perf_counter() - t0
        if samples.shape != (CHAINS, steps, PAPER_D) or not np.all(np.isfinite(samples)):
            raise RuntimeError(f"{name}: bad draws {samples.shape}")
        draws[name] = samples
        print(f"  {name:18s} kernel={'tpu_custom_call' in text} "
              f"run {secs:.2f}s (compiled above) "
              f"accept={float(np.mean(np.asarray(infos.accepted))):.3f}")
    out = {}
    for a, b in (("mesh_2d", "unsharded"), ("mesh_1d", "unsharded_unfused"),
                 ("mesh_1d", "unsharded"), ("unsharded", "unsharded_unfused")):
        x, y = draws[a], draws[b]
        cmp = {"bitwise": bool(np.array_equal(x, y)),
               "max_abs_diff": float(np.max(np.abs(x - y))),
               "frac_equal_draws": float(np.mean(np.all(x == y, axis=-1)))}
        out[f"{a}_vs_{b}"] = cmp
        print(f"  {a} vs {b}: {cmp}")
    # The mesh contract: sharding a route changes no bit of its draws. The
    # fused and vmapped routes differ in float order, so those two pairs
    # are reported, not required.
    for pair in ("mesh_2d_vs_unsharded", "mesh_1d_vs_unsharded_unfused"):
        if not out[pair]["bitwise"]:
            raise RuntimeError(f"{pair}: sharded draws differ from unsharded")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip mesh comparison")
    args = ap.parse_args()

    device = _device()
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device['platform']}); refusing "
              "to report CPU figures", file=sys.stderr)
        return 1

    from repro import compile_cache
    from repro.kernels import ops

    cache = compile_cache.enable()
    print(f"jax {jax.__version__}; {ops.dispatch_summary()}; "
          f"device {device}; compile cache {cache}")
    if "dispatch=pallas " not in ops.dispatch_summary():
        raise RuntimeError("auto dispatch does not select the Pallas kernels")
    print("note: single-run smoke figures, not benchmark numbers")
    results = multichip() if args.multichip else one_chip()
    print(json.dumps({"results": results}, default=float))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
