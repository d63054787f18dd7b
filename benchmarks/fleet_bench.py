"""Fleet-layer benchmark: replica scaling, delta streaming, 2-d mesh steps.

Three measurements of the sharded serving fleet (``repro.fleet``), written
machine-readably to ``BENCH_fleet.json`` next to the other bench artifacts:

  * **replica scaling** — req/s and p50/p95 latency vs replica count, served
    through the router's per-lane workers with the ``proc`` transport (one
    OS process per replica, the configuration whose lanes actually run in
    parallel). The acceptance bar tracked across PRs: >= 1.5x req/s at 3
    replicas vs 1 on the 2-core CPU container.
  * **delta streaming** — wire bytes of the incremental snapshot deltas the
    writer broadcasts each sync vs what full-snapshot streaming would cost
    (measured on the same pickled payloads the process transport sends).
  * **2-d mesh** — steady-state ensemble step time under the
    chains x data 2-d mesh vs the 1-d chain mesh vs unsharded, at 4 forced
    host devices (run in a subprocess: JAX pins the device count at first
    init).

Reproduction guide: docs/BENCHMARKS.md.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from .multichain_bench import bench_json_path

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One serving shape for every scaling point: enough draws x rows per query
# that the replica-side evaluation dominates parent-side dispatch (pickle,
# concat, GIL wakeups), so lane parallelism is measurable.
_SCALE_KW = dict(n_train=2000, d=16, batch_size=100)
_CHAINS, _WINDOW, _ROWS = 8, 64, 512


def _build_fleet(replicas: int, transport: str):
    import jax

    from repro.fleet import Fleet, FleetConfig
    from repro.serving import FreshnessPolicy, ServingConfig

    config = FleetConfig(
        replicas=replicas,
        shards=1,
        transport=transport,
        serving=ServingConfig(
            num_chains=_CHAINS,
            refresh_steps=32,
            window=_WINDOW,
            micro_batch=_ROWS,
            max_batch=8,
            freshness=FreshnessPolicy(
                max_staleness_s=1e9, min_draws=_CHAINS * _WINDOW
            ),
            default_deadline_s=10.0,
            seed=0,
        ),
    )
    fleet = Fleet(config)
    fleet.add_workload("bayeslr", **_SCALE_KW)
    fleet.warm()
    # Warm every replica's evaluator outside the measured window.
    spec = fleet.workload("bayeslr").query_specs["predictive"]
    for shard in fleet.shards("bayeslr"):
        for replica in shard.replicas:
            replica.serve(spec, "predictive",
                          spec.make_queries(jax.random.key(0), _ROWS))
    return fleet, spec


def _measure_point(fleet, spec, replicas: int, num_queries: int) -> dict:
    """One serving pass restricted to the shard's first ``replicas`` lanes."""
    import jax

    from repro.fleet import FleetRouter

    router = FleetRouter(fleet, max_batch=8, default_deadline_s=10.0,
                         lanes_per_shard=replicas)
    key = jax.random.key(1)
    queries = []
    for _ in range(num_queries):
        key, sub = jax.random.split(key)
        queries.append(spec.make_queries(sub, _ROWS))
    router.start_workers(max_wait_s=0.0)
    t0 = time.perf_counter()
    reqs = [router.submit("bayeslr", "predictive", xs) for xs in queries]
    for req in reqs:
        req.result(timeout_s=120.0)
    wall = time.perf_counter() - t0
    router.stop_workers()
    entry = router.slo_report()["classes"]["bayeslr.predictive"]
    return {"qps": num_queries / max(wall, 1e-12),
            "p50_ms": entry["p50_ms"], "p95_ms": entry["p95_ms"], "wall_s": wall}


def bench_scaling(replica_counts, num_queries: int, repeats: int = 3,
                  transport: str = "proc") -> list[dict]:
    """Replica-scaling sweep over ONE warmed fleet.

    The container's effective CPU allocation fluctuates (shared host), so a
    single pass per point is unreliable: the sweep interleaves the replica
    counts ``repeats`` times over the same warmed fleet (round-robin, so a
    slow phase of the box taxes every point) and keeps each point's best
    pass — the closest observable to the quiet-box capacity.
    """
    max_r = max(replica_counts)
    fleet, spec = _build_fleet(max_r, transport)
    best: dict[int, dict] = {}
    # Shorter GIL switch interval while driving many lane threads: a lane
    # waking from its pipe recv otherwise waits up to the default 5 ms for
    # the interpreter, which serializes the lanes at high RPC rates.
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    try:
        for _ in range(repeats):
            for r in replica_counts:
                res = _measure_point(fleet, spec, r, num_queries)
                if r not in best or res["qps"] > best[r]["qps"]:
                    best[r] = res
    finally:
        sys.setswitchinterval(prev_switch)
        fleet.close()
    return [
        {
            "kind": "scaling",
            "transport": transport,
            "replicas": r,
            "queries": num_queries,
            "rows_per_query": _ROWS,
            "repeats": repeats,
            **best[r],
        }
        for r in replica_counts
    ]


def bench_delta_stream(pumps: int) -> dict:
    """Measure incremental-delta vs full-snapshot wire bytes over a run of
    refresh+broadcast rounds (warm full sync excluded: steady state)."""
    fleet, _ = _build_fleet(1, "inproc")
    try:
        base = dict(fleet.sync_stats)  # includes the warm full resync
        for _ in range(pumps):
            fleet.pump("bayeslr")
        stats = fleet.sync_stats
        syncs = stats["syncs"] - base["syncs"]
        delta = stats["delta_wire_bytes"] - base["delta_wire_bytes"]
        full = stats["full_wire_bytes"] - base["full_wire_bytes"]
        return {
            "kind": "delta_stream",
            "syncs": syncs,
            "delta_wire_bytes": delta,
            "full_wire_bytes": full,
            "delta_bytes_per_sync": delta / max(syncs, 1),
            "full_bytes_per_sync": full / max(syncs, 1),
            "ratio": delta / max(full, 1),
            "window": _WINDOW,
            "refresh_steps": 32,
        }
    finally:
        fleet.close()


_MESH_SCRIPT = r"""
import json
import jax, jax.numpy as jnp
from repro.core import ChainEnsemble, RandomWalk, SubsampledMHConfig
from repro.core.target_builder import build_target

n, d, K, steps = 4000, 8, 8, %(steps)d
kx, ky = jax.random.split(jax.random.key(0))
x = jax.random.normal(kx, (n, d))
y = jnp.where(jax.random.bernoulli(ky, 0.5, (n,)), 1.0, -1.0)
target = build_target("logit", (x, y), n,
                      prior_logpdf=lambda w: -0.5 * jnp.sum(w**2))
cfg = SubsampledMHConfig(batch_size=200, epsilon=0.05)
out = {"n_devices": len(jax.devices())}
for name, shard in (("unsharded", False), ("mesh_1d", True),
                    ("mesh_2d", {"chains": 2, "data": 2})):
    ens = ChainEnsemble(target, RandomWalk(0.05), K, config=cfg, shard=shard)
    state = ens.init(jnp.zeros(d))
    # steady state: run_timed warms per-block compiles before timing
    _, timed = ens.run_timed(jax.random.key(1), state, steps, block_every=steps)
    out[name] = timed["transitions_per_sec"]
print(json.dumps(out))
"""


def bench_mesh_2d(steps: int) -> dict:
    """2-d vs 1-d vs unsharded step throughput at 4 forced host devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT % {"steps": steps}],
        capture_output=True, text=True, env=env, cwd=_REPO, timeout=900,
    )
    if out.returncode != 0:
        raise RuntimeError(f"mesh subprocess failed:\n{out.stderr[-2000:]}")
    res = json.loads(out.stdout.strip().splitlines()[-1])
    return {
        "kind": "mesh_2d",
        "steps": steps,
        "n_devices": res["n_devices"],
        "tps_unsharded": res["unsharded"],
        "tps_mesh_1d": res["mesh_1d"],
        "tps_mesh_2d": res["mesh_2d"],
    }


def main(fast: bool = True):
    import jax

    if jax.default_backend() == "tpu":
        # The scaling and mesh points start CPU child processes after this
        # process took the chip: on a TPU host they would report CPU numbers
        # as replica and mesh scaling.
        raise SystemExit("fleet_bench measures CPU child processes; it "
                         "refuses to run on a TPU host")
    if fast:
        num_queries, pumps, mesh_steps, repeats = 120, 6, 120, 3
        replica_counts = (1, 2, 3)
    else:
        num_queries, pumps, mesh_steps, repeats = 360, 12, 400, 4
        replica_counts = (1, 2, 3, 4)

    rows_out, records = [], []
    scaling = bench_scaling(replica_counts, num_queries, repeats=repeats)
    base_qps = scaling[0]["qps"]
    for rec in scaling:
        records.append(rec)
        rows_out.append((
            f"fleet_scaling_r{rec['replicas']}",
            1e6 / rec["qps"],
            f"qps={rec['qps']:.0f}_p95_ms={rec['p95_ms']:.2f}"
            f"_speedup={rec['qps'] / base_qps:.2f}x",
        ))
    delta = bench_delta_stream(pumps)
    records.append(delta)
    rows_out.append((
        "fleet_delta_stream",
        delta["delta_bytes_per_sync"],
        f"delta_per_sync={delta['delta_bytes_per_sync']:.0f}B"
        f"_full_per_sync={delta['full_bytes_per_sync']:.0f}B"
        f"_ratio={delta['ratio']:.2f}",
    ))
    mesh = bench_mesh_2d(mesh_steps)
    records.append(mesh)
    rows_out.append((
        "fleet_mesh_2d",
        1e6 / mesh["tps_mesh_2d"],
        f"tps_2d={mesh['tps_mesh_2d']:.0f}_tps_1d={mesh['tps_mesh_1d']:.0f}"
        f"_tps_unsharded={mesh['tps_unsharded']:.0f}",
    ))

    path = bench_json_path("fleet")
    with open(path, "w") as f:
        json.dump({"bench": "fleet", "records": records}, f, indent=1)
    rows_out.append((f"fleet_json:{path}", 0.0, "machine-readable output"))
    return rows_out, records


if __name__ == "__main__":
    for name, us, derived in main()[0]:
        print(f"{name},{us:.1f},{derived}")
