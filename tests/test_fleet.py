"""Fleet subsystem: delta streaming, replicas, routing/admission, 2-d mesh.

Single-device tests cover the host-side fleet semantics (delta algebra,
replica parity, router priority and shedding, warm restore). The
multi-device contracts — 2-d chains x data sharding bit-for-bit, sharded
fleet checkpoint round-trips — run in subprocesses under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (JAX pins the
device count at first init), marked slow like the other multi-device
cases.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core import ChainEnsemble, RandomWalk, SubsampledMHConfig
from repro.fleet import (
    AdmissionConfig,
    Fleet,
    FleetConfig,
    FleetRouter,
    ReplicaEnsemble,
    SnapshotDelta,
    apply_delta,
    make_delta,
    payload_nbytes,
    wire_bytes,
)
from repro.serving import FreshnessPolicy, ServingConfig
from repro.serving.resident import Snapshot

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fleet_config(replicas=2, shards=1, window=16, refresh_steps=8,
                  num_chains=2, transport="inproc", mesh="auto"):
    return FleetConfig(
        replicas=replicas,
        shards=shards,
        transport=transport,
        mesh=mesh,
        serving=ServingConfig(
            num_chains=num_chains,
            refresh_steps=refresh_steps,
            window=window,
            micro_batch=8,
            max_batch=4,
            freshness=FreshnessPolicy(max_staleness_s=1e9, min_draws=num_chains * 4),
            seed=0,
        ),
    )


def _tiny_fleet(**kw) -> Fleet:
    fleet = Fleet(_fleet_config(**kw))
    fleet.add_workload("bayeslr", smoke=True, n_train=400, d=3, batch_size=50)
    return fleet


@pytest.fixture(scope="module")
def warm_fleet():
    fleet = _tiny_fleet()
    fleet.warm()
    return fleet


# ---------------------------------------------------------------------------
# Delta algebra
# ---------------------------------------------------------------------------


def _snap(draws, steps):
    return Snapshot(draws=draws, num_draws=int(np.prod(draws.shape[:2])),
                    steps_done=steps, staleness_s=0.1, summary={}, created_at=0.0)


def test_make_delta_incremental_reconstructs_window():
    window = 6
    full = np.arange(2 * 10, dtype=np.float32).reshape(2, 10)
    # writer at v=8 (window holds draws 2..8), replica synced at v=5
    writer = full[:, 8 - window:8]
    delta = make_delta(_snap(writer, 8), base_version=5, window=window)
    assert not delta.full and delta.base_version == 5 and delta.version == 8
    assert delta.draws.shape == (2, 3)  # exactly the 3 new columns
    replica = full[:, max(5 - window, 0):5]  # replica's (still-filling) window at v=5
    np.testing.assert_array_equal(apply_delta(replica, delta), writer)


def test_make_delta_falls_back_to_full_resync():
    window = 4
    writer = np.arange(8, dtype=np.float32).reshape(2, 4)
    # gap >= window width: only a full window can reconcile
    delta = make_delta(_snap(writer, 20), base_version=2, window=window)
    assert delta.full and delta.base_version == 0
    np.testing.assert_array_equal(apply_delta(None, delta), writer)
    # replica ahead of writer (restore to older checkpoint): full again
    assert make_delta(_snap(writer, 20), base_version=30, window=window).full


def test_make_delta_zero_gap_is_empty():
    writer = np.ones((2, 4), np.float32)
    delta = make_delta(_snap(writer, 7), base_version=7, window=4)
    assert delta.draws is None and payload_nbytes(delta.draws) == 0
    np.testing.assert_array_equal(apply_delta(writer, delta), writer)


@settings(max_examples=40, deadline=None)
@given(
    st.integers(min_value=1, max_value=4),    # chains K
    st.integers(min_value=2, max_value=12),   # window depth
    st.integers(min_value=0, max_value=64),   # replica version b
    st.integers(min_value=0, max_value=64),   # writer advance beyond b
)
def test_delta_roundtrip_property(k, window, base, advance):
    """apply(make(replica@b -> writer@v)) == writer window, bit for bit, for
    ANY (K, window, versions) — including cold replicas, still-filling
    windows, and replicas ahead of the writer (checkpoint restore)."""
    version = base + advance
    if version == 0:
        return  # writer has produced nothing: no snapshot to stream
    # One global draw sequence; a window at version v is its last columns.
    seq = np.arange(k * 80, dtype=np.float32).reshape(k, 80)
    win_at = lambda v: seq[:, max(v - window, 0):v] if v else None
    writer = win_at(version)
    delta = make_delta(_snap(writer, version), base, window)
    result = apply_delta(win_at(base), delta)
    np.testing.assert_array_equal(result, writer)
    assert delta.version == version


@settings(max_examples=40, deadline=None)
@given(
    st.integers(min_value=2, max_value=12),
    st.integers(min_value=1, max_value=64),
    st.integers(min_value=0, max_value=64),
)
def test_delta_full_resync_iff_gap_reaches_window(window, base, advance):
    """The delta degrades to a full-window resync exactly when the gap can't
    be bridged: replica cold (b=0), replica ahead, or gap >= the writer
    window's actual width (min(version, window) — still-filling windows
    included)."""
    version = base + advance
    seq = np.arange(80, dtype=np.float32).reshape(1, 80)
    writer = seq[:, max(version - window, 0):version]
    delta = make_delta(_snap(writer, version), base, window)
    width = writer.shape[1]
    assert delta.full == (base == 0 or version - base >= width)
    if delta.full:
        assert delta.base_version == 0  # applies to any replica state


@settings(max_examples=40, deadline=None)
@given(
    st.integers(min_value=1, max_value=4),
    st.integers(min_value=2, max_value=12),
    st.integers(min_value=1, max_value=64),
    st.integers(min_value=0, max_value=64),
)
def test_delta_payload_accounting_invariants(k, window, base, advance):
    """Byte accounting the fleet bench reports: an empty delta costs zero
    payload, an incremental delta carries exactly the new tail columns and
    never more than the full window, and the pickled wire size bounds the
    raw payload from above."""
    version = base + advance
    seq = np.arange(k * 80, dtype=np.float32).reshape(k, 80)
    writer = seq[:, max(version - window, 0):version]
    delta = make_delta(_snap(writer, version), base, window)
    payload = payload_nbytes(delta.draws)
    full_payload = payload_nbytes(writer)
    if delta.draws is None:
        assert payload == 0
        assert version == base  # only a zero gap streams nothing
    elif delta.full:
        assert payload == full_payload
    else:
        gap = version - base
        assert payload == k * gap * 4  # exactly the new f32 tail
        assert payload < full_payload
    assert wire_bytes(delta) >= payload  # pickle overhead, never compression


def test_replica_rejects_mismatched_incremental():
    rep = ReplicaEnsemble("r", micro_batch=4)
    writer = np.ones((2, 4), np.float32)
    full = make_delta(_snap(writer, 4), 0, 4)
    rep.apply_delta(full)
    bad = SnapshotDelta("", base_version=99, version=101,
                        draws=np.ones((2, 2), np.float32), window=4,
                        summary={}, staleness_s=0.0, full=False)
    with pytest.raises(ValueError, match="full resync required"):
        rep.apply_delta(bad)


# ---------------------------------------------------------------------------
# Fleet sync: replicas mirror writers bit for bit, deltas beat full windows
# ---------------------------------------------------------------------------


def test_replica_window_matches_writer_bit_for_bit(warm_fleet):
    fleet = warm_fleet
    for _ in range(3):
        fleet.pump("bayeslr")
    for shard in fleet.shards("bayeslr"):
        wsnap = shard.writer.snapshot()
        for replica in shard.replicas:
            rsnap = replica.snapshot()
            assert rsnap.steps_done == wsnap.steps_done
            np.testing.assert_array_equal(
                np.asarray(jax.tree.leaves(wsnap.draws)[0]),
                np.asarray(jax.tree.leaves(rsnap.draws)[0]),
            )
    stats = fleet.sync_stats
    assert stats["delta_wire_bytes"] < stats["full_wire_bytes"]
    assert stats["delta_payload_bytes"] < stats["full_payload_bytes"]


def test_replica_serves_bit_for_bit_what_writer_would(warm_fleet):
    fleet = warm_fleet
    fleet.sync_all()
    shard = fleet.shards("bayeslr")[0]
    spec = fleet.spec("bayeslr", "predictive")
    xs = spec.make_queries(jax.random.key(3), 8)
    w_vals, _ = shard.writer.query(spec, xs)
    r_vals, staleness = shard.replicas[0].serve(spec, "predictive", xs)
    np.testing.assert_array_equal(np.asarray(w_vals), np.asarray(r_vals))
    assert np.isfinite(staleness)


def test_replica_staleness_compounds_writer_staleness():
    rep = ReplicaEnsemble("r", micro_batch=4)
    assert rep.snapshot().staleness_s == float("inf")
    delta = make_delta(_snap(np.ones((2, 4), np.float32), 4), 0, 4)
    delta = delta._replace(staleness_s=1.5)
    rep.apply_delta(delta)
    snap = rep.snapshot()
    assert snap.staleness_s >= 1.5  # never younger than the writer's stamp


def test_two_shards_have_independent_chains():
    fleet = _tiny_fleet(shards=2)
    fleet.warm()
    s0, s1 = fleet.shards("bayeslr")
    a = np.asarray(jax.tree.leaves(s0.writer.snapshot().draws)[0])
    b = np.asarray(jax.tree.leaves(s1.writer.snapshot().draws)[0])
    assert a.shape == b.shape
    assert not np.array_equal(a, b)  # fold_in(seed, shard) keys differ


# ---------------------------------------------------------------------------
# Router: load spreading, priority, admission control
# ---------------------------------------------------------------------------


def test_router_batch_result_transparent(warm_fleet):
    fleet = warm_fleet
    fleet.sync_all()
    router = FleetRouter(fleet, max_batch=4, default_deadline_s=30.0)
    spec = fleet.spec("bayeslr", "predictive")
    xs_list = [spec.make_queries(jax.random.key(i), 3) for i in range(6)]
    reqs = [router.submit("bayeslr", "predictive", xs) for xs in xs_list]
    router.drain()
    shard = fleet.shards("bayeslr")[0]
    for req, xs in zip(reqs, xs_list):
        solo, _ = shard.writer.query(spec, xs)
        np.testing.assert_array_equal(np.asarray(req.result(1.0)), np.asarray(solo))
    report = router.slo_report()
    entry = report["classes"]["bayeslr.predictive"]
    assert entry["admitted"] == 6 and entry["shed"] == 0
    assert report["shed"] == 0 and report["errors"] == 0


def test_router_spreads_load_across_lanes(warm_fleet):
    fleet = warm_fleet
    fleet.sync_all()
    router = FleetRouter(fleet, max_batch=2, default_deadline_s=30.0)
    spec = fleet.spec("bayeslr", "predictive")
    for i in range(8):
        router.submit("bayeslr", "predictive", spec.make_queries(jax.random.key(i), 2))
    lanes = router._lanes["bayeslr"]
    depths = [len(l.pending) for l in lanes]
    assert max(depths) - min(depths) <= 1  # least-loaded placement
    router.drain()


def test_router_serves_high_priority_first(warm_fleet):
    fleet = warm_fleet
    fleet.sync_all()
    router = FleetRouter(fleet, priorities={"predictive": 2, "vote": 0},
                         max_batch=8, default_deadline_s=30.0)
    spec_p = fleet.spec("bayeslr", "predictive")
    spec_v = fleet.spec("bayeslr", "vote")
    low = [router.submit("bayeslr", "vote", spec_v.make_queries(jax.random.key(i), 2))
           for i in range(3)]
    high = [router.submit("bayeslr", "predictive",
                          spec_p.make_queries(jax.random.key(10 + i), 2))
            for i in range(3)]
    served = router.drain()
    # Within each lane the high-priority batch went first; verify globally by
    # completion order: every high request precedes any low request served on
    # the same lane. Cheap proxy: first completions are all high-priority.
    first_classes = [r.query_class for r in served[:len(high)]]
    assert all(c == "predictive" for c in first_classes)
    assert all(r.done.is_set() for r in low + high)


def test_admission_sheds_lowest_class_first(warm_fleet):
    fleet = warm_fleet
    fleet.sync_all()
    router = FleetRouter(
        fleet, priorities={"predictive": 1, "vote": 0},
        admission=AdmissionConfig(max_depth=6, min_observations=10**9),
        max_batch=4, default_deadline_s=30.0,
    )
    spec = fleet.spec("bayeslr", "predictive")
    reqs = []
    for i in range(24):
        cls = "predictive" if i % 2 else "vote"
        reqs.append(router.submit("bayeslr", cls, spec.make_queries(jax.random.key(i), 2)))
    router.drain()
    report = router.slo_report()
    assert report["classes"]["bayeslr.vote"]["shed"] > 0
    assert report["classes"]["bayeslr.predictive"]["shed"] == 0
    assert report["shed"] == report["classes"]["bayeslr.vote"]["shed"]
    shed_req = next(r for r in reqs if (r.error or "").startswith("shed"))
    with pytest.raises(RuntimeError, match="shed"):
        shed_req.result(timeout_s=1.0)


def test_admission_trips_on_predicted_miss_rate(warm_fleet):
    fleet = warm_fleet
    fleet.sync_all()
    router = FleetRouter(
        fleet, priorities={"predictive": 1, "vote": 0},
        admission=AdmissionConfig(max_depth=10**6, max_miss_rate=0.5,
                                  miss_window=8, min_observations=4),
        max_batch=4, default_deadline_s=30.0,
    )
    spec = fleet.spec("bayeslr", "predictive")
    # Deadline 0 => every completion is a miss; the predictor trips.
    for i in range(6):
        router.submit("bayeslr", "predictive",
                      spec.make_queries(jax.random.key(i), 2), deadline_s=0.0)
    router.drain()
    assert router.predicted_miss_rate() > 0.5
    low = router.submit("bayeslr", "vote", spec.make_queries(jax.random.key(99), 2))
    high = router.submit("bayeslr", "predictive",
                         spec.make_queries(jax.random.key(100), 2))
    assert (low.error or "").startswith("shed")
    assert high.error is None
    router.drain()
    report = router.slo_report()
    assert report["admission"]["shed_floor"] == 1
    assert report["classes"]["bayeslr.vote"]["shed"] == 1


def test_single_class_is_never_shed(warm_fleet):
    fleet = warm_fleet
    fleet.sync_all()
    router = FleetRouter(
        fleet, priorities={"predictive": 0, "vote": 0},
        admission=AdmissionConfig(max_depth=2, min_observations=10**9),
        max_batch=4, default_deadline_s=30.0,
    )
    spec = fleet.spec("bayeslr", "predictive")
    for i in range(10):  # equal priorities: no lower class to shed first
        router.submit("bayeslr", "predictive", spec.make_queries(jax.random.key(i), 2))
    router.drain()
    assert router.slo_report()["shed"] == 0


def test_admission_floor_steps_at_max_depth_multiples(warm_fleet):
    """Hysteresis of the depth-driven shed floor across three priority
    levels: each ``max_depth`` multiple of backlog raises the floor one
    level (never past the top class), and draining drops it back to None."""
    fleet = warm_fleet
    fleet.sync_all()
    depth = 4
    # Three levels: "bulk" exists only in the priority map (submissions for
    # it queue like any class) so the floor has two steps to climb.
    router = FleetRouter(
        fleet, priorities={"predictive": 2, "vote": 1, "bulk": 0},
        admission=AdmissionConfig(max_depth=depth, min_observations=10**9),
        max_batch=4, default_deadline_s=30.0,
    )
    spec = fleet.spec("bayeslr", "predictive")
    qs = lambda i: spec.make_queries(jax.random.key(i), 2)

    assert router.slo_report()["admission"]["shed_floor"] is None
    assert router.submit("bayeslr", "bulk", qs(0)).error is None  # admitted

    # Build backlog (no workers running) out of top-class requests only —
    # they are always admitted, so the depth is exactly controllable.
    floors = {}
    for i in range(1, 2 * depth + 1):
        router.submit("bayeslr", "predictive", qs(i))
        floors[router.pending_count] = (
            router.slo_report()["admission"]["shed_floor"]
        )
    # below max_depth: everything admitted; the first multiple cuts
    # priority-0; the second cuts priority-1 as well; never priority-2.
    assert floors[depth - 1] is None
    assert floors[depth] == 1
    assert floors[2 * depth] == 2

    low = router.submit("bayeslr", "bulk", qs(100))
    mid = router.submit("bayeslr", "vote", qs(101))
    top = router.submit("bayeslr", "predictive", qs(102))
    assert (low.error or "").startswith("shed")
    assert (mid.error or "").startswith("shed")
    assert top.error is None

    # The one pre-floor bulk request fails at serve time (no such spec) —
    # that must fail the request, not the drain.
    router.drain()
    report = router.slo_report()
    assert report["admission"]["shed_floor"] is None  # backlog gone: recovered
    assert report["classes"]["bayeslr.bulk"]["shed"] == 1
    assert report["classes"]["bayeslr.vote"]["shed"] == 1
    assert report["classes"]["bayeslr.predictive"]["shed"] == 0
    admit = router.submit("bayeslr", "vote", qs(103))
    assert admit.error is None  # floor lifted: low classes admitted again


# ---------------------------------------------------------------------------
# Warm checkpoint round-trip through the fleet
# ---------------------------------------------------------------------------


def test_fleet_checkpoint_roundtrip_resumes_key_schedule(tmp_path):
    fleet1 = _tiny_fleet()
    fleet1.warm()
    fleet1.save(str(tmp_path))

    fleet2 = _tiny_fleet()
    step = fleet2.restore(str(tmp_path))
    s1 = fleet1.shards("bayeslr")[0]
    s2 = fleet2.shards("bayeslr")[0]
    assert step == s1.writer.steps_done == s2.writer.steps_done
    # restored replicas already mirror the restored writer window
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(s1.replicas[0].snapshot().draws)[0]),
        np.asarray(jax.tree.leaves(s2.replicas[0].snapshot().draws)[0]),
    )
    # the restored fleet's next refresh+broadcast continues the exact key
    # schedule: writer windows AND replica copies stay bit-for-bit equal
    fleet1.pump("bayeslr")
    fleet2.pump("bayeslr")
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(s1.writer.snapshot().draws)[0]),
        np.asarray(jax.tree.leaves(s2.writer.snapshot().draws)[0]),
    )
    for r1, r2 in zip(s1.replicas, s2.replicas):
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(r1.snapshot().draws)[0]),
            np.asarray(jax.tree.leaves(r2.snapshot().draws)[0]),
        )


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------


def test_fleet_config_validation():
    with pytest.raises(ValueError, match="replicas and shards"):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError, match="unknown transport"):
        FleetConfig(transport="carrier-pigeon")
    with pytest.raises(ValueError, match="max_depth"):
        AdmissionConfig(max_depth=0)
    with pytest.raises(ValueError, match="max_miss_rate"):
        AdmissionConfig(max_miss_rate=0.0)


def test_ensemble_2d_shard_validation(gaussian_target_factory):
    target, _, _ = gaussian_target_factory(n=100, seed=0)
    cfg = SubsampledMHConfig(batch_size=20, epsilon=0.05)
    with pytest.raises(ValueError, match="must name the mesh axes"):
        ChainEnsemble(target, RandomWalk(0.1), 4, config=cfg, shard=("rows", "cols"))
    with pytest.raises(ValueError, match="subset"):
        ChainEnsemble(target, RandomWalk(0.1), 4, config=cfg,
                      shard={"chains": 2, "batch": 2})
    with pytest.raises(ValueError, match="subsampled kernel"):
        ChainEnsemble(target, RandomWalk(0.1), 4, kernel="exact",
                      shard=("chains", "data"))
    with pytest.raises(ValueError, match="'auto', True, False"):
        ChainEnsemble(target, RandomWalk(0.1), 4, config=cfg, shard="yes")


def test_ensemble_2d_single_device_matches_default(gaussian_target_factory):
    """On one device the 2-d request runs the batched-transition scan —
    still bit-for-bit the default vmapped engine."""
    target, _, _ = gaussian_target_factory(n=200, seed=1)
    cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05)
    keys = jax.random.split(jax.random.key(2), 4)
    ens2d = ChainEnsemble(target, RandomWalk(0.1), 4, config=cfg,
                          shard=("chains", "data"))
    plain = ChainEnsemble(target, RandomWalk(0.1), 4, config=cfg, shard=False)
    _, s2, i2 = ens2d.run(keys, ens2d.init(jnp.zeros(())), 30)
    _, sp, ip = plain.run(keys, plain.init(jnp.zeros(())), 30)
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(sp))
    np.testing.assert_array_equal(np.asarray(i2.n_evaluated),
                                  np.asarray(ip.n_evaluated))


# ---------------------------------------------------------------------------
# Multi-device contracts (subprocess: JAX pins device count at first init)
# ---------------------------------------------------------------------------


def _run_forced_devices(script: str, devices: int = 4) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=_REPO, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_2d_sharded_run_bit_for_bit_vs_unsharded():
    """Lock-step AND masked 2-d-sharded runs == unsharded at 4 devices."""
    script = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import ChainEnsemble, RandomWalk, SubsampledMHConfig, from_iid_loglik

n = 400
x = 0.7 + jnp.asarray(jax.random.normal(jax.random.key(1), (n,)))
target = from_iid_loglik(lambda th: -0.5 * jnp.sum(th**2),
                         lambda th, idx: -0.5 * (x[idx] - th) ** 2, None, n)
cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05)
keys = jax.random.split(jax.random.key(5), 8)

out = {"n_devices": len(jax.devices())}
for stepping in ("lockstep", "masked"):
    runs = {}
    for name, shard in (("2d", ("chains", "data")),
                        ("2d_dict", {"chains": 2, "data": 2}),
                        ("off", False)):
        ens = ChainEnsemble(target, RandomWalk(0.05), 8, config=cfg,
                            shard=shard, stepping=stepping)
        _, s, i = ens.run(keys, ens.init(jnp.zeros(())), 60)
        runs[name] = (np.asarray(s), np.asarray(i.n_evaluated))
    out[stepping] = bool(
        np.array_equal(runs["2d"][0], runs["off"][0])
        and np.array_equal(runs["2d"][1], runs["off"][1])
        and np.array_equal(runs["2d_dict"][0], runs["off"][0])
    )
print(json.dumps(out))
"""
    res = _run_forced_devices(script)
    assert res["n_devices"] == 4
    assert res["lockstep"] is True
    assert res["masked"] is True


@pytest.mark.slow
def test_2d_sharded_fused_family_bit_for_bit():
    """The registry-threaded fused route under the 2-d mesh == its
    unsharded self (and allclose to the unfused reference)."""
    script = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import ChainEnsemble, RandomWalk, SubsampledMHConfig
from repro.core.target_builder import build_target

n, d = 256, 3
kx, ky = jax.random.split(jax.random.key(0))
x = jax.random.normal(kx, (n, d))
y = jnp.where(jax.random.bernoulli(ky, 0.5, (n,)), 1.0, -1.0)
target = build_target("logit", (x, y), n,
                      prior_logpdf=lambda w: -0.5 * jnp.sum(w**2))
cfg = SubsampledMHConfig(batch_size=64, epsilon=0.05)
keys = jax.random.split(jax.random.key(3), 8)
outs = {}
for name, kw in (("fused_2d", dict(shard=("chains", "data"), fused_kernels="always")),
                 ("fused_off", dict(shard=False, fused_kernels="always")),
                 ("plain", dict(shard=False, fused_kernels="never"))):
    ens = ChainEnsemble(target, RandomWalk(0.1), 8, config=cfg, **kw)
    _, s, _ = ens.run(keys, ens.init(jnp.zeros(d)), 40)
    outs[name] = np.asarray(s)
print(json.dumps({
    "n_devices": len(jax.devices()),
    "bitexact": bool(np.array_equal(outs["fused_2d"], outs["fused_off"])),
    "allclose": bool(np.allclose(outs["fused_2d"], outs["plain"], rtol=2e-4, atol=2e-5)),
}))
"""
    res = _run_forced_devices(script)
    assert res["bitexact"] is True and res["allclose"] is True


@pytest.mark.slow
def test_sharded_fleet_checkpoint_roundtrip_at_4_devices(tmp_path):
    """A fleet whose writers run the 2-d mesh checkpoints and restores
    warm: the restored key schedule continues bit for bit and the replicas
    mirror it."""
    script = r"""
import json, tempfile
import jax, numpy as np
from repro.fleet import Fleet, FleetConfig
from repro.serving import FreshnessPolicy, ServingConfig

def build():
    cfg = FleetConfig(
        replicas=2, shards=1, mesh=("chains", "data"),
        serving=ServingConfig(num_chains=4, refresh_steps=8, window=16,
                              micro_batch=8,
                              freshness=FreshnessPolicy(max_staleness_s=1e9,
                                                        min_draws=8),
                              seed=0),
    )
    fleet = Fleet(cfg)
    fleet.add_workload("bayeslr", smoke=True, n_train=400, d=3, batch_size=50)
    return fleet

ckpt = tempfile.mkdtemp()
f1 = build(); f1.warm(); f1.save(ckpt)
f2 = build(); step = f2.restore(ckpt)
f1.pump(); f2.pump()
s1, s2 = f1.shards("bayeslr")[0], f2.shards("bayeslr")[0]
w1 = np.asarray(jax.tree.leaves(s1.writer.snapshot().draws)[0])
w2 = np.asarray(jax.tree.leaves(s2.writer.snapshot().draws)[0])
r1 = np.asarray(jax.tree.leaves(s1.replicas[1].snapshot().draws)[0])
r2 = np.asarray(jax.tree.leaves(s2.replicas[1].snapshot().draws)[0])
print(json.dumps({
    "n_devices": len(jax.devices()),
    "step": step,
    "writers_equal": bool(np.array_equal(w1, w2)),
    "replicas_equal": bool(np.array_equal(r1, r2)),
    "replica_mirrors_writer": bool(np.array_equal(w2, r2)),
}))
"""
    res = _run_forced_devices(script)
    assert res["n_devices"] == 4
    assert res["writers_equal"] and res["replicas_equal"]
    assert res["replica_mirrors_writer"]


@pytest.mark.slow
def test_proc_transport_replica_parity():
    """Process-group replicas (spawned workers) serve bit-for-bit what the
    writer serves, fed only by pickled deltas over the pipe."""
    script = r"""
import json
import jax, numpy as np
from repro.fleet import Fleet, FleetConfig
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
    fleet.warm()
    fleet.pump()
    shard = fleet.shards("bayeslr")[0]
    spec = fleet.spec("bayeslr", "predictive")
    xs = spec.make_queries(jax.random.key(9), 8)
    w_vals, _ = shard.writer.query(spec, xs)
    r_vals, _ = shard.replicas[0].serve(spec, "predictive", xs)
    stats = shard.replicas[0].stats()
    fleet.close()
    print(json.dumps({
        "equal": bool(np.array_equal(np.asarray(w_vals), np.asarray(r_vals))),
        "deltas_applied": stats["deltas_applied"],
        "bytes_received": stats["bytes_received"],
    }))

if __name__ == "__main__":
    main()
"""
    res = _run_forced_devices(script, devices=1)
    assert res["equal"] is True
    assert res["deltas_applied"] >= 2 and res["bytes_received"] > 0


def test_router_workers_serve_mixed_classes_correctly(warm_fleet):
    """Background lane workers with interleaved classes: every request must
    be answered with ITS class's functional (a merged cross-class batch
    would silently serve the wrong spec) and none may be dropped."""
    fleet = warm_fleet
    fleet.sync_all()
    router = FleetRouter(fleet, priorities={"predictive": 1, "vote": 0},
                         max_batch=4, default_deadline_s=30.0)
    spec_p = fleet.spec("bayeslr", "predictive")
    spec_v = fleet.spec("bayeslr", "vote")
    shard = fleet.shards("bayeslr")[0]
    router.start_workers(max_wait_s=0.001)
    try:
        reqs = []
        for i in range(16):
            cls = "predictive" if i % 2 else "vote"
            xs = (spec_p if cls == "predictive" else spec_v).make_queries(
                jax.random.key(i), 3)
            reqs.append((cls, xs, router.submit("bayeslr", cls, xs)))
        for cls, xs, req in reqs:
            got = req.result(timeout_s=30.0)  # hangs = dropped request
            spec = spec_p if cls == "predictive" else spec_v
            want, _ = shard.writer.query(spec, xs)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    finally:
        router.stop_workers()


# ---------------------------------------------------------------------------
# Runtime scaling — add_replica / remove_replica + attach_lane / detach_lane
# ---------------------------------------------------------------------------


def test_add_replica_joins_bit_exact_with_fresh_name():
    fleet = _tiny_fleet()
    fleet.warm()
    try:
        assert fleet.replica_count("bayeslr") == 2
        shard_before = fleet.shards("bayeslr")[0]
        shard, replica = fleet.add_replica("bayeslr")
        assert fleet.replica_count("bayeslr") == 3
        # the shard entry was swapped, not mutated: the new tuple is the
        # old one plus the newcomer, and the live list holds the new entry
        assert fleet.shards("bayeslr")[0] is shard
        assert shard.replicas[:-1] == shard_before.replicas
        assert replica is shard.replicas[-1]
        assert replica.name == f"{shard.name}#r2"
        # the join resync seeded the full window: bit-exact immediately
        assert replica.version == shard.writer.steps_done
        spec = fleet.spec("bayeslr", "predictive")
        xs = spec.make_queries(jax.random.key(0), 8)
        want, _ = shard.writer.query(spec, xs)
        got, _ = replica.serve(spec, "predictive", xs)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        # retire + re-add: the #rN sequence never reuses a name
        fleet.remove_replica("bayeslr", replica_name=replica.name)
        _, again = fleet.add_replica("bayeslr")
        assert again.name == f"{shard.name}#r3"
    finally:
        fleet.close()


def test_remove_replica_retires_newest_and_guards_the_last():
    fleet = _tiny_fleet()  # 2 launch replicas
    fleet.warm()
    try:
        _, added = fleet.add_replica("bayeslr")
        assert fleet.remove_replica("bayeslr", replica_name=added.name) \
            == added.name
        shard = fleet.shards("bayeslr")[0]
        assert added not in shard.replicas
        assert fleet.replica_count("bayeslr") == 2
        with pytest.raises(KeyError):
            fleet.remove_replica("bayeslr", replica_name=added.name)
        # no name: the newest goes first
        newest = shard.replicas[-1].name
        assert fleet.remove_replica("bayeslr") == newest
        assert fleet.replica_count("bayeslr") == 1
        with pytest.raises(ValueError, match="last replica"):
            fleet.remove_replica("bayeslr")
        assert fleet.replica_count("bayeslr") == 1
    finally:
        fleet.close()


def test_attach_lane_serves_and_detach_reroutes_cleanly():
    fleet = _tiny_fleet(replicas=1)
    fleet.warm()
    try:
        spec = fleet.spec("bayeslr", "predictive")
        router = FleetRouter(fleet, priorities={"predictive": 0},
                             max_batch=4, default_deadline_s=30.0)
        shard, replica = fleet.add_replica("bayeslr")
        router.attach_lane(shard, replica)
        reqs = []
        for i in range(12):
            xs = spec.make_queries(jax.random.key(i), 2)
            reqs.append((xs, router.submit("bayeslr", "predictive", xs)))
        router.drain()
        for xs, req in reqs:
            want, _ = shard.writer.query(spec, xs)
            np.testing.assert_array_equal(
                np.asarray(req.result()), np.asarray(want))
        lanes = router._lanes["bayeslr"]
        assert len(lanes) == 2
        assert all(l.served > 0 for l in lanes)  # least-loaded used both
        # detach with a backlog queued: the pending work reroutes, nothing
        # is dropped, and the surviving lane keeps serving
        tail = []
        for i in range(6):
            xs = spec.make_queries(jax.random.key(100 + i), 2)
            tail.append((xs, router.submit("bayeslr", "predictive", xs)))
        assert router.detach_lane("bayeslr", replica.name) is True
        fleet.remove_replica("bayeslr", replica_name=replica.name)
        router.drain()
        for xs, req in tail:
            want, _ = shard.writer.query(spec, xs)
            np.testing.assert_array_equal(
                np.asarray(req.result()), np.asarray(want))
        assert router.slo_report()["errors"] == 0
    finally:
        fleet.close()
