"""Statistical ground-truth harness for data-parallel subposterior MCMC.

The conjugate Gaussian-mean model gives a closed-form posterior, so the
partition -> temper -> sample -> combine pipeline (:mod:`repro.partition`)
is tested against *exact* answers, not a reference chain:

  * partitioning covers/disjoints the pool; P=1 is the same object;
  * the tempered subposterior log-densities sum to the full posterior's;
  * consensus and density-product combination recover the exact posterior
    mean and covariance at P in {1, 2, 4};
  * combination is invariant under permuting the partitions;
  * fleet wiring: P=1 is bit-for-bit the unpartitioned serving path, P=2
    serves finite, deterministic combined answers through the router;
  * streaming append: any chunking equals a full rebuild on the
    concatenated pool (property-tested), the empty append is a no-op, and
    the freshness policy refuses pre-append windows (staleness reset
    regression).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import append_observations, build_target, spec_of
from repro.partition import (
    combine_draws,
    combine_snapshots,
    consensus_combine,
    flatten_draws,
    partition_append_indices,
    partition_indices,
    partition_target,
    product_moments,
    take_sections,
    trim_windows,
    unflatten_draws,
)

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

# ---------------------------------------------------------------------------
# Partitioner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", ["stride", "block"])
@pytest.mark.parametrize("n,num_p", [(10, 1), (10, 3), (7, 7), (64, 4)])
def test_partition_indices_cover_and_disjoint(n, num_p, scheme):
    parts = partition_indices(n, num_p, scheme)
    assert len(parts) == num_p
    merged = np.concatenate(parts)
    assert sorted(merged.tolist()) == list(range(n))
    assert all(len(p) >= 1 for p in parts)
    sizes = [len(p) for p in parts]
    assert max(sizes) - min(sizes) <= 1  # balanced to within one row


def test_partition_indices_rejects_bad_shapes():
    with pytest.raises(ValueError):
        partition_indices(3, 4)
    with pytest.raises(ValueError):
        partition_indices(8, 0)
    with pytest.raises(ValueError):
        partition_indices(8, 2, "zigzag")


@pytest.mark.parametrize("n_before,n_new,num_p", [(10, 7, 3), (8, 1, 4), (5, 0, 2)])
def test_partition_append_indices_extend_stride_partition(n_before, n_new, num_p):
    """Appending chunk[idx_p] to shard p == stride-partitioning the concat."""
    parts_before = partition_indices(n_before, num_p)
    parts_after = partition_indices(n_before + n_new, num_p) if n_new else parts_before
    appended = partition_append_indices(n_before, n_new, num_p)
    for p in range(num_p):
        grown = np.concatenate([parts_before[p], appended[p] + n_before])
        np.testing.assert_array_equal(grown, parts_after[p])


def test_partition_append_indices_require_stride():
    with pytest.raises(ValueError):
        partition_append_indices(8, 4, 2, scheme="block")


def test_partition_p1_is_same_object(conjugate_posterior):
    target = conjugate_posterior["target"]
    parts = partition_target(target, 1)
    assert len(parts) == 1 and parts[0] is target


def test_tempered_subposteriors_sum_to_full_posterior(conjugate_posterior):
    """sum_p [ (1/P) log prior + local loglik ] == full log posterior."""
    target = conjugate_posterior["target"]
    theta = jnp.asarray([0.25, -0.8])
    full = float(target.log_density(theta))
    for num_p in (2, 4):
        parts = partition_target(target, num_p)
        assert all(p.spec.prior_scale == pytest.approx(1.0 / num_p) for p in parts)
        total = sum(float(p.log_density(theta)) for p in parts)
        assert total == pytest.approx(full, rel=1e-5, abs=1e-3)


# ---------------------------------------------------------------------------
# Combination math
# ---------------------------------------------------------------------------


def test_flatten_unflatten_roundtrip(rng):
    draws = {
        "a": rng.normal(size=(3, 5, 2)).astype(np.float32),
        "b": rng.normal(size=(3, 5)).astype(np.float32),
    }
    flat = flatten_draws(draws)
    assert flat.shape == (15, 3)
    back = unflatten_draws(flat, draws)
    for k in draws:
        np.testing.assert_array_equal(back[k], draws[k])


def test_trim_windows_keeps_trailing_draws(rng):
    a = rng.normal(size=(2, 10, 3))
    b = rng.normal(size=(2, 6, 3))
    ta, tb = trim_windows([a, b])
    np.testing.assert_array_equal(ta, a[:, -6:])
    np.testing.assert_array_equal(tb, b)
    with pytest.raises(ValueError):
        trim_windows([a, rng.normal(size=(3, 6, 3))])  # chain-count mismatch


def test_single_partition_combination_is_passthrough(rng):
    draws = rng.normal(size=(2, 8, 3))
    for method in ("consensus", "product"):
        assert combine_draws([draws], method) is draws


@pytest.mark.parametrize("num_p", [1, 2, 4])
@pytest.mark.parametrize("method", ["consensus", "product"])
def test_combination_recovers_conjugate_posterior(
    conjugate_posterior, num_p, method
):
    """The headline ground-truth bar: recombined subposterior MCMC draws
    match the closed-form posterior N(n xbar/(n+1), I/(n+1))."""
    cp = conjugate_posterior
    draws = cp["run"](num_p)
    combined = np.asarray(
        combine_draws(draws, method, seed=17), np.float64
    ).reshape(-1, cp["d"])
    post_std = np.sqrt(cp["post_var"])
    err_mean = np.max(np.abs(combined.mean(axis=0) - cp["post_mean"])) / post_std
    assert err_mean < 0.5, (
        f"P={num_p} {method}: combined mean off by {err_mean:.2f} "
        f"posterior std"
    )
    var_ratio = combined.var(axis=0, ddof=1) / cp["post_var"]
    assert np.all(var_ratio > 0.45) and np.all(var_ratio < 2.2), (
        f"P={num_p} {method}: variance ratio {var_ratio} outside [0.45, 2.2]"
    )


def test_p1_combination_matches_unpartitioned_chain(conjugate_posterior):
    """P=1 'combination' must be the unpartitioned window itself, bit for
    bit — there is nothing to combine."""
    draws = conjugate_posterior["run"](1)
    for method in ("consensus", "product"):
        out = combine_draws(draws, method)
        assert out is draws[0]


def test_combination_invariant_under_partition_permutation(conjugate_posterior):
    draws = conjugate_posterior["run"](4)
    perm = [2, 0, 3, 1]
    base = np.asarray(combine_draws(draws, "consensus"))
    permuted = np.asarray(combine_draws([draws[i] for i in perm], "consensus"))
    np.testing.assert_allclose(permuted, base, rtol=1e-8, atol=1e-10)
    flats = [flatten_draws(d) for d in draws]
    m0, c0 = product_moments(flats)
    m1, c1 = product_moments([flats[i] for i in perm])
    np.testing.assert_allclose(m1, m0, rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(c1, c0, rtol=1e-10, atol=1e-12)


def test_consensus_requires_aligned_shapes(rng):
    with pytest.raises(ValueError):
        consensus_combine([rng.normal(size=(10, 2)), rng.normal(size=(8, 2))])


def test_combine_snapshots_versions_and_staleness(rng):
    from repro.serving.resident import Snapshot

    def snap(version, staleness):
        return Snapshot(
            draws=rng.normal(size=(2, 6, 2)),
            num_draws=12, steps_done=version, staleness_s=staleness,
            summary={}, created_at=0.0,
        )

    combined = combine_snapshots([snap(32, 0.5), snap(48, 2.5)], "consensus")
    assert combined.steps_done == 80  # version sum: the generation key
    assert combined.staleness_s == 2.5  # only as fresh as the stalest input
    assert combined.num_draws == 12
    assert combined.summary["combine"] == {
        "method": "consensus", "partitions": 2,
    }
    with pytest.raises(RuntimeError, match="no window"):
        combine_snapshots(
            [snap(1, 0.0), snap(2, 0.0)._replace(draws=None)], "consensus"
        )


# ---------------------------------------------------------------------------
# Streaming append: target rebuild properties
# ---------------------------------------------------------------------------


def _toy_target(x):
    return build_target(
        "gaussian_mean", jnp.asarray(x), int(np.shape(x)[0]),
        prior_logpdf=lambda th: -0.5 * jnp.sum(th ** 2, axis=-1),
    )


@settings(max_examples=12, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(st.lists(st.integers(min_value=1, max_value=7), min_size=0, max_size=4))
def test_append_chunking_matches_full_rebuild(chunk_sizes):
    """Any append order/chunking == one build on the concatenated pool:
    same spec data bitwise, same log density bitwise."""
    rng = np.random.default_rng(11)
    base = rng.normal(size=(9, 2)).astype(np.float32)
    extra = rng.normal(size=(sum(chunk_sizes), 2)).astype(np.float32)
    target = _toy_target(base)
    offset = 0
    for size in chunk_sizes:
        target = append_observations(target, extra[offset:offset + size])
        offset += size
    rebuilt = _toy_target(np.concatenate([base, extra], axis=0))
    assert target.num_sections == rebuilt.num_sections
    np.testing.assert_array_equal(
        np.asarray(spec_of(target).data), np.asarray(spec_of(rebuilt).data)
    )
    theta = jnp.asarray([0.3, -0.2])
    assert float(target.log_density(theta)) == float(rebuilt.log_density(theta))


def test_empty_append_is_identity():
    target = _toy_target(np.zeros((5, 2), np.float32))
    out = append_observations(target, np.zeros((0, 2), np.float32))
    assert out is target


# ---------------------------------------------------------------------------
# Streaming append: resident fold-in + freshness regression
# ---------------------------------------------------------------------------


def _make_resident(x, *, key, window=8, refresh_steps=4):
    from repro.core import ChainEnsemble, RandomWalk, SubsampledMHConfig
    from repro.serving.resident import ResidentEnsemble

    target = _toy_target(x)
    cfg = SubsampledMHConfig(
        batch_size=min(16, target.num_sections), epsilon=0.01,
        sampler="stream",
    )
    ens = ChainEnsemble(target, RandomWalk(0.15), 2, config=cfg)
    return ResidentEnsemble(
        ens, jnp.zeros(2), key=key, window=window, refresh_steps=refresh_steps,
        name="stream-test",
    )


def test_resident_append_then_refresh_matches_concat_build(rng, key):
    """Appending before the first refresh == building on the concatenated
    pool: identical step-key schedule from the same base key, so the first
    window is bit-for-bit equal."""
    base = rng.normal(size=(20, 2)).astype(np.float32)
    extra = rng.normal(size=(12, 2)).astype(np.float32)
    streamed = _make_resident(base, key=key)
    added = streamed.append(extra)
    assert added == 12
    assert streamed.ensemble.target.num_sections == 32
    rebuilt = _make_resident(np.concatenate([base, extra]), key=key)
    streamed.refresh()
    rebuilt.refresh()
    np.testing.assert_array_equal(
        np.asarray(streamed.snapshot().draws), np.asarray(rebuilt.snapshot().draws)
    )


def test_resident_append_continues_running_chains(rng, key):
    """Mid-run append: steps_done and theta carry over (no restart), the
    window survives, and the next refresh advances the grown target."""
    base = rng.normal(size=(20, 2)).astype(np.float32)
    extra = rng.normal(size=(8, 2)).astype(np.float32)
    res = _make_resident(base, key=key)
    res.refresh()
    res.refresh()
    theta_before = np.asarray(res.state.theta)
    draws_before = np.asarray(res.snapshot().draws)
    assert res.steps_done == 8
    added = res.append(extra)
    assert added == 8
    assert res.steps_done == 8  # schedule position preserved
    np.testing.assert_array_equal(np.asarray(res.state.theta), theta_before)
    np.testing.assert_array_equal(np.asarray(res.snapshot().draws), draws_before)
    res.refresh()
    assert res.steps_done == 12
    assert res.ensemble.target.num_sections == 28


def test_resident_empty_append_is_bitwise_noop(rng, key):
    res = _make_resident(rng.normal(size=(10, 2)).astype(np.float32), key=key)
    res.refresh()
    target_before = res.ensemble.target
    state_before = res._state
    stale_before = res.snapshot().staleness_s
    assert res.append(np.zeros((0, 2), np.float32)) == 0
    assert res.ensemble.target is target_before
    assert res._state is state_before
    assert np.isfinite(stale_before)
    assert np.isfinite(res.snapshot().staleness_s)  # clock NOT reset


def test_append_resets_freshness_staleness(rng, key):
    """Regression: the max_staleness_s gate must refuse pre-append windows.
    Before the fix, staleness only tracked the last draw-refresh, so a
    just-refreshed resident kept serving the pre-append posterior as
    fresh after new observations arrived."""
    from repro.serving import FreshnessPolicy

    res = _make_resident(rng.normal(size=(16, 2)).astype(np.float32), key=key)
    policy = FreshnessPolicy(max_staleness_s=3600.0, min_draws=4)
    res.refresh()
    snap = res.snapshot()
    assert policy.is_fresh(snap), policy.stale_reason(snap)
    res.append(rng.normal(size=(4, 2)).astype(np.float32))
    snap = res.snapshot()
    assert snap.staleness_s == float("inf")
    reason = policy.stale_reason(snap)
    assert reason is not None and "stale" in reason
    # one refresh folds the appended data in and the gate re-admits
    res.refresh()
    assert policy.is_fresh(res.snapshot())


# ---------------------------------------------------------------------------
# Fleet wiring
# ---------------------------------------------------------------------------


_FLEET_KW = dict(n_train=96, d=3, batch_size=32)


def _fleet_serving_config():
    from repro.serving import FreshnessPolicy, ServingConfig

    return ServingConfig(
        num_chains=2, refresh_steps=4, window=8, micro_batch=16, max_batch=4,
        freshness=FreshnessPolicy(max_staleness_s=3600.0, min_draws=4),
        seed=0,
    )


def test_fleet_p1_bitexact_vs_unpartitioned_serving(key):
    """The P=1 fleet configuration IS the unpartitioned path: same shard
    names, same chain keys, and bit-for-bit the same windows as a plain
    resident built the way the pre-partition fleet built it."""
    from repro.fleet import Fleet, FleetConfig
    from repro.serving.resident import ResidentEnsemble
    from repro.serving.workloads import build_serving_workload

    scfg = _fleet_serving_config()
    fleet = Fleet(FleetConfig(replicas=1, subposterior=1, serving=scfg))
    (shard,) = fleet.add_workload("bayeslr", **_FLEET_KW)
    assert shard.name == "bayeslr@0" and shard.partition == 0
    assert fleet.num_partitions("bayeslr") == 1

    wl = build_serving_workload("bayeslr", num_chains=2, seed=0, **_FLEET_KW)
    reference = ResidentEnsemble(
        wl.ensemble, wl.theta0,
        key=jax.random.fold_in(jax.random.key(0), 0),
        window=scfg.window, refresh_steps=scfg.refresh_steps,
        micro_batch=scfg.micro_batch, name="reference",
    )
    for _ in range(3):
        shard.writer.refresh()
        reference.refresh()
    np.testing.assert_array_equal(
        np.asarray(shard.writer.snapshot().draws),
        np.asarray(reference.snapshot().draws),
    )
    fleet.close()


def test_fleet_p2_partitions_data_and_keys():
    from repro.fleet import Fleet, FleetConfig

    fleet = Fleet(
        FleetConfig(replicas=1, subposterior=2, serving=_fleet_serving_config())
    )
    shards = fleet.add_workload("bayeslr", **_FLEET_KW)
    assert [s.name for s in shards] == ["bayeslr@p0@0", "bayeslr@p1@0"]
    assert [s.partition for s in shards] == [0, 1]
    sections = [s.writer.ensemble.target.num_sections for s in shards]
    assert sum(sections) == _FLEET_KW["n_train"]
    specs = [spec_of(s.writer.ensemble.target) for s in shards]
    assert all(sp.prior_scale == pytest.approx(0.5) for sp in specs)
    fleet.close()


def test_fleet_p2_combined_serving_is_deterministic():
    """Router combine-at-query: P=2 queries complete with finite values,
    identical on repeat against unchanged windows, and report the max of
    the partitions' staleness."""
    from repro.fleet import Fleet, FleetConfig, FleetRouter

    fleet = Fleet(
        FleetConfig(replicas=2, subposterior=2, combine="consensus",
                    serving=_fleet_serving_config())
    )
    fleet.add_workload("bayeslr", **_FLEET_KW)
    fleet.warm()
    router = FleetRouter(fleet)
    wl = fleet.workload("bayeslr")
    cls = wl.default_class
    xs = wl.query_specs[cls].make_queries(jax.random.key(5), 8)

    def ask():
        req = router.submit("bayeslr", cls, xs)
        router.drain()
        assert req.error is None, req.error
        return np.asarray(req.values), req.staleness_s

    v1, stale1 = ask()
    v2, _ = ask()
    assert v1.shape == (8,) and np.all(np.isfinite(v1))
    np.testing.assert_array_equal(v1, v2)  # same windows -> same combine
    assert stale1 >= 0.0  # max over the partitions' window staleness
    # after a pump the combined window changes and queries still serve
    fleet.pump("bayeslr")
    v3, _ = ask()
    assert np.all(np.isfinite(v3))
    fleet.close()


def test_fleet_append_routes_rows_to_partitions(rng):
    from repro.fleet import Fleet, FleetConfig

    fleet = Fleet(
        FleetConfig(replicas=1, subposterior=2, serving=_fleet_serving_config())
    )
    shards = fleet.add_workload("bayeslr", **_FLEET_KW)
    n = _FLEET_KW["n_train"]
    before = [s.writer.ensemble.target.num_sections for s in shards]
    tspec = spec_of(fleet.workload("bayeslr").ensemble.target)
    idx = rng.integers(0, n, size=7)
    chunk = jax.tree.map(lambda a: np.asarray(a)[idx], tspec.data)
    added = fleet.append_observations("bayeslr", chunk)
    assert added == 7
    after = [s.writer.ensemble.target.num_sections for s in shards]
    expected = [
        len(p) for p in partition_append_indices(n, 7, 2)
    ]
    assert [a - b for a, b in zip(after, before)] == expected
    # per-partition slices match a from-scratch stride partition of concat
    merged = jax.tree.map(
        lambda a, b: np.concatenate([np.asarray(a), np.asarray(b)]),
        tspec.data, chunk,
    )
    for shard in shards:
        want = take_sections(merged, partition_indices(n + 7, 2)[shard.partition])
        got = spec_of(shard.writer.ensemble.target).data
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    fleet.close()


def test_replica_window_rpc_version_gate():
    from repro.fleet.replica import ReplicaEnsemble
    from repro.fleet.delta import make_delta
    from repro.serving.resident import Snapshot

    replica = ReplicaEnsemble("w0#r0")
    version, snap = replica.window()
    assert version == 0 and snap.draws is None
    rng = np.random.default_rng(0)
    source = Snapshot(
        draws=rng.normal(size=(2, 4, 3)), num_draws=8, steps_done=16,
        staleness_s=0.1, summary={}, created_at=0.0,
    )
    replica.apply_delta(make_delta(source, 0, 4, "w0"))
    version, snap = replica.window(-1)
    assert version == 16 and snap is not None
    np.testing.assert_array_equal(np.asarray(snap.draws), source.draws)
    version, snap = replica.window(16)  # caller already current
    assert version == 16 and snap is None
