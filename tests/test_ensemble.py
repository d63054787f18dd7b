"""Multi-chain ensemble engine: vmap-vs-sequential equivalence, cross-chain
diagnostics, batched sampler properties, multi-device fan-out."""
import dataclasses
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    ChainEnsemble,
    RandomWalk,
    ScheduleConfig,
    SubsampledMHConfig,
    SubsampledMHInfo,
    ensemble_summary,
    fy_draw,
    fy_init,
    fy_reset,
    multichain_ess,
    run_chain,
    split_rhat,
)

# ---------------------------------------------------------------------------
# K vmapped chains == K sequential run_chain calls, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["subsampled", "exact"])
def test_ensemble_matches_sequential_chains_bit_for_bit(kernel, gaussian_target_factory):
    target, _, _ = gaussian_target_factory(n=600, seed=1)
    cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05)
    K, T = 3, 100
    ens = ChainEnsemble(target, RandomWalk(0.05), K, kernel=kernel, config=cfg)
    state = ens.init(jnp.zeros(()))
    keys = jax.random.split(jax.random.key(7), K)
    state, samples, infos = ens.run(keys, state, T)
    assert samples.shape == (K, T)
    for k in range(K):
        _, s_seq, i_seq = run_chain(
            keys[k], jnp.zeros(()), target, RandomWalk(0.05), T, kernel=kernel, config=cfg
        )
        np.testing.assert_array_equal(np.asarray(samples[k]), np.asarray(s_seq))
        np.testing.assert_array_equal(np.asarray(infos.accepted[k]), np.asarray(i_seq.accepted))
        np.testing.assert_array_equal(
            np.asarray(infos.n_evaluated[k]), np.asarray(i_seq.n_evaluated)
        )


def test_ensemble_chains_are_distinct(gaussian_target_factory):
    """Different per-chain keys must yield different trajectories."""
    target, _, _ = gaussian_target_factory(n=600, seed=1)
    ens = ChainEnsemble(target, RandomWalk(0.05), 3,
                        config=SubsampledMHConfig(batch_size=50, epsilon=0.05))
    state, samples, _ = ens.run(jax.random.key(0), ens.init(jnp.zeros(())), 100)
    s = np.asarray(samples)
    assert not np.array_equal(s[0], s[1])
    assert not np.array_equal(s[1], s[2])


# ---------------------------------------------------------------------------
# Cross-chain diagnostics on a conjugate Gaussian target
# ---------------------------------------------------------------------------


def test_ensemble_rhat_near_one_on_conjugate_gaussian(gaussian_target_factory):
    target, pm, ps = gaussian_target_factory(n=400, seed=1)
    K, T = 4, 600
    ens = ChainEnsemble(target, RandomWalk(0.08), K,
                        config=SubsampledMHConfig(batch_size=200, epsilon=0.05))
    # overdispersed starts around the posterior, per-chain
    theta0 = jnp.asarray([-1.0, -0.3, 0.3, 1.0]) + pm
    state = ens.init(theta0, batched=True)
    state, samples, infos = ens.run(jax.random.key(2), state, T)
    w = np.asarray(samples)[:, T // 2:]
    rhat = split_rhat(w)
    assert rhat < 1.1, f"chains did not mix: rhat={rhat}"
    assert abs(w.mean() - pm) < 6 * ps
    assert multichain_ess(w) > 4 * 10  # at least ~10 effective draws per chain
    summ = ensemble_summary(infos)
    assert summ["accept_rate"].shape == (K,)
    assert 0.0 < summ["accept_rate_overall"] < 1.0
    assert summ["mean_n_evaluated_overall"] < target.num_sections


def test_split_rhat_flags_disjoint_chains():
    rng = np.random.default_rng(0)
    good = rng.normal(0.0, 1.0, size=(4, 400))
    bad = good + np.asarray([0.0, 0.0, 5.0, 5.0])[:, None]
    assert split_rhat(good) < 1.05
    assert split_rhat(bad) > 1.5
    # vectorized over trailing param dims
    stacked = np.stack([good, bad], axis=-1)
    r = split_rhat(stacked)
    assert r.shape == (2,)
    assert r[0] < 1.05 < r[1]


# ---------------------------------------------------------------------------
# Batched Fisher–Yates: per-chain draws stay distinct and in range
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(st.sampled_from([2, 4]), st.sampled_from([10, 37]), st.sampled_from([8, 16]),
       st.integers(0, 2**31 - 1))
def test_batched_fy_draws_distinct_and_in_range_per_chain(k_chains, n, m, seed):
    state = jax.vmap(lambda _: fy_reset(fy_init(n)))(jnp.arange(k_chains))
    keys = jax.random.split(jax.random.key(seed), k_chains)
    vdraw = jax.jit(jax.vmap(fy_draw, in_axes=(0, 0, None)), static_argnums=2)
    drawn = [[] for _ in range(k_chains)]
    rounds = -(-n // m)
    for r in range(rounds):
        keys = jax.vmap(lambda kk: jax.random.split(kk)[0])(keys)
        subs = jax.vmap(lambda kk: jax.random.split(kk)[1])(keys)
        state, idx, valid = vdraw(subs, state, m)
        for c in range(k_chains):
            drawn[c].extend(np.asarray(idx[c])[np.asarray(valid[c])].tolist())
    for c in range(k_chains):
        assert len(drawn[c]) == n
        assert set(drawn[c]) == set(range(n)), "per-chain exhaustive draw must be a permutation"


def test_batched_fy_chains_use_independent_randomness():
    n, m, k_chains = 50, 10, 4
    state = jax.vmap(lambda _: fy_reset(fy_init(n)))(jnp.arange(k_chains))
    keys = jax.random.split(jax.random.key(3), k_chains)
    _, idx, _ = jax.vmap(fy_draw, in_axes=(0, 0, None))(keys, state, m)
    rows = [tuple(np.asarray(idx[c]).tolist()) for c in range(k_chains)]
    assert len(set(rows)) > 1, "chains drew identical mini-batches"


# ---------------------------------------------------------------------------
# Engine plumbing
# ---------------------------------------------------------------------------


def test_ensemble_state_persists_across_runs(gaussian_target_factory):
    """The carried EnsembleState fully determines the continuation: same
    (state, key) -> identical trajectories; different carried state ->
    different trajectories."""
    target, _, _ = gaussian_target_factory(n=600, seed=1)
    cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05)
    ens = ChainEnsemble(target, RandomWalk(0.05), 2, config=cfg)
    keys = jax.random.split(jax.random.key(11), 2)
    st_a, s_a, _ = ens.run(keys, ens.init(jnp.zeros(())), 60)
    # purity: continuing twice from the same state with the same key is
    # bit-identical (state is consumed, never mutated in place)
    _, s_c1, _ = ens.run(jax.random.key(12), st_a, 10)
    _, s_c2, _ = ens.run(jax.random.key(12), st_a, 10)
    np.testing.assert_array_equal(np.asarray(s_c1), np.asarray(s_c2))
    # the carried state matters: same key from a fresh init diverges
    _, s_fresh, _ = ens.run(jax.random.key(12), ens.init(jnp.zeros(())), 10)
    assert not np.array_equal(np.asarray(s_c1), np.asarray(s_fresh))
    # and the continuation picks up where the first run left off
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(st_a.theta)[0]), np.asarray(s_a[:, -1])
    )


def test_ensemble_collect_and_pytree_theta(gaussian_target_factory):
    target, _, _ = gaussian_target_factory(n=600, seed=1)
    cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05)
    ens = ChainEnsemble(
        target, RandomWalk(0.05), 3, config=cfg, collect=lambda th: th * 2.0
    )
    state, samples, _ = ens.run(jax.random.key(0), ens.init(jnp.zeros(())), 20)
    assert samples.shape == (3, 20)


def test_ensemble_rejects_bad_kernel_and_shape(gaussian_target_factory):
    target, _, _ = gaussian_target_factory(n=600, seed=1)
    with pytest.raises(ValueError):
        ChainEnsemble(target, RandomWalk(0.05), 2, kernel="nope")
    ens = ChainEnsemble(target, RandomWalk(0.05), 4)
    with pytest.raises(ValueError):
        ens.init(jnp.zeros((3,)), batched=True)  # 3 != num_chains 4


@pytest.mark.slow
def test_ensemble_shard_map_matches_single_device(gaussian_target_factory):
    """Chains sharded over 4 forced host devices == unsharded ensemble."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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

sharded = ChainEnsemble(target, RandomWalk(0.05), 8, config=cfg, shard=True)
local = ChainEnsemble(target, RandomWalk(0.05), 8, config=cfg, shard=False)
_, s_sh, _ = sharded.run(keys, sharded.init(jnp.zeros(())), 60)
_, s_lo, _ = local.run(keys, local.init(jnp.zeros(())), 60)
print(json.dumps({
    "n_devices": len(jax.devices()),
    "max_diff": float(np.max(np.abs(np.asarray(s_sh) - np.asarray(s_lo)))),
}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=repo, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    import json

    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_devices"] == 4
    assert res["max_diff"] < 1e-5, res


# ---------------------------------------------------------------------------
# Stream windows: each chain's round read as one slice, bit for bit
# ---------------------------------------------------------------------------

# N not a multiple of m, and a small epsilon: some chains exhaust the pool,
# so the round whose run crosses its end takes the index gather.
_STREAM_N, _STREAM_M = 1030, 100


@functools.lru_cache(maxsize=None)
def _stream_target():
    from repro.experiments import bayeslr

    data = bayeslr.synth_2d(jax.random.key(0), n=_STREAM_N)
    return bayeslr.make_target(data.x_train, data.y_train)


def _index_twin(target):
    """``target`` with every Window handed back as its index block: the
    same rounds, read through the element gather."""
    from repro.core.target import Window

    def as_idx(sec):
        if isinstance(sec, Window):
            return sec.start[..., None] + jnp.arange(sec.size, dtype=jnp.int32)
        return sec

    local, fused = target.log_local, target.log_local_ensemble
    return dataclasses.replace(
        target,
        log_local=lambda a, b, sec: local(a, b, as_idx(sec)),
        log_local_ensemble=lambda a, b, sec: fused(a, b, as_idx(sec)),
    )


def _assert_runs_equal(a, b, *, same_program=True):
    """Samples and every info field bit for bit. Across different programs
    (the batched scan against ``run_chain``) ``pvalue`` is compared to f32
    tolerance: XLA fuses the betainc tail differently there, which moves
    its last ulp without touching any decision."""
    (s_a, i_a), (s_b, i_b) = a, b
    np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_b))
    for field in SubsampledMHInfo._fields:
        got, want = np.asarray(getattr(i_a, field)), np.asarray(getattr(i_b, field))
        if field == "pvalue" and not same_program:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-30)
        else:
            np.testing.assert_array_equal(got, want, err_msg=field)


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
@pytest.mark.parametrize("fused", ["never", "always", "interpret"])
@pytest.mark.parametrize("stepping", ["lockstep", "masked"])
def test_stream_window_rounds_bit_for_bit(stepping, fused, adaptive, monkeypatch):
    """A ``stream`` ensemble whose rounds read windows equals the same
    ensemble reading index blocks, and K sequential ``run_chain`` calls (the
    vmapped single-chain scan, when adaptive), in samples and every info
    field (``pvalue`` to f32 tolerance across programs); on runs where the
    gather branch runs too."""
    if fused == "interpret":  # the Pallas kernel, in interpret mode
        monkeypatch.setenv("REPRO_FUSED", "always")
    target = _stream_target()
    cfg = SubsampledMHConfig(batch_size=_STREAM_M, epsilon=0.01, sampler="stream")
    sched = ScheduleConfig() if adaptive else None
    K, T = 3, 24
    keys = jax.random.split(jax.random.key(5), K)
    # shard=("chains", "data") on one device: the batched lock-step scan
    kw = dict(config=cfg, stepping=stepping, schedule=sched, shard=("chains", "data"),
              fused_kernels="never" if fused == "never" else "always")
    ens = ChainEnsemble(target, RandomWalk(0.1), K, **kw)
    twin = ChainEnsemble(_index_twin(target), RandomWalk(0.1), K, **kw)
    _, s, infos = ens.run(keys, ens.init(jnp.zeros(2)), T)
    _, s_twin, i_twin = twin.run(keys, twin.init(jnp.zeros(2)), T)
    tags = ens.section_tags(jax.tree.map(np.asarray, infos))
    assert tags["sections"] == "window"
    assert 0 < tags["gather_rounds"] < tags["rounds"], tags
    _assert_runs_equal((s, infos), (s_twin, i_twin))
    if fused == "interpret":
        return
    if adaptive:
        seq = ChainEnsemble(target, RandomWalk(0.1), K, config=cfg, schedule=sched,
                            fused_kernels="never", shard=False)
        _, s_seq, i_seq = seq.run(keys, seq.init(jnp.zeros(2)), T)
        _assert_runs_equal((s, infos), (s_seq, i_seq), same_program=False)
        return
    for k in range(K):
        _, s_seq, i_seq = run_chain(keys[k], jnp.zeros(2), target, RandomWalk(0.1), T,
                                    config=cfg)
        _assert_runs_equal((s[k], jax.tree.map(lambda l: l[k], infos)), (s_seq, i_seq),
                           same_program=False)


@pytest.mark.parametrize("adaptive", [False, True], ids=["fixed", "adaptive"])
@pytest.mark.parametrize("stepping", ["lockstep", "masked"])
def test_section_tags_count_the_gather_rounds_the_program_takes(stepping, adaptive):
    """``section_tags`` derives a block's gather-branch rounds from its
    infos; the count equals the rounds in which the program handed the
    family an index block, counted on the device."""
    from repro.core.target import Window

    target = _stream_target()
    taken = []
    fused = target.log_local_ensemble

    def counting(a, b, sec):
        if not isinstance(sec, Window):
            jax.debug.callback(lambda: taken.append(1))
        return fused(a, b, sec)

    cfg = SubsampledMHConfig(batch_size=_STREAM_M, epsilon=0.01, sampler="stream")
    ens = ChainEnsemble(dataclasses.replace(target, log_local_ensemble=counting),
                        RandomWalk(0.1), 4, config=cfg, stepping=stepping,
                        schedule=ScheduleConfig() if adaptive else None,
                        fused_kernels="always")
    _, _, infos = ens.run(jax.random.key(3), ens.init(jnp.zeros(2)), 30)
    jax.effects_barrier()
    tags = ens.section_tags(jax.tree.map(np.asarray, infos))
    assert tags["sections"] == "window"
    assert tags["gather_rounds"] == len(taken) > 0
    rounds = np.asarray(infos.rounds)
    assert tags["rounds"] == (rounds.sum(axis=1).max() if stepping == "masked"
                              else rounds.max(axis=0).sum())


def test_section_tags_name_the_gather_where_no_window_is_read(gaussian_target_factory):
    target, _, _ = gaussian_target_factory(n=600, seed=1)
    fy = ChainEnsemble(target, RandomWalk(0.05), 2,
                       config=SubsampledMHConfig(batch_size=50, epsilon=0.05))
    _, _, infos = fy.run(jax.random.key(0), fy.init(jnp.zeros(())), 4)
    assert fy.section_tags(infos) == {"sections": "gather"}
