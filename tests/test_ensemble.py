"""Multi-chain ensemble engine: vmap-vs-sequential equivalence, cross-chain
diagnostics, batched sampler properties, multi-device fan-out."""
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
    SubsampledMHConfig,
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
