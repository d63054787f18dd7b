"""Core algorithm tests: sequential test, samplers, exact + subsampled MH."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    RandomWalk,
    SubsampledMHConfig,
    Welford,
    from_iid_loglik,
    fy_draw,
    fy_from_buffer,
    fy_init,
    fy_reset,
    mh_step,
    run_chain,
    sequential_test,
    student_t_sf,
    trial_run_report,
)


# ---------------------------------------------------------------------------
# Student-t survival function vs scipy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,df", [(0.0, 3), (0.5, 1), (1.3, 5), (2.1, 99), (4.5, 12), (10.0, 2)])
def test_student_t_sf_matches_scipy(t, df):
    from scipy import stats as ss

    np.testing.assert_allclose(float(student_t_sf(t, df)), ss.t.sf(t, df), atol=2e-5)


# ---------------------------------------------------------------------------
# Welford streaming statistics == batch statistics (property test)
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.floats(-100, 100), min_size=4, max_size=60),
    st.integers(min_value=1, max_value=7),
)
def test_welford_streaming_equals_batch(values, chunk):
    arr = np.asarray(values, np.float32)
    w = Welford.empty()
    for i in range(0, len(arr), chunk):
        w = w.merge_batch(jnp.asarray(arr[i : i + chunk]))
    np.testing.assert_allclose(float(w.mean), arr.mean(), rtol=1e-4, atol=1e-4)
    if len(arr) > 1 and arr.std() > 1e-6:
        np.testing.assert_allclose(
            float(w.std), arr.std(ddof=1), rtol=2e-3, atol=1e-3
        )


def test_welford_mask():
    w = Welford.empty()
    vals = jnp.asarray([1.0, 2.0, 3.0, 99.0])
    w = w.merge_batch(vals, mask=jnp.asarray([True, True, True, False]))
    assert float(w.count) == 3
    np.testing.assert_allclose(float(w.mean), 2.0, atol=1e-6)


# ---------------------------------------------------------------------------
# Fisher–Yates without-replacement sampler
# ---------------------------------------------------------------------------


# m and n come from small fixed menus so the jitted draw compiles a handful
# of times instead of once per random example (fy_draw's batch size is a
# static argument; free-ranging integers forced a retrace every example).
_FY_JIT = jax.jit(fy_draw, static_argnums=2)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from([10, 33, 128, 200]), st.sampled_from([1, 7, 40]),
       st.integers(0, 2**31 - 1))
def test_fy_draws_are_distinct_and_in_range(n, m, seed):
    state = fy_reset(fy_init(200))._replace(size=jnp.asarray(n, jnp.int32))
    key = jax.random.key(seed)
    drawn = []
    while True:
        key, sub = jax.random.split(key)
        state, idx, valid = _FY_JIT(sub, state, m)
        drawn.extend(np.asarray(idx)[np.asarray(valid)].tolist())
        if not bool(np.asarray(valid).all()) or len(drawn) >= n:
            break
    assert len(drawn) == len(set(drawn)), "without-replacement violated"
    assert all(0 <= d < n for d in drawn)
    if len(drawn) == n:
        assert set(drawn) == set(range(n)), "exhaustive draw must be a permutation"


def test_fy_is_uniform():
    # empirical check: first drawn element uniform over [0, n)
    n, trials = 8, 1500
    counts = np.zeros(n)
    state0 = fy_init(n)
    draw = jax.jit(lambda k, s: fy_draw(k, s, 2))
    for t in range(trials):
        _, idx, _ = draw(jax.random.key(t), fy_reset(state0))
        counts[int(idx[0])] += 1
    freq = counts / trials
    assert np.all(np.abs(freq - 1 / n) < 4 * np.sqrt((1 / n) * (1 - 1 / n) / trials) + 0.01)


def test_fy_dynamic_pool_size():
    # logical pool smaller than the buffer: draws stay within the prefix
    buf = jnp.arange(100, dtype=jnp.int32)
    state = fy_from_buffer(buf, 7)
    key = jax.random.key(0)
    state, idx, valid = fy_draw(key, fy_reset(state), 10)
    got = np.asarray(idx)[np.asarray(valid)]
    assert len(got) == 7 and set(got.tolist()) == set(range(7))


# ---------------------------------------------------------------------------
# Sequential test: agrees with the exact decision when epsilon is tiny,
# evaluates fewer sections when the decision is easy
# ---------------------------------------------------------------------------


import functools


@functools.lru_cache(maxsize=None)
def _jitted_seq_test(n, m, eps):
    """One compile per (n, m, eps); l_values/mu0 stay traced so the property
    test's examples all hit the same executable."""

    def f(key, l_values, mu0):
        return sequential_test(
            key=key,
            mu0=mu0,
            draw_fn=fy_draw,
            eval_fn=lambda idx: l_values[idx],
            sampler_state=fy_reset(fy_init(n)),
            num_sections=n,
            batch_size=m,
            epsilon=eps,
        )

    return jax.jit(f)


def _run_test(l_values, mu0, m=20, eps=0.05, seed=0):
    l_values = jnp.asarray(l_values, jnp.float32)
    n = l_values.shape[0]
    return _jitted_seq_test(n, m, eps)(
        jax.random.key(seed), l_values, jnp.asarray(mu0, jnp.float32)
    )


def test_sequential_test_easy_decision_is_sublinear():
    rng = np.random.default_rng(0)
    l = rng.normal(5.0, 1.0, size=5000)  # mean >> mu0=0: trivially accept
    res = _run_test(l, mu0=0.0, m=50, eps=0.05)
    assert bool(res.decision)
    assert int(res.n_evaluated) <= 200, "easy decision should stop early"


def test_sequential_test_exhaustion_gives_exact_decision():
    rng = np.random.default_rng(1)
    l = rng.normal(0.0, 1.0, size=300)
    mu0 = float(l.mean()) - 1e-4  # decision within noise: must exhaust
    res = _run_test(l, mu0=mu0, m=50, eps=1e-6)
    assert int(res.n_evaluated) == 300
    assert bool(res.decision) == bool(l.mean() > mu0)


def test_sequential_test_zero_variance_guard():
    l = np.full(200, 2.0)  # s_l = 0 everywhere: must exhaust, then exact
    res = _run_test(l, mu0=1.0, m=20, eps=0.05)
    assert bool(res.decision)
    assert int(res.n_evaluated) == 200


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_sequential_test_error_rate_bounded(seed):
    """Property: with well-separated decisions the test matches the exact
    rule (the paper's claim that errors concentrate on hard decisions)."""
    rng = np.random.default_rng(seed)
    mu_true = rng.choice([-1.0, 1.0]) * rng.uniform(0.5, 2.0)
    l = rng.normal(mu_true, 1.0, size=2000)
    res = _run_test(l, mu0=0.0, m=100, eps=0.01, seed=seed)
    assert bool(res.decision) == (l.mean() > 0.0)


# ---------------------------------------------------------------------------
# MH correctness on a conjugate Gaussian (exact posterior known). Targets come
# from the session-cached gaussian_target_factory fixture (tests/conftest.py).
# ---------------------------------------------------------------------------


def test_exact_mh_recovers_conjugate_posterior(gaussian_target_factory):
    target, pm, ps = gaussian_target_factory(n=800)
    _, samples, infos = run_chain(
        jax.random.key(0), jnp.zeros(()), target, RandomWalk(0.07), 2000, kernel="exact"
    )
    w = np.asarray(samples)[500:]
    assert abs(w.mean() - pm) < 4 * ps
    np.testing.assert_allclose(w.std(), ps, rtol=0.35)


def test_subsampled_mh_recovers_conjugate_posterior_and_subsamples(gaussian_target_factory):
    target, pm, ps = gaussian_target_factory(n=800)
    cfg = SubsampledMHConfig(batch_size=200, epsilon=0.05)
    _, samples, infos = run_chain(
        jax.random.key(0), jnp.zeros(()), target, RandomWalk(0.07), 1500,
        kernel="subsampled", config=cfg,
    )
    w = np.asarray(samples)[400:]
    assert abs(w.mean() - pm) < 5 * ps
    np.testing.assert_allclose(w.std(), ps, rtol=0.5)
    assert np.mean(np.asarray(infos.n_evaluated)) < target.num_sections


def test_exact_mh_chunked_equals_unchunked(gaussian_target_factory):
    target, _, _ = gaussian_target_factory(n=500)
    th1, s1, i1 = run_chain(jax.random.key(3), jnp.zeros(()), target, RandomWalk(0.1), 50, kernel="exact")
    th2, s2, i2 = run_chain(
        jax.random.key(3), jnp.zeros(()), target, RandomWalk(0.1), 50, kernel="exact", chunk_size=64
    )
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Sec 3.3 safeguard
# ---------------------------------------------------------------------------


def test_trial_run_report_flags_clean_problem_as_safe(gaussian_target_factory):
    target, _, _ = gaussian_target_factory(n=800)
    rep = trial_run_report(
        jax.random.key(0), jnp.zeros(()), target, RandomWalk(0.05),
        batch_size=50, epsilon=0.05, num_trials=6,
    )
    assert rep.num_trials == 6
    assert 0.0 <= rep.mean_fraction_evaluated <= 1.0
    assert rep.decision_error_rate <= 0.3
