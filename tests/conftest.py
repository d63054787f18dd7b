"""Shared fixtures: seeded PRNGs and session-cached expensive setups.

The `slow` marker is registered in pyproject.toml (and defensively here);
the default run excludes it via `addopts = "-m 'not slow'"` so the tier-1
command stays CPU-minutes cheap. Run `pytest -m slow` (or override with
`-m ''`) for the full-size chains and subprocess multi-device cases.
"""
import jax
import numpy as np
import pytest

from repro import compile_cache

# Persistent XLA compilation cache: the tier-1 suite is dominated by jit
# compiles of the MH-in-while_loop graphs, which are identical run to run.
# Warm runs cut compile time ~5x. $JAX_COMPILATION_CACHE_DIR places it from
# outside; otherwise it is <checkout>/.jax_cache.
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minute-plus cases excluded from the default run"
    )


@pytest.fixture
def rng(request):
    """Per-test numpy Generator seeded from the test id (stable across runs)."""
    import zlib

    return np.random.default_rng(zlib.crc32(request.node.nodeid.encode()))


@pytest.fixture
def key(request):
    """Per-test jax PRNG key seeded from the test id."""
    import zlib

    import jax

    return jax.random.key(zlib.crc32(request.node.nodeid.encode()))


# ---------------------------------------------------------------------------
# Session-scoped caches for expensive jitted setups. Building the reduced LM
# (params + first jitted step) and the conjugate-Gaussian target dominates
# several modules' runtime; sharing them collapses that to one compile each.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="session")
def lm_setup():
    """(reduced_config, params, 8x24 token batch) for the chatglm3-6b LM —
    the `_setup()` tuple test_bayes builds per test, built once per session."""
    import jax

    from repro.configs import ARCHS, reduce_config
    from repro.data import DataConfig, TokenStream
    from repro.models import init_params

    rc = reduce_config(ARCHS["chatglm3-6b"])
    params = init_params(jax.random.key(0), rc)
    batch = TokenStream(
        DataConfig(vocab=rc.vocab, seq_len=24, global_batch=8, seed=0)
    ).batch(0)
    return rc, params, batch


@pytest.fixture(scope="session")
def conjugate_posterior():
    """The subposterior ground-truth harness: a D=2 conjugate Gaussian-mean
    model (prior N(0, I), x_i ~ N(theta, I)) whose exact posterior is
    ``N(n xbar / (n+1), I/(n+1))``, plus a memoized ``run(P)`` that returns
    the P per-partition subsampled-MH windows (each (K, W, D)) sampled
    against the stride-partitioned, prior-tempered slice targets.

    Session-scoped and lazy: each P's chains run once, shared by every
    statistical test. ``run(1)`` is the unpartitioned reference chain.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import (
        ChainEnsemble,
        RandomWalk,
        SubsampledMHConfig,
        build_target,
    )
    from repro.partition import partition_target

    n, d, chains, burn, keep, seed = 768, 2, 4, 250, 350, 3
    theta_true = jnp.asarray([0.6, -0.3])
    x = theta_true + jax.random.normal(jax.random.key(seed), (n, d))
    target = build_target(
        "gaussian_mean", x, n,
        prior_logpdf=lambda th: -0.5 * jnp.sum(th ** 2, axis=-1),
    )
    xbar = np.asarray(jnp.mean(x, axis=0), np.float64)
    cache = {}

    def run(num_partitions):
        if num_partitions not in cache:
            draws = []
            for p, t in enumerate(partition_target(target, num_partitions)):
                cfg = SubsampledMHConfig(
                    batch_size=min(128, t.num_sections), epsilon=0.005,
                    sampler="stream",
                )
                # proposal scaled to the subposterior width sqrt(P/(n+1))
                sigma = 1.7 * float(np.sqrt(num_partitions / (n + 1.0)))
                ens = ChainEnsemble(t, RandomWalk(sigma), chains, config=cfg)
                state = ens.init(jnp.zeros(d))
                key = jax.random.fold_in(jax.random.key(seed + 1), p)
                state, _, _ = ens.run(
                    None, state, burn, step_keys=ens.step_keys(key, 0, burn)
                )
                state, samples, _ = ens.run(
                    None, state, keep, step_keys=ens.step_keys(key, burn, keep)
                )
                draws.append(np.asarray(samples))
            cache[num_partitions] = draws
        return cache[num_partitions]

    return {
        "n": n,
        "d": d,
        "chains": chains,
        "target": target,
        "data": x,
        "post_mean": n * xbar / (n + 1.0),
        "post_var": 1.0 / (n + 1.0),
        "run": run,
    }


@pytest.fixture(scope="session")
def gaussian_target_factory():
    """Memoized conjugate-Gaussian targets keyed by (n, seed): returns
    (PartitionedTarget, posterior_mean, posterior_std)."""
    import jax
    import jax.numpy as jnp

    from repro.core import from_iid_loglik

    cache = {}

    def build(n=1500, seed=1):
        if (n, seed) not in cache:
            x = 0.7 + jnp.asarray(jax.random.normal(jax.random.key(seed), (n,)))
            prior = lambda th: -0.5 * jnp.sum(th**2)
            loglik = lambda th, idx: -0.5 * (x[idx] - th) ** 2
            post_mean = float(x.sum() / (n + 1))
            post_std = float(np.sqrt(1.0 / (n + 1)))
            cache[(n, seed)] = (from_iid_loglik(prior, loglik, None, n), post_mean, post_std)
        return cache[(n, seed)]

    return build
