"""Bayesian logistic regression (paper Sec. 4.1).

    w ~ N(0, 0.1 I_D),   y_i ~ Logit(y | x_i, w),  y ∈ {−1, +1}

Scaffold: D = {w, z_i}, A = {y_i}; the border node is w itself and the N
local sections are the (z_i → y_i) chains — Table 1 row 1, scaling N.

Provides the MNIST-like feature set used for the Fig-4 risk experiment
(12214 train / 2037 test, 50-dim PCA features — synthesized here with the
same shape/scale since the container is offline) and the 2-feature synthetic
of Fig. 5 used for the sublinearity study.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.target import PartitionedTarget
from ..core.target_builder import build_target
from ..kernels.ref import logit_loglik

PRIOR_VAR = 0.1


class LRData(NamedTuple):
    x_train: jax.Array  # (N, D)
    y_train: jax.Array  # (N,) in {-1, +1}
    x_test: jax.Array
    y_test: jax.Array
    w_true: jax.Array


def synth_mnist_like(
    key: jax.Array, n_train: int = 12214, n_test: int = 2037, d: int = 50
) -> LRData:
    """Two-class feature clouds with PCA-like decaying variance per dim,
    matching the scale of the paper's 7-vs-9 MNIST PCA features."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scales = 1.0 / jnp.sqrt(1.0 + jnp.arange(d, dtype=jnp.float32))
    w_true = jax.random.normal(k1, (d,)) * scales * 2.0
    x_train = jax.random.normal(k2, (n_train, d)) * scales
    x_test = jax.random.normal(k3, (n_test, d)) * scales
    p_train = jax.nn.sigmoid(x_train @ w_true)
    p_test = jax.nn.sigmoid(x_test @ w_true)
    u = jax.random.uniform(k4, (n_train + n_test,))
    y_train = jnp.where(u[:n_train] < p_train, 1.0, -1.0)
    y_test = jnp.where(u[n_train:] < p_test, 1.0, -1.0)
    return LRData(x_train, y_train, x_test, y_test, w_true)


def synth_2d(key: jax.Array, n: int) -> LRData:
    """Fig. 5a style data: two 2-d blobs separated along a diagonal."""
    k1, k2 = jax.random.split(key)
    w_true = jnp.asarray([2.0, -2.0])
    x = jax.random.normal(k1, (n, 2))
    p = jax.nn.sigmoid(x @ w_true)
    y = jnp.where(jax.random.uniform(k2, (n,)) < p, 1.0, -1.0)
    return LRData(x, y, x[: max(n // 10, 1)], y[: max(n // 10, 1)], w_true)


# The shared reference logistic factor lives in repro.kernels.ref; re-exported
# here because the experiments historically imported it from this module.
loglik = logit_loglik


def make_target(x: jax.Array, y: jax.Array, prior_var: float = PRIOR_VAR) -> PartitionedTarget:
    """BayesLR partitioned target via the ``logit`` kernel family: the
    builder attaches ``log_local`` and the fused (K, m) ``log_local_ensemble``
    (one pallas_call per multi-chain sequential-test round on TPU, pure-jnp
    ref elsewhere) — no hand-wired kernel hookup."""
    return build_target(
        "logit",
        (x, y),
        x.shape[0],
        prior_logpdf=lambda w: (-0.5 / prior_var) * jnp.sum(w**2),
    )


def make_grad_fn(x: jax.Array, y: jax.Array, prior_var: float = PRIOR_VAR, subsample: int | None = None):
    """Gradient of the log posterior (optionally on a fixed subsample with
    N/|S| rescaling) — powers the MALA proposal."""
    n = x.shape[0]

    def full_logpost(w):
        return (-0.5 / prior_var) * jnp.sum(w**2) + loglik(w, x, y).sum()

    if subsample is None:
        return jax.grad(full_logpost)

    sub = min(subsample, n)

    def sub_grad(w):
        xi, yi = x[:sub], y[:sub]

        def f(wv):
            return (-0.5 / prior_var) * jnp.sum(wv**2) + (n / sub) * loglik(wv, xi, yi).sum()

        return jax.grad(f)(w)

    return sub_grad


def run_posterior_ensemble(
    key: jax.Array,
    data: LRData,
    num_chains: int = 8,
    num_steps: int = 1000,
    kernel: str = "subsampled",
    batch_size: int = 100,
    epsilon: float = 0.05,
    sampler: str = "stream",
    sigma: float = 0.05,
    overdisperse: float = 0.5,
    stepping: str = "lockstep",
    schedule=None,
):
    """K-chain posterior sampling with cross-chain diagnostics.

    Runs a :class:`repro.core.ensemble.ChainEnsemble` from overdispersed
    starting points and returns (samples (K, T, D), diagnostics dict with
    per-dimension split-R-hat, total ESS of w[0], and the per-chain
    acceptance / evaluated-section summaries). ``stepping="masked"`` plus a
    :class:`repro.core.schedule.ScheduleConfig` turns on the adaptive
    masked-continuation engine.
    """
    from ..core import (
        ChainEnsemble,
        RandomWalk,
        SubsampledMHConfig,
        ensemble_summary,
        multichain_ess,
        split_rhat,
    )

    target = make_target(data.x_train, data.y_train)
    d = data.x_train.shape[1]
    cfg = SubsampledMHConfig(batch_size=batch_size, epsilon=epsilon, sampler=sampler)
    ens = ChainEnsemble(target, RandomWalk(sigma), num_chains, kernel=kernel, config=cfg,
                        stepping=stepping, schedule=schedule)
    k_init, k_run = jax.random.split(key)
    theta0 = overdisperse * jax.random.normal(k_init, (num_chains, d))
    state = ens.init(theta0, batched=True)
    state, samples, infos = ens.run(k_run, state, num_steps)
    w = np.asarray(samples)[:, num_steps // 2:]  # (K, T/2, D) post burn-in
    diagnostics = {
        "rhat": split_rhat(w),
        "ess_w0": multichain_ess(w[..., 0]),
        **ensemble_summary(infos),
    }
    return np.asarray(samples), diagnostics


def make_serving_workload(
    *,
    smoke: bool = False,
    num_chains: int = 8,
    n_train: int | None = None,
    d: int | None = None,
    batch_size: int | None = None,
    epsilon: float = 0.05,
    sigma: float = 0.05,
    stepping: str = "lockstep",
    schedule=None,
    seed: int = 0,
):
    """The BayesLR posterior as a servable workload (see
    :mod:`repro.serving.workloads`): the ``logit``-family target behind a
    :class:`~repro.core.ensemble.ChainEnsemble`, with two request classes —

      * ``predictive``: posterior-predictive P(y=+1 | x) for test rows,
      * ``vote``: the posterior fraction of draws classifying x as +1
        (a calibration-style uncertainty signal on the same inputs).

    Query inputs are rows of the held-out test set.
    """
    from ..core import ChainEnsemble, RandomWalk, SubsampledMHConfig
    from ..serving.resident import QuerySpec
    from ..serving.workloads import ServingWorkload, row_sampler

    n_train = n_train if n_train is not None else (2_000 if smoke else 12_000)
    d = d if d is not None else (4 if smoke else 20)
    batch_size = batch_size if batch_size is not None else (100 if smoke else 500)
    data = synth_mnist_like(
        jax.random.key(seed), n_train=n_train, n_test=max(512, d * 16), d=d
    )
    target = make_target(data.x_train, data.y_train)
    cfg = SubsampledMHConfig(batch_size=batch_size, epsilon=epsilon, sampler="stream")
    ens = ChainEnsemble(target, RandomWalk(sigma), num_chains, config=cfg,
                        stepping=stepping, schedule=schedule)
    make_queries = row_sampler(np.asarray(data.x_test))
    # Full-f32 matmuls: a TPU's default precision would round the operands
    # to bfloat16, and the served predictive is checked against an f64
    # reference at rtol 1e-4.
    logits = lambda w, xs: jnp.dot(xs, w, precision=jax.lax.Precision.HIGHEST)
    specs = {
        "predictive": QuerySpec(
            fn=lambda w, xs: jax.nn.sigmoid(logits(w, xs)),
            aggregate="mean",
            make_queries=make_queries,
            name="predictive",
        ),
        "vote": QuerySpec(
            fn=lambda w, xs: (logits(w, xs) > 0).astype(jnp.float32),
            aggregate="mean",
            make_queries=make_queries,
            name="vote",
        ),
    }
    return ServingWorkload(
        name="bayeslr",
        ensemble=ens,
        theta0=jnp.zeros(d),
        query_specs=specs,
        default_class="predictive",
        description=f"Bayesian logistic regression, N={n_train}, D={d}",
    )


def predictive_mean_prob(w_samples: np.ndarray, x_test: np.ndarray) -> np.ndarray:
    """Running posterior-predictive mean P(y=+1|x) per test point: (T, Ntest)."""
    w_samples = np.asarray(w_samples)
    logits = w_samples @ np.asarray(x_test).T  # (T, Ntest)
    probs = 1.0 / (1.0 + np.exp(-logits))
    return np.cumsum(probs, axis=0) / np.arange(1, len(probs) + 1)[:, None]


def risk_vs_reference(pred_running: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """Risk of the predictive mean (Korattikara et al. 2014): mean squared
    error of the running predictive mean vs a long-run reference, per step."""
    return np.mean((pred_running - reference[None, :]) ** 2, axis=1)


def test_error(w: np.ndarray, x_test: np.ndarray, y_test: np.ndarray) -> float:
    pred = np.sign(np.asarray(x_test) @ np.asarray(w))
    return float(np.mean(pred != np.asarray(y_test)))
