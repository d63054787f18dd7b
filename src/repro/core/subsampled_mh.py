"""Alg. 3: the sublinear-time subsampled MH transition.

Interleaves scaffold-section materialization with the sequential test: local
sections are only evaluated when the test asks for another mini-batch, so the
per-transition cost is O(m * rounds) with rounds determined adaptively by the
test — sublinear in N whenever the decision is statistically easy.

The kernel is fully jittable (while_loop + cond) and SPMD-friendly: with
sections sharded over the data mesh axes, each round's evaluation is data
parallel and the test statistics reduce with a scalar psum (see bayes/).

The per-transition knobs (``epsilon``, effective batch size) may be traced
per-chain values supplied by the adaptive scheduler
(:mod:`repro.core.schedule`) instead of the static config scalars — the
ensemble threads its controller state through the keyword overrides of
:func:`subsampled_mh_step`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .samplers import make_bounded_draw, make_sampler
from .sequential_test import sequential_test
from .target import PartitionedTarget

Params = Any


class SubsampledMHInfo(NamedTuple):
    accepted: jax.Array  # bool
    n_evaluated: jax.Array  # int32: sections actually evaluated
    rounds: jax.Array  # int32: mini-batches drawn
    mu_hat: jax.Array  # f32
    mu0: jax.Array  # f32
    pvalue: jax.Array  # f32
    log_u: jax.Array  # f32
    epsilon: jax.Array  # f32: tolerance this transition ran with
    batch_eff: jax.Array  # int32: effective mini-batch size this transition

    # The last two fields are the adaptation trace: constant copies of the
    # config under static scheduling, the controller's per-transition knob
    # settings under repro.core.schedule.


@dataclasses.dataclass(frozen=True)
class SubsampledMHConfig:
    """Static kernel configuration for one subsampled-MH chain.

    ``batch_size`` (m) sections are drawn per sequential-test round;
    ``epsilon`` is the test's p-value tolerance (smaller = closer to exact
    MH, more sections evaluated); ``max_rounds`` caps the test (default:
    enough rounds to exhaust the pool, at which point the decision is
    exact); ``sampler`` picks the without-replacement scheme.

    Example::

        >>> cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05)
        >>> cfg.batch_size, cfg.sampler
        (50, 'fy')
    """

    batch_size: int = 100  # m: mini-batch of local sections per round
    epsilon: float = 0.01  # tolerance of the sequential test
    max_rounds: int | None = None  # default ceil(N/m): exhaust the pool
    sampler: str = "fy"  # "fy" (Fisher–Yates) | "stream" (pre-permuted pool)


def _tree_select(pred: jax.Array, on_true: Params, on_false: Params) -> Params:
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), on_true, on_false)


def propose_and_mu0(
    key: jax.Array, theta: Params, target: PartitionedTarget, proposal,
    prop_scale=None,
) -> tuple[Params, jax.Array, jax.Array, jax.Array]:
    """Steps 2–6 of Alg. 3: draw u, propose, evaluate the global section.

    Returns ``(theta_prime, mu0, log_u, key_test)`` where ``key_test`` seeds
    the sequential test. Factored out so the masked-continuation ensemble
    stepping reproduces the scanned single-chain kernel bit for bit.

    ``prop_scale`` (a traced scalar, or None) is forwarded to the proposal's
    ``scale`` argument — the adaptive-proposal hook; ``None`` keeps the
    two-argument call and is bit-for-bit the pre-scale behavior.
    """
    with jax.named_scope("propose"):
        k_u, k_prop, k_test = jax.random.split(key, 3)
        log_u = jnp.log(jax.random.uniform(k_u, (), jnp.float32, 1e-20, 1.0))
        if prop_scale is None:
            theta_p, corr = proposal(k_prop, theta)
        else:
            theta_p, corr = proposal(k_prop, theta, prop_scale)
        g = target.log_global(theta, theta_p) + corr  # Detach&Regen(global)
        mu0 = (log_u - g) / target.num_sections
    return theta_p, mu0, log_u, k_test


def subsampled_mh_step(
    key: jax.Array,
    theta: Params,
    sampler_state,
    target: PartitionedTarget,
    proposal,
    config: SubsampledMHConfig,
    reset_fn,
    draw_fn,
    *,
    epsilon=None,
    batch_eff=None,
    draw_bounded_fn=None,
    max_rounds: int | None = None,
    batch_max: int | None = None,
    prop_scale=None,
) -> tuple[Params, Any, SubsampledMHInfo]:
    """One approximate MH transition (Alg. 3). Returns (theta', sampler', info).

    Steps map to the paper: 2 sample u; 3–4 construct+evaluate the global
    section; 6 compute mu0; 7–14 sequential test with lazily-materialized
    local sections; 15–19 accept or restore.

    The keyword overrides accept *traced* per-chain values from the adaptive
    scheduler: ``epsilon`` replaces ``config.epsilon``, ``batch_eff`` (with
    its ``draw_bounded_fn``, see :func:`repro.core.samplers.make_bounded_draw`)
    caps each round at an effective batch while shapes stay static at
    ``batch_max`` (the scheduler's largest bucket; defaults to
    ``config.batch_size``), and ``max_rounds`` must then cover exhaustion at
    the smallest batch bucket.

    Example — one transition on a 200-section conjugate target::

        >>> import jax, jax.numpy as jnp
        >>> from repro.core import (RandomWalk, SubsampledMHConfig,
        ...                         from_iid_loglik, make_kernel)
        >>> x = 0.5 + jax.random.normal(jax.random.key(0), (200,))
        >>> target = from_iid_loglik(lambda th: -0.5 * th**2,
        ...                          lambda th, idx: -0.5 * (x[idx] - th) ** 2,
        ...                          None, 200)
        >>> state0, step = make_kernel(target, RandomWalk(0.1),
        ...                            SubsampledMHConfig(batch_size=50, epsilon=0.05))
        >>> theta, state, info = step(jax.random.key(1), jnp.zeros(()), state0)
        >>> theta.shape, int(info.n_evaluated) <= 200
        ((), True)
    """
    theta_p, mu0, log_u, k_test = propose_and_mu0(key, theta, target, proposal, prop_scale)
    eps = config.epsilon if epsilon is None else epsilon

    res = sequential_test(
        key=k_test,
        mu0=mu0,
        draw_fn=draw_fn,
        eval_fn=lambda idx: target.log_local(theta, theta_p, idx),
        sampler_state=reset_fn(sampler_state),
        num_sections=target.num_sections,
        batch_size=config.batch_size if batch_max is None else batch_max,
        epsilon=eps,
        max_rounds=config.max_rounds if max_rounds is None else max_rounds,
        batch_eff=batch_eff,
        draw_bounded_fn=draw_bounded_fn,
    )
    accept = res.decision
    theta_new = _tree_select(accept, theta_p, theta)
    info = SubsampledMHInfo(
        accepted=accept,
        n_evaluated=res.n_evaluated,
        rounds=res.rounds,
        mu_hat=res.mu_hat,
        mu0=mu0,
        pvalue=res.pvalue,
        log_u=log_u,
        epsilon=jnp.asarray(eps, jnp.float32),
        batch_eff=jnp.asarray(
            config.batch_size if batch_eff is None else batch_eff, jnp.int32
        ),
    )
    return theta_new, res.sampler_state, info


def adaptive_max_rounds(config: SubsampledMHConfig, num_sections: int, buckets) -> int:
    """Static round cap covering pool exhaustion at the smallest bucket."""
    if config.max_rounds is not None:
        return config.max_rounds
    m_min = max(1, min(int(b) for b in buckets))
    return int(math.ceil(num_sections / m_min))


def make_kernel(
    target: PartitionedTarget,
    proposal,
    config: SubsampledMHConfig | None = None,
    *,
    scheduled: bool = False,
    batch_max: int | None = None,
):
    """Bundle a jit-ready (init_state, step) pair.

    step(key, theta, sampler_state) -> (theta', sampler_state', info)

    With ``scheduled=True`` the step instead has signature
    ``step(key, theta, sampler_state, epsilon, batch_eff, max_rounds=None,
    prop_scale=None)`` and accepts the adaptive controller's traced per-chain knobs
    (:func:`repro.core.schedule.controller_params`); ``batch_max`` sets the
    static per-round draw shape (the scheduler's largest bucket — without it
    buckets above ``config.batch_size`` could never actually be drawn).
    """
    config = config or SubsampledMHConfig()
    state0, reset_fn, draw_fn = make_sampler(config.sampler, target.num_sections)

    if scheduled:
        draw_bounded = make_bounded_draw(config.sampler)

        def step(key, theta, sampler_state, epsilon, batch_eff, max_rounds=None,
                 prop_scale=None):
            return subsampled_mh_step(
                key, theta, sampler_state, target, proposal, config, reset_fn, draw_fn,
                epsilon=epsilon, batch_eff=batch_eff, draw_bounded_fn=draw_bounded,
                max_rounds=max_rounds, batch_max=batch_max, prop_scale=prop_scale,
            )

        return state0, step

    def step(key, theta, sampler_state):
        return subsampled_mh_step(
            key, theta, sampler_state, target, proposal, config, reset_fn, draw_fn
        )

    return state0, step
