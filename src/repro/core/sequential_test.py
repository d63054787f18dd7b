"""Alg. 2: the sequential Student-t test for the MH accept decision.

Reformulation (Eq. 6): given u ~ U[0,1], accept iff mu > mu0 where

    mu0 = (log u - sum_{n in global} log w_n) / N
    mu  = (1/N) sum_i l_i,   l_i = sum_{n in local_i} log w_n.

The test consumes mini-batches of l_i drawn WITHOUT replacement, keeps a
Welford accumulator, applies the finite-population correction, and stops when
the two-sided t p-value of (mu_hat - mu0)/s drops below epsilon — or when the
pool is exhausted (n = N), at which point the decision is exact.

Guard (paper Sec. 2, Alg. 2 step 8): when s_l = 0 the t-test is skipped and
another batch is drawn, preventing false early decisions when a small subset
happens to contain all-equal values.

This module is deliberately independent of MH: it tests H1: mu > mu0 against
H2: mu < mu0 for ANY batched supplier of l_i values, so it can be unit-tested
and reused (e.g. model-based alternatives, Sec. 5 of the paper).
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .stats import Welford, finite_population_std_err, two_sided_t_pvalue


def test_round_decision(welford: Welford, mu0, n_total, epsilon):
    """One round's stopping logic on the running accumulator (Alg. 2 steps
    7–14). Returns ``(decision, pvalue, test_ok, exhausted)``; shared by
    :func:`sequential_test` and the masked-continuation superstep of
    :class:`repro.core.ensemble.ChainEnsemble` so the two stepping modes are
    float-for-float identical. ``epsilon`` may be a traced per-chain scalar
    (the adaptive scheduler's knob)."""
    n = welford.count
    exhausted = n >= n_total
    s = finite_population_std_err(welford, n_total)
    df = jnp.maximum(n - 1.0, 1.0)
    tstat = jnp.where(s > 0, jnp.abs(welford.mean - mu0) / jnp.maximum(s, 1e-30), jnp.inf)
    pval = jnp.where(s > 0, two_sided_t_pvalue(tstat, df), jnp.zeros((), jnp.float32))
    # s_l == 0 guard: no test unless the sample std is positive — except
    # when the pool is exhausted, where the comparison is exact anyway.
    test_ok = (welford.std > 0) & (pval < epsilon)
    decision = welford.mean > mu0
    return decision, pval, test_ok, exhausted


class SeqTestResult(NamedTuple):
    decision: jax.Array  # bool: True = H1 (mu > mu0) = accept
    n_evaluated: jax.Array  # int32: local sections actually evaluated
    rounds: jax.Array  # int32: mini-batches drawn
    mu_hat: jax.Array  # f32
    pvalue: jax.Array  # f32 (final)
    sampler_state: tuple  # threaded sampler state
    aux: tuple = ()  # threaded eval auxiliary state (e.g. loglik caches)


def sequential_test(
    key: jax.Array,
    mu0: jax.Array,
    draw_fn: Callable,
    eval_fn: Callable[[jax.Array], jax.Array],
    sampler_state,
    num_sections: int,
    batch_size: int,
    epsilon: float,
    max_rounds: int | None = None,
    aux=None,
    batch_eff=None,
    draw_bounded_fn: Callable | None = None,
) -> SeqTestResult:
    """Run the sequential test inside a single jittable while_loop.

    draw_fn(key, sampler_state, m) -> (sampler_state, idx[m], valid[m])
    eval_fn(idx[m]) -> l[m]   (per-section log-weight sums)

    ``epsilon`` may be a traced scalar (per-chain adaptive tolerance). With
    ``batch_eff`` (a traced effective batch size <= ``batch_size``) and a
    matching ``draw_bounded_fn(key, state, m_max, m_eff)``, each round's
    shapes stay at the static ``batch_size`` but only ``batch_eff`` sections
    are drawn, evaluated into the statistics, and consumed from the pool —
    the adaptive scheduler's bucket mechanism (see
    :mod:`repro.core.schedule`). Pass an explicit ``max_rounds`` that covers
    pool exhaustion at the smallest bucket in that case.

    When ``aux`` is given, eval_fn is stateful: eval_fn(idx, aux) -> (l, aux).
    This lets evaluators carry caches across rounds (the Sec-3.5 lazy
    stale-value mechanism at tensor scale).

    Doctest — an easy decision (all l_i far above mu0) stops after one round::

        >>> import jax, jax.numpy as jnp
        >>> from repro.core import make_sampler, sequential_test
        >>> state0, reset, draw = make_sampler("stream", 1000)
        >>> res = sequential_test(
        ...     key=jax.random.key(0), mu0=jnp.float32(-1.0), draw_fn=draw,
        ...     eval_fn=lambda idx: idx.astype(jnp.float32),
        ...     sampler_state=reset(state0), num_sections=1000,
        ...     batch_size=50, epsilon=0.05)
        >>> bool(res.decision), int(res.rounds), int(res.n_evaluated)
        (True, 1, 50)
    """
    n_total = num_sections
    if batch_eff is not None and draw_bounded_fn is None:
        raise ValueError("batch_eff requires a matching draw_bounded_fn")
    if max_rounds is None:
        try:
            max_rounds = int(math.ceil(int(n_total) / batch_size))
        except TypeError as e:  # traced pool size (e.g. random cluster count)
            raise ValueError(
                "num_sections is traced; pass an explicit static max_rounds"
            ) from e

    class _St(NamedTuple):
        key: jax.Array
        sampler: tuple
        welford: Welford
        rounds: jax.Array
        done: jax.Array
        decision: jax.Array
        pvalue: jax.Array
        aux: tuple

    st0 = _St(
        key=key,
        sampler=sampler_state,
        welford=Welford.empty(),
        rounds=jnp.zeros((), jnp.int32),
        done=jnp.zeros((), bool),
        decision=jnp.zeros((), bool),
        pvalue=jnp.ones((), jnp.float32),
        aux=() if aux is None else aux,
    )
    stateful = aux is not None

    def cond(st: _St):
        return ~st.done

    def body(st: _St):
        key, sub = jax.random.split(st.key)
        with jax.named_scope("draw"):
            if batch_eff is None:
                sampler, idx, valid = draw_fn(sub, st.sampler, batch_size)
            else:
                sampler, idx, valid = draw_bounded_fn(
                    sub, st.sampler, batch_size, batch_eff
                )
        with jax.named_scope("delta"):
            if stateful:
                l, new_aux = eval_fn(idx, st.aux)
            else:
                l, new_aux = eval_fn(idx), st.aux
        with jax.named_scope("seq_test"):
            w = st.welford.merge_batch(l, valid)
            rounds = st.rounds + 1
            decision, pval, test_ok, exhausted = test_round_decision(
                w, mu0, n_total, epsilon
            )
        done = test_ok | exhausted | (rounds >= max_rounds)
        return _St(key, sampler, w, rounds, done, decision, pval, new_aux)

    st = jax.lax.while_loop(cond, body, st0)
    return SeqTestResult(
        decision=st.decision,
        n_evaluated=st.welford.count.astype(jnp.int32),
        rounds=st.rounds,
        mu_hat=st.welford.mean,
        pvalue=st.pvalue,
        sampler_state=st.sampler,
        aux=st.aux,
    )


def expected_batches_theoretical(l_values, mu0: float, batch_size: int, epsilon: float) -> float:
    """Host-side expectation of evaluated sections for a FIXED (theta, theta')
    pair, following Korattikara et al. (2014) Eq. 19: walk the test forward on
    the population moments (mean/std of {l_i}) instead of Monte Carlo draws.

    Used by benchmarks/fig5 to draw the theoretical sublinearity curve.
    """
    import numpy as np
    from scipy import stats as sstats

    l = np.asarray(l_values, np.float64)
    n_total = len(l)
    mu = l.mean()
    sl = l.std(ddof=1)
    if sl == 0:
        return float(n_total)
    p_not_stopped = 1.0
    expected = 0.0
    n = 0
    while n < n_total and p_not_stopped > 1e-12:
        m = min(batch_size, n_total - n)
        n += m
        expected += m * p_not_stopped
        corr = max(1.0 - (n - 1) / max(n_total - 1, 1), 0.0)
        s = sl / math.sqrt(n) * math.sqrt(corr)
        if s == 0:
            break
        t = abs(mu - mu0) / s
        pval = 2.0 * sstats.t.sf(t, df=max(n - 1, 1))
        p_stop = 1.0 if pval < epsilon else 0.0
        # Eq.19-style deterministic walk on population moments: the test stat
        # concentrates fast, so the stop event is ~deterministic per n.
        p_not_stopped *= 1.0 - p_stop
    return float(expected)
