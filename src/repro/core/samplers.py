"""Without-replacement mini-batch samplers with sublinear per-round cost.

The paper (Alg. 2/3) draws mini-batches of local sections *without
replacement*. Regenerating a full random permutation per transition costs
O(N) and would break the sublinear bound, so the default sampler is a
**partial Fisher–Yates shuffle** over a persistent index array:

  * state: (idx: int32[N], pos: scalar) — idx persists across transitions,
  * a round draws m indices with m in-place random swaps → O(m) work,
  * ``reset`` (per transition) just rewinds ``pos`` to 0; restarting a
    Fisher–Yates walk from position 0 with fresh randomness yields an exactly
    uniform without-replacement sample regardless of the array's current
    permutation state.

This is the faithful CPU-algorithm analog. At LM scale the bayes/ layer
instead slices a pre-permuted stream (distributionally equivalent, zero
gather cost on a sharded pool) — see DESIGN.md §3.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class FisherYatesState(NamedTuple):
    idx: jax.Array  # int32[capacity], a permutation buffer
    pos: jax.Array  # int32 scalar, number of indices consumed this transition
    size: jax.Array  # int32 scalar, logical pool size (≤ capacity, may be traced)

    @property
    def capacity(self) -> int:
        return self.idx.shape[0]


def fy_init(n: int, size=None) -> FisherYatesState:
    """Pool over [0, n). ``size`` (possibly traced) restricts to a logical
    prefix — used when the pool is a padded member buffer (e.g. the points of
    one DP-mixture cluster, whose count N_k is itself random)."""
    if size is None:
        size = n
    return FisherYatesState(
        jnp.arange(n, dtype=jnp.int32),
        jnp.zeros((), jnp.int32),
        jnp.asarray(size, jnp.int32),
    )


def fy_from_buffer(idx_buffer: jax.Array, size) -> FisherYatesState:
    """Pool drawing from an explicit (padded) index buffer of logical ``size``."""
    return FisherYatesState(
        idx_buffer.astype(jnp.int32), jnp.zeros((), jnp.int32), jnp.asarray(size, jnp.int32)
    )


def fy_reset(state: FisherYatesState) -> FisherYatesState:
    """Rewind for a new transition (O(1)); the array itself persists."""
    return FisherYatesState(state.idx, jnp.zeros((), jnp.int32), state.size)


def fy_draw(
    key: jax.Array, state: FisherYatesState, m: int
) -> tuple[FisherYatesState, jax.Array, jax.Array]:
    """Draw ``m`` indices without replacement from the logical pool.

    Returns (new_state, indices int32[m], valid bool[m]). When fewer than m
    indices remain, the tail entries are repeats of valid draws but flagged
    invalid; callers mask them out of the test statistics.
    """
    cap = state.idx.shape[0]
    n = state.size
    keys = jax.random.split(key, m)

    def body(k, carry):
        idx, pos = carry
        p = jnp.minimum(pos + k, cap - 1)
        # swap target uniform in [p, n)
        span = jnp.maximum(n - p, 1)
        j = jnp.minimum(p + jax.random.randint(keys[k], (), 0, span, dtype=jnp.int32), cap - 1)
        vi, vj = idx[p], idx[j]
        idx = idx.at[p].set(vj).at[j].set(vi)
        return idx, pos

    idx, _ = jax.lax.fori_loop(0, m, body, (state.idx, state.pos))
    offs = state.pos + jnp.arange(m, dtype=jnp.int32)
    valid = offs < n
    out = idx[jnp.minimum(offs, cap - 1)]
    new_pos = jnp.minimum(state.pos + m, n)
    return FisherYatesState(idx, new_pos, state.size), out, valid


def fy_draw_bounded(
    key: jax.Array, state: FisherYatesState, m_max: int, m_eff: jax.Array
) -> tuple[FisherYatesState, jax.Array, jax.Array]:
    """Fisher–Yates draw with a *traced* effective batch size.

    Shapes stay static at ``m_max`` (so one compiled program serves every
    batch-size bucket of the adaptive scheduler); only the first ``m_eff``
    lanes are valid and only those consume pool positions — the next draw
    resumes at ``pos + m_eff``. The extra swaps beyond ``m_eff`` merely
    re-permute the tail, which leaves future without-replacement draws
    exactly uniform (any permutation is a valid Fisher–Yates start state).
    """
    m_eff = jnp.clip(jnp.asarray(m_eff, jnp.int32), 0, m_max)
    new_state, idx, valid = fy_draw(key, state, m_max)
    valid = valid & (jnp.arange(m_max, dtype=jnp.int32) < m_eff)
    new_pos = jnp.minimum(state.pos + m_eff, state.size)
    return FisherYatesState(new_state.idx, new_pos, state.size), idx, valid


class StreamSliceState(NamedTuple):
    """TPU-native without-replacement sampler over a pre-permuted pool.

    The pool (e.g. the resident global batch of sequences) is assumed already
    randomly ordered by the data pipeline; a round consumes the next
    contiguous run [pos, pos + m). Equivalent in distribution to Fisher–Yates
    draws while keeping every gather local to its shard. The draw returns
    the run as an index block, clamped at the pool's end; the ensemble's
    batched rounds read each chain's run as one
    :class:`~repro.core.target.Window` instead (one slice per chain, not m
    indices) whenever every run lies inside the pool, and the index block
    only in the round where a chain's run reaches the end.
    """

    pos: jax.Array  # int32 scalar
    n: int

    @property
    def num_sections(self) -> int:
        return self.n


def stream_init(n: int) -> StreamSliceState:
    return StreamSliceState(jnp.zeros((), jnp.int32), n)


def stream_reset(state: StreamSliceState) -> StreamSliceState:
    return StreamSliceState(jnp.zeros((), jnp.int32), state.n)


def stream_draw(
    key: jax.Array, state: StreamSliceState, m: int
) -> tuple[StreamSliceState, jax.Array, jax.Array]:
    del key  # randomness lives in the stream order
    offs = state.pos + jnp.arange(m, dtype=jnp.int32)
    valid = offs < state.n
    out = jnp.minimum(offs, state.n - 1).astype(jnp.int32)
    return StreamSliceState(jnp.minimum(state.pos + m, state.n), state.n), out, valid


def stream_draw_bounded(
    key: jax.Array, state: StreamSliceState, m_max: int, m_eff: jax.Array
) -> tuple[StreamSliceState, jax.Array, jax.Array]:
    """Stream-slice draw with a traced effective batch size <= ``m_max``.

    Lanes past ``m_eff`` are flagged invalid and do NOT advance the stream
    position, so the pool is consumed at exactly the adaptive rate.
    """
    del key
    m_eff = jnp.clip(jnp.asarray(m_eff, jnp.int32), 0, m_max)
    offs = state.pos + jnp.arange(m_max, dtype=jnp.int32)
    valid = (offs < state.n) & (jnp.arange(m_max, dtype=jnp.int32) < m_eff)
    out = jnp.minimum(offs, state.n - 1).astype(jnp.int32)
    return StreamSliceState(jnp.minimum(state.pos + m_eff, state.n), state.n), out, valid


def make_sampler(kind: str, n: int):
    """Returns (init_state, reset_fn, draw_fn) for ``kind`` in {fy, stream}."""
    if kind == "fy":
        return fy_init(n), fy_reset, fy_draw
    if kind == "stream":
        return stream_init(n), stream_reset, stream_draw
    raise ValueError(f"unknown sampler kind: {kind!r}")


def make_bounded_draw(kind: str):
    """The bounded twin of ``make_sampler``'s draw_fn:
    draw(key, state, m_max static, m_eff traced) -> (state, idx[m_max], valid).
    """
    if kind == "fy":
        return fy_draw_bounded
    if kind == "stream":
        return stream_draw_bounded
    raise ValueError(f"unknown sampler kind: {kind!r}")
