"""Pallas TPU kernel: fused large-vocab log-likelihood (blocked online
logsumexp).

The per-transition hot spot of subsampled MH over an LM is the per-sequence
log-likelihood: logits = h @ W_vocab^T with V up to 262k (gemma3). Naively
that materializes a (T, V) tensor in HBM (tens of GB per round). This kernel
streams vocab tiles through VMEM with a flash-style running (max, sum)
accumulator and a one-hot target extraction, so HBM traffic is
O(T*D + V*D + T) instead of O(T*V).

Grid: (K, T/tile_t, V/tile_v); the vocab axis is the accumulation loop.
MXU work per step is a (tile_v x D) @ (D x tile_t) matmul. The logits are
computed transposed, vocab along sublanes and tokens along lanes, so the
per-token state (targets, running max/sum/target logit, result) is one
lane-dense (1, tile_t) row: per-token vectors travel as (K, 1, T) arrays,
whose (1, tile_t) blocks a TPU accepts for tile_t a multiple of 128 (a
(1, tile_t) block of a (K, T) array it refuses). Validated in interpret
mode on CPU against ref.py and compiled for TPU v5e in the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(tgt_ref, h_ref, tab_ref, out_ref, m_ref, s_ref, t_ref,
            *, tile_v, n_v, v_real, shared_table):
    vj = pl.program_id(2)

    @pl.when(vj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    h = h_ref[0]  # (tile_t, D) of this chain
    tab = tab_ref[...] if shared_table else tab_ref[0]  # (tile_v, D)
    logits = jax.lax.dot_general(
        tab, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (tile_v, tile_t)
    # mask vocab-padding rows out of the logsumexp
    rows = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    logits = jnp.where(vj * tile_v + rows < v_real, logits, _NEG)

    m_old = m_ref[...]  # (1, tile_t)
    m_new = jnp.maximum(m_old, logits.max(axis=0, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    s_ref[...] = s_ref[...] * corr + jnp.exp(logits - m_new).sum(axis=0, keepdims=True)
    m_ref[...] = m_new

    # target logit if it falls inside this vocab tile
    local = tgt_ref[0] - vj * tile_v  # (1, tile_t)
    t_ref[...] = t_ref[...] + jnp.where(rows == local, logits, 0.0).sum(
        axis=0, keepdims=True
    )

    @pl.when(vj == n_v - 1)
    def _finish():
        out_ref[0] = t_ref[...] - (jnp.log(s_ref[...]) + m_ref[...])


@functools.partial(jax.jit, static_argnames=("tile_t", "tile_v", "interpret"))
def batched_fused_ce(
    h: jax.Array,  # (K, T, D) per-chain token activations
    table: jax.Array,  # (V, D) shared vocab table, or (K, V, D) per-chain
    targets: jax.Array,  # (K, T) int32
    *,
    tile_t: int = 256,
    tile_v: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Ensemble-batched per-token log-likelihood: the (K, m) multi-chain
    round of the LM likelihood, one ``pallas_call`` for all K chains.

    The chain axis joins the grid (grid = (K, T/tile_t, V/tile_v), vocab-major
    accumulation per (chain, token-tile)). ``table`` may be shared (the
    common case: chains sample activations-producing parameters) or carry a
    per-chain leading axis (chains sample the table itself, e.g. an
    unembedding MH move). On a TPU ``tile_t`` must be a multiple of 128
    unless it covers all of T, and ``tile_v`` a multiple of 8.
    """
    k, t, d = h.shape
    shared_table = table.ndim == 2
    v = table.shape[0] if shared_table else table.shape[1]
    tile_t = min(tile_t, t)
    tile_v = min(tile_v, v)
    pad_t = (-t) % tile_t
    pad_v = (-v) % tile_v
    if pad_t:
        h = jnp.pad(h, ((0, 0), (0, pad_t), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad_t)))
    if pad_v:
        pad_spec = ((0, pad_v), (0, 0)) if shared_table else ((0, 0), (0, pad_v), (0, 0))
        table = jnp.pad(table, pad_spec)
    tp, vp = t + pad_t, v + pad_v
    n_t, n_v = tp // tile_t, vp // tile_v

    if shared_table:
        tab_spec = pl.BlockSpec((tile_v, d), lambda c, i, j: (j, 0))
    else:
        tab_spec = pl.BlockSpec((1, tile_v, d), lambda c, i, j: (c, j, 0))
    row = pl.BlockSpec((1, 1, tile_t), lambda c, i, j: (c, 0, i))
    out = pl.pallas_call(
        functools.partial(_kernel, tile_v=tile_v, n_v=n_v, v_real=v,
                          shared_table=shared_table),
        grid=(k, n_t, n_v),
        in_specs=[
            row,
            pl.BlockSpec((1, tile_t, d), lambda c, i, j: (c, i, 0)),
            tab_spec,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((k, 1, tp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, tile_t), jnp.float32)] * 3,
        interpret=interpret,
    )(targets.astype(jnp.int32)[:, None, :], h, table)
    return out[:, 0, :t]


@functools.partial(jax.jit, static_argnames=("tile_t", "tile_v", "interpret"))
def fused_ce(
    h: jax.Array,  # (T, D)
    table: jax.Array,  # (V, D)
    targets: jax.Array,  # (T,) int32
    *,
    tile_t: int = 256,
    tile_v: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-token log-likelihood of one sequence: the one-chain case of
    :func:`batched_fused_ce`."""
    return batched_fused_ce(
        h[None], table, targets[None],
        tile_t=tile_t, tile_v=tile_v, interpret=interpret,
    )[0]
