"""Pallas kernel: ensemble-batched BayesLR delta-log-likelihood.

The multi-chain engine (:class:`repro.core.ensemble.ChainEnsemble`) turns
every sequential-test round into a (K, m) block of local-section evaluations
— K chains, each with its own gathered mini-batch and its own (w, w') pair.
This kernel fuses the whole block into one ``pallas_call``: per (chain, tile)
grid step it reads one (tile_m, D) slab of gathered features and the chain's
(2, D) stacked weight pair, does a single MXU matmul for BOTH sides of the
MH ratio (the pair-fusion of :mod:`repro.kernels.logit_loglik`, lifted over
the chain axis), and writes the (tile_m,) delta.

Layout: per-section vectors (labels, deltas) travel as (K, 1, m) arrays so
each grid step moves a lane-dense (1, tile_m) row — a TPU block's last two
dims must be (8, 128)-aligned or span the array, which a (1, tile_m) block
of a (K, m) array is not. The matmul is written transposed,
``(2, D) x (tile_m, D)^T -> (2, tile_m)``, so its result already lies along
the lanes of that row.

Inputs are the *gathered* per-chain mini-batches — the O(m) gather stays
outside the kernel where XLA can fuse it with the sampler's index production.

Grid: (K, ceil(m / tile_m)). ``ref.batched_logit_delta_ref`` is the pure-jnp
twin used for interpret-mode parity tests on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xg_ref, yg_ref, w2_ref, out_ref):
    x = xg_ref[0]  # (tile_m, D) gathered features of this chain's tile
    w2 = w2_ref[0]  # (2, D): [w_cur; w_prop] of this chain
    z = jax.lax.dot_general(
        w2, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (2, tile_m)
    y = yg_ref[0].astype(jnp.float32)  # (1, tile_m)
    lc = -jnp.logaddexp(0.0, -y * z[0:1])
    lp = -jnp.logaddexp(0.0, -y * z[1:2])
    out_ref[0] = lp - lc


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def batched_logit_delta(
    xg: jax.Array,  # (K, m, D) gathered features, one mini-batch per chain
    yg: jax.Array,  # (K, m) labels in {-1, +1}
    w_cur: jax.Array,  # (K, D)
    w_prop: jax.Array,  # (K, D)
    *,
    tile_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """l[k, i] = log sig(y x·w'_k) - log sig(y x·w_k) for all K chains at once.

    On a TPU ``tile_m`` must be a multiple of 128 unless it covers all of m.
    """
    k, m, d = xg.shape
    tile_m = min(tile_m, m)
    pad = (-m) % tile_m
    if pad:
        xg = jnp.pad(xg, ((0, 0), (0, pad), (0, 0)))
        yg = jnp.pad(yg, ((0, 0), (0, pad)), constant_values=1.0)
    w2 = jnp.stack([w_cur, w_prop], axis=1)  # (K, 2, D)
    out = pl.pallas_call(
        _kernel,
        grid=(k, (m + pad) // tile_m),
        in_specs=[
            pl.BlockSpec((1, tile_m, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, tile_m), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 2, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_m), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((k, 1, m + pad), jnp.float32),
        interpret=interpret,
    )(xg, yg[:, None, :], w2)
    return out[:, 0, :m]


def gather_and_delta(
    x: jax.Array,  # (N, D) full feature pool
    y: jax.Array,  # (N,)
    idx: jax.Array,  # (K, m) int32 per-chain mini-batch indices
    w_cur: jax.Array,  # (K, D)
    w_prop: jax.Array,  # (K, D)
    *,
    tile_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Gather each chain's mini-batch then run the fused (K, m) kernel."""
    return batched_logit_delta(
        x[idx], y[idx], w_cur, w_prop, tile_m=tile_m, interpret=interpret
    )
