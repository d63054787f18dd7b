"""Pallas kernel: ensemble-batched AR(1) transition-factor delta.

The stochastic-volatility local sections (paper Sec. 4.3) are the T
transition factors N(h_t | phi h_{t-1}, sigma^2); a multi-chain sequential-
test round over K chains evaluates a (K, m) block of pair-deltas

    l[k, i] = log N(xt[k,i] | phi'_k xp[k,i], s2'_k)
            - log N(xt[k,i] | phi_k  xp[k,i], s2_k)

with per-chain (phi, sigma^2) pairs. Pure VPU work — the fusion win is a
single kernel launch per round with the per-chain parameter broadcast, the
masking, and both sides of the MH ratio in one pass over the gathered
(K, m) slabs (which stay outside the kernel, fused with the sampler's index
production, exactly like :mod:`repro.kernels.batched_loglik`).

Layout: the slabs travel as (K, 1, m) arrays, one lane-dense (1, tile_m)
row per grid step (see :mod:`repro.kernels.batched_loglik`). The four
per-chain scalars are scalar-prefetched into SMEM as one flat (4K,) vector
and read by chain index, then broadcast against the row.

Grid: (K, ceil(m / tile_m)). ``ref.batched_gaussian_ar1_delta_ref`` is the
pure-jnp twin used for interpret-mode parity tests on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(par_ref, xt_ref, xp_ref, out_ref):
    base = 4 * pl.program_id(0)
    xt = xt_ref[0].astype(jnp.float32)  # (1, tile_m) gathered x_t of this chain
    xp = xp_ref[0].astype(jnp.float32)  # (1, tile_m) gathered x_{t-1}
    row = lambda j: jnp.full(xt.shape, par_ref[base + j], jnp.float32)
    phi_c, s2_c, phi_p, s2_p = row(0), row(1), row(2), row(3)
    s2_c = jnp.maximum(s2_c, 1e-12)
    s2_p = jnp.maximum(s2_p, 1e-12)
    lc = -0.5 * ((xt - phi_c * xp) ** 2 / s2_c + jnp.log(s2_c))
    lp = -0.5 * ((xt - phi_p * xp) ** 2 / s2_p + jnp.log(s2_p))
    out_ref[0] = lp - lc


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def batched_gaussian_ar1_delta(
    xt: jax.Array,  # (K, m) gathered x_t, one mini-batch per chain
    xp: jax.Array,  # (K, m) gathered x_{t-1}
    phi_cur: jax.Array,  # (K,)
    s2_cur: jax.Array,  # (K,)
    phi_prop: jax.Array,  # (K,)
    s2_prop: jax.Array,  # (K,)
    *,
    tile_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """(K, m) AR(1) pair-delta block — one call per multi-chain test round.

    bfloat16 ``xt``/``xp`` slabs are streamed as-is (half the HBM bytes of
    the memory-bound gather path) and upcast to float32 inside the kernel;
    any other dtype is cast to float32 up front as before. On a TPU
    ``tile_m`` must be a multiple of 128 unless it covers all of m.
    """
    k, m = xt.shape
    if xt.dtype != jnp.bfloat16:
        xt = xt.astype(jnp.float32)
        xp = xp.astype(jnp.float32)
    tile_m = min(tile_m, m)
    pad = (-m) % tile_m
    if pad:
        xt = jnp.pad(xt, ((0, 0), (0, pad)))
        xp = jnp.pad(xp, ((0, 0), (0, pad)))
    par = jnp.stack(
        [phi_cur, s2_cur, phi_prop, s2_prop], axis=-1
    ).astype(jnp.float32).reshape(-1)  # (4K,): chain k at [4k, 4k + 4)
    row = pl.BlockSpec((1, 1, tile_m), lambda i, j, par: (i, 0, j))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k, (m + pad) // tile_m),
            in_specs=[row, row],
            out_specs=row,
        ),
        out_shape=jax.ShapeDtypeStruct((k, 1, m + pad), jnp.float32),
        interpret=interpret,
    )(par, xt[:, None, :], xp[:, None, :])
    return out[:, 0, :m]
