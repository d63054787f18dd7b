"""Pallas TPU kernel: fused BayesLR delta-log-likelihood.

The paper's own hot spot (Sec. 4.1): every sequential-test round evaluates
l_i = log sig(y_i x_i.w') - log sig(y_i x_i.w) for a mini-batch. Evaluating
theta and theta' separately reads the feature tile x twice; MH always needs
the PAIR, so the kernel computes both dot products per x-tile read — the
data movement is halved versus two passes (a beyond-paper fusion enabled by
the structure of the MH ratio; see DESIGN.md §6).

The single-chain form is the one-chain case of
:func:`repro.kernels.batched_loglik.batched_logit_delta`: one kernel body,
one TPU layout. Grid: (1, N/tile_n). Per step: one (2 x D) @ (D x tile_n)
MXU matmul, then the log-sigmoid deltas on the VPU.
"""
from __future__ import annotations

import functools

import jax

from .batched_loglik import batched_logit_delta


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def logit_delta(
    x: jax.Array,  # (N, D)
    y: jax.Array,  # (N,) in {-1, +1}
    w_cur: jax.Array,  # (D,)
    w_prop: jax.Array,  # (D,)
    *,
    tile_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    return batched_logit_delta(
        x[None], y[None], w_cur[None], w_prop[None],
        tile_m=tile_n, interpret=interpret,
    )[0]
