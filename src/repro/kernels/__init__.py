"""Pallas TPU kernels for the likelihood hot spots (+ jnp oracles).

Per-section vectors move as lane-dense (1, tile) rows of (K, 1, m) arrays,
the block layout a TPU accepts; interpret mode on CPU checks values, and
tests/test_tpu_compile.py checks the layouts against the v5e compiler.

batched_fused_ce          — vocab-blocked per-token log-likelihood (online
                            logsumexp) over (K, T): one grid over chains
fused_ce                  — its single-sequence (K = 1) case
batched_logit_delta       — pair-fused BayesLR MH delta (x read once for theta,
                            theta') over (K, m): one fused pallas_call per
                            multi-chain sequential-test round
logit_delta               — its single-chain (K = 1) case
batched_gaussian_ar1_delta — the (K, m) AR(1) transition-factor delta (stochvol)
batched_pgibbs_sweep      — fused particle-Gibbs sweep: all (K chains, S series,
                            P particles) advanced by ONE time-major scan, sharing
                            the AR(1) propagate math with the delta kernels
ops                       — jit'd dispatch wrappers (mode="auto|always|never":
                            kernel on TPU, interpret/ref on CPU, REPRO_FUSED env
                            overrides the auto default; precision="fp32|bf16|auto"
                            picks the gather/delta data path, fp32 accumulation
                            always)
autotune                  — per-backend Pallas block-size tuner with an on-disk
                            winner cache (REPRO_AUTOTUNE, REPRO_AUTOTUNE_DIR)
ref                       — pure-jnp oracles (the allclose ground truth) and the
                            shared reference likelihoods (logit_loglik,
                            ar1_propagate, sv_obs_loglik)
"""
from . import autotune, ops, ref
from .batched_loglik import batched_logit_delta, gather_and_delta
from .fused_ce import batched_fused_ce, fused_ce
from .gaussian_ar1 import batched_gaussian_ar1_delta
from .logit_loglik import logit_delta
from .pgibbs import batched_pgibbs_sweep, pgibbs_sweep_fused

__all__ = [
    "autotune",
    "batched_fused_ce",
    "batched_gaussian_ar1_delta",
    "batched_logit_delta",
    "batched_pgibbs_sweep",
    "fused_ce",
    "gather_and_delta",
    "logit_delta",
    "ops",
    "pgibbs_sweep_fused",
    "ref",
]
