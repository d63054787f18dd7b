"""Partitioned targets: the TPU-tensor form of a partitioned scaffold.

A ``PartitionedTarget`` is what the ppl/ layer emits after computing the
scaffold s(rho, v) for a global variable v and partitioning it into the
*global* section plus N structurally-identical *local* sections (paper
Defs. 6–8). The MH kernels in this package consume only this interface:

  log_global(theta, theta_prime) -> scalar
      sum over the global section of log w_n, i.e.
      log p_global(theta') - log p_global(theta). Proposal corrections are
      handled by the Proposal object, not here.

  log_local(theta, theta_prime, idx) -> (m,)
      l_i for the requested local sections (an int index array, or a
      :class:`Window` where ``local_window`` says it reads one): the
      per-section log-weight products sum_{n in local_i} log w_n. For
      symmetric proposals over a Bayesian-network-shaped scaffold this is
      log p(x_{local_i} | theta') - log p(x_{local_i} | theta).

  num_sections
      N, the number of children of the border node b(s, v).

The callables must be jit-traceable. ``theta`` is an arbitrary pytree.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

Params = Any


@dataclasses.dataclass(frozen=True)
class Window:
    """A round's sections as one contiguous run ``[start, start + size)`` of
    the pool: the ``stream`` sampler's round when the whole run lies inside
    the pool. ``start`` is a scalar, or (K,) with one run per chain; ``size``
    is static. A family reads it as one slice per chain, where an index
    array costs one gathered element per section."""

    start: jax.Array
    size: int


jax.tree_util.register_dataclass(Window, data_fields=["start"], meta_fields=["size"])


@dataclasses.dataclass(frozen=True)
class PartitionedTarget:
    num_sections: int
    log_global: Callable[[Params, Params], jax.Array]
    log_local: Callable[[Params, Params, jax.Array], jax.Array]
    # Optional: full-posterior log density (global part + all sections), used
    # by diagnostics and by gradient-informed proposals. May be None.
    log_density: Callable[[Params], jax.Array] | None = None
    # Optional ensemble-fused local evaluation: (theta, theta', idx) with a
    # leading (K,) chain axis on every argument (idx a (K, m) index array or a
    # Window with (K,) starts) -> (K, m) deltas, backed by a
    # fused kernel (e.g. repro.kernels.ops.batched_logit_delta). When set and
    # the ops dispatch selects Pallas, ChainEnsemble routes each sequential-
    # test round through it instead of vmapping ``log_local``.
    log_local_ensemble: Callable[[Params, Params, jax.Array], jax.Array] | None = None
    # Name of the registered kernel family (repro.core.target_builder) that
    # built log_local / log_local_ensemble, or None for hand-wired targets.
    family: str | None = None
    # True when ``log_local`` also takes a Window in place of its index
    # array (the builder's family closures); hand-wired ones take indices.
    local_window: bool = False
    # Optional construction recipe (repro.core.target_builder.TargetSpec):
    # the family name, the section-pool data arrays, and the (possibly
    # tempered) prior the builder assembled this target from. Carrying the
    # recipe is what makes targets *re-buildable* — the dataset partitioner
    # (repro.partition) slices the pool per subposterior worker, and the
    # streaming append path concatenates new observations — without any
    # per-workload code. None for hand-wired or latent-dependent targets.
    spec: Any | None = None


def from_iid_loglik(
    prior_logpdf: Callable[[Params], jax.Array],
    loglik_fn: Callable[[Params, jax.Array], jax.Array],
    data: Any,
    num_sections: int,
) -> PartitionedTarget:
    """Convenience constructor for the BayesLR-shaped scaffold (Table 1 row 1):
    theta ~ prior, sections are iid observations.

    ``loglik_fn(theta, idx) -> (m,)`` per-observation log-likelihoods; ``data``
    is closed over by loglik_fn's caller — kept here only for documentation.
    """
    del data

    def log_global(theta, theta_p):
        return prior_logpdf(theta_p) - prior_logpdf(theta)

    def log_local(theta, theta_p, idx):
        return loglik_fn(theta_p, idx) - loglik_fn(theta, idx)

    def log_density(theta):
        import jax.numpy as jnp

        idx = jnp.arange(num_sections, dtype=jnp.int32)
        return prior_logpdf(theta) + loglik_fn(theta, idx).sum()

    return PartitionedTarget(num_sections, log_global, log_local, log_density)
