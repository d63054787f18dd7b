"""Serving-workload registry: every kernel family serves for free.

A :class:`ServingWorkload` bundles what the pool needs to keep a posterior
resident: a configured :class:`~repro.core.ensemble.ChainEnsemble` (whose
target went through :func:`repro.core.target_builder.build_target`, so the
fused multi-chain kernels ride along wherever dispatch selects them), the
initial parameters, and the workload's request classes
(:class:`~repro.serving.resident.QuerySpec`).

The three paper workloads register through their experiment drivers'
``make_serving_workload()`` entries (lazy imports keep the serving layer
importable without pulling every experiment); the ``ppl`` workload compiles
a probabilistic *program* through :func:`repro.ppl.compile_partitioned_target`
— the end-to-end demonstration that a registered-family program gets the
whole serving stack (resident ensemble, batching, freshness, checkpoints)
without any workload-specific code.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ensemble import ChainEnsemble
from .resident import QuerySpec

Params = Any


@dataclasses.dataclass(frozen=True)
class ServingWorkload:
    """One servable posterior: ensemble + initial point + request classes."""

    name: str
    ensemble: ChainEnsemble
    theta0: Params
    query_specs: dict[str, QuerySpec]
    default_class: str
    description: str = ""

    def __post_init__(self):
        if self.default_class not in self.query_specs:
            raise ValueError(
                f"default_class {self.default_class!r} not in query_specs "
                f"{sorted(self.query_specs)}"
            )


def row_sampler(rows: np.ndarray) -> Callable[[jax.Array, int], np.ndarray]:
    """A ``QuerySpec.make_queries`` that samples request inputs uniformly
    from a host-side pool of rows (the shared idiom of the predictive
    workloads: query points drawn from the held-out set)."""
    rows = np.asarray(rows)

    def make_queries(qkey: jax.Array, n: int) -> np.ndarray:
        idx = np.asarray(jax.random.randint(qkey, (n,), 0, rows.shape[0]))
        return rows[idx]

    return make_queries


_REGISTRY: dict[str, Callable[..., ServingWorkload]] = {}


def register_serving_workload(name: str, builder: Callable[..., ServingWorkload]):
    """Register (or overwrite) a workload builder under ``name``."""
    _REGISTRY[name] = builder
    return builder


def serving_workloads() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def build_serving_workload(name: str, **kw) -> ServingWorkload:
    """Instantiate a registered workload (builders accept ``smoke=`` plus
    size/engine keywords; see each experiment's ``make_serving_workload``)."""
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown serving workload {name!r}; registered: {serving_workloads()}"
        )
    return _REGISTRY[name](**kw)


# ---------------------------------------------------------------------------
# Built-in workloads. The experiment-backed builders import lazily so that
# `import repro.serving` stays cheap and cycle-free.
# ---------------------------------------------------------------------------


def _bayeslr_builder(**kw) -> ServingWorkload:
    from ..experiments import bayeslr

    return bayeslr.make_serving_workload(**kw)


def _stochvol_builder(**kw) -> ServingWorkload:
    from ..experiments import stochvol

    return stochvol.make_serving_workload(**kw)


def _jointdpm_builder(**kw) -> ServingWorkload:
    from ..experiments import jointdpm

    return jointdpm.make_serving_workload(**kw)


def make_ppl_workload(
    *,
    smoke: bool = False,
    num_chains: int = 4,
    n: int | None = None,
    d: int = 3,
    batch_size: int = 50,
    epsilon: float = 0.05,
    sigma: float = 0.08,
    seed: int = 0,
) -> ServingWorkload:
    """Serve a *compiled probabilistic program*: a plated Bernoulli-logit
    regression written against :mod:`repro.ppl`, lowered by
    ``compile_partitioned_target`` (which recognizes the ``logit`` family and
    attaches the fused ensemble kernel), then dropped into a stock
    :class:`~repro.core.ensemble.ChainEnsemble`."""
    from ..core import SubsampledMHConfig
    from ..core.proposals import RandomWalk
    from ..ppl import Trace, compile_partitioned_target, dists

    n = n if n is not None else (300 if smoke else 2000)
    key = jax.random.key(seed)
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (n, d))
    w_true = jnp.linspace(-1.0, 1.0, d)
    yv = jnp.where(
        jax.random.bernoulli(ky, jax.nn.sigmoid(x @ w_true)), 1.0, -1.0
    )
    tr = Trace()
    w = tr.sample(
        "w", dists.mvnormal_diag,
        tr.constant("mu_w", jnp.zeros(d)),
        tr.constant("sig_w", jnp.sqrt(0.1) * jnp.ones(d)),
        value=jnp.zeros(d),
    )
    with tr.plate("data", n):
        xn = tr.constant("x", x)
        z = tr.det("z", lambda xx, ww: xx @ ww, xn, w)
        yn = tr.sample("y", dists.bernoulli_logits, z, value=yv)
        tr.observe(yn, yv)
    target = compile_partitioned_target(tr, w)
    ens = ChainEnsemble(
        target, RandomWalk(sigma), num_chains,
        config=SubsampledMHConfig(batch_size=min(batch_size, n), epsilon=epsilon),
    )
    make_queries = row_sampler(np.asarray(x))
    def _level_sampler(qkey: jax.Array, n_rows: int) -> np.ndarray:
        return np.asarray(
            jax.random.uniform(qkey, (n_rows,), minval=0.05, maxval=0.95)
        )

    specs = {
        "predictive": QuerySpec(
            fn=lambda wd, xs: jax.nn.sigmoid(
                jnp.dot(xs, wd, precision=jax.lax.Precision.HIGHEST)
            ),
            aggregate="mean",
            make_queries=make_queries,
            name="predictive",
        ),
        # posterior quantiles of the coefficient norm — request rows are
        # quantile levels; the whole (S, mb) -> (mb,) reduction runs on
        # device inside SnapshotEvaluator
        "wnorm_quantile": QuerySpec(
            fn=lambda wd, xs: jnp.broadcast_to(
                jnp.linalg.norm(wd), xs.shape
            ),
            aggregate="quantile",
            make_queries=_level_sampler,
            name="wnorm_quantile",
        ),
    }
    return ServingWorkload(
        name="ppl",
        ensemble=ens,
        theta0=jnp.zeros(d),
        query_specs=specs,
        default_class="predictive",
        description=f"compiled Bernoulli-logit program, N={n}, D={d}",
    )


register_serving_workload("bayeslr", _bayeslr_builder)
register_serving_workload("stochvol", _stochvol_builder)
register_serving_workload("jointdpm", _jointdpm_builder)
register_serving_workload("ppl", make_ppl_workload)
