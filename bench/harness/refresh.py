"""Traffic kind ``refresh``: back-to-back refresh blocks, no queries.

The offline deployment: ``ResidentEnsemble.refresh`` (reached through
the ``EnsemblePool`` that holds it) advances every chain one block of
transitions, pulls the draws to the host and commits them. The window is
a whole number of blocks, at least ``--seconds`` long, ending at the
first block that completes after it. One transition is one step of every
chain: for a composite ``cycle`` it is one whole cycle, every component
applied once.

The model module (``bench/models/<model>.py``) must define

* ``make_data(cfg, data_seed) -> dict`` of device arrays;
* ``build_pool(cfg, data, program_seed) -> (EnsemblePool, name)``;
* ``check_refresh(cfg, host_data, rec, program_seed, rng) -> dict`` of
  the numbers compared with ``cfg["limits"]["refresh"]``;

and may define

* ``sizes(cfg) -> dict``: ``{"num_sections": n, "width": w}`` for a single
  operator, or ``{"ops": {name: {"num_sections": n, "width": w}}}`` per
  operator of a composite; without it, ``cfg["data"]["n_train"]`` and
  ``cfg["data"]["d"]``;
* ``after_block(resident, block_index, rng)``: called after each timed
  block, inside the ``bench`` span. It may keep device arrays the draws do
  not carry (``resident.state``: a composite's latent paths and sampler
  states) by returning them; it must neither wait for the device
  nor pull to the host. ``rng`` is seeded from the run's seed and starts
  from the same state at every call, so a hook picks its blocks from it
  without keeping state of its own.

``rec``, as ``check_refresh`` gets it:

* ``theta_before`` (the collected draw before the window, leaves (K, ...)),
  ``start_step`` and ``draws`` (leaves (K, T, ...), T = steps × blocks):
  pytrees of the shape the ensemble's ``collect`` gives, a bare array for
  a flat θ;
* a single operator (infos a ``SubsampledMHInfo``): ``accepted``,
  ``n_evaluated``, ``mu_hat``, ``mu0``, ``rounds``, each (K, T);
* a composite (infos a dict by operator name): ``ops[name]`` holds every
  field of that operator's info, each (K, T), and its ``sizes``; a
  subsampled-MH operator also has ``traced_n_evaluated``, the part of
  ``n_evaluated`` in the traced blocks;
* with ``after_block``: ``kept``, a list of ``(block_index, value)`` for
  each value it returned, its device arrays pulled to the host after the
  window.

What ``run`` returns, which the metric readers get, adds to the timing
``transitions`` (chains × committed steps) and the sizes; for a single
operator ``rounds``, ``n_evaluated`` and ``traced_n_evaluated``, for a
composite ``ops``. A composite has no flat ``rounds`` or ``n_evaluated``,
so a reader of one operator's counts finds nothing to read there.
"""
from __future__ import annotations

import gc

import jax
import numpy as np

from .context import TRACE_SECONDS, RunContext, now
from .device import peak_bytes


def _concat(blocks):
    """The window's blocks joined on the step axis, leaf by leaf."""
    return jax.tree.map(lambda *a: np.concatenate([np.asarray(x) for x in a], 1),
                        *blocks)


def _fields(info) -> dict:
    """An operator's info as a dict of fields (a NamedTuple or a dict)."""
    return info._asdict() if hasattr(info, "_asdict") else dict(info)


def run(ctx: RunContext) -> dict:
    cfg = ctx.config
    model = ctx.cell.model
    after_block = getattr(model, "after_block", None)
    data = model.make_data(cfg, ctx.seeds["data"])
    pool, name = model.build_pool(cfg, data, ctx.seeds["program"])
    resident = pool.resident(name)
    steps = resident.refresh_steps
    pool.warm()  # compiles the refresh program and runs the first block
    resident.refresh()  # a second block: every host path of a refresh warm
    theta_before = jax.tree.map(lambda a: np.asarray(a)[:, -1], resident._draws)
    start_step = resident.steps_done
    setup_s = now() - ctx.t_process

    draws, infos, kept = [], [], []
    profiler = ctx.profiler()
    traced_blocks = 0
    profiler.start()
    t0 = now()
    while True:
        with ctx.span("refresh"):
            resident.refresh()
        with ctx.span("bench"):
            # The block the refresh just committed: the newest draws of the
            # window, and the per-transition infos it pulled.
            draws.append(jax.tree.map(lambda a: a[:, -steps:], resident._draws))
            infos.append(resident._last_infos)
            if after_block is not None:
                value = after_block(resident, len(draws) - 1, ctx.rng("after_block"))
                if value is not None:
                    kept.append((len(draws) - 1, value))
        elapsed = now() - t0
        if profiler.active:
            traced_blocks += 1
            if elapsed >= TRACE_SECONDS:
                profiler.stop()
        if elapsed >= ctx.seconds:
            break
    window_s = now() - t0
    profiler.stop()
    peak = peak_bytes()
    k = resident.ensemble.num_chains
    host_data = {key: np.asarray(v) for key, v in data.items()}
    kept = [(b, jax.tree.map(np.asarray, v)) for b, v in kept]
    del pool, resident, data
    gc.collect()

    sizes = (model.sizes(cfg) if hasattr(model, "sizes")
             else {"num_sections": cfg["data"]["n_train"], "width": cfg["data"]["d"]})
    traced = traced_blocks * steps
    rec = {"theta_before": theta_before, "start_step": start_step,
           "draws": _concat(draws)}
    out = {key: v for key, v in sizes.items() if key != "ops"}
    if isinstance(infos[0], dict):
        op_sizes = sizes.get("ops", {})
        rec["ops"] = {}
        for op in infos[0]:
            fields = _concat([_fields(i[op]) for i in infos])
            if "n_evaluated" in fields:
                fields["traced_n_evaluated"] = fields["n_evaluated"][:, :traced]
            rec["ops"][op] = {**fields, **op_sizes.get(op, {})}
        out["ops"] = rec["ops"]
    else:
        fields = _concat([_fields(i) for i in infos])
        rec.update({f: fields[f] for f in ("accepted", "n_evaluated", "mu_hat", "mu0", "rounds")})
        out.update(rounds=rec["rounds"], n_evaluated=rec["n_evaluated"],
                   traced_n_evaluated=rec["n_evaluated"][:, :traced])
    if after_block is not None:
        rec["kept"] = kept
    checks = model.check_refresh(cfg, host_data, rec, ctx.seeds["program"],
                                 ctx.rng("check"))
    transitions = k * steps * len(draws)
    return {
        "setup_s": setup_s,
        "window_s": window_s,
        "attempted": transitions,
        "failed": 0,
        "peak_bytes": peak,
        "checks": checks,
        "transitions": transitions,
        **out,
    }
