"""Traffic kind ``refresh``: back-to-back refresh blocks, no queries.

The offline deployment: ``ResidentEnsemble.refresh`` (reached through
the ``EnsemblePool`` that holds it) advances every chain one block of
transitions, pulls the draws to the host and commits them. The window is
a whole number of blocks, at least ``--seconds`` long, ending at the
first block that completes after it.
"""
from __future__ import annotations

import gc

import numpy as np

from .context import TRACE_SECONDS, RunContext, now
from .device import peak_bytes


def run(ctx: RunContext) -> dict:
    cfg = ctx.config
    model = ctx.cell.model
    data = model.make_data(cfg, ctx.seeds["data"])
    pool, name = model.build_pool(cfg, data, ctx.seeds["program"])
    resident = pool.resident(name)
    steps = resident.refresh_steps
    pool.warm()  # compiles the refresh program and runs the first block
    resident.refresh()  # a second block: every host path of a refresh warm
    theta_before = np.asarray(resident._draws)[:, -1]
    start_step = resident.steps_done
    setup_s = now() - ctx.t_process

    draws, infos = [], []
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
            draws.append(resident._draws[:, -steps:])
            infos.append(resident._last_infos)
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
    del pool, resident, data
    gc.collect()

    cat = lambda field: np.concatenate([np.asarray(getattr(i, field)) for i in infos], 1)
    rec = {
        "theta_before": theta_before, "start_step": start_step,
        "draws": np.concatenate(draws, axis=1),
        "accepted": cat("accepted"), "n_evaluated": cat("n_evaluated"),
        "mu_hat": cat("mu_hat"), "mu0": cat("mu0"), "rounds": cat("rounds"),
    }
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
        "rounds": rec["rounds"],
        "n_evaluated": rec["n_evaluated"],
        "traced_n_evaluated": rec["n_evaluated"][:, : traced_blocks * steps],
        "num_sections": cfg["data"]["n_train"],
        "width": cfg["data"]["d"],
    }
