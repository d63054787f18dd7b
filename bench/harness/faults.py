"""The control and the planted faults that the comparison must catch.

Each entry patches the timed path for the length of one run and is
undone afterwards. None of them is used by a benchmark run: they serve
``bench/control.py`` (the readings on the chip that set the limits) and
the tests under ``bench/tests``.

* ``control``: the nearest precision below the configuration's float32.
  For refresh the program's own bfloat16 data path (``REPRO_PRECISION=bf16``
  at JAX's default matmul precision: the TPU compiler refuses bfloat16
  operands under ``highest``, and at the default the kernel's float32
  products are bit for bit this path's); for serving, the reference's
  functionals computed with bfloat16 operands in the evaluator's place.
* ``frozen``: a refresh that returns its state unchanged (the chains
  never move, the infos still come from a real block).
* ``half``: half of the batch left out, the mean taken over the rest. For
  refresh, the second half of every round's deltas replaced by the first
  half's mean; for serving, the functional averaged over the first half
  of the snapshot's draws.
* ``altered_draw``: one coordinate of every fourth draw shifted by a
  thousandth of the proposal scale where the refresh produces them (the
  chains themselves go on from their true states).
* ``altered_answer``: every served value shifted by 1e-3.
"""
from __future__ import annotations

import contextlib
import os

import numpy as np

@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def _bf16_refresh():
    import jax

    old_env = os.environ.get("REPRO_PRECISION")
    old_precision = jax.config.jax_default_matmul_precision
    os.environ["REPRO_PRECISION"] = "bf16"
    jax.config.update("jax_default_matmul_precision", "default")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", old_precision)
        if old_env is None:
            os.environ.pop("REPRO_PRECISION", None)
        else:
            os.environ["REPRO_PRECISION"] = old_env


def _frozen_run(orig):
    import jax.numpy as jnp

    def run(self, key, state, num_steps, *, step_keys=None):
        _, samples, infos = orig(self, key, state, num_steps, step_keys=step_keys)
        stuck = jnp.broadcast_to(state.theta[:, None], samples.shape)
        return state, stuck, infos

    return run


def _altered_run(orig, sigma):
    def run(self, key, state, num_steps, *, step_keys=None):
        new, samples, infos = orig(self, key, state, num_steps, step_keys=step_keys)
        return new, samples.at[:, ::4, 0].add(1e-3 * sigma), infos

    return run


def _half_deltas(orig):
    import jax.numpy as jnp

    def delta(xg, yg, w_cur, w_prop, **kw):
        l = orig(xg, yg, w_cur, w_prop, **kw)
        h = l.shape[1] // 2
        first = jnp.mean(l[:, :h], axis=1, keepdims=True)
        return l.at[:, h:].set(jnp.broadcast_to(first, l[:, h:].shape))

    return delta


def _evaluate_with(values_fn):
    """A ``SnapshotEvaluator.evaluate`` that answers with ``values_fn``."""

    def evaluate(self, spec, snap, xs, span_sink=None):
        draws = np.asarray(snap.draws).reshape(-1, np.asarray(snap.draws).shape[-1])
        return values_fn(spec.name, np.atleast_2d(np.asarray(xs)), draws)

    return evaluate


@contextlib.contextmanager
def planted(fault: str | None, model, cfg: dict, kind: str):
    """Break the timed path of a ``kind`` ('refresh' | 'open_loop') run;
    ``model`` is the configuration's reference module."""
    if fault is None:
        yield
        return
    import jax.numpy as jnp

    from repro.core.ensemble import ChainEnsemble
    from repro.kernels import ops
    from repro.serving.resident import SnapshotEvaluator

    ref = model.reference_values
    serve = kind == "open_loop"
    if fault == "control" and not serve:
        ctx = _bf16_refresh()
    elif fault == "control":
        ctx = _patch(SnapshotEvaluator, "evaluate", _evaluate_with(
            lambda cls, xs, d: ref(cls, xs, d, jnp.bfloat16)))
    elif fault == "frozen":
        ctx = _patch(ChainEnsemble, "run", _frozen_run(ChainEnsemble.run))
    elif fault == "half" and not serve:
        ctx = _patch(ops, "batched_logit_delta", _half_deltas(ops.batched_logit_delta))
    elif fault == "half":
        ctx = _patch(SnapshotEvaluator, "evaluate", _evaluate_with(
            lambda cls, xs, d: ref(cls, xs, d[: d.shape[0] // 2])))
    elif fault == "altered_draw":
        ctx = _patch(ChainEnsemble, "run",
                     _altered_run(ChainEnsemble.run, cfg["builder"]["sigma"]))
    elif fault == "altered_answer" and serve:
        ctx = _patch(SnapshotEvaluator, "evaluate", _evaluate_with(
            lambda cls, xs, d: ref(cls, xs, d) + 1e-3))
    else:
        raise ValueError(f"no fault {fault!r} for traffic kind {kind!r}")
    with ctx:
        yield
