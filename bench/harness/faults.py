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
  never move, every leaf of the draws holds the collected state, the
  infos still come from a real block).
* ``half``: half of the batch left out, the mean taken over the rest. For
  refresh, the second half of every round's deltas replaced by the first
  half's mean, in every batched delta of ``repro.kernels.ops``
  (``batched_<family>_delta``), so whichever family a model's operators
  evaluate; for serving, the functional averaged over the first half of
  the snapshot's draws.
* ``altered_draw``: in every leaf of the draws, the first coordinate of
  every fourth draw shifted by a thousandth of that leaf's proposal scale
  where the refresh produces them (the chains themselves go on from their
  true states). The scale is the model module's ``draw_scale(cfg)`` (one
  number, or a dict by the leaves of the collected draws), else
  ``cfg["builder"]["sigma"]``.
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
    import jax
    import jax.numpy as jnp

    def run(self, key, state, num_steps, *, step_keys=None):
        _, samples, infos = orig(self, key, state, num_steps, step_keys=step_keys)
        held = state.theta if self.collect is None else jax.vmap(self.collect)(state.theta)
        stuck = jax.tree.map(lambda h, s: jnp.broadcast_to(h[:, None], s.shape),
                             held, samples)
        return state, stuck, infos

    return run


def _shifted(leaf, scale):
    """``leaf`` (K, T, ...) with the first coordinate of every fourth draw
    moved by a thousandth of ``scale``."""
    return leaf.at[(slice(None), slice(None, None, 4)) + (0,) * (leaf.ndim - 2)].add(
        1e-3 * scale)


def _altered_run(orig, scale):
    import jax

    def run(self, key, state, num_steps, *, step_keys=None):
        new, samples, infos = orig(self, key, state, num_steps, step_keys=step_keys)
        scales = scale if isinstance(scale, dict) else jax.tree.map(lambda _: scale, samples)
        return new, jax.tree.map(_shifted, samples, scales), infos

    return run


def _half_deltas(orig):
    import jax.numpy as jnp

    def delta(*args, **kw):
        l = orig(*args, **kw)  # (K, m)
        h = l.shape[1] // 2
        first = jnp.mean(l[:, :h], axis=1, keepdims=True)
        return l.at[:, h:].set(jnp.broadcast_to(first, l[:, h:].shape))

    return delta


@contextlib.contextmanager
def _half_every_delta(ops):
    """``_half_deltas`` on every ``batched_<family>_delta`` of ``ops``."""
    with contextlib.ExitStack() as stack:
        for name in dir(ops):
            if name.startswith("batched_") and name.endswith("_delta"):
                stack.enter_context(_patch(ops, name, _half_deltas(getattr(ops, name))))
        yield


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

    ref = getattr(model, "reference_values", None)
    serve = kind == "open_loop"
    if fault == "control" and not serve:
        ctx = _bf16_refresh()
    elif fault == "control":
        ctx = _patch(SnapshotEvaluator, "evaluate", _evaluate_with(
            lambda cls, xs, d: ref(cls, xs, d, jnp.bfloat16)))
    elif fault == "frozen":
        ctx = _patch(ChainEnsemble, "run", _frozen_run(ChainEnsemble.run))
    elif fault == "half" and not serve:
        ctx = _half_every_delta(ops)
    elif fault == "half":
        ctx = _patch(SnapshotEvaluator, "evaluate", _evaluate_with(
            lambda cls, xs, d: ref(cls, xs, d[: d.shape[0] // 2])))
    elif fault == "altered_draw":
        scale = (model.draw_scale(cfg) if hasattr(model, "draw_scale")
                 else cfg["builder"]["sigma"])
        ctx = _patch(ChainEnsemble, "run", _altered_run(ChainEnsemble.run, scale))
    elif fault == "altered_answer" and serve:
        ctx = _patch(SnapshotEvaluator, "evaluate", _evaluate_with(
            lambda cls, xs, d: ref(cls, xs, d) + 1e-3))
    else:
        raise ValueError(f"no fault {fault!r} for traffic kind {kind!r}")
    with ctx:
        yield
