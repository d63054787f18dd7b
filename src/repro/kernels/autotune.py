"""Per-backend Pallas block-size autotuner with a disk-backed winner cache.

The Pallas kernels' tile sizes (``tile_m``, ``tile_t``/``tile_v``, …) were
hard-coded guesses; the right block depends on backend generation and the
shape regime. This module benchmarks a small candidate grid per *kernel
family* the first time a (family, shape-bucket) combination is dispatched,
and caches the winner on disk keyed by ``(backend, family, shape-bucket)``
so every later process start skips straight to the tuned block.

Scope and knobs:

* ``REPRO_AUTOTUNE=1`` forces tuning on, ``REPRO_AUTOTUNE=0`` pins the
  shipped defaults (:data:`DEFAULT_TILES`). Unset/``auto`` tunes only on a
  real TPU backend — interpret-mode timings on CPU say nothing about MXU/
  VMEM behaviour, so CPU runs stay deterministic and fast by default.
* ``REPRO_AUTOTUNE_DIR`` relocates the cache (CI sets it to a workspace
  path and uploads the JSON as a build artifact); the default is
  ``<checkout>/.autotune`` — nothing outside the checkout is read or
  written, and a missing cache only means the race is run again.
* Shapes are bucketed to powers of two: one measurement covers the whole
  regime, and the compiled-kernel cache can't be flooded by ragged shapes.
* Every candidate tile is a multiple of 128: the kernels move per-section
  rows as lane-dense (1, tile) blocks, which a TPU accepts only then (or
  when the tile spans the whole axis). A race in which no candidate runs
  raises with the first failure instead of falling back to defaults that
  would fail the same way.

Consulted by :mod:`repro.kernels.ops` — explicit ``tile_*`` kwargs always
win over the tuner, so call sites keep full control.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import CHECKOUT

ENV_VAR = "REPRO_AUTOTUNE"
DIR_ENV_VAR = "REPRO_AUTOTUNE_DIR"

#: The shipped block sizes — what ``REPRO_AUTOTUNE=0`` pins, and the
#: starting candidate of every grid (so tuning can never do worse than the
#: defaults on the measured workload, up to timer noise).
DEFAULT_TILES: dict[str, dict[str, int]] = {
    "logit_delta": {"tile_n": 512},
    "batched_loglik": {"tile_m": 256},
    "gaussian_ar1": {"tile_m": 256},
    "fused_ce": {"tile_t": 256, "tile_v": 512},
    "batched_fused_ce": {"tile_t": 256, "tile_v": 512},
}

CANDIDATES: dict[str, tuple[dict[str, int], ...]] = {
    "logit_delta": tuple({"tile_n": n} for n in (256, 512, 1024, 2048)),
    "batched_loglik": tuple({"tile_m": m} for m in (128, 256, 512, 1024)),
    "gaussian_ar1": tuple({"tile_m": m} for m in (128, 256, 512, 1024)),
    "fused_ce": tuple(
        {"tile_t": t, "tile_v": v} for t in (128, 256) for v in (256, 512, 1024)
    ),
    "batched_fused_ce": tuple(
        {"tile_t": t, "tile_v": v} for t in (128, 256) for v in (256, 512, 1024)
    ),
}

_memory_cache: dict[str, dict[str, Any]] = {}
_loaded_backends: set[str] = set()


def enabled() -> bool:
    """Tune? ``REPRO_AUTOTUNE`` 1/0 forces; unset tunes on TPU only."""
    env = os.environ.get(ENV_VAR, "auto").lower()
    if env in ("0", "false", "off", "never"):
        return False
    if env in ("1", "true", "on", "always"):
        return True
    return jax.default_backend() == "tpu"


def cache_dir() -> str:
    return os.environ.get(DIR_ENV_VAR) or str(CHECKOUT / ".autotune")


def _cache_path(backend: str) -> str:
    return os.path.join(cache_dir(), f"{backend}.json")


def clear_cache(memory_only: bool = False) -> None:
    """Forget tuned winners (tests; or after a toolchain upgrade)."""
    _memory_cache.clear()
    _loaded_backends.clear()
    if memory_only:
        return
    for backend in ("tpu", "cpu", "gpu"):
        path = _cache_path(backend)
        if os.path.exists(path):
            os.remove(path)


def _load_disk(backend: str) -> None:
    if backend in _loaded_backends:
        return
    _loaded_backends.add(backend)
    path = _cache_path(backend)
    try:
        with open(path) as f:
            _memory_cache.update(json.load(f))
    except (OSError, ValueError):
        pass


def _save_disk(backend: str) -> None:
    path = _cache_path(backend)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        entries = {k: v for k, v in _memory_cache.items()
                   if k.startswith(f"{backend}|")}
        with open(tmp, "w") as f:
            json.dump(entries, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS: in-memory winner still applies this process


def _bucket(n: int) -> int:
    return 1 if n <= 1 else int(2 ** int(np.ceil(np.log2(n))))


def cache_key(family: str, shape: tuple[int, ...], backend: str) -> str:
    bucket = "x".join(str(_bucket(int(d))) for d in shape)
    return f"{backend}|{family}|{bucket}"


def _time_once(fn: Callable[[], Any]) -> float:
    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _synth_inputs(family: str, shape: tuple[int, ...]):
    """Random concrete inputs at the bucketed shape for offline timing."""
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pm1 = lambda *s: jnp.asarray(
        np.where(rng.standard_normal(s) > 0, 1.0, -1.0), jnp.float32
    )
    if family == "logit_delta":
        n, d = shape
        return (f32(n, d), pm1(n), f32(d), f32(d))
    if family == "batched_loglik":
        k, m, d = shape
        return (f32(k, m, d), pm1(k, m), f32(k, d), f32(k, d))
    if family == "gaussian_ar1":
        k, m = shape
        pos = jnp.abs(f32(k)) + 0.01
        return (f32(k, m), f32(k, m), f32(k) * 0.1 + 0.9, pos,
                f32(k) * 0.1 + 0.9, pos)
    if family == "fused_ce":
        t, d, v = shape
        tgt = jnp.asarray(rng.integers(0, v, size=(t,)), jnp.int32)
        return (f32(t, d), f32(v, d), tgt)
    if family == "batched_fused_ce":
        k, t, d, v = shape
        tgt = jnp.asarray(rng.integers(0, v, size=(k, t)), jnp.int32)
        return (f32(k, t, d), f32(v, d), tgt)
    raise KeyError(f"unknown kernel family {family!r}")


def _kernel_fn(family: str) -> Callable:
    # local imports: ops imports this module, kernels are leaf modules
    if family == "logit_delta":
        from .logit_loglik import logit_delta
        return logit_delta
    if family == "batched_loglik":
        from .batched_loglik import batched_logit_delta
        return batched_logit_delta
    if family == "gaussian_ar1":
        from .gaussian_ar1 import batched_gaussian_ar1_delta
        return batched_gaussian_ar1_delta
    if family == "fused_ce":
        from .fused_ce import fused_ce
        return fused_ce
    if family == "batched_fused_ce":
        from .fused_ce import batched_fused_ce
        return batched_fused_ce
    raise KeyError(f"unknown kernel family {family!r}")


def _benchmark(family: str, shape: tuple[int, ...], interpret: bool) -> dict:
    """Race the candidate grid at the bucketed shape; return the entry."""
    bucketed = tuple(_bucket(int(d)) for d in shape)
    args = _synth_inputs(family, bucketed)
    kernel = _kernel_fn(family)
    timings, failures = [], []
    for cand in CANDIDATES[family]:
        try:
            sec = _time_once(lambda: kernel(*args, interpret=interpret, **cand))
        except Exception as e:  # noqa: BLE001 — candidate invalid here: skip it
            failures.append(f"{cand}: {type(e).__name__}: {e}")
            continue
        timings.append((sec, cand))
    if not timings:
        raise RuntimeError(
            f"autotune: no {family} candidate tile compiled at shape "
            f"{bucketed} on {jax.default_backend()}; first failure: {failures[0]}"
        )
    timings.sort(key=lambda tc: tc[0])
    best_sec, best = timings[0]
    return {
        "tiles": dict(best),
        "us": best_sec * 1e6,
        "candidates": len(timings),
        "default_us": next(
            (s * 1e6 for s, c in timings if c == DEFAULT_TILES[family]), None
        ),
    }


def tiles_for(family: str, shape: tuple[int, ...]) -> dict[str, int]:
    """The block sizes to dispatch ``family`` with at ``shape``.

    Returns the shipped defaults when tuning is disabled; otherwise the
    cached winner, measuring the candidate grid on first use (concrete
    synthesized inputs — safe to call during tracing, shapes are static).
    """
    if family not in DEFAULT_TILES:
        raise KeyError(f"unknown kernel family {family!r}")
    if not enabled():
        return dict(DEFAULT_TILES[family])
    backend = jax.default_backend()
    key = cache_key(family, shape, backend)
    _load_disk(backend)
    entry = _memory_cache.get(key)
    if entry is None:
        entry = _benchmark(family, shape, interpret=backend != "tpu")
        _memory_cache[key] = entry
        _save_disk(backend)
    return dict(entry["tiles"])


def warm(families: tuple[str, ...] | None = None, fast: bool = True) -> dict:
    """Tune representative shape buckets for each family (the CI artifact
    producer: ``python -m repro.kernels.autotune``)."""
    shapes: dict[str, list[tuple[int, ...]]] = {
        "logit_delta": [(4096, 64)],
        "batched_loglik": [(8, 256, 64)],
        "gaussian_ar1": [(8, 1024)],
        "fused_ce": [(256, 256, 8192)],
        "batched_fused_ce": [(4, 256, 256, 8192)],
    }
    if not fast:
        shapes["logit_delta"].append((65536, 64))
        shapes["batched_loglik"].append((64, 512, 64))
        shapes["gaussian_ar1"].append((64, 4096))
    out = {}
    for family in families or tuple(shapes):
        for shape in shapes[family]:
            out[cache_key(family, shape, jax.default_backend())] = tiles_for(
                family, shape
            )
    return out


if __name__ == "__main__":
    os.environ.setdefault(ENV_VAR, "1")
    for k, tiles in warm().items():
        print(f"{k}: {tiles}")
    print(f"cache: {_cache_path(jax.default_backend())}")
