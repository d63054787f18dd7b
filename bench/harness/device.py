"""The device the run is on, as JAX reports it."""
from __future__ import annotations

import jax


def describe(chips: int) -> dict:
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(chips: int = 1) -> int | None:
    """``peak_bytes_in_use`` on the fullest of the first ``chips`` devices
    (None where the backend does not report it)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
