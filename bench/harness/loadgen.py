"""The open-loop load generator: it sleeps until each request is due and
submits it, and does nothing else.

The whole schedule (due offsets and request payloads) is made from the
seed with numpy before the window opens, so the generator thread makes no
JAX call and no device round trip. It records the time each submit
actually happened, so its lag behind the schedule can be reported.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Sequence

import numpy as np


def poisson_schedule(rng: np.random.Generator, rate_per_s: float,
                     seconds: float) -> np.ndarray:
    """Due offsets of ``round(rate * seconds)`` arrivals of a Poisson
    process over ``[0, seconds)``.

    Every seed gets the same multiset of gaps (the exponential
    distribution's quantiles at the midpoints of ``n`` equal slices,
    scaled to span the window) in its own order, so seeds differ in order
    and not in the amount of work or its burstiness."""
    n = int(round(rate_per_s * seconds))
    if n < 1:
        raise ValueError(f"a rate of {rate_per_s}/s gives no request in {seconds}s")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps / gaps.sum() * seconds
    rng.shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


class OpenLoop:
    """Submit ``payloads[i]`` at ``t0 + due[i]`` on a thread of its own.

    ``submit(payload)`` must not block on the server; the queue's
    ``submit`` only appends. ``span`` (optional) opens a host span named
    ``generator`` around each submit for the traced run.
    """

    def __init__(self, due: Sequence[float], payloads: Sequence[Any],
                 submit: Callable[[Any], Any],
                 span: Callable[[str], Any] | None = None):
        if len(due) != len(payloads):
            raise ValueError("one due time per payload")
        self.due = np.asarray(due, np.float64)
        self.payloads = list(payloads)
        self.submit = submit
        self.span = span or (lambda name: contextlib.nullcontext())
        self.t0: float | None = None
        self.submitted_at = np.full(len(self.payloads), np.nan)
        self.handles: list[Any] = [None] * len(self.payloads)
        self._thread: threading.Thread | None = None
        self.error: BaseException | None = None

    def start(self, t0: float) -> None:
        self.t0 = t0
        self._thread = threading.Thread(target=self._run, name="open-loop",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        try:
            for i, (offset, payload) in enumerate(zip(self.due, self.payloads)):
                wait = self.t0 + offset - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                with self.span("generator"):
                    self.submitted_at[i] = time.monotonic()
                    self.handles[i] = self.submit(payload)
        except BaseException as e:  # noqa: BLE001 — reported by join()
            self.error = e

    def join(self, timeout_s: float) -> None:
        self._thread.join(timeout=timeout_s)
        if self._thread.is_alive():
            raise RuntimeError("the load generator did not finish")
        if self.error is not None:
            raise RuntimeError("the load generator failed") from self.error

    def due_abs(self) -> np.ndarray:
        return self.t0 + self.due

    def lag_s(self) -> np.ndarray:
        """Actual submit time minus due time, per request."""
        return self.submitted_at - self.due_abs()
