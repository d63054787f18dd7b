"""What one run of a cell carries between the harness and a traffic
driver: the cell, the seeds, the window length, and the tracing switch
with its host spans."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import time

import numpy as np

from .spec import ROOT, Cell

#: Where a traced run writes its profile (listed in .gitignore; the
#: directory is emptied after the trace is reduced).
TRACE_DIR = ROOT / ".bench_out" / "trace"
#: How much of the window a traced run profiles (see Profiler).
TRACE_SECONDS = 2.0


def derive_seeds(seed: int) -> dict[str, int]:
    """Independent 31-bit seeds for the data, the chains and the traffic,
    from any whole ``--seed`` (negative or past 2**32 included)."""
    words = np.random.SeedSequence(abs(int(seed))).generate_state(3)
    data, program, traffic = (int(w) & 0x7FFFFFFF for w in words)
    return {"data": data, "program": program, "traffic": traffic}


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float  # time.monotonic() when the process started
    config: dict  # the configuration, with rehearsal overrides applied

    @property
    def seeds(self) -> dict[str, int]:
        return derive_seeds(self.seed)

    def rng(self, what: str) -> np.random.Generator:
        return np.random.default_rng([self.seeds["traffic"], sum(map(ord, what))])

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op when not tracing):
        what labels the device's idle gaps."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def profiler(self) -> "Profiler":
        return Profiler(self.trace)


class Profiler:
    """Profiles the start of the measured window in a traced run (a no-op
    otherwise). The chip records about a million device operations per
    second of refresh, more than its trace buffer keeps over a whole
    window, so a run traces only about its first ``TRACE_SECONDS`` (whole
    blocks, for refresh); the ``window`` span marks the traced part."""

    def __init__(self, on: bool):
        self.on = on
        self.active = False
        self._span = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # user annotations only
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("window")
        self._span.__enter__()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False


def now() -> float:
    return time.monotonic()
