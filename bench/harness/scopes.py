"""Reduce a profiler trace by the program's own marks: the named scopes its
compiled operations carry, and the ``repro.*`` annotations its spans open
(``repro.obs.trace.program_span``).

* An operation's scope path is its ``op_name`` metadata, for example
  ``jit(run_all)/while/body/seq_test/vmap()/div``; a fusion carries the
  op_name of its root instruction. The operation is *in* scope ``s`` when
  ``s`` is one of the path's components other than the last, which names
  the primitive (the primitive ``gather`` is not the scope ``gather``); a
  ``vmap(s)`` component counts as ``s``.
* The op_name is read from the device op's event stats (``tf_op`` or
  ``op_name``) where the trace carries them, else from ``op_names``: a map
  from ``(module, instruction)`` to op_name, read off the compiled HLO text
  with :func:`op_names_from_hlo`. An operation's module is its
  ``hlo_module`` stat, else the event of the plane's ``XLA Modules`` line
  that covers its start.
* Scope time is the summed duration of a scope's operations inside the
  traced window, averaged over devices. Control-flow operations (a
  ``while`` inside a scope, say) are left out: their events span the
  operations they run, which the trace lists on their own. Idle time inside an annotation is
  the part of its extent, clipped to the window, in which no operation ran
  on the first device (as the idle gaps of ``trace.reduce_trace``).

The functions take the same plain objects as ``trace.py``, so a test can
feed a synthetic trace.
"""
from __future__ import annotations

import bisect
import collections
import functools
import re
from typing import Iterable, Sequence

from .trace import (OPS_LINE, WINDOW_SPAN, _is_container, clip, device_planes,
                    host_spans, op_name, union)

MODULES_LINE = "XLA Modules"
#: Event stats that carry an operation's op_name metadata.
OP_NAME_STATS = ("tf_op", "op_name")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*op_name=\"([^\"]*)\"")


def module_name(name: str) -> str:
    """``jit_run_all(1234)`` and ``jit_run_all`` name the same module."""
    return name.split("(", 1)[0].strip()


def op_names_from_hlo(texts: Iterable[str]) -> dict[tuple[str, str], str]:
    """``(module, instruction) -> op_name`` from compiled HLO texts
    (``compiled.as_text()``): the entry and fused computations alike, so a
    fusion instruction maps to its own (its root's) op_name."""
    out = {}
    for text in texts:
        head = re.search(r"^HloModule\s+([\w.\-]+)", text, re.M)
        module = module_name(head.group(1)) if head else ""
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                out[(module, m.group(1))] = m.group(2)
    return out


@functools.lru_cache(maxsize=None)
def scopes_of(path: str) -> frozenset[str]:
    """The named scopes an op_name puts its operation in."""
    parts = path.split("/")[:-1]
    return frozenset(p for part in parts for p in re.split(r"[()]", part) if p)


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (AttributeError, TypeError):
        return {}


def device_ops(plane, op_names: dict | None = None) -> list[tuple]:
    """``(start_s, end_s, op_name, source, container)`` of every device
    operation on ``plane``; ``source`` says where the op_name came from
    (``stats``, ``hlo``), and both are None where neither knows it;
    ``container`` marks control flow (``trace.CONTAINERS``)."""
    modules = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            modules = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                              module_name(e.name)) for e in line.events)
    starts = [m[0] for m in modules]
    out = []
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        for e in line.events:
            start = e.start_ns * 1e-9
            stats = _stats(e)
            path = next((str(stats[k]) for k in OP_NAME_STATS if stats.get(k)), None)
            source = "stats" if path else None
            if path is None and op_names:
                module = stats.get("hlo_module")
                if module is None and modules:
                    i = bisect.bisect_right(starts, start) - 1
                    if i >= 0 and modules[i][1] >= start:
                        module = modules[i][2]
                path = op_names.get((module_name(str(module or "")), op_name(e.name)))
                source = "hlo" if path else None
            out.append((start, start + e.duration_ns * 1e-9, path, source,
                        _is_container(op_name(e.name))))
    return out


def _window(planes, ops_by_dev) -> tuple[float, float]:
    spans = host_spans(planes, [WINDOW_SPAN])
    if spans.get(WINDOW_SPAN):
        return spans[WINDOW_SPAN][0]
    everything = [op[:2] for ops in ops_by_dev for op in ops]
    return min(a for a, _ in everything), max(b for _, b in everything)


class _Busy:
    """Busy time of disjoint sorted intervals up to any instant, by
    bisection (a traced window holds about a million device operations)."""

    def __init__(self, busy: list[tuple[float, float]]):
        self.starts = [a for a, _ in busy]
        self.ends = [b for _, b in busy]
        self.before = [0.0]
        for a, b in busy:
            self.before.append(self.before[-1] + (b - a))

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        return self.before[i] + min(t, self.ends[i]) - self.starts[i]

    def idle(self, a: float, b: float) -> float:
        return (b - a) - (self.upto(b) - self.upto(a))


def reduce_program(planes, scopes: Sequence[str] = (), spans: Sequence[str] = (),
                   op_names: dict | None = None) -> dict:
    """What the program's marks say about the traced window.

    Returns ``scope_s`` (scope -> device seconds), ``idle_in_span_s``
    (annotation -> device idle seconds inside it), ``span_count``
    (annotation -> how many start inside the window), ``first_op_delay_s``
    (annotation -> scope -> for each such annotation, seconds from its
    start to the start of the first operation in the scope at or after
    it, None where none follows inside the window) and ``op_names_from``
    (how many device operations got their op_name from the trace's stats,
    from ``op_names``, or from neither).
    """
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    per_dev = [device_ops(p, op_names) for p in devs]
    lo, hi = _window(planes, per_dev)
    host = host_spans(planes, spans)

    scope_s = {s: 0.0 for s in scopes}
    scope_starts: dict[str, list[float]] = {s: [] for s in scopes}
    sources: dict[str, int] = collections.Counter({"stats": 0, "hlo": 0, "none": 0})
    for i, ops in enumerate(per_dev):
        for a, b, path, source, container in ops:
            sources[source or "none"] += 1
            if path is None or container:
                continue
            a_in, b_in = max(a, lo), min(b, hi)
            inside = scopes_of(path)
            for s in scopes:
                if s in inside:
                    if b_in > a_in:
                        scope_s[s] += (b_in - a_in) / len(per_dev)
                    if i == 0 and lo <= a <= hi:
                        scope_starts[s].append(a)
    for s in scopes:
        scope_starts[s].sort()

    busy = _Busy(union(clip([op[:2] for op in per_dev[0]], lo, hi)))
    idle_in_span_s, span_count, delays = {}, {}, {}
    for name in spans:
        found = sorted(host.get(name, ()))
        idle_in_span_s[name] = sum(busy.idle(a, b) for a, b in union(clip(found, lo, hi)))
        opened = [a for a, _ in found if lo <= a <= hi]
        span_count[name] = len(opened)
        delays[name] = {}
        for s in scopes:
            starts = scope_starts[s]
            row = []
            for a in opened:
                j = bisect.bisect_left(starts, a)
                row.append(starts[j] - a if j < len(starts) else None)
            delays[name][s] = row
    return {
        "scope_s": scope_s,
        "idle_in_span_s": idle_in_span_s,
        "span_count": span_count,
        "first_op_delay_s": delays,
        "op_names_from": dict(sources),
    }


def declared(readers) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """The scopes and annotations a set of metric readers declare
    (``SCOPES``, ``SPANS``), each once, in first-seen order."""
    readers = list(readers)
    scopes = dict.fromkeys(s for r in readers for s in getattr(r, "SCOPES", ()))
    spans = dict.fromkeys(s for r in readers for s in getattr(r, "SPANS", ()))
    return tuple(scopes), tuple(spans)
