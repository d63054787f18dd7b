"""Reduce a JAX profiler trace (``.xplane.pb``) to the device's busy time,
its idle gaps and the time of named kernels.

* Busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to the
  traced window and averaged over the devices used.
* Each idle gap is labelled by the host span that the benchmark itself
  opened around it (``jax.profiler.TraceAnnotation``): the span covering
  the gap's midpoint, the first of ``labels`` in priority order.
* Kernel time is the summed duration of the device operations whose HLO
  instruction name contains one of the kernel's patterns.
* The ranking of device ops leaves out control-flow ops (``while`` and
  the like), whose events span the ops they run.

The functions take plain objects with ``name``/``lines``/``events`` and
``start_ns``/``duration_ns``, as ``jax.profiler.ProfileData`` gives them,
so a test can feed a synthetic trace.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Iterable, Sequence

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
#: Control-flow ops whose events span the ops they run: busy, but not
#: work of their own, so they are left out of the ranking of device ops.
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op (the trace gives the whole
    instruction text: ``%fusion.3 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _is_container(name: str) -> bool:
    return name.split(".", 1)[0] in CONTAINERS


def load_planes(trace_dir: str):
    """The planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    newest = max(files, key=os.path.getmtime)
    return list(ProfileData.from_file(newest).planes)


def device_planes(planes) -> list:
    return [p for p in planes if p.name.startswith("/device:")
            and any(line.name == OPS_LINE for line in p.lines)]


def _ops(plane) -> list[tuple[str, float, float]]:
    """(name, start_s, end_s) of every device operation on ``plane``."""
    out = []
    for line in plane.lines:
        if line.name == OPS_LINE:
            for e in line.events:
                start = e.start_ns * 1e-9
                out.append((e.name, start, start + e.duration_ns * 1e-9))
    return out


def host_spans(planes, names: Iterable[str]) -> dict[str, list[tuple[float, float]]]:
    """(start_s, end_s) of every host event named in ``names``."""
    wanted = set(names)
    out: dict[str, list] = collections.defaultdict(list)
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name in wanted:
                    start = e.start_ns * 1e-9
                    out[e.name].append((start, start + e.duration_ns * 1e-9))
    return out


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _label(t: float, spans: dict, labels: Sequence[str]) -> str:
    for name in labels:
        for a, b in spans.get(name, ()):
            if a <= t <= b:
                return name
    return "other"


def reduce_trace(planes, labels: Sequence[str] = (),
                 kernels: dict[str, Sequence[str]] | None = None,
                 top: int = 10) -> dict:
    """Busy and idle time over the traced window, the top device ops, the
    longest idle gaps by label, and the time of each named kernel.

    The window is the host span named ``window``; without one, the span
    of all device operations. Returns ``busy_s`` (mean over devices),
    ``window_s``, ``devices``, ``device_ops`` and ``idle_gaps`` (lists of
    ``[name, seconds]``, at most ``top`` each), and ``kernel_s``.
    """
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    per_dev = [_ops(p) for p in devs]
    spans = host_spans(planes, [WINDOW_SPAN, *labels])
    if spans.get(WINDOW_SPAN):
        lo, hi = spans[WINDOW_SPAN][0]
    else:
        everything = [(a, b) for ops in per_dev for _, a, b in ops]
        lo, hi = min(a for a, _ in everything), max(b for _, b in everything)
    window_s = hi - lo

    busy = []
    op_time: dict[str, float] = collections.Counter()
    kernel_s = {k: 0.0 for k in (kernels or {})}
    gaps = []
    for i, ops in enumerate(per_dev):
        inside = clip([(a, b) for _, a, b in ops], lo, hi)
        merged = union(inside)
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            short = op_name(name)
            if not _is_container(short):
                op_time[short] += b - a
            for kernel, patterns in (kernels or {}).items():
                if any(p in short for p in patterns):
                    kernel_s[kernel] += (b - a) / len(per_dev)
        if i == 0:  # idle gaps are read on the first device
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((_label(0.5 * (a + b), spans, labels), b - a))
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "devices": len(devs),
        "device_ops": [[n, s / len(devs)] for n, s in top_ops],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
        "kernel_s": kernel_s,
    }
