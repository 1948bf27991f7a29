"""Reduction of a profiler trace (``.xplane.pb``) to what the benchmark
reports: the device's busy and idle time over the measured window, the
device operations that took most of it, and the longest idle gaps,
each named by the benchmark span the host had open at the time.

The window is the host span ``bench.window`` that the benchmark opens
around its measured loop (``jax.profiler.TraceAnnotation``); the host's
other ``bench.*`` spans name the gaps. Device time is the union of the
intervals of the operations on each device's ``XLA Ops`` line, clipped
to the window and averaged over the devices that ran any.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def find_trace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def op_name(event_name: str) -> str:
    """A device op's short name: its HLO instruction, without the
    ``= shape op(...)`` text the TPU trace appends (``fusion.342``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def host_spans(planes) -> List[Tuple[str, float, float]]:
    """Every ``bench.*`` span on the host's threads."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(ev for ev in _events(line)
                       if ev[0].startswith(SPAN_PREFIX))
    return out


def device_ops(planes) -> Dict[str, List[Tuple[str, float, float]]]:
    """Operations per device plane, from its ``XLA Ops`` line."""
    out = {}
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = [ev for line in plane.lines if line.name == OPS_LINE
               for ev in _events(line)]
        if ops:
            out[plane.name] = ops
    return out


def merge(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Union of ``intervals`` clipped to [lo, hi], sorted."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] around the merged ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def open_span(spans, t: float) -> str:
    """Name of the innermost benchmark span open at ``t``, without the
    prefix ("window" where only the window is); "none" outside all."""
    best: Optional[Tuple[float, float, str]] = None
    for name, s, e in spans:
        if s <= t < e and (best is None or (s, -e) > best[:2]):
            best = (s, -e, name)
    return best[2][len(SPAN_PREFIX):] if best else "none"


def reduce(planes, top: int = 10) -> dict:
    """``{"window_s", "busy_s", "device_ops", "idle_gaps"}`` of a trace
    (``ProfileData.planes``); seconds throughout. Raises where the
    trace holds no window span or no device operation."""
    planes = list(planes)
    spans = host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    per_device = device_ops(planes)
    if not per_device:
        raise ValueError("trace holds no device operation")
    busy_ns, op_ns = [], collections.Counter()
    all_gaps = []
    for ops in per_device.values():
        busy = merge([(s, e) for _, s, e in ops], lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[op_name(name)] += d
        all_gaps.extend(gaps(busy, lo, hi))
    n = len(per_device)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "device_ops": [[name, ns / n / 1e9]
                       for name, ns in op_ns.most_common(top)],
        "idle_gaps": [[open_span(spans, (s + e) / 2), (e - s) / 1e9]
                      for s, e in longest],
    }


def reduce_file(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes, top)
