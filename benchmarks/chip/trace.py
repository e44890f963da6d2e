"""From a profiler trace to device busy time, idle gaps and time inside the
benchmark's own spans.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two lists: the device's operations (the ``XLA Ops`` line of each TPU plane)
and the benchmark's host spans (``TraceAnnotation`` names starting with
``bench.``), both as ``[name, start_ns, end_ns]`` on the profiler's clock.
Everything after ``load`` works on those lists alone, so a test can drive
it with a small recorded trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def load(log_dir: str) -> dict:
    """``{"device_ops": {plane: [[name, t0, t1], ...]}, "spans": [...]}``
    from the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: Dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            evs = [[_op_name(e.name), e.start_ns, e.start_ns + e.duration_ns]
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if evs:
                ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns,
                                      e.start_ns + e.duration_ns])
    return {"device_ops": ops, "spans": spans}


def _op_name(name: str) -> str:
    """An operation's HLO instruction name, without its text."""
    return name.split(" = ", 1)[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def spans_named(trace: dict, name: str) -> List[Interval]:
    return union((a, b) for n, a, b in trace["spans"] if n == name)


def window(trace: dict) -> Interval:
    """The traced window: the one ``bench.window`` span."""
    w = spans_named(trace, SPAN_PREFIX + "window")
    if len(w) != 1:
        raise RuntimeError(f"expected one {SPAN_PREFIX}window span, "
                           f"found {len(w)}")
    return w[0]


def busy(trace: dict) -> Dict[str, List[Interval]]:
    """Per device plane: merged intervals in which an operation ran, inside
    the window."""
    lo, hi = window(trace)
    return {plane: clip(union((a, b) for _, a, b in evs), lo, hi)
            for plane, evs in trace["device_ops"].items()}


def reduce(trace: dict) -> dict:
    """Window length, device busy seconds (mean over planes with work) and
    the busy seconds inside each span name, all in seconds."""
    lo, hi = window(trace)
    per_plane = busy(trace)
    planes = [iv for iv in per_plane.values() if iv]
    names = {n for n, _, _ in trace["spans"]} - {SPAN_PREFIX + "window"}
    inside = {}
    for name in sorted(names):
        sp = clip(spans_named(trace, name), lo, hi)
        inside[name] = (sum(length(intersect(iv, sp)) for iv in planes)
                        / max(len(planes), 1) * 1e-9)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(length(iv) for iv in planes) / max(len(planes), 1)
                   * 1e-9),
        "span_s": {n: length(clip(spans_named(trace, n), lo, hi)) * 1e-9
                   for n in sorted(names)},
        "busy_in_span_s": inside,
    }


def top_ops(trace: dict, k: int = 10) -> List[list]:
    """The k device operations with the most time in the window, summed by
    name (mean over planes)."""
    lo, hi = window(trace)
    tot: Dict[str, float] = defaultdict(float)
    planes = [p for p, evs in trace["device_ops"].items() if evs]
    for p in planes:
        for name, a, b in trace["device_ops"][p]:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                tot[name] += (b - a) * 1e-9 / len(planes)
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: dict, k: int = 10) -> List[list]:
    """The k longest stretches of the window with no device operation, each
    named by the innermost benchmark span that covers its middle (or
    ``host`` where none does)."""
    lo, hi = window(trace)
    per_plane = busy(trace)
    planes = [iv for iv in per_plane.values() if iv] or [[]]
    gaps = []
    for iv in planes:
        t = lo
        for a, b in iv + [(hi, hi)]:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
    spans = [(a, b, n) for n, a, b in trace["spans"]
             if n != SPAN_PREFIX + "window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (a + b)
        cover = [(y - x, n) for x, y, n in spans if x <= mid <= y]
        name = min(cover)[1] if cover else "host"
        out.append([name, (b - a) * 1e-9])
    return out
