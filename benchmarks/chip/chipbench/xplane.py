"""Reduce a profiler trace (``.xplane.pb``) to busy time, device time
inside the harness's host spans, and a breakdown.

The harness wraps each call into the program in a
``jax.profiler.TraceAnnotation`` named ``bench.<kind>``.  Those spans are
on the host's clock; the device's operations are on the device's clock,
which the profiler aligns to the host only to within a millisecond or
two.  Every device program the serving path launches ends inside the
call that launched it (each call blocks on its result), so the device
clock is shifted by the offset that puts the most programs inside
``prefill`` and ``decode`` calls.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end) in seconds

SPAN_PREFIX = "bench."
ALIGN_KINDS = ("prefill", "decode")     # calls that block on their result
SEARCH_S = 0.02                         # offsets searched: +- 20 ms
# innermost first: an idle gap is named by the first kind covering it
GAP_ORDER = ("prefill", "decode", "release", "idle", "tick", "window")


@dataclasses.dataclass
class Trace:
    """What the reduction reads from a trace, in seconds."""

    ops: List[List[Tuple[float, float, str]]]   # per chip: (start, dur, op)
    modules: List[Tuple[float, float]]          # chip 0: (start, dur)
    spans: Dict[str, List[Interval]]            # kind -> host intervals


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                         # mean over chips
    device_s: Dict[str, float]            # kind -> chip-0 busy inside it
    shift_s: float                        # device clock shift applied
    device_ops: List[Tuple[str, float]]   # longest ops, "<kind>/<op>"
    idle_gaps: List[Tuple[str, float]]    # longest gaps, named by kind


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops: List[List[Tuple[float, float, str]]] = []
    modules: List[Tuple[float, float]] = []
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip_ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip_ops = [(e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                 op_name(e.name)) for e in line.events]
                elif line.name == "XLA Modules" and not modules:
                    modules = [(e.start_ns * 1e-9, e.duration_ns * 1e-9)
                               for e in line.events]
            ops.append(chip_ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans[e.name[len(SPAN_PREFIX):]].append(
                            (s, s + e.duration_ns * 1e-9))
    return Trace(ops, modules, dict(spans))


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def best_shift(modules: Sequence[Tuple[float, float]],
               calls: Sequence[Interval]) -> float:
    """The device clock shift that puts the most device programs wholly
    inside a call; the middle of the best range of shifts."""
    calls = union(calls)
    starts = [c[0] for c in calls]
    edges = []
    for m0, dur in modules:
        # calls that can hold the program under some shift within range
        i = bisect.bisect_right(starts, m0 + SEARCH_S) - 1
        while i >= 0 and calls[i][1] >= m0 + dur - SEARCH_S:
            lo = max(calls[i][0] - m0, -SEARCH_S)
            hi = min(calls[i][1] - (m0 + dur), SEARCH_S)
            if hi >= lo:
                edges += [(lo, 1), (hi, -1)]
            i -= 1
    if not edges:
        return 0.0
    edges.sort(key=lambda x: (x[0], -x[1]))
    best, best_lo, best_hi, depth = -1, 0.0, 0.0, 0
    for k, (x, d) in enumerate(edges):
        depth += d
        if d > 0 and depth > best:
            best, best_lo = depth, x
            best_hi = next(y for y, dd in edges[k + 1:] if dd < 0)
    return (best_lo + best_hi) / 2


def reduce(trace: Trace, top: int = 10) -> Reduced:
    """Busy and idle time over the ``window`` span, device time inside
    each kind of span, and the longest device ops and idle gaps."""
    (w0, w1), = trace.spans["window"]
    calls = [iv for k in ALIGN_KINDS for iv in trace.spans.get(k, [])]
    shift = best_shift(trace.modules, calls)
    kinds = {k: union(v) for k, v in trace.spans.items()}
    busy, device_s = [], {}
    ops_by = defaultdict(float)
    chip0: List[Interval] = []
    for c, chip_ops in enumerate(trace.ops):
        iv = union(clip([(s + shift, s + shift + d) for s, d, _ in chip_ops],
                        w0, w1))
        busy.append(sum(e - s for s, e in iv))
        if c == 0:
            chip0 = iv
            for s, d, name in chip_ops:
                lo, hi = max(s + shift, w0), min(s + shift + d, w1)
                if hi > lo:
                    ops_by[f"{kind_at(kinds, (lo + hi) / 2)}/{name}"] += \
                        hi - lo
    for k, iv in kinds.items():
        device_s[k] = overlap(chip0, clip(iv, w0, w1))
    gaps = []
    prev = w0
    for s, e in chip0 + [(w1, w1)]:
        if s > prev:
            gaps.append((kind_at(kinds, (prev + s) / 2), s - prev))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    dev_ops = sorted(ops_by.items(), key=lambda o: -o[1])[:top]
    return Reduced(window_s=w1 - w0,
                   busy_s=sum(busy) / max(1, len(busy)),
                   device_s=device_s, shift_s=shift, device_ops=dev_ops,
                   idle_gaps=gaps[:top])


def kind_at(kinds: Dict[str, List[Interval]], t: float) -> str:
    """The innermost span kind covering host time ``t``."""
    for k in GAP_ORDER + tuple(sorted(set(kinds) - set(GAP_ORDER))):
        iv = kinds.get(k, [])
        i = bisect.bisect_right(iv, (t, float("inf"))) - 1
        if i >= 0 and iv[i][0] <= t <= iv[i][1]:
            return k
    return "outside"
