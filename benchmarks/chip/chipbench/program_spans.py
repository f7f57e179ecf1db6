"""The program's own host spans in a profiler trace.

With ``repro.obs.spans.enable(jax.profiler.TraceAnnotation)`` the served
path records ``repro.*`` spans on the same host plane and clock as the
harness's ``bench.*`` spans (``xplane``).  This reduction gives, per span
name inside the window, the count, total and self seconds (self: the
span's time less the ``repro.*`` spans nested in it); the time of each
name's spans nested in another name's; and each idle gap of the device
put down to the innermost program span the host was in.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from chipbench import xplane

PREFIX = "repro."
NONE = "none"          # idle time no program span covers

Event = Tuple[str, float, float, str]   # (name, start, end, thread)


@dataclasses.dataclass
class SpanStat:
    count: int
    total_s: float
    self_s: float


@dataclasses.dataclass
class ProgramSpans:
    spans: Dict[str, SpanStat]            # full span name -> stat
    nested_s: Dict[Tuple[str, str], float]   # (parent, child) -> seconds
    idle_gaps: List[Tuple[str, float]]    # "<kind>/<span>", longest first
    idle_by_span: Dict[str, float]        # innermost span -> idle seconds


def load(trace_dir: str) -> List[Event]:
    """The ``repro.*`` events of the newest ``.xplane.pb`` under
    ``trace_dir``, in seconds, each with the host thread it ran on."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}"
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    out.append((e.name, s, s + e.duration_ns * 1e-9, thread))
    return out


def parents(events: Sequence[Event]) -> List[int]:
    """For each event, the index of the event it is nested in on its
    thread (-1 at the top).  Spans on one thread nest properly."""
    parent = [-1] * len(events)
    by_thread: Dict[str, List[int]] = defaultdict(list)
    for i, ev in enumerate(events):
        by_thread[ev[3]].append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (events[i][1], -events[i][2]))
        stack: List[int] = []
        for i in idx:
            while stack and events[stack[-1]][2] < events[i][2]:
                stack.pop()
            parent[i] = stack[-1] if stack else -1
            stack.append(i)
    return parent


def stats(events: Sequence[Event], window: xplane.Interval
          ) -> Tuple[Dict[str, SpanStat], Dict[Tuple[str, str], float]]:
    """Per span name, the spans wholly inside ``window``: count, total
    and self seconds; and per (parent, child) name the seconds of child
    spans nested directly in such parents."""
    w0, w1 = window
    parent = parents(events)
    child_s = [0.0] * len(events)
    nested: Dict[Tuple[str, str], float] = defaultdict(float)
    for i, p in enumerate(parent):
        if p >= 0:
            dur = events[i][2] - events[i][1]
            child_s[p] += dur
            if w0 <= events[p][1] and events[p][2] <= w1:
                nested[(events[p][0], events[i][0])] += dur
    out: Dict[str, SpanStat] = {}
    for i, (name, s, e, _) in enumerate(events):
        if w0 <= s and e <= w1:
            st = out.setdefault(name, SpanStat(0, 0.0, 0.0))
            st.count += 1
            st.total_s += e - s
            st.self_s += e - s - child_s[i]
    return out, dict(nested)


def innermost(events: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """Host time cut into disjoint pieces, each named by the innermost
    span covering it: the one that started last (on a tie, the shorter),
    so a collection on another thread names the time it held the
    interpreter."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], events[i][2]))
    pts = sorted({t for ev in events for t in ev[1:3]})
    heap: List[Tuple[float, float, int]] = []
    out: List[Tuple[float, float, str]] = []
    k = 0
    for a, b in zip(pts, pts[1:]):
        while k < len(order) and events[order[k]][1] <= a:
            i = order[k]
            heapq.heappush(heap, (-events[i][1], events[i][2], i))
            k += 1
        while heap and events[heap[0][2]][2] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = events[heap[0][2]][0]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def short(name: str) -> str:
    return name[len(PREFIX):] if name.startswith(PREFIX) else name


def idle_gaps(trace: xplane.Trace, shift: float) -> List[xplane.Interval]:
    """The window's gaps between chip 0's operations, as ``xplane.reduce``
    finds them under the device clock shift ``shift``."""
    (w0, w1), = trace.spans["window"]
    ops = trace.ops[0] if trace.ops else []
    busy = xplane.union(xplane.clip(
        [(s + shift, s + shift + d) for s, d, _ in ops], w0, w1))
    gaps, prev = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def reduce(trace: xplane.Trace, reduced: xplane.Reduced,
           events: Sequence[Event], top: int = 10) -> ProgramSpans:
    """The program spans of ``trace``'s window, and its idle gaps: each
    named ``<bench kind>/<innermost program span>`` at its middle (the
    kind alone where no program span covers it), and their seconds
    summed per innermost program span over the whole of each gap."""
    (w0, w1), = trace.spans["window"]
    span_stats, nested = stats(events, (w0, w1))
    pieces = innermost(events)
    starts = [p[0] for p in pieces]
    kinds = {k: xplane.union(v) for k, v in trace.spans.items()}
    named = []
    by_span: Dict[str, float] = defaultdict(float)
    for a, b in idle_gaps(trace, reduced.shift_s):
        mid = (a + b) / 2
        kind = xplane.kind_at(kinds, mid)
        inner = covering(pieces, starts, a, b)
        at_mid = [name for lo, hi, name in inner if lo <= mid <= hi]
        named.append((f"{kind}/{short(at_mid[0])}" if at_mid else kind,
                      b - a))
        for lo, hi, name in inner:
            by_span[short(name)] += hi - lo
        by_span[NONE] += (b - a) - sum(hi - lo for lo, hi, _ in inner)
    named.sort(key=lambda g: -g[1])
    return ProgramSpans(span_stats, nested, named[:top], dict(by_span))


def covering(pieces: Sequence[Tuple[float, float, str]],
             starts: Sequence[float], a: float, b: float
             ) -> List[Tuple[float, float, str]]:
    """The pieces of ``innermost`` (starting at ``starts``) clipped to
    ``[a, b]``."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    out = []
    while i < len(pieces) and pieces[i][0] < b:
        lo, hi = max(pieces[i][0], a), min(pieces[i][1], b)
        if hi > lo:
            out.append((lo, hi, pieces[i][2]))
        i += 1
    return out
