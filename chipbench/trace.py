"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

``load_events`` turns the trace into plain event tuples; everything
after that is arithmetic on those tuples, so the CPU tests check it on
a small recorded trace without a chip.

* busy time -- the union of the intervals in which an operation ran on
  a device (the device plane's ``XLA Ops`` line), clipped to the
  window, averaged over the device planes;
* the window -- the benchmark's own ``bench.window`` host span;
* device ops -- device time summed by op name;
* idle gaps -- the stretches of the window in which no device op ran,
  each named by the innermost ``bench.*`` host span around its
  midpoint (what the host was doing), summed by that name.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def op_name(name: str) -> str:
    """The HLO instruction's name: a TPU trace names each op by its whole
    HLO text (``%fusion.89 = f32[...] fusion(...)``)."""
    head, sep, _ = name.partition(" = ")
    return head.lstrip("%") if sep else name


def load_events(path: str, device_prefix: str = DEVICE_PREFIX
                ) -> List[Event]:
    """The device planes' op events and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    out.append(Event(plane.name, line.name,
                                     op_name(e.name), float(e.start_ns),
                                     float(e.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.append(Event(plane.name, line.name, e.name,
                                         float(e.start_ns),
                                         float(e.duration_ns)))
    return out


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of closed intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over device planes
    devices: int
    device_ops: List[Tuple[str, float]]  # (name, seconds), largest first
    idle_gaps: List[Tuple[str, float]]   # (host span, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def host_timeline(spans: Sequence[Event]
                  ) -> List[Tuple[float, float, str]]:
    """Flatten nested host spans into disjoint segments, each labelled
    with the innermost span that covers it."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    cur = 0.0

    def emit(upto: float) -> None:
        if stack and upto > cur:
            segs.append((cur, upto, stack[-1].name))

    for sp in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1].end_ns <= sp.start_ns:
            emit(stack[-1].end_ns)
            cur = stack.pop().end_ns
        emit(sp.start_ns)
        cur = sp.start_ns
        stack.append(sp)
    while stack:
        emit(stack[-1].end_ns)
        cur = stack.pop().end_ns
    return segs


def window_bounds(events: Sequence[Event]) -> Tuple[float, float]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def reduce_events(events: Sequence[Event], top: int = 10) -> Reduced:
    lo, hi = window_bounds(events)
    planes = sorted({e.plane for e in events
                     if e.plane != HOST_PLANE})
    ops = [e for e in events if e.plane in planes
           and e.end_ns > lo and e.start_ns < hi]
    if not planes or not ops:
        raise ValueError("no device op ran inside the window")
    busy_by_plane = {}
    for p in planes:
        iv = merge(clip([(e.start_ns, e.end_ns) for e in ops
                         if e.plane == p], lo, hi))
        busy_by_plane[p] = iv
    busy_ns = sum(sum(e - s for s, e in iv)
                  for iv in busy_by_plane.values()) / len(planes)

    by_name: Dict[str, float] = {}
    for e in ops:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        by_name[e.name] = by_name.get(e.name, 0.0) + (t - s) * 1e-9
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]

    timeline = host_timeline([e for e in events if e.plane == HOST_PLANE
                              and e.name != WINDOW_SPAN])
    starts = [seg[0] for seg in timeline]
    gaps: Dict[str, float] = {}
    edges = [lo] + [x for iv in busy_by_plane[planes[0]] for x in iv] + [hi]
    for s, t in zip(edges[0::2], edges[1::2]):
        if t <= s:
            continue
        mid = 0.5 * (s + t)
        i = bisect.bisect_right(starts, mid) - 1
        name = (timeline[i][2] if i >= 0 and timeline[i][1] >= mid
                else "outside_spans")
        gaps[name] = gaps.get(name, 0.0) + (t - s) * 1e-9
    idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9,
                   devices=len(planes), device_ops=device_ops,
                   idle_gaps=idle_gaps)
