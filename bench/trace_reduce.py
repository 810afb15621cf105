"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The trace is read with ``jax.profiler.ProfileData``.  Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
device operation, named by its HLO instruction (``%paged_flash_decode.11 =
... custom-call(...)``; loops and the ops inside them both appear), and
the ``XLA Modules`` line one per program run, named after the jitted
function (``jit_paged_decode_step(<hash>)``).  The host
plane ``/host:CPU`` holds the benchmark's own ``TraceAnnotation`` spans,
all named ``bench.*``, on the same clock.  Every interval is clipped to
the ``bench.slice`` span, the stretch of the window that was traced.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # seconds, trace clock

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SLICE_SPAN = "bench.slice"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Chip:
    index: int
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    spans: List[Event]      # host spans named bench.*
    window: Interval        # the traced slice

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _event(ev) -> Event:
    start = ev.start_ns * 1e-9
    return Event(ev.name, start, start + ev.duration_ns * 1e-9)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips, spans = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [_event(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [_event(e) for e in line.events]
            chips.append(Chip(int(m.group(1)), ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [_event(e) for e in line.events
                          if e.name.startswith("bench.")]
    chips.sort(key=lambda c: c.index)
    return from_parts(chips, spans)


def from_parts(chips: List[Chip], spans: List[Event]) -> Trace:
    sl = [s for s in spans if s.name == SLICE_SPAN]
    if not sl:
        raise ValueError(f"trace has no {SLICE_SPAN} span")
    window = (sl[0].start, sl[0].end)
    return Trace(chips, spans, window)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def clip(events: Sequence[Event], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def busy_s(trace: Trace) -> float:
    """Seconds of the slice in which some operation ran on the device,
    averaged over the chips."""
    if not trace.chips:
        return 0.0
    return sum(length(union(clip(c.ops, trace.window)))
               for c in trace.chips) / len(trace.chips)


# ---------------------------------------------------------------------------
# selections
# ---------------------------------------------------------------------------


def matching(events: Sequence[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def inside(events: Sequence[Event], window: Interval) -> List[Event]:
    """Events that start inside the window (whole runs, not clipped)."""
    lo, hi = window
    return [e for e in events if lo <= e.start < hi]


def per_call_s(trace: Trace, line: str, pattern: str
               ) -> Optional[Tuple[float, int]]:
    """(mean seconds per event, events per chip) of the matching events
    that start in the slice, over all chips; None if there are none."""
    n, total = 0, 0.0
    for c in trace.chips:
        evs = inside(matching(getattr(c, line), pattern), trace.window)
        n += len(evs)
        total += sum(e.end - e.start for e in evs)
    if not n:
        return None
    return total / n, n // max(len(trace.chips), 1)


def within(ops: Sequence[Event], runs: Sequence[Event]) -> List[Event]:
    """The ops that lie inside one of the program runs ``runs``."""
    runs = sorted(runs, key=lambda r: r.start)
    starts = [r.start for r in runs]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.end <= runs[i].end + 1e-9:
            out.append(o)
    return out


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The k device operations that took most time in the slice, in
    seconds per chip, named ``<program>/<op>``."""
    tot: Dict[str, float] = {}
    for c in trace.chips:
        mods = sorted(c.modules, key=lambda m: m.start)
        starts = [m.start for m in mods]
        for o in inside(c.ops, trace.window):
            i = bisect.bisect_right(starts, o.start) - 1
            prog = (mods[i].name.split("(")[0]
                    if i >= 0 and o.start <= mods[i].end else "?")
            key = f"{prog}/{o.name.split(' = ')[0]}"
            tot[key] = tot.get(key, 0.0) + (o.end - o.start)
    n = max(len(trace.chips), 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, s / n] for name, s in ranked]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The k longest stretches of the slice in which chip 0 ran nothing,
    each named by the innermost ``bench.*`` host span at its midpoint
    (``host.other`` where the loop was in none)."""
    if not trace.chips:
        return []
    busy = union(clip(trace.chips[0].ops, trace.window))
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in trace.spans if s.name != SLICE_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (a + b)
        cover = [s for s in spans if s.start <= mid <= s.end]
        name = (min(cover, key=lambda s: s.end - s.start).name
                if cover else "host.other")
        out.append([f"{name} at +{a - lo:.6f}s", b - a])
    return out
