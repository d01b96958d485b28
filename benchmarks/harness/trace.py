"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, time per kind of operation, idle gaps
labelled by what the host was doing, and the split by step or tick.

Read with ``jax.profiler.ProfileData`` alone.  What a TPU trace looks like
(JAX 0.9.0, libtpu 0.0.34, seen on a v5e): one plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per executed program),
``XLA Ops`` (one per HLO instruction, named by its whole HLO text, e.g.
``%fusion.83 = s32[264]{...} fusion(...)``) and ``Async XLA Ops`` (copies
in flight, which overlap the others and are not counted as busy); the
host plane ``/host:CPU`` carries ``TraceAnnotation`` spans on the same
clock.  A Pallas kernel is an op whose text has
``custom_call_target="tpu_custom_call"``; a collective has
``all-reduce``, ``all-gather``, ``reduce-scatter``, ``all-to-all`` or
``collective-permute`` as its opcode.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]            # seconds on the trace's clock

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"        # the benchmark's own spans, kept without it
PROGRAM_PREFIX = "pt:"        # the program's phases, kept with it
WINDOW_SPAN = "trace_window"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclasses.dataclass
class Op:
    name: str        # "%fusion.83"
    text: str        # the whole HLO line
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def opcode(self) -> str:
        """``fusion``, ``custom-call``, ``all-reduce-start``, ..."""
        m = re.search(r"\)?\s([a-z][a-z0-9\-]*)\(", self.text.split(" = ", 1)[-1])
        return m.group(1) if m else ""

    @property
    def label(self) -> str:
        """The name without its number, with the shape it produces:
        ``slice-done f32[1,64,128,4,128]``."""
        kind = re.sub(r"[.\d]+$", "", self.name.lstrip("%"))
        rhs = self.text.split(" = ", 1)[-1].lstrip("(")
        shape = re.match(r"[a-z0-9]+\[[\d,]*\]", rhs)
        return f"{kind} {shape.group(0)}" if shape else kind


@dataclasses.dataclass
class Chip:
    index: int
    ops: List[Op]
    modules: List[Op]


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    # (name, t0, t1): the benchmark's spans by their bare names, which
    # the readers select by, and the program's as ``pt:<phase>``, so that an
    # idle gap is named by the phase of the tick that covers it
    spans: List[Tuple[str, float, float]]
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clipped_seconds(merged: Sequence[Interval], lo: float, hi: float
                    ) -> float:
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def load(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    chips: List[Chip] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops: List[Op] = []
            modules: List[Op] = []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                dest = ops if line.name == "XLA Ops" else modules
                for e in line.events:
                    t0 = e.start_ns * 1e-9
                    dest.append(Op(e.name.split(" = ", 1)[0], e.name, t0,
                                   t0 + e.duration_ns * 1e-9))
            chips.append(Chip(int(m.group(1)), ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        name = e.name[len(SPAN_PREFIX):]
                    elif e.name.startswith(PROGRAM_PREFIX):
                        name = e.name
                    else:
                        continue
                    t0 = e.start_ns * 1e-9
                    spans.append((name, t0, t0 + e.duration_ns * 1e-9))
    chips.sort(key=lambda c: c.index)
    spans.sort(key=lambda s: s[1])
    marked = [s for s in spans if s[0] == WINDOW_SPAN]
    if marked:
        window = (marked[0][1], marked[0][2])
    else:
        every = [(o.start, o.end) for c in chips for o in c.ops] + \
            [(a, b) for name, a, b in spans
             if not name.startswith(PROGRAM_PREFIX)]
        window = (min(a for a, _ in every), max(b for _, b in every)) \
            if every else (0.0, 0.0)
    return Trace(chips, [s for s in spans if s[0] != WINDOW_SPAN], window)


def busy(chip: Chip) -> List[Interval]:
    return merge([(o.start, o.end) for o in chip.ops])


def busy_seconds(trace: Trace, lo: Optional[float] = None,
                 hi: Optional[float] = None) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    lo = trace.window[0] if lo is None else lo
    hi = trace.window[1] if hi is None else hi
    if not trace.chips:
        return 0.0
    return sum(clipped_seconds(busy(c), lo, hi)
               for c in trace.chips) / len(trace.chips)


def op_seconds(trace: Trace, pred: Callable[[Op], bool], chip: int = 0
               ) -> Tuple[float, int]:
    """(seconds, count) of one chip's ops inside the window that match."""
    lo, hi = trace.window
    sel = [o for o in trace.chips[chip].ops
           if pred(o) and o.start >= lo and o.end <= hi]
    return sum(o.seconds for o in sel), len(sel)


def is_kernel(o: Op) -> bool:
    return KERNEL_MARK in o.text


def is_collective(o: Op) -> bool:
    return any(o.opcode.startswith(c) for c in COLLECTIVES)


def top_ops(trace: Trace, n: int = 10, chip: int = 0
            ) -> List[List[object]]:
    """The ``n`` kinds of operation with most time inside the window."""
    lo, hi = trace.window
    total: Dict[str, float] = {}
    for o in trace.chips[chip].ops:
        if o.start >= lo and o.end <= hi:
            total[o.label] = total.get(o.label, 0.0) + o.seconds
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def idle_gaps(trace: Trace, n: int = 10, chip: int = 0
              ) -> List[List[object]]:
    """Idle seconds of one chip inside the window, summed by the host span
    that covers the middle of each gap (the shortest such span; ``between``
    where none does), largest first."""
    lo, hi = trace.window
    gaps: List[Interval] = []
    at = lo
    for a, b in busy(trace.chips[chip]):
        if b <= lo or a >= hi:
            continue
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < hi:
        gaps.append((at, hi))
    total: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [s for s in trace.spans if s[1] <= mid <= s[2]]
        label = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
            else "between"
        total[label] = total.get(label, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]


def spans_named(trace: Trace, name: str) -> List[Interval]:
    lo, hi = trace.window
    return [(a, b) for n_, a, b in trace.spans
            if n_ == name and a >= lo and b <= hi]


def per_span(trace: Trace, name: str) -> List[Tuple[float, float]]:
    """For each whole span of that name inside the window: (its length,
    the device-busy seconds inside it, averaged over the chips)."""
    merged = [busy(c) for c in trace.chips]
    out = []
    for a, b in spans_named(trace, name):
        inside = sum(clipped_seconds(m, a, b) for m in merged) / \
            max(1, len(merged))
        out.append((b - a, inside))
    return out
