"""The program's own phases in a profiler trace: the host spans whose
names start with ``pt:`` (``pt:tick``, ``pt:tick.schedule``, ...), which
``paddle_tpu.obs`` tracers write as ``TraceAnnotation``s on the device
trace's clock.  ``trace.load`` keeps the
benchmark's own ``bench:`` spans only, so the readers of the phases come
here: the same file, the same clock, clipped to the same window.

A program that has no such spans (a commit before they existed) gives an
empty list, and every reader built on this file then returns ``None``.
"""

from __future__ import annotations

import statistics
from typing import List, Optional, Sequence, Tuple

from . import trace as T

PREFIX = "pt:"
Span = Tuple[str, float, float]          # (name without prefix, t0, t1)

TICK = "tick"
TICK_PHASES = ("tick.schedule", "tick.assemble", "tick.upload", "tick.wait",
               "tick.sample")


def load(path: str) -> List[Span]:
    """Every ``pt:`` span of the host plane, sorted by start."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name != T.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    t0 = e.start_ns * 1e-9
                    spans.append((e.name[len(PREFIX):], t0,
                                  t0 + e.duration_ns * 1e-9))
    spans.sort(key=lambda s: s[1])
    return spans


def of_run(run: dict) -> List[Span]:
    """The ``pt:`` spans wholly inside the traced window of a run's record
    (read once, then kept on the record); empty without a trace."""
    if "program_spans" not in run:
        tr, tracing = run.get("trace"), run.get("tracing")
        path = tracing.file() if tr is not None and tracing is not None \
            else None
        lo, hi = tr.window if tr is not None else (0.0, 0.0)
        run["program_spans"] = [s for s in (load(path) if path else [])
                                if s[1] >= lo and s[2] <= hi]
    return run["program_spans"]


def named(spans: Sequence[Span], name: str) -> List[T.Interval]:
    return [(a, b) for n, a, b in spans if n == name]


def seconds_inside(spans: Sequence[Span], names: Sequence[str],
                   unit: T.Interval) -> float:
    """Summed length of the spans of those names that lie inside ``unit``
    (a phase may come more than once in a tick: a second dispatch, the
    bookkeeping at its end)."""
    lo, hi = unit
    return sum(b - a for n, a, b in spans
               if n in names and a >= lo and b <= hi)


def median_ms(values: Sequence[float]) -> Optional[float]:
    return 1e3 * statistics.median(values) if values else None


def phase_ms_per_tick(run: dict, phase: str) -> Optional[float]:
    """Median over the whole ticks of the traced window of the time one
    phase took in a tick; ``None`` for a run that is no serving run or
    whose program wrote no ``pt:tick`` spans."""
    if run["kind"] != "serve":
        return None
    spans = of_run(run)
    return median_ms([seconds_inside(spans, (phase,), tick)
                      for tick in named(spans, TICK)])


def host_seconds(trace: T.Trace, intervals: Sequence[T.Interval]
                 ) -> List[float]:
    """For each interval, its length minus the device-busy seconds inside
    it, chips averaged: ``trace.per_span``'s own count, over spans that
    ``trace.load`` did not keep."""
    rows = T.per_span(T.Trace(trace.chips, [("it", a, b)
                                            for a, b in intervals],
                              trace.window), "it")
    return [length - busy for length, busy in rows]
