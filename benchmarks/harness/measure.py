"""What every driver measures with: the device it must find, the table
of peaks, the clock, host spans, compile events and percentiles."""

from __future__ import annotations

import contextlib
import json
import math
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from . import cells


class NoDevice(Exception):
    """JAX found no accelerator, too few chips, or a kind with no peaks."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def start_program() -> str:
    """Start JAX and the program as every run does; returns the compile
    cache's directory.  Every program goes to the persistent cache, the
    small ones too, so that only a checkout's first run of a cell compiles;
    the directory is the program's own choice
    (``platform/compile_cache.py``)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.platform.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    paddle.init()
    return enable_compile_cache()


def require_devices(chips: int, rehearsal: bool):
    """The ``chips`` devices to run on.  A real cell needs that many TPU
    chips; only a rehearsal manifest may run on the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearsal:
        raise NoDevice(f"no TPU: jax found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, jax found {len(devs)}")
    return devs[:chips]


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip; a kind not in the table is an error."""
    table = cells.load_json(os.path.join(cells.ROOT, "peaks.json"))
    if device_kind not in table["kinds"]:
        raise NoDevice(f"device kind {device_kind!r} is not in peaks.json "
                       f"(it has {sorted(table['kinds'])})")
    return table["kinds"][device_kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend, as the
    CPU's, reports none)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileWatch:
    """Counts backend compilations and persistent-cache hits and misses as
    JAX reports them, each with the time it was seen, so that one inside
    the measured window is found."""

    def __init__(self):
        import jax

        self.compiles: List[Tuple[float, float]] = []   # (seen at, took)
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), float(secs)))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def inside(self, t0: float, t1: float) -> List[float]:
        """Seconds each compilation seen inside [t0, t1] took."""
        return [took for t, took in self.compiles if t0 <= t <= t1]


class Spans:
    """The benchmark's own host spans: (name, start, end) on
    ``time.perf_counter``, and the same span as a ``TraceAnnotation`` so
    that a traced run has it on the profiler's clock too.  ``open`` and
    ``close`` serve spans that begin in one callback and end in another."""

    def __init__(self):
        self.rows: List[Tuple[str, float, float]] = []
        self._open: Dict[str, tuple] = {}

    def open(self, name: str) -> None:
        import jax

        ann = jax.profiler.TraceAnnotation("bench:" + name)
        ann.__enter__()
        self._open[name] = (ann, time.perf_counter())

    def close(self, name: str) -> None:
        ann, t0 = self._open.pop(name)
        ann.__exit__(None, None, None)
        self.rows.append((name, t0, time.perf_counter()))

    @contextlib.contextmanager
    def span(self, name: str):
        self.open(name)
        try:
            yield
        finally:
            self.close(name)


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


class Tracing:
    """A profiler trace of a part of the window, in a directory inside
    the checkout; the python tracer is off (it slows the host)."""

    def __init__(self, name: str):
        self.dir = os.path.join(cells.REPO, ".bench_traces", name)
        self.on = False
        self.t0 = self.t1 = None      # the traced interval, host clock
        self.ended = None             # when stop_trace had written it

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.on = False
        self.ended = time.perf_counter()

    def file(self) -> Optional[str]:
        import glob

        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                breakdown: Optional[dict], compared: Dict[str, list]) -> str:
    """``compared`` ({name: [number, limit]}, what decided ``correct``)
    comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)
