"""What every traffic loop shares: set-up notes, the window's opening and
closing, the profiler trace of the window, the device's memory peak,
host spans, seeded streams and device keys.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import trace as tr


def span(name: str):
    """A host span (``jax.profiler.TraceAnnotation``): the trace names
    each idle gap of the device by the innermost one open."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent host stream ``stream`` of ``seed`` (any size of int)."""
    return np.random.default_rng([seed, stream])


def device_key(seed: int, j: int):
    """Device key of input set ``j``; seeds past 32 bits stay distinct."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), j)


def note(phase: str, t0: float) -> float:
    """Print how long a set-up phase took; returns the time now."""
    now = time.perf_counter()
    print(f"[bench] setup {phase} {now - t0:.3f} s", file=sys.stderr)
    return now


class CompileEvents:
    """JAX's traces, compiles and compile-cache reads between ``open_window``
    and ``close_window``, by event name: nothing should compile there."""
    counts: collections.Counter = collections.Counter()
    on = False
    listening = False

    @classmethod
    def hear(cls, event: str, *args, **kwargs) -> None:
        if cls.on and event.startswith(("/jax/core/compile/",
                                        "/jax/compilation_cache/cache_")):
            cls.counts[event.rsplit("/", 1)[1]] += 1


def open_window(t_start: float) -> float:
    """Set-up ends here: garbage from set-up is collected, and what
    survives it is frozen until ``close_window``, so no full collection
    lands in the window; JAX's compile events are counted from here.
    Returns the set-up's seconds since ``t_start``."""
    import jax.monitoring
    if not CompileEvents.listening:
        jax.monitoring.register_event_listener(CompileEvents.hear)
        jax.monitoring.register_event_duration_secs_listener(CompileEvents.hear)
        CompileEvents.listening = True
    CompileEvents.counts.clear()
    CompileEvents.on = True
    gc.collect()
    gc.freeze()
    return time.perf_counter() - t_start


def close_window() -> None:
    gc.unfreeze()
    CompileEvents.on = False
    print(f"[bench] compile events in the window: {dict(CompileEvents.counts)}",
          file=sys.stderr)


@contextlib.contextmanager
def profiled(on: bool):
    """A profiler trace of the block, into a fresh directory under TMPDIR
    (host Python calls are not traced: only the benchmark's spans)."""
    if not on:
        yield None
        return
    import jax
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def reduce_trace(log_dir: str | None, spans):
    """The trace's device numbers (``bench/trace.py``), None untraced."""
    if log_dir is None:
        return None
    try:
        return tr.reduce(tr.load(log_dir, spans))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_))
