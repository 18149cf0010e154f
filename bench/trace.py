"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain ``Trace``: per device, the intervals of its operations; on the
host, the benchmark's own spans (a traffic kind's ``SPANS``).
Everything else works on that structure, so the arithmetic is tested on
a synthetic trace.

* busy: the union of all of a device's operation intervals inside the
  window (never a sum filtered by name, so overlapping or nested events
  count once and no operation is left out);
* idle share: 1 - busy / window, averaged over the devices used;
* idle gaps: the complement of busy in the window, each named by the
  innermost benchmark span open on the host at its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

#: trace line that holds a TPU's operations
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    #: device plane -> [(op name, start_ns, end_ns)]
    ops: dict[str, list[tuple[str, float, float]]]
    #: [(span name, start_ns, end_ns)] of the benchmark's host spans
    spans: list[tuple[str, float, float]]

    def window(self) -> tuple[float, float]:
        """The ``window`` span: the measured window on the trace's clock."""
        wins = [(s, e) for name, s, e in self.spans if name == "window"]
        if len(wins) != 1:
            raise ValueError(f"expected one 'window' span, found {len(wins)}")
        return wins[0]


def load(log_dir: str, span_names) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    data = jax.profiler.ProfileData.from_file(files[0])
    ops, spans = {}, []
    wanted = set(span_names)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            ops[plane.name] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for line in plane.lines if line.name == OPS_LINE
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name in wanted]
    return Trace(ops, spans)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of the merged ``busy`` intervals in [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_s(trace: Trace) -> dict[str, float]:
    """Seconds each device was busy inside the window."""
    lo, hi = trace.window()
    return {dev: sum(e - s for s, e in union(((s, e) for _, s, e in evs),
                                             lo, hi)) / 1e9
            for dev, evs in trace.ops.items()}


def op_count(trace: Trace) -> dict[str, int]:
    """Operations each device started inside the window."""
    lo, hi = trace.window()
    return {dev: sum(1 for _, s, _ in evs if lo <= s < hi)
            for dev, evs in trace.ops.items()}


def op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction; keep the
    result's name and the operation (``%fusion.3 custom-call``)."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name
    op = re.search(r"\s([a-z][\w-]*)\(", " " + rhs)
    return f"{lhs} {op.group(1)}" if op else lhs


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` operations with the most device time in the window,
    ``[name, seconds]``, summed over devices."""
    lo, hi = trace.window()
    total: dict[str, float] = collections.Counter()
    for evs in trace.ops.values():
        for name, s, e in evs:
            if e > lo and s < hi:
                total[op_name(name)] += (min(e, hi) - max(s, lo)) / 1e9
    return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]


def idle_by_span(trace: Trace, k: int = 10) -> list[list]:
    """Idle device seconds in the window by the innermost benchmark span
    open on the host at each gap's midpoint (``"none"`` outside all but
    the window), ``[span, seconds]``, averaged over devices."""
    lo, hi = trace.window()
    # the loops' spans follow one another and never nest, so the span
    # open at a point, if any, is the last one to start before it
    inner = sorted((s, e, n) for n, s, e in trace.spans if n != "window")
    starts = [s for s, _, _ in inner]
    total: dict[str, float] = collections.Counter()
    for evs in trace.ops.values():
        busy = union(((s, e) for _, s, e in evs), lo, hi)
        for gs, ge in gaps(busy, lo, hi):
            mid = (gs + ge) / 2
            i = bisect.bisect_right(starts, mid) - 1
            name = inner[i][2] if i >= 0 and mid < inner[i][1] else "none"
            total[name] += (ge - gs) / 1e9
    n_dev = max(1, len(trace.ops))
    return [[n, t / n_dev]
            for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]


def reduce(trace: Trace) -> dict:
    """The numbers a traced run reports: ``busy_s`` and ``idle_share``
    averaged over the devices used, ``window_s``, operations started in
    the window over all devices, and the breakdown.  A device is used if
    it started an operation in the window: a cell that drives one chip
    of a host is averaged over that chip alone."""
    lo, hi = trace.window()
    started = op_count(trace)
    trace = Trace({d: evs for d, evs in trace.ops.items() if started[d]},
                  trace.spans)
    if not trace.ops:
        raise ValueError("no TPU device ran an operation in the window")
    busy = busy_s(trace)
    window = (hi - lo) / 1e9
    mean_busy = sum(busy.values()) / len(busy)
    return {"busy_s": mean_busy, "window_s": window,
            "idle_share": 1.0 - mean_busy / window,
            "ops": sum(op_count(trace).values()),
            "breakdown": {"device_ops": top_ops(trace),
                          "idle_gaps": idle_by_span(trace)}}
