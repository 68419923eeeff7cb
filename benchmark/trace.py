"""Profiler trace -> numbers. The reduction is code kept with the benchmark
so that every PR computes the same number the same way.

A traced run profiles a steady slice of the window (a few seconds in its
middle). ``capture`` brackets the slice with two ``TraceAnnotation``
markers whose ``time.time()`` instants are recorded, which (a) bound the
traced window on the trace's own clock and (b) give the offset between
that clock and the wall clock the program's step records are stamped
with, so an idle gap on the device can be charged to what the host was
doing.

``reduce`` works on plain ``Event`` tuples, so it is checked on hand-made
event lists (``benchmark/tests``); ``load`` turns an ``.xplane.pb`` into
them with nothing but JAX.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import os
import re
import time
from typing import NamedTuple

BEGIN, END = "bench_slice_begin", "bench_slice_end"
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
EDGE_NS = 1e6


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load(path: str, rehearsal: bool = False) -> list[Event]:
    """Device-plane events and the benchmark's markers of one trace. In a
    CPU rehearsal, where there is no device plane, the host plane's events
    stand in for one so that the reduction runs; nothing is reported."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        stand_in = rehearsal and plane.name == "/host:CPU"
        for line in plane.lines:
            for e in line.events:
                if e.name in (BEGIN, END):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
                elif device or (stand_in and e.duration_ns > 0):
                    out.append(Event(
                        "/device:rehearsal" if stand_in else plane.name,
                        line.name, e.name, float(e.start_ns),
                        float(e.duration_ns)))
    return out


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {log_dir}")
    return found[-1]


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the body. Yields a dict that holds, after the body, the
    wall-clock instants (``time.time()``) of the two markers."""
    import jax.profiler as prof

    marks = {}
    # no Python call stacks: they are most of a trace's events (minutes
    # to write and to read back) and of what tracing costs the host; the
    # markers and the device planes do not need them
    options = prof.ProfileOptions()
    options.python_tracer_level = 0
    prof.start_trace(log_dir, profiler_options=options)
    try:
        marks["begin_wall"] = time.time()
        with prof.TraceAnnotation(BEGIN):
            pass
        yield marks
        marks["end_wall"] = time.time()
        with prof.TraceAnnotation(END):
            pass
    finally:
        prof.stop_trace()


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def program_name(event_name: str) -> str:
    """``jit__paged_decode_fn(8123456789)`` -> ``jit__paged_decode_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(events: list[Event]) -> dict:
    """Busy and idle time, per-program executions, top operations and the
    idle gaps of a traced slice.

    - window: marker to marker when both are there, else first device
      event's start to the last one's end.
    - busy: on each device plane the UNION of the ``XLA Ops`` intervals
      (any line but ``XLA Modules``/``Steps`` where a plane has no such
      line) clipped to the window; ``busy_s`` is the mean over the device
      planes that ran anything.
    - programs: name -> durations (s) of the executions on the
      ``XLA Modules`` line that lie wholly inside the window and clear of
      the capture's edges (the profiler records an execution that was
      under way when it started or stopped cut short).
    """
    marks = {e.name: e for e in events if e.name in (BEGIN, END)}
    dev = [e for e in events if e.plane.startswith("/device:")
           and e.name not in (BEGIN, END)]
    if not dev:
        raise RuntimeError("the trace holds no device event: nothing ran on "
                           "the device inside the traced slice")
    if BEGIN in marks and END in marks:
        w0 = marks[BEGIN].start_ns
        w1 = marks[END].start_ns
    else:
        w0 = min(e.start_ns for e in dev)
        w1 = max(e.start_ns + e.dur_ns for e in dev)
    planes = sorted({e.plane for e in dev})
    busy, gaps, op_time = [], [], {}
    for p in planes:
        mine = [e for e in dev if e.plane == p]
        lines = {e.line for e in mine}
        if OP_LINE in lines:
            ops = [e for e in mine if e.line == OP_LINE]
        else:
            ops = [e for e in mine if e.line not in (MODULE_LINE, "Steps")]
        spans = []
        for e in ops:
            a, b = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
            if b > a:
                spans.append((a, b))
                if p == planes[0]:
                    key = op_name(e.name)
                    op_time[key] = op_time.get(key, 0.0) + (b - a)
        merged = _union(spans)
        if merged:
            busy.append(sum(b - a for a, b in merged))
        if p == planes[0]:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    # an execution under way when the capture began or ended is recorded
    # cut short: keep only those clear of the capture's own edges
    edge0 = min(e.start_ns for e in dev)
    edge1 = max(e.start_ns + e.dur_ns for e in dev)
    programs: dict[str, list[float]] = {}
    for e in dev:
        if (e.plane == planes[0] and e.line == MODULE_LINE
                and e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1
                and e.start_ns - edge0 > EDGE_NS
                and edge1 - (e.start_ns + e.dur_ns) > EDGE_NS):
            programs.setdefault(program_name(e.name), []).append(
                e.dur_ns * 1e-9)
    lines: dict[str, int] = {}
    for e in dev:
        key = f"{e.plane} | {e.line}"
        lines[key] = lines.get(key, 0) + 1
    return {
        "lines": lines,
        "window_ns": (w0, w1),
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": (sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
        "device_planes": planes,
        "programs": programs,
        "op_seconds": {k: v * 1e-9 for k, v in op_time.items()},
        "gaps_ns": gaps,
    }


@functools.lru_cache(maxsize=None)
def op_name(event_name: str) -> str:
    """The device plane prints an operation as its whole HLO instruction;
    keep the instruction's name and its opcode:
    ``%sort.5 = (f32[16,151936]...) sort(...)`` -> ``sort.5 sort``."""
    head, _, rest = event_name.partition(" = ")
    m = re.search(r"(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(", rest)
    name = head.strip().lstrip("%")
    return (f"{name} {m.group(1)}" if m else name)[:120]


def top(op_seconds: dict, n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(op_seconds.items(),
                                      key=lambda kv: -kv[1])[:n]]


def charge_gaps(reduced: dict, marks: dict, steps: list[dict],
                n: int = 10) -> list[list]:
    """The longest idle gaps by what the host was doing: each gap is laid
    over the program's step records (``start_s`` on ``time.time()``,
    ``wall_s``, ``activities``) through the begin marker's two clocks and
    charged to the largest host activity of the step its middle falls in,
    or to ``between steps`` where no step covers it. Finer than a step
    the records do not go. Returns ``[[label, seconds], ...]`` summed by
    label, longest first."""
    w0 = reduced["window_ns"][0]
    wall0 = marks.get("begin_wall")
    steps = sorted(steps, key=lambda r: r["start_s"])
    by: dict[str, float] = {}
    for a, b in reduced["gaps_ns"]:
        label = "between steps"
        if wall0 is not None:
            mid = wall0 + ((a + b) / 2 - w0) * 1e-9
            for r in steps:
                if r["start_s"] <= mid < r["start_s"] + r["wall_s"]:
                    acts = {k: v for k, v in r["activities"].items() if v > 0}
                    label = ("step: " + max(acts, key=acts.get)
                             if acts else "step")
                    break
        by[label] = by.get(label, 0.0) + (b - a) * 1e-9
    return top(by, n)
