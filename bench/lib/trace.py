"""Reduction of a JAX profiler trace to the numbers the per-layer metrics read.

The profiler writes an XSpace (``*.xplane.pb``): planes (one per device, one
for the host), lines within them, and timed events. ``load`` keeps what the
reduction needs as plain tuples; ``summarize`` reduces that to:

- ``busy_s``: the union of the device's op intervals inside the window,
  averaged over the devices;
- ``kernel_s`` and ``kernel_calls``: the summed duration and the count of the
  op events of each named Pallas kernel, and ``kernel_op``: one such event's
  name, the compiled instruction with its operand shapes;
- ``program_s`` and ``program_calls``: the summed duration and the count of
  executions of each jitted program whose name holds a given fragment;
- ``idle_gaps``: the device's idle time inside the window, split into gaps,
  each gap named by the host event that overlaps it most (the benchmark's
  own ``TraceAnnotation`` spans first), summed by name;
- ``device_ops``: device time by op name.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import itertools
import os
import re

Event = collections.namedtuple("Event", "name start_ns dur_ns")

DEVICE_OP_LINES = ("XLA Ops",)
DEVICE_PROGRAM_LINES = ("XLA Modules",)
WINDOW_SPAN = "bench_window"  # the benchmark's span around the traced window
LONG_HOST_NS = 10_000_000
GAP_MIN_NS = 1_000
NAME_CHARS = 160  # an op's name is its HLO instruction; the head says enough  # shorter gaps between ops are the device's own issue time


@dataclasses.dataclass
class Trace:
    """Device op and program events per device, and host events."""
    ops: dict        # device plane name -> [Event] (op events)
    programs: dict   # device plane name -> [Event] (program executions)
    host: list       # [Event] over every host line


def _is_device(plane_name: str) -> bool:
    return re.fullmatch(r"/device:(TPU|GPU):\d+", plane_name) is not None


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    pd = ProfileData.from_file(path)
    ops, programs, host = {}, {}, []
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name in DEVICE_OP_LINES:
                    ops.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns) for e in line.events)
                elif line.name in DEVICE_PROGRAM_LINES:
                    programs.setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns) for e in line.events)
    return Trace(ops=ops, programs=programs, host=host)


def union(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def _window(trace: Trace, window) -> tuple:
    if window is not None:
        return window
    evs = [e for evs in trace.ops.values() for e in evs]
    return (min(e.start_ns for e in evs), max(e.start_ns + e.dur_ns for e in evs))


def _in_window(ev: Event, lo, hi) -> bool:
    mid = ev.start_ns + ev.dur_ns / 2
    return lo <= mid < hi


def summarize(trace: Trace, kernels=(), programs=(), window=None) -> dict:
    """Reduce ``trace`` over ``window`` (start_ns, end_ns; default: the span
    of the device ops). ``kernels``: names of Pallas kernels, matched as
    fragments of op names; ``programs``: fragments of program names. An op or
    program counts in the window where its midpoint lies."""
    lo, hi = _window(trace, window)
    n_dev = max(len(trace.ops), 1)
    busy = 0.0
    gaps = []
    device_ops = collections.Counter()
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    kernel_op = {}
    for evs in trace.ops.values():
        merged = clip(union((e.start_ns, e.start_ns + e.dur_ns) for e in evs), lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] - edges[i] >= GAP_MIN_NS)
        for e in evs:
            if not _in_window(e, lo, hi):
                continue
            device_ops[e.name] += e.dur_ns
            for k in kernels:
                if k in e.name:
                    kernel_s[k] += e.dur_ns * 1e-9
                    kernel_calls[k] += 1
                    kernel_op.setdefault(k, e.name)
    program_s = {p: 0.0 for p in programs}
    program_calls = {p: 0 for p in programs}
    for evs in trace.programs.values():
        for e in evs:
            if not _in_window(e, lo, hi):
                continue
            for p in programs:
                if p in e.name:
                    program_s[p] += e.dur_ns * 1e-9
                    program_calls[p] += 1
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9 / n_dev,
        "n_devices": n_dev,
        "kernel_s": {k: v / n_dev for k, v in kernel_s.items()},
        "kernel_calls": kernel_calls,
        "kernel_op": kernel_op,
        "program_s": {p: v / n_dev for p, v in program_s.items()},
        "program_calls": program_calls,
        "device_ops": [[name[:NAME_CHARS], ns * 1e-9 / n_dev]
                       for name, ns in device_ops.most_common(10)],
        "idle_gaps": attribute_gaps(gaps, trace.host, n_dev),
    }


def attribute_gaps(gaps, host_events, n_dev: int = 1, top: int = 10,
                   skip=(WINDOW_SPAN,)) -> list:
    """[[host event name, idle seconds], ...] for the ``top`` names. Each gap
    goes to the shortest host event that covers at least half of it (the
    most specific thing the host was doing), else to the one that overlaps
    it most, else to "(no host event)". Events named in ``skip`` (the span
    that marks the window itself) take no gaps."""
    host = [e for e in host_events if e.name not in skip]
    # long events are few: check them all; short ones through a sorted index
    long_ = [e for e in host if e.dur_ns > LONG_HOST_NS]
    short = sorted((e for e in host if e.dur_ns <= LONG_HOST_NS), key=lambda e: e.start_ns)
    starts = [e.start_ns for e in short]
    by_name = collections.Counter()
    for g0, g1 in gaps:
        i = bisect.bisect_left(starts, g0 - LONG_HOST_NS)
        j = bisect.bisect_left(starts, g1)
        covering, widest = None, None
        for e in itertools.chain(short[i:j], long_):
            ov = min(g1, e.start_ns + e.dur_ns) - max(g0, e.start_ns)
            if ov > 0:
                if 2 * ov >= g1 - g0 and (covering is None or e.dur_ns < covering.dur_ns):
                    covering = e
                if widest is None or ov > widest[0]:
                    widest = (ov, e)
        name = (covering.name if covering is not None
                else widest[1].name if widest is not None else "(no host event)")
        by_name[name] += g1 - g0
    return [[name[:NAME_CHARS], ns * 1e-9 / n_dev] for name, ns in by_name.most_common(top)]
