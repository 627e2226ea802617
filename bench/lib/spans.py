"""The device's idle time inside the program's own spans, from a profiler trace.

An enabled ``repro.core.profile.Profiler`` span opens a ``TraceAnnotation``
named ``repro.<span>``, so the program's spans lie on the trace's host plane,
on the device ops' clock. ``program_spans`` reduces them over a window to

- ``span_calls``: the events of each span name whose midpoint lies in the
  window;
- ``idle_by_span``: the device's idle seconds inside the union of each
  name's intervals, clipped to the window: their length less the time a
  device op ran in them, averaged over the devices as ``busy_s`` is. A key
  that joins names with ``+`` (``unions``) is the union of their intervals,
  so that the share of the window's idle a set of spans covers can be read.

It is the program-named view of the idle that ``trace.attribute_gaps``
names by host event (mostly frames of the Python tracer).
"""
from __future__ import annotations

import collections

from lib import trace as btrace

PREFIX = "repro."


def overlap(a, b) -> float:
    """Total length of the intersection of two sorted, disjoint interval
    lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def program_spans(tr: btrace.Trace, window=None, unions=()) -> dict:
    """``window_s``, ``idle_s`` (the window's device idle), ``span_calls``
    and ``idle_by_span`` over ``window`` (start_ns, end_ns; default: the
    span of the device ops). ``unions``: tuples of span names whose union's
    idle is reported under the names joined by ``+``."""
    lo, hi = btrace._window(tr, window)
    n_dev = max(len(tr.ops), 1)
    busy = [btrace.clip(btrace.union((e.start_ns, e.start_ns + e.dur_ns) for e in evs), lo, hi)
            for evs in tr.ops.values()]
    by_name = collections.defaultdict(list)
    for e in tr.host:
        if e.name.startswith(PREFIX):
            by_name[e.name[len(PREFIX):]].append(e)

    def idle(evs) -> float:
        spans = btrace.clip(btrace.union((e.start_ns, e.start_ns + e.dur_ns) for e in evs),
                            lo, hi)
        length = sum(e - s for s, e in spans)
        return (n_dev * length - sum(overlap(spans, b) for b in busy)) * 1e-9 / n_dev

    idle_by_span = {name: idle(evs) for name, evs in sorted(by_name.items())}
    for names in unions:
        idle_by_span["+".join(names)] = idle([e for n in names for e in by_name.get(n, ())])
    busy_s = sum(e - s for b in busy for s, e in b) * 1e-9 / n_dev
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": (hi - lo) * 1e-9 - busy_s,
        "span_calls": {name: sum(btrace._in_window(e, lo, hi) for e in evs)
                       for name, evs in sorted(by_name.items())},
        "idle_by_span": idle_by_span,
    }
