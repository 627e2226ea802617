"""Phase-span profiler (DESIGN.md §11): span exactness, nesting, parents and
tags on an injected fake clock, cross-thread interval merging +
read∩compute overlap, and the disabled-mode contract (``NULL_PROFILER`` hands
out one shared no-op span and records nothing — the hot paths rely on that
being free). That an enabled span lands on a device trace's host plane, and
a null one does not, is pinned in bench/tests/test_bench_trace.py."""
import threading

import pytest

from repro.core.profile import (
    NULL_PROFILER, NullProfiler, Profiler, SpanRecord,
)


class FakeClock:
    """Deterministic monotonic clock: every read advances by ``step``."""

    def __init__(self, step=1.0, t=0.0):
        self.t = t
        self.step = step

    def __call__(self):
        now = self.t
        self.t += self.step
        return now


# ----------------------------------------------------------------- spans

def test_span_exact_on_fake_clock():
    prof = Profiler(clock=FakeClock())
    with prof.span("read"):
        pass
    (r,) = prof.records
    assert r == SpanRecord("read", 0.0, 1.0, 0)
    assert r.seconds == 1.0


def test_span_nesting_depths_and_order():
    """Nested spans carry depth = outer + 1 and close inner-first; sibling
    spans after the nest return to the outer depth."""
    prof = Profiler(clock=FakeClock())
    with prof.span("outer"):
        with prof.span("inner"):
            pass
        with prof.span("inner2"):
            pass
    with prof.span("top"):
        pass
    names = [(r.name, r.depth) for r in prof.records]
    assert names == [
        ("inner", 1), ("inner2", 1), ("outer", 0), ("top", 0),
    ]
    inner, inner2, outer, top = prof.records
    # clock reads: outer.t0=0, inner=(1,2), inner2=(3,4), outer.t1=5, top=(6,7)
    assert (outer.t0, outer.t1) == (0.0, 5.0)
    assert (inner.t0, inner.t1) == (1.0, 2.0)
    assert (inner2.t0, inner2.t1) == (3.0, 4.0)
    assert (top.t0, top.t1) == (6.0, 7.0)


def test_span_records_on_exception_and_restores_depth():
    prof = Profiler(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with prof.span("boom"):
            raise RuntimeError("x")
    (r,) = prof.records
    assert r.name == "boom" and r.seconds == 1.0
    # depth must be back at 0: a new span records at depth 0
    with prof.span("after"):
        pass
    assert prof.records[-1].depth == 0


def test_depth_is_per_thread():
    """A span open on the main thread does not deepen a worker's spans —
    the Prefetcher-reader-thread sharing contract."""
    prof = Profiler(clock=FakeClock())
    done = threading.Event()

    def worker():
        with prof.span("read"):
            pass
        done.set()

    with prof.span("compute"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert done.is_set()
    depths = {r.name: r.depth for r in prof.records}
    assert depths == {"read": 0, "compute": 0}


def test_parent_tag_and_max_on_fake_clock():
    """A span's parent is the span open on its thread when it opened; its tag
    is what the caller gave; totals keep the longest span per name."""
    prof = Profiler(clock=FakeClock())
    with prof.span("engine_batch", tag=7):          # t0 = 0
        with prof.span("engine_call", tag=7):       # (1, 2)
            pass
        with prof.span("engine_call", tag=7):       # (3, 6)
            with prof.span("compute"):              # (4, 5)
                pass
    prof.add("engine_queue", 10.0, 13.5, tag=7)
    recs = {(r.name, r.t0): r for r in prof.records}
    assert recs[("engine_call", 1.0)] == SpanRecord(
        "engine_call", 1.0, 2.0, 1, "engine_batch", 7)
    assert recs[("compute", 4.0)].parent == "engine_call"
    assert recs[("compute", 4.0)].tag is None and recs[("compute", 4.0)].depth == 2
    assert recs[("engine_batch", 0.0)].parent is None
    assert recs[("engine_queue", 10.0)] == SpanRecord(
        "engine_queue", 10.0, 13.5, 0, None, 7)
    t = prof.totals()
    assert t["engine_call"] == {"seconds": 4.0, "count": 2, "max_s": 3.0}
    assert t["engine_batch"]["max_s"] == 7.0          # (0, 7)
    assert t["engine_queue"]["max_s"] == 3.5


def test_parent_is_per_thread():
    """A span opened on a worker thread while the main thread holds one has
    no parent: parents follow the thread, as depth does."""
    prof = Profiler(clock=FakeClock())

    def worker():
        with prof.span("read"):
            pass

    with prof.span("compute"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    parents = {r.name: r.parent for r in prof.records}
    assert parents == {"read": None, "compute": None}


# ------------------------------------------------- totals / intervals

def test_totals_and_reset():
    prof = Profiler()
    prof.add("read", 0.0, 2.0)
    prof.add("read", 5.0, 6.0)
    prof.add("compute", 1.0, 4.0)
    t = prof.totals()
    assert t["read"] == {"seconds": 3.0, "count": 2, "max_s": 2.0}
    assert t["compute"] == {"seconds": 3.0, "count": 1, "max_s": 3.0}
    prof.reset()
    assert prof.records == () and prof.totals() == {}


def test_intervals_merge_overlapping_and_adjacent():
    prof = Profiler()
    prof.add("read", 0.0, 2.0)
    prof.add("read", 1.5, 3.0)   # overlaps the first
    prof.add("read", 3.0, 4.0)   # touches → merges
    prof.add("read", 10.0, 11.0)
    assert prof.intervals("read") == [(0.0, 4.0), (10.0, 11.0)]
    assert prof.intervals("nope") == []


def test_overlap_seconds_exact():
    """read∩compute over hand-built intervals: the tuner's primitive."""
    prof = Profiler()
    prof.add("read", 0.0, 4.0)
    prof.add("read", 8.0, 10.0)
    prof.add("compute", 2.0, 9.0)
    # [0,4]∩[2,9] = 2, [8,10]∩[2,9] = 1
    assert prof.overlap_seconds("read", "compute") == pytest.approx(3.0)
    assert prof.overlap_seconds("compute", "read") == pytest.approx(3.0)
    assert prof.overlap_seconds("read", "nope") == 0.0


def test_overlap_zero_when_serialised():
    """Phases that never coexist on the wall clock — the prefetch=0 story —
    measure exactly zero overlap."""
    prof = Profiler()
    for i in range(4):
        prof.add("read", 2 * i, 2 * i + 1)
        prof.add("compute", 2 * i + 1, 2 * i + 2)
    assert prof.overlap_seconds("read", "compute") == 0.0


# --------------------------------------------------------- disabled mode

def test_null_profiler_records_nothing():
    with NULL_PROFILER.span("read"):
        with NULL_PROFILER.span("disk_read"):
            pass
    NULL_PROFILER.add("read", 0.0, 1.0)
    assert NULL_PROFILER.records == ()
    assert not NULL_PROFILER.enabled and Profiler.enabled


def test_null_profiler_span_is_shared_singleton():
    """``span()`` hands back the *same* object every call — the
    zero-allocation contract the hot-path defaults rely on."""
    a = NULL_PROFILER.span("a")
    b = NULL_PROFILER.span("b")
    assert a is b
    assert a is NullProfiler().span("c")
