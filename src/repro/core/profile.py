"""Phase spans on the device trace's clock (DESIGN.md §11).

The disk paths hide three hand-tuned overlap knobs — query ``pipeline=``,
store ``prefetch=``, chunk/fragment size — the serving engine waits,
fills and dispatches on a thread of its own, and the build's split
cascade interleaves host reads with device programs. This module is the
one measurement layer for all of them: named **spans** on an injectable
monotonic clock (the same seam as ``engine.LatencyRecorder``), recorded as
plain :class:`SpanRecord` tuples cheap enough to thread through the hot
paths — ``store.BlockCache``/``store.Prefetcher``,
``query._pipeline_chunks`` / ``query._store_chunk_iter``, ``ktree.build``
/ ``build_from_store`` / ``insert``, and the ``engine.ServingEngine``
dispatch loop all take an optional profiler.

An enabled span also opens ``jax.profiler.TraceAnnotation("repro." +
name)`` for its lifetime, so under ``jax.profiler.start_trace`` it lands on
the host plane of the ``.xplane.pb``, on the same clock as the device's
ops; with no trace running the annotation costs well under a microsecond.
The records keep the bare name.

Span names used by the wired paths (callers may add their own):

- ``"read"`` — one chunk/batch's store row fetch (on the consumer thread
  when ``prefetch=0``, on the ``Prefetcher`` reader thread when ≥ 1 — the
  wall-clock intervals then genuinely interleave with compute, which is
  exactly what :meth:`Profiler.overlap_seconds` measures);
- ``"disk_read"`` — one block decode inside ``BlockCache.get`` (nested
  under ``"read"``);
- ``"dispatch"`` — H2D staging + jit dispatch of one query chunk;
- ``"compute"`` — the blocking ``device_get`` on one chunk's in-flight
  result (device compute + D2H copy-out);
- ``"build_batch"`` — one build batch inserted until every row is placed;
  inside it ``"insert_wave"`` (one ``_insert_wave`` with its depth and
  ``accepted`` reads) and ``"split_cascade"`` (one
  ``_split_all_overflowing``), and inside that ``"split_scan"`` (one
  round's host reads of ``n_nodes``, ``n_entries``, ``root`` and
  ``height``) and ``"split_round"`` (the dispatch of one ``split_node`` or
  ``split_nodes_batch``);
- ``"engine_wait"`` — the engine's dispatcher waits on an empty queue;
  ``"engine_fill"`` — requests are queued and it waits for the batch to
  fill or for the forcing point;
- ``"engine_batch"`` / ``"engine_call"`` — one serving-engine batch /
  one offline-engine call inside it, tagged with the batch number;
- ``"engine_queue"`` — one request from admission to the moment its batch
  pops it, tagged with that batch's number. It is timed afterwards with
  :meth:`Profiler.add` on the engine's clock, so it has no annotation and
  does not appear in a device trace.

Disabled mode: pass ``NULL_PROFILER`` (the default everywhere). Its
``span()`` returns one preallocated no-op context manager — no clock
reads, no record allocation, no annotation, no per-call garbage — so
instrumented code pays a single attribute lookup and a branch-free
``with`` when profiling is off (pinned by tests/test_profile.py's
zero-allocation test).

Thread safety: records append to a plain list (atomic under the GIL) and
the stack of open spans is kept per thread, so a ``Prefetcher`` reader
thread and the consumer loop can share one profiler; interval queries
merge across threads, which is what makes cross-thread overlap measurable
at all.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

ANNOTATION_PREFIX = "repro."  # an enabled span's name on the trace's host plane


class SpanRecord(NamedTuple):
    """One closed span: ``name``, clock times ``t0 ≤ t1``, ``depth`` (0 =
    outermost on its thread; nested spans count up), ``parent`` (the name of
    the span open on the same thread when this one opened, ``None`` at depth
    0) and ``tag`` (an optional integer id, e.g. the engine's batch number,
    shared by the records of one unit of work)."""

    name: str
    t0: float
    t1: float
    depth: int
    parent: Optional[str] = None
    tag: Optional[int] = None

    @property
    def seconds(self) -> float:
        """Span duration on the profiler's clock."""
        return self.t1 - self.t0


class _SpanCtx:
    """Context manager for one in-flight span (see :meth:`Profiler.span`)."""

    __slots__ = ("_prof", "_name", "_tag", "_t0", "_stack", "_ann")

    def __init__(self, prof: "Profiler", name: str, tag: Optional[int]):
        self._prof = prof
        self._name = name
        self._tag = tag

    def __enter__(self) -> "_SpanCtx":
        tls = self._prof._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        self._stack = stack
        stack.append(self._name)
        self._ann = TraceAnnotation(ANNOTATION_PREFIX + self._name)
        self._ann.__enter__()
        self._t0 = self._prof.clock()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = self._prof.clock()
        self._ann.__exit__(None, None, None)
        stack = self._stack
        stack.pop()
        self._prof._records.append(SpanRecord(
            self._name, self._t0, t1, len(stack),
            stack[-1] if stack else None, self._tag,
        ))
        return False


class _NullSpan:
    """The do-nothing span ``NULL_PROFILER.span()`` hands out — one shared
    instance, so the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class Profiler:
    """Span recorder on an injectable monotonic clock.

    ``clock`` defaults to ``time.perf_counter``; tests inject a fake ticking
    clock and assert span exactness (the ``LatencyRecorder`` pattern).
    ``enabled`` is ``True`` — hot paths guard optional extra work (e.g. the
    block-level ``"disk_read"`` spans) on it so the :data:`NULL_PROFILER`
    singleton stays free."""

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self._records: List[SpanRecord] = []
        self._tls = threading.local()

    def span(self, name: str, tag: Optional[int] = None) -> _SpanCtx:
        """A context manager timing one named phase::

            with prof.span("read"):
                rows = store.take_rows(ids)

        Nesting is tracked per thread (the inner span's ``depth`` is the
        outer's + 1 and its ``parent`` the outer's name); the record, with
        ``tag``, lands when the block exits. For the block's lifetime a
        ``jax.profiler.TraceAnnotation`` named ``"repro." + name`` is open."""
        return _SpanCtx(self, name, tag)

    def add(self, name: str, t0: float, t1: float, depth: int = 0,
            parent: Optional[str] = None, tag: Optional[int] = None) -> None:
        """Record a span measured externally (pre-timed phases, tests). It
        opens no annotation."""
        self._records.append(
            SpanRecord(name, float(t0), float(t1), depth, parent, tag))

    @property
    def records(self) -> Tuple[SpanRecord, ...]:
        """All closed spans, in completion order (across threads)."""
        return tuple(self._records)

    def reset(self) -> None:
        """Drop all recorded spans (between sweep cells)."""
        self._records.clear()

    def totals(self) -> Dict[str, Dict[str, float]]:
        """Per-name aggregate: ``{name: {"seconds": Σ duration, "count": n,
        "max_s": longest}}``.

        Nested same-name spans both count — callers that need exclusive
        time should use distinct names per level (the wired paths do)."""
        out: Dict[str, Dict[str, float]] = {}
        for r in self._records:
            agg = out.setdefault(r.name, {"seconds": 0.0, "count": 0, "max_s": 0.0})
            agg["seconds"] += r.seconds
            agg["count"] += 1
            agg["max_s"] = max(agg["max_s"], r.seconds)
        return out

    def intervals(self, name: str) -> List[Tuple[float, float]]:
        """The merged (disjoint, sorted) wall-clock intervals covered by any
        span named ``name`` — across threads and nesting levels."""
        spans = sorted(
            (r.t0, r.t1) for r in self._records if r.name == name
        )
        merged: List[Tuple[float, float]] = []
        for t0, t1 in spans:
            if merged and t0 <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], t1))
            else:
                merged.append((t0, t1))
        return merged

    def overlap_seconds(self, a: str, b: str) -> float:
        """Wall-clock seconds during which an ``a`` span and a ``b`` span
        were *simultaneously* open — the measured-overlap primitive the
        auto-tuner's report is built on (``core/autotune.py``): with
        ``prefetch ≥ 1`` the ``"read"`` spans run on the reader thread and
        genuinely intersect the consumer's ``"compute"`` spans; at depth 0
        they cannot, and this returns ~0."""
        ia, ib = self.intervals(a), self.intervals(b)
        total, i, j = 0.0, 0, 0
        while i < len(ia) and j < len(ib):
            lo = max(ia[i][0], ib[j][0])
            hi = min(ia[i][1], ib[j][1])
            if hi > lo:
                total += hi - lo
            if ia[i][1] <= ib[j][1]:
                i += 1
            else:
                j += 1
        return total


class NullProfiler(Profiler):
    """The disabled profiler: every ``span()`` returns the same no-op
    context manager and nothing is ever recorded or annotated. Hot paths
    take this as their default so instrumentation has near-zero cost when
    off."""

    enabled = False

    def __init__(self):
        super().__init__()

    def span(self, name: str, tag: Optional[int] = None) -> _NullSpan:  # type: ignore[override]
        """The shared no-op span — same object every call (no allocation)."""
        return _NULL_SPAN

    def add(self, name: str, t0: float, t1: float, depth: int = 0,
            parent: Optional[str] = None, tag: Optional[int] = None) -> None:
        """Dropped."""


NULL_PROFILER = NullProfiler()
