"""Continuous-batching serving engine (DESIGN.md §8).

``topk_search`` and friends are *offline* engines: hand them a fixed query
array and they answer it as one closed batch. A service sees something else —
requests arriving one at a time, each with its own query rows, ``k``,
``beam``, and latency deadline — and the paper's operational claim ("suitable
for large document collections" at scale) is about that regime. This module
is the front end that turns the offline engines into a service:

    submit() ──► bounded admission queue ──► batcher ──► engine call ──► demux
                   │ full → shed               │ dispatch when the row budget
                   │ (reject now, never        │ fills OR the oldest request's
                   │  queue unboundedly)       │ deadline forcing-point arrives
                                               │ fragments bucketed per (k, beam)

- **Admission** — :meth:`ServingEngine.submit` enqueues a request and returns
  a :class:`ResultHandle` future. The queue is bounded: when it is full the
  request is *rejected immediately* (:class:`EngineSaturated`, counted in
  ``shed``) instead of absorbed into an ever-growing backlog — under overload
  latency stays bounded and the caller learns to back off.
- **Dynamic batching** — the dispatcher thread drains the queue FIFO into a
  batch of up to ``row_budget`` query rows, waiting for more arrivals only
  until the oldest pending request's *forcing point*: ``admit + max_wait``,
  tightened to ``deadline − dispatch_margin`` for requests that carry one. A
  full batch dispatches immediately; a lone request on an idle engine waits
  at most ``max_wait``.
- **Bucketed, chunk-aligned execution** — the drained batch is fragmented by
  ``(k, beam, pow2 request-size bucket)``: one offline-engine call per
  distinct setting and size class, each request's rows padded to the bucket
  (:func:`pow2_pad_rows`) and the call chunked *at* the bucket, so every
  query chunk gathers exactly one request's rows — the same tensor its
  standalone offline call gathers, which is what makes every request's
  answer **bit-identical** to the offline engines (XLA numerics depend on
  the gathered chunk shape, so naive concatenation would drift by ulps).
  Compiles stay bounded by (settings × pow2 buckets) actually served, not by
  batch composition — the same bucketing discipline as descent depths and
  chunk sizes (DESIGN.md §6).
- **Cache staging** — an optional :class:`repro.core.query.AnswerCache` runs
  as a pre-batch stage (:func:`repro.core.query.cache_stage`): hit rows are
  answered without occupying engine rows, misses are deduplicated, and every
  computed answer is inserted — exactly :func:`topk_search_cached`'s
  accounting, applied per fragment.
- **Observability** — per-request latency lands in a
  :class:`LatencyRecorder` (injectable monotonic clock — the fake-clock seam
  the timing tests pin); :meth:`ServingEngine.stats` reports p50/p95/p99
  latency, QPS, queue depth, shed/deadline-miss counters, batch occupancy,
  and (when ``block_caches`` are wired, e.g. a store-backed corpus) the
  per-batch peak disk residency via ``BlockCache.reset_peak``.
- **Robustness** (DESIGN.md §10) — a **watchdog** thread guarantees that
  every admitted request resolves — an answer, a typed error, or a timeout —
  so a caller blocked in :meth:`ResultHandle.result` can never hang forever.
  It enforces the engine-wide ``request_timeout_s`` (overdue requests, queued
  *or* in flight behind a wedged ``search_fn``, fail with
  :class:`EngineTimeout`) and restarts the dispatcher thread if it ever dies
  (the orphaned in-flight batch fails with :class:`EngineFault`; later
  requests are served by the replacement). ``close(drain=False)`` fails
  queued and in-flight requests with :class:`EngineClosed` instead of
  waiting on them. Degraded answers from the offline engines'
  ``on_fault="degrade"`` mode (see :func:`make_search_fn`) surface on the
  handle as ``ResultHandle.degraded`` plus the
  :class:`repro.core.faults.FaultReport` in ``ResultHandle.report``.

The engine owns one dispatcher thread; ``submit`` is safe from any number of
threads. All timing uses a monotonic clock (``time.perf_counter`` by
default) — wall-clock ``time.time`` can step under NTP and corrupt latency
percentiles.
"""
from __future__ import annotations

import dataclasses
import inspect
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.autotune import resolve_knobs
from repro.core.profile import NULL_PROFILER
from repro.core.query import (
    AnswerCache,
    cache_fill,
    cache_stage,
    concat_request_rows,
    split_batch_answers,
    topk_search,
    topk_search_sharded,
)


class EngineSaturated(RuntimeError):
    """Admission rejected: the bounded request queue is full (the request was
    counted in ``shed``). Back off and retry — the alternative, unbounded
    queueing, converts overload into unbounded latency for everyone."""


class EngineClosed(RuntimeError):
    """The engine has been closed; no further requests are admitted. Also the
    failure attached to queued/in-flight handles abandoned by
    ``close(drain=False)``."""


class EngineTimeout(TimeoutError):
    """A request exceeded its time budget: either the caller's
    ``result(timeout=...)`` wait elapsed, or the engine watchdog expired the
    request against the engine-wide ``request_timeout_s`` (in which case the
    handle is *failed* with this error — the request will never deliver an
    answer). Subclasses :class:`TimeoutError`."""


class EngineFault(RuntimeError):
    """The dispatcher thread died while this request was in flight; the
    watchdog failed the orphaned handle with this error and restarted the
    dispatcher. The request was *not* answered — resubmit if desired."""


class ResultHandle:
    """Future for one admitted request: ``result()`` blocks until the batch
    containing the request completes and returns ``(doc_ids i32[r, k],
    sqdist f32[r, k])`` — bit-identical to the offline engine on the same
    rows. ``deadline_missed`` is set (post-completion) when the answer landed
    after the request's deadline; the answer is still delivered.

    Resolution is **set-once**: the first of {answer, engine error, watchdog
    timeout, close} to land wins and every later attempt is a no-op, so the
    dispatcher completing a request the watchdog already expired cannot
    overwrite the timeout (and vice versa). ``degraded`` is True when the
    answer came from a degraded engine call (``on_fault="degrade"`` with
    quarantined blocks — DESIGN.md §10); ``report`` then carries the
    :class:`repro.core.faults.FaultReport`."""

    def __init__(self):
        self._done = threading.Event()
        self._lock = threading.Lock()
        self._value: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._error: Optional[BaseException] = None
        self.deadline_missed = False
        self.degraded = False
        self.report = None

    def _resolve(self, value) -> bool:
        """Attach the answer unless already resolved; True if this call won."""
        with self._lock:
            if self._done.is_set():
                return False
            self._value = value
            self._done.set()
            return True

    def _resolve_error(self, err: BaseException) -> bool:
        """Attach a failure unless already resolved; True if this call won."""
        with self._lock:
            if self._done.is_set():
                return False
            self._error = err
            self._done.set()
            return True

    # older internal spellings (kept for any external caller)
    _set = _resolve
    _set_error = _resolve_error

    def done(self) -> bool:
        """True once the request has an answer (or a failure) attached."""
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        """Block (up to ``timeout`` seconds) for the answer; re-raises the
        engine-call exception if the dispatching batch failed. A ``timeout``
        elapsing raises :class:`EngineTimeout` (a :class:`TimeoutError`) —
        the request itself is still pending and may resolve later."""
        if not self._done.wait(timeout):
            raise EngineTimeout("request not completed within timeout")
        if self._error is not None:
            raise self._error
        return self._value


@dataclasses.dataclass
class _Pending:
    """One queued request (internal): rows + per-request engine settings,
    admit timestamp, absolute deadline / forcing point (engine clock), and
    the caller's handle."""

    rows: np.ndarray
    k: int
    beam: int
    t_admit: float
    deadline: Optional[float]
    force_t: float
    handle: ResultHandle


class LatencyRecorder:
    """Thread-safe per-request latency sink with percentile reporting.

    ``clock`` is the one timing seam: every duration is the difference of two
    ``clock()`` readings, monotonic by default (``time.perf_counter``) so an
    NTP step or a coarse wall clock can never corrupt the percentiles — the
    regression tests drive a fake clock through here and pin the arithmetic.
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self._lock = threading.Lock()
        self._samples: List[float] = []
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

    def now(self) -> float:
        """One clock reading (the engine stamps admits/completions here so
        every timestamp shares the recorder's clock)."""
        return self.clock()

    def record(self, t_start: float, t_done: Optional[float] = None) -> float:
        """Append one latency sample ``t_done − t_start`` (``t_done`` defaults
        to now); returns the sample seconds."""
        if t_done is None:
            t_done = self.clock()
        lat = t_done - t_start
        with self._lock:
            self._samples.append(lat)
            if self._t_first is None:
                self._t_first = t_start
            self._t_last = t_done if self._t_last is None else max(self._t_last, t_done)
        return lat

    def __len__(self) -> int:
        with self._lock:
            return len(self._samples)

    def percentiles(self, qs: Sequence[float] = (50, 95, 99)) -> Dict[str, float]:
        """``{"p50": ms, ...}`` over all recorded samples (empty → zeros)."""
        with self._lock:
            samples = np.asarray(self._samples, np.float64)
        if samples.size == 0:
            return {f"p{int(q)}": 0.0 for q in qs}
        return {
            f"p{int(q)}": float(np.percentile(samples, q) * 1e3) for q in qs
        }

    def throughput(self) -> float:
        """Completed requests per second over the span from the first admit
        to the last completion (0.0 until two timestamps exist)."""
        with self._lock:
            n = len(self._samples)
            if n == 0 or self._t_first is None or self._t_last is None:
                return 0.0
            span = self._t_last - self._t_first
        return n / span if span > 0 else 0.0


def pow2_bucket(n: int) -> int:
    """Smallest power of two ≥ ``n`` (n ≥ 1) — the row-count bucket a request
    or batch lands in, mirroring ``_levels_bucket``'s pow2 discipline."""
    return 1 << max(int(n) - 1, 0).bit_length()


def pow2_pad_rows(x: np.ndarray, to: Optional[int] = None) -> Tuple[np.ndarray, int]:
    """Pad a row batch to ``to`` rows (default: the next power of two) by
    repeating the last row; returns ``(x_padded, n_real)``.

    Two jobs at once. (1) Compile bounding: the offline engines' jit
    signature includes the query batch's ``[n, d]`` shape, so without padding
    every distinct dynamic-batch size would compile afresh — the
    serving-batch application of the ``padded_chunk_rows`` bucketing
    discipline (DESIGN.md §6). (2) Bit-identity: an offline call on ``r``
    rows pads its chunk row *ids* to ``pow2_bucket(r)`` by repeating the last
    id — padding the row *content* the same way feeds the gathered scoring
    kernel the identical tensor, so a request executed inside a chunk-aligned
    batch answers bit-identically to its standalone call. Per-row
    independence makes the padded rows' answers discards: the dispatcher
    slices back to ``n_real`` before demuxing."""
    n = x.shape[0]
    m = pow2_bucket(n) if to is None else int(to)
    if m == n:
        return x, n
    return np.concatenate([x, np.repeat(x[-1:], m - n, axis=0)]), n


def make_search_fn(
    tree, *, mesh=None, corpus=None, chunk: Optional[int] = None,
    pipeline: Optional[int] = None, prefetch: Optional[int] = None,
    on_fault: Optional[str] = None, rp=None, rp_corpus=None, tuned=None,
    profiler=None,
) -> Callable[..., Tuple[np.ndarray, np.ndarray]]:
    """Adapt the offline engines to the ``search_fn(x, k, beam,
    chunk_rows=None)`` signature :class:`ServingEngine` dispatches through.

    ``mesh=None`` → :func:`topk_search` (single device; ``corpus`` unused).
    With a mesh → :func:`topk_search_sharded` over ``corpus`` — pass a
    pre-sharded handle (``backend.shard(mesh)`` or
    ``backend.shard_from_store``) so rows/partitions are placed once, not per
    batch. ``chunk_rows`` overrides the query chunk size for one call — the
    engine passes each fragment's request bucket here so every chunk gathers
    exactly one request's (padded) rows, which is what makes batched answers
    bit-identical to standalone calls (see :func:`pow2_pad_rows`). The
    returned callable carries the default chunk as ``fn.chunk`` so the engine
    knows when a request is too large to chunk-align.

    ``on_fault`` (DESIGN.md §10): ``None`` keeps the offline engines'
    default (``"raise"`` — unreadable corpus blocks fail the batch with a
    typed store error). ``"degrade"`` serves past quarantined blocks: calls
    return a third :class:`repro.core.faults.FaultReport` element, which the
    engine strips off the answer and surfaces as ``ResultHandle.degraded`` /
    ``.report``.

    ``rp``/``rp_corpus`` (DESIGN.md §5.1): a random-projection routing spec
    forwarded verbatim to the offline engines — the tree descends in the
    projected space, answers are exact-rescored from ``rp_corpus`` (or the
    RP backend's base). Incompatible with ``on_fault="degrade"``.

    Knob resolution (DESIGN.md §11): ``chunk``/``pipeline``/``prefetch``
    left ``None`` resolve through ``tuned=`` (a ``core.autotune.TunedKnobs``,
    e.g. loaded from the store's ``TUNE.json`` sidecar) then the repo
    defaults — resolved eagerly so ``fn.chunk`` is always a concrete int.
    ``profiler=`` (a ``core.profile.Profiler``) is forwarded to every
    offline-engine call; answers are unaffected."""
    chunk, pipeline, prefetch = resolve_knobs(
        tuned, chunk=chunk, pipeline=pipeline, prefetch=prefetch,
    )
    kw = {} if on_fault is None else {"on_fault": on_fault}
    if rp is not None:
        kw["rp"] = rp
        kw["rp_corpus"] = rp_corpus
    if profiler is not None:
        kw["profiler"] = profiler
    if mesh is None:
        def fn(x, k, beam, chunk_rows=None):
            return topk_search(
                tree, x, k=k, beam=beam, chunk=chunk_rows or chunk,
                pipeline=pipeline, prefetch=prefetch, **kw,
            )
    else:
        def fn(x, k, beam, chunk_rows=None):
            return topk_search_sharded(
                mesh, tree, x, corpus=corpus, k=k, beam=beam,
                chunk=chunk_rows or chunk, pipeline=pipeline,
                prefetch=prefetch, **kw,
            )
    fn.chunk = chunk
    fn.pipeline = pipeline
    fn.prefetch = prefetch
    fn.on_fault = on_fault
    return fn


class ServingEngine:
    """Continuous-batching front end over an offline search engine.

    ``search_fn(x f32[R, d], k, beam) -> (docs i32[R, k], dist f32[R, k])``
    is the execution seam — :func:`make_search_fn` builds it for the
    single-device, sharded, and store-backed paths; any callable with the
    same contract (per-row-independent answers) slots in.

    Parameters:

    - ``row_budget`` — max query rows per dispatched batch (the batch fills
      to this, then dispatches; one oversized request still dispatches alone
      — the offline engines chunk internally).
    - ``max_queue`` — admission bound in *requests*; a full queue sheds.
    - ``max_wait_s`` — idle dispatch latency cap: a batch never waits longer
      than this for more arrivals.
    - ``dispatch_margin_s`` — headroom subtracted from a request's deadline
      to get its forcing point (estimated service time, so dispatch happens
      early enough to matter).
    - ``cache``/``corpus_token`` — optional :class:`AnswerCache` pre-batch
      stage; the cache is bound to ``tree`` (required then) and
      ``corpus_token`` exactly like :func:`topk_search_cached`.
    - ``block_caches`` — ``BlockCache`` handles of a store-backed corpus;
      the engine resets their peak residency per batch and reports the
      largest per-batch disk working set.
    - ``clock`` — monotonic time source shared with the
      :class:`LatencyRecorder` (fake-clock seam for tests).
    - ``profiler`` — optional ``repro.core.profile.Profiler`` (DESIGN.md
      §11). On the dispatcher thread it records ``"engine_wait"`` while the
      queue is empty, ``"engine_fill"`` while queued requests wait for the
      batch to fill or for the forcing point, one ``"engine_batch"`` span per
      dispatched batch and one ``"engine_call"`` span per offline-engine
      call inside it, both tagged with the batch number; and, per request,
      an ``"engine_queue"`` record from admission to the moment its batch is
      popped, on this engine's ``clock`` and with the batch's tag (timed
      afterwards, so absent from a device trace). The default
      ``NULL_PROFILER`` is free.
    - ``request_timeout_s`` — engine-wide per-request time budget (admit →
      answer), enforced by the watchdog thread: an overdue request — still
      queued *or* in flight behind a wedged ``search_fn`` — is failed with
      :class:`EngineTimeout` so its caller unblocks. ``None`` (default)
      disables expiry; the watchdog still runs for dispatcher restarts.

    Use as a context manager; :meth:`close` drains admitted requests before
    stopping, so no accepted request is ever dropped. ``close(drain=False)``
    abandons queued/in-flight requests with :class:`EngineClosed` instead —
    the escape hatch when the search fn itself is wedged.
    """

    def __init__(
        self,
        search_fn: Callable[[np.ndarray, int, int], Tuple[np.ndarray, np.ndarray]],
        *,
        row_budget: int = 256,
        max_queue: int = 128,
        max_wait_s: float = 2e-3,
        dispatch_margin_s: float = 0.0,
        request_timeout_s: Optional[float] = None,
        cache: Optional[AnswerCache] = None,
        tree=None,
        corpus_token: Optional[str] = None,
        block_caches: Sequence = (),
        clock: Callable[[], float] = time.perf_counter,
        profiler=NULL_PROFILER,
    ):
        if row_budget < 1 or max_queue < 1:
            raise ValueError(
                f"row_budget and max_queue must be ≥ 1, got "
                f"{row_budget}/{max_queue}"
            )
        if max_wait_s < 0 or dispatch_margin_s < 0:
            raise ValueError("max_wait_s and dispatch_margin_s must be ≥ 0")
        if request_timeout_s is not None and request_timeout_s <= 0:
            raise ValueError(
                f"request_timeout_s must be > 0 when set, got "
                f"{request_timeout_s}"
            )
        if cache is not None and tree is None:
            raise ValueError("cache staging needs the tree to bind to")
        self.search_fn = search_fn
        try:
            self._accepts_chunk = (
                "chunk_rows" in inspect.signature(search_fn).parameters
            )
        except (TypeError, ValueError):
            self._accepts_chunk = False
        self._chunk_cap = int(getattr(search_fn, "chunk", 512))
        self.row_budget = int(row_budget)
        self.max_queue = int(max_queue)
        self.max_wait_s = float(max_wait_s)
        self.dispatch_margin_s = float(dispatch_margin_s)
        self.request_timeout_s = (
            None if request_timeout_s is None else float(request_timeout_s)
        )
        self.cache = cache
        self.profiler = profiler
        self.block_caches = tuple(block_caches)
        if cache is not None:
            cache.bind(tree, corpus_token)
        self.recorder = LatencyRecorder(clock)
        self._cv = threading.Condition()
        self._queue: "deque[_Pending]" = deque()
        self._closing = False
        self._abort = False
        self._inflight: Optional[List[_Pending]] = None
        # counters (under _cv's lock: the dispatcher and submit already hold it)
        self._admitted = 0
        self._shed = 0
        self._completed = 0
        self._failed = 0
        self._deadline_misses = 0
        self._timeouts = 0
        self._watchdog_restarts = 0
        self._degraded = 0
        self._n_batches = 0
        self._batch_tag = -1  # number of the batch last popped (dispatcher only)
        self._n_fragments = 0
        self._occupancy_sum = 0.0
        self._max_queue_depth = 0
        self._peak_batch_store_bytes = 0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        self._watchdog_stop = threading.Event()
        self._watchdog_tick = (
            0.02 if self.request_timeout_s is None
            else min(0.02, self.request_timeout_s / 4.0)
        )
        self._watchdog_thread = threading.Thread(
            target=self._watchdog_loop, daemon=True
        )
        self._watchdog_thread.start()

    # ---------------------------------------------------------------- admit
    def submit(
        self, rows: np.ndarray, k: int = 10, beam: int = 4,
        deadline_s: Optional[float] = None,
    ) -> ResultHandle:
        """Admit one request (``rows`` f32[r, d] query vectors, per-request
        ``k``/``beam``, optional relative latency ``deadline_s``) and return
        its :class:`ResultHandle`.

        Raises :class:`EngineSaturated` (and counts a shed) when the bounded
        queue is full — admission control is immediate rejection, never
        unbounded queueing — and :class:`EngineClosed` after :meth:`close`."""
        rows = np.asarray(rows)
        if rows.ndim != 2 or rows.shape[0] < 1:
            raise ValueError(
                f"request rows must be [r ≥ 1, d], got shape {rows.shape}"
            )
        if k < 1 or beam < 1:
            raise ValueError(f"k and beam must be ≥ 1, got k={k} beam={beam}")
        t = self.recorder.now()
        force_t = t + self.max_wait_s
        deadline = None
        if deadline_s is not None:
            deadline = t + float(deadline_s)
            force_t = min(force_t, deadline - self.dispatch_margin_s)
        handle = ResultHandle()
        with self._cv:
            if self._closing:
                raise EngineClosed("engine is closed")
            if len(self._queue) >= self.max_queue:
                self._shed += 1
                raise EngineSaturated(
                    f"queue full ({self.max_queue} requests) — shed"
                )
            self._queue.append(_Pending(
                rows=rows, k=int(k), beam=int(beam), t_admit=t,
                deadline=deadline, force_t=force_t, handle=handle,
            ))
            self._admitted += 1
            self._max_queue_depth = max(self._max_queue_depth, len(self._queue))
            self._cv.notify()
        return handle

    @property
    def queue_depth(self) -> int:
        """Requests currently waiting for dispatch."""
        with self._cv:
            return len(self._queue)

    # ------------------------------------------------------------- dispatch
    def _take_batch(self) -> List[_Pending]:
        """Pop FIFO requests up to ``row_budget`` rows (caller holds the
        lock; always pops at least one), number the batch, and record each
        request's ``"engine_queue"`` wait under that number."""
        batch: List[_Pending] = [self._queue.popleft()]
        rows = batch[0].rows.shape[0]
        while self._queue and rows + self._queue[0].rows.shape[0] <= self.row_budget:
            nxt = self._queue.popleft()
            rows += nxt.rows.shape[0]
            batch.append(nxt)
        self._batch_tag += 1
        if self.profiler.enabled:
            now = self.recorder.now()
            for p in batch:
                self.profiler.add("engine_queue", p.t_admit, now, tag=self._batch_tag)
        return batch

    def _loop(self) -> None:
        """Dispatcher thread: wait for fill-or-forcing-point, then execute.

        The in-flight batch is published as ``_inflight`` (set under the lock
        in the same critical section that pops it, cleared only after every
        handle is resolved) so the watchdog can expire or orphan-fail it —
        if this thread dies mid-batch, ``_inflight`` still names exactly the
        handles that would otherwise hang."""
        while True:
            with self._cv:
                if not self._queue:
                    with self.profiler.span("engine_wait"):
                        while not self._queue:
                            if self._closing or self._abort:
                                return
                            self._cv.wait(0.05)
                # wait for the batch to fill — but never past the oldest
                # pending request's forcing point (the watchdog may expire
                # queued requests concurrently, so re-check for emptiness)
                with self.profiler.span("engine_fill"):
                    while self._queue:
                        total = sum(p.rows.shape[0] for p in self._queue)
                        force_t = min(p.force_t for p in self._queue)
                        now = self.recorder.now()
                        if (total >= self.row_budget or now >= force_t
                                or self._closing or self._abort):
                            break
                        self._cv.wait(min(max(force_t - now, 0.0), 0.05))
                if self._abort:
                    return
                if not self._queue:
                    continue
                batch = self._take_batch()
                self._inflight = batch
            self._execute(batch)
            with self._cv:
                self._inflight = None

    # ------------------------------------------------------------- watchdog
    def _watchdog_loop(self) -> None:
        """Watchdog thread: one :meth:`_watchdog_pass` per tick until
        :meth:`close` stops it."""
        while not self._watchdog_stop.wait(self._watchdog_tick):
            self._watchdog_pass()

    def _watchdog_pass(self) -> None:
        """One watchdog sweep — the no-hang guarantee (DESIGN.md §10).

        (a) Dispatcher liveness: if the dispatcher thread died (a bug or
        BaseException below :meth:`_execute`'s own handler), fail its
        orphaned in-flight handles with :class:`EngineFault` and start a
        replacement dispatcher, so the engine keeps serving.
        (b) Request expiry (when ``request_timeout_s`` is set): fail every
        queued or in-flight request older than the budget with
        :class:`EngineTimeout` — resolution is set-once, so a later engine
        answer for an expired request is discarded, never double-counted."""
        with self._cv:
            stopped = self._closing or self._abort
            dead = not self._thread.is_alive()
        if dead and not stopped:
            with self._cv:
                orphans = list(self._inflight or [])
                self._inflight = None
                self._watchdog_restarts += 1
                replacement = threading.Thread(target=self._loop, daemon=True)
                self._thread = replacement
            err = EngineFault(
                "dispatcher thread died mid-batch; request abandoned "
                "(dispatcher restarted — resubmit if desired)"
            )
            n_orphaned = sum(
                1 for p in orphans if p.handle._resolve_error(err)
            )
            with self._cv:
                self._failed += n_orphaned
            replacement.start()
        budget = self.request_timeout_s
        if budget is None:
            return
        now = self.recorder.now()
        expired: List[_Pending] = []
        with self._cv:
            if any(now - p.t_admit > budget for p in self._queue):
                keep: "deque[_Pending]" = deque()
                for p in self._queue:
                    (expired if now - p.t_admit > budget else keep).append(p)
                self._queue = keep
            expired.extend(
                p for p in (self._inflight or [])
                if now - p.t_admit > budget
            )
        if not expired:
            return
        n_timed_out = 0
        for p in expired:
            err = EngineTimeout(
                f"request exceeded request_timeout_s={budget:g}s "
                f"(admitted {now - p.t_admit:.3f}s ago) — expired by the "
                f"engine watchdog"
            )
            if p.handle._resolve_error(err):
                n_timed_out += 1
        with self._cv:
            self._timeouts += n_timed_out
            self._failed += n_timed_out

    def _fragments(self, batch: List[_Pending]):
        """Group a drained batch by (k, beam, request row bucket), preserving
        FIFO order within each group — one engine call per distinct setting
        and pow2 size class, so the chunk-aligned dispatch (see
        :meth:`_execute`) keeps every request's answer bit-identical to its
        standalone offline call. Requests too large to chunk-align (rows >
        the search fn's default chunk) get ``bucket None`` and dispatch solo
        with offline semantics."""
        groups: "Dict[Tuple[int, int, Optional[int]], List[_Pending]]" = {}
        for p in batch:
            r = p.rows.shape[0]
            bucket = None if r > self._chunk_cap else pow2_bucket(r)
            groups.setdefault((p.k, p.beam, bucket), []).append(p)
        return groups

    def _call(self, x, k, beam, chunk_rows=None):
        """One offline-engine call, forwarding ``chunk_rows`` only when the
        search fn takes it (custom callables without the seam still work —
        they just don't get the chunk-alignment bit-identity guarantee).

        Normalizes the return to ``(docs, dist, report)``: degrade-mode
        engines (``on_fault="degrade"``) return a third
        :class:`repro.core.faults.FaultReport` element; plain engines get
        ``report=None``."""
        with self.profiler.span("engine_call", tag=self._batch_tag):
            if chunk_rows is not None and self._accepts_chunk:
                out = self.search_fn(x, k, beam, chunk_rows=chunk_rows)
            else:
                out = self.search_fn(x, k, beam)
        if len(out) == 3:
            docs, dist, report = out
        else:
            docs, dist = out
            report = None
        return np.asarray(docs), np.asarray(dist), report

    def _run_fragment(self, group: List[_Pending], k: int, beam: int,
                      bucket: Optional[int]):
        """Execute one (k, beam, bucket) fragment and return per-request
        ``(docs, dist)`` answers in group order.

        Chunk-aligned dispatch (``bucket`` set): each request's rows are
        padded to the bucket, concatenated, and run with ``chunk_rows =
        bucket`` — every query chunk then gathers exactly one request's
        (padded) rows, the same tensor its standalone offline call gathers,
        so answers are bit-identical per request. The fragment's chunk count
        is padded to a power of two as well (whole dummy chunks of the last
        row) so compiles stay bounded per (bucket, pow2 chunk count), not per
        batch composition. ``bucket None`` (oversized requests) dispatches
        each request in the group alone with the search fn's own default
        chunking — the literal offline call per request.

        With a cache staged, hit rows are answered without engine rows and
        the deduplicated miss batch runs at ``chunk_rows = 1`` — each cache
        entry is then the bit-exact answer of a standalone single-row call,
        so repeat single-row requests stay bit-identical however they
        batch. A *degraded* miss batch (on_fault="degrade" with quarantined
        blocks) is scattered to its requests but **not** inserted into the
        cache — a degraded answer must never outlive the fault that produced
        it.

        Answers come back as ``(docs, dist, report)`` triples; in a
        chunk-aligned fragment every request shares the fragment's report
        (corpus-side quarantine affects the whole call)."""
        if bucket is None:
            return [self._call(p.rows, k, beam) for p in group]
        x, bounds = concat_request_rows([p.rows for p in group])
        if self.cache is not None:
            report = None
            docs, dist, miss = cache_stage(self.cache, x, k, beam)
            if miss:
                rep = np.asarray([rows[0] for rows in miss.values()])
                xm, n_miss = pow2_pad_rows(x[rep])
                d_new, s_new, report = self._call(xm, k, beam, chunk_rows=1)
                if report is not None and report.degraded:
                    # scatter only — degraded answers stay out of the cache
                    for j, (_, rows) in enumerate(miss.items()):
                        for i in rows:
                            docs[i], dist[i] = d_new[j], s_new[j]
                else:
                    cache_fill(self.cache, miss, d_new[:n_miss],
                               s_new[:n_miss], docs, dist)
            return [
                (d, s, report)
                for d, s in split_batch_answers(docs, dist, bounds)
            ]
        parts = [pow2_pad_rows(p.rows, to=bucket)[0] for p in group]
        n_pad = pow2_bucket(len(parts)) - len(parts)
        parts.extend(np.repeat(parts[-1][-1:], bucket, axis=0)
                     for _ in range(n_pad))
        xb, _ = concat_request_rows(parts)
        d_all, s_all, report = self._call(xb, k, beam, chunk_rows=bucket)
        return [
            (d_all[i * bucket:i * bucket + p.rows.shape[0]].copy(),
             s_all[i * bucket:i * bucket + p.rows.shape[0]].copy(),
             report)
            for i, p in enumerate(group)
        ]

    def _execute(self, batch: List[_Pending]) -> None:
        """Run one dispatched batch: per-(k, beam, bucket) fragment through
        :meth:`_run_fragment`, then answer demux, latency + occupancy +
        per-batch store-residency accounting."""
        for c in self.block_caches:
            c.reset_peak()
        n_frags = 0
        batch_span = self.profiler.span("engine_batch", tag=self._batch_tag)
        batch_span.__enter__()
        try:
            for (k, beam, bucket), group in self._fragments(batch).items():
                n_frags += 1
                answers = self._run_fragment(group, k, beam, bucket)
                if len(answers) != len(group):
                    raise RuntimeError(
                        f"fragment (k={k}, beam={beam}, bucket={bucket}) "
                        f"returned {len(answers)} answers for "
                        f"{len(group)} requests"
                    )
                for p, (d, s, report) in zip(group, answers):
                    t_done = self.recorder.now()
                    missed = p.deadline is not None and t_done > p.deadline
                    degraded = report is not None and report.degraded
                    p.handle.deadline_missed = missed
                    p.handle.degraded = degraded
                    p.handle.report = report
                    if p.handle._resolve((d, s)):
                        # a watchdog-expired handle keeps its timeout;
                        # only a winning resolve counts as completed
                        self.recorder.record(p.t_admit, t_done)
                        with self._cv:
                            self._completed += 1
                            if missed:
                                self._deadline_misses += 1
                            if degraded:
                                self._degraded += 1
        except BaseException as e:
            for p in batch:
                if p.handle._resolve_error(e):
                    with self._cv:
                        self._failed += 1
        finally:
            batch_span.__exit__(None, None, None)
            store_peak = sum(
                int(c.peak_resident_bytes) for c in self.block_caches
            )
            with self._cv:
                self._n_batches += 1
                self._n_fragments += n_frags
                self._occupancy_sum += (
                    sum(p.rows.shape[0] for p in batch) / self.row_budget
                )
                self._peak_batch_store_bytes = max(
                    self._peak_batch_store_bytes, store_peak
                )

    # ---------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Serving report snapshot: latency percentiles (ms), QPS, admission
        counters (admitted/completed/shed/failed/deadline_misses), queue
        depth (current + high-water), batch counts + mean row occupancy,
        per-batch peak store residency, and the answer-cache stats when one
        is staged."""
        with self._cv:
            snap = dict(
                admitted=self._admitted,
                completed=self._completed,
                shed=self._shed,
                failed=self._failed,
                deadline_misses=self._deadline_misses,
                timeouts=self._timeouts,
                watchdog_restarts=self._watchdog_restarts,
                degraded=self._degraded,
                queue_depth=len(self._queue),
                max_queue_depth=self._max_queue_depth,
                n_batches=self._n_batches,
                n_fragments=self._n_fragments,
                batch_occupancy=(
                    self._occupancy_sum / self._n_batches
                    if self._n_batches else 0.0
                ),
                peak_batch_store_bytes=self._peak_batch_store_bytes,
            )
        snap["latency_ms"] = self.recorder.percentiles()
        snap["qps"] = self.recorder.throughput()
        if self.cache is not None:
            snap["cache"] = self.cache.stats
        return snap

    # ---------------------------------------------------------------- close
    def close(self, drain: bool = True) -> None:
        """Stop admitting and shut down (idempotent).

        ``drain=True`` (default): every already-admitted request completes
        before the dispatcher joins — no accepted request is ever dropped.
        ``drain=False``: queued and in-flight requests are *failed* with
        :class:`EngineClosed` immediately, so their callers unblock even if
        the search fn is wedged; the dispatcher thread is abandoned (daemon)
        if it does not exit within a grace period and any late answer it
        produces is discarded by set-once resolution."""
        with self._cv:
            self._closing = True
            dropped: List[_Pending] = []
            if not drain:
                self._abort = True
                dropped = list(self._queue)
                self._queue.clear()
                dropped.extend(self._inflight or [])
            self._cv.notify_all()
        if drain:
            self._thread.join()
        else:
            err = EngineClosed(
                "engine closed with drain=False; request abandoned"
            )
            n_dropped = sum(
                1 for p in dropped if p.handle._resolve_error(err)
            )
            with self._cv:
                self._failed += n_dropped
            self._thread.join(timeout=1.0)
        self._watchdog_stop.set()
        self._watchdog_thread.join(timeout=1.0)

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
