"""Continuous-batching serving engine (DESIGN.md §8): every answer the engine
hands back must be bit-identical to the offline engine on the same rows —
across mixed per-request (k, beam) settings, dense and ELL corpora, and (in a
forced-8-device subprocess) the sharded and store-backed paths. Overload must
shed at a bounded queue, never queue unboundedly; the deadline forcing point
must dispatch an underfull batch early; the latency recorder's arithmetic is
pinned through a fake clock; close() drains every admitted request."""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest
import jax.numpy as jnp

from fixtures import build_tree, clustered_corpus, random_corpus, sparsify, corpus_data

from repro.core.engine import (
    EngineClosed,
    EngineSaturated,
    LatencyRecorder,
    ServingEngine,
    make_search_fn,
)
from repro.core.profile import Profiler
from repro.core.query import AnswerCache, topk_search
from repro.launch.engine import (
    open_loop_arrivals,
    report_lines,
    request_pool,
    run_load,
    submit_all,
)

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
_TESTS = os.path.abspath(os.path.dirname(__file__))


class FakeClock:
    """Deterministic monotonic clock: returns a scripted value, advanced by
    the test."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------- recorder

def test_latency_recorder_fake_clock_exact():
    # the monotonic-clock regression seam: scripted clock, exact arithmetic
    clk = FakeClock()
    rec = LatencyRecorder(clock=clk)
    t0 = rec.now()
    clk.advance(0.010)
    assert rec.record(t0) == pytest.approx(0.010)
    t1 = rec.now()
    clk.advance(0.030)
    rec.record(t1)
    clk.advance(0.5)
    t2 = rec.now()
    clk.advance(0.020)
    rec.record(t2)
    assert len(rec) == 3
    p = rec.percentiles((50, 95, 99))
    # samples (ms): 10, 30, 20 -> p50 exactly the median
    assert p["p50"] == pytest.approx(20.0)
    assert p["p95"] == pytest.approx(np.percentile([10.0, 30.0, 20.0], 95))
    assert p["p99"] <= 30.0 + 1e-9
    # span = first admit (0.0) .. last completion (0.56)
    assert rec.throughput() == pytest.approx(3 / 0.56)


def test_latency_recorder_empty():
    rec = LatencyRecorder()
    assert len(rec) == 0
    assert rec.percentiles() == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    assert rec.throughput() == 0.0


def test_latency_recorder_immune_to_wall_clock_steps():
    # an NTP-style wall-clock step must not corrupt samples: the recorder
    # only ever differences its injected clock, which is monotonic here
    clk = FakeClock(1000.0)
    rec = LatencyRecorder(clock=clk)
    t0 = rec.now()
    clk.advance(0.005)  # a wall clock could jump backwards; perf_counter not
    rec.record(t0)
    assert rec.percentiles()["p50"] == pytest.approx(5.0)


# ---------------------------------------------------------------- helpers

def _mini_case(sparse=False):
    rng = np.random.default_rng(3 if sparse else 2)
    x = clustered_corpus(rng, n_clusters=4, per_cluster=40, d=8)
    if sparse:
        x = sparsify(rng, x, density=0.5)
    data = corpus_data(x, sparse)
    tree = build_tree(data, order=6, medoid=sparse, batch_size=32, seed=1)
    q = x[:40] + 0.05 * rng.normal(0, 1, (40, 8)).astype(np.float32)
    return tree, q.astype(np.float32)


def _offline(tree, rows, k, beam):
    d, s = topk_search(tree, jnp.asarray(rows), k=k, beam=beam)
    return np.asarray(d), np.asarray(s)


def _assert_bit_identical(got, want):
    d1, s1 = got
    d2, s2 = want
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


# ------------------------------------------------------------ bit-identity

@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "ell"])
def test_engine_answers_bit_identical_to_offline(sparse):
    tree, q = _mini_case(sparse)
    fn = make_search_fn(tree)
    for b in (1, 2, 4, 8):  # warm the chunk-aligned buckets outside the engine
        fn(q[:b], 5, 3, chunk_rows=b)
    reqs = [q[0:1], q[1:4], q[4:6], q[6:13], q[13:14]]
    with ServingEngine(fn, row_budget=8, max_queue=32, max_wait_s=5e-3) as eng:
        handles = [eng.submit(r, k=5, beam=3) for r in reqs]
        results = [h.result(timeout=120) for h in handles]
    for r, got in zip(reqs, results):
        _assert_bit_identical(got, _offline(tree, r, 5, 3))
    st = eng.stats()
    assert st["completed"] == len(reqs) and st["failed"] == 0


def test_engine_mixed_k_beam_bucketing_bit_identical():
    # the satellite: mixed (k, beam) requests in one dispatched batch must
    # each match a standalone offline call with the same settings
    tree, q = _mini_case()
    fn = make_search_fn(tree)
    settings = [(5, 2), (7, 3), (5, 2), (3, 1), (7, 3)]
    for kk, bb in set(settings):  # warm each setting's chunk-aligned shapes
        for s in (4, 8):
            fn(q[:s], kk, bb, chunk_rows=4)
    reqs = [(q[i * 3:(i + 1) * 3], kk, bb)
            for i, (kk, bb) in enumerate(settings)]
    with ServingEngine(fn, row_budget=64, max_queue=32,
                       max_wait_s=0.25) as eng:
        handles = [eng.submit(r, k=kk, beam=bb) for r, kk, bb in reqs]
        results = [h.result(timeout=120) for h in handles]
    for (r, kk, bb), got in zip(reqs, results):
        _assert_bit_identical(got, _offline(tree, r, kk, bb))
    st = eng.stats()
    # 15 rows over budget 64 with a generous max_wait: one batch, one
    # fragment per distinct (k, beam)
    assert st["n_fragments"] >= len(set(settings))


def test_engine_oversized_request_still_served():
    # a single request larger than row_budget dispatches alone
    tree, q = _mini_case()
    fn = make_search_fn(tree)
    fn(q[:1], 4, 2)
    with ServingEngine(fn, row_budget=4, max_queue=8) as eng:
        got = eng.submit(q[:11], k=4, beam=2).result(timeout=120)
    _assert_bit_identical(got, _offline(tree, q[:11], 4, 2))


def test_engine_two_oversized_same_setting_requests_both_served():
    # regression: two bucket-None requests (rows > the search fn's chunk)
    # sharing (k, beam) land in ONE fragment; each must get its own offline
    # call — the engine once answered only the first and left every later
    # handle in the group unset (its caller blocked forever)
    tree, q = _mini_case()
    fn = make_search_fn(tree, chunk=8)
    reqs = [q[:11], q[3:13]]
    for r in reqs:  # warm the offline shapes outside the engine
        fn(r, 4, 2)
    with ServingEngine(fn, row_budget=64, max_queue=8,
                       max_wait_s=0.25) as eng:
        handles = [eng.submit(r, k=4, beam=2) for r in reqs]
        results = [h.result(timeout=120) for h in handles]
    for r, got in zip(reqs, results):
        _assert_bit_identical(got, fn(r, 4, 2))
    st = eng.stats()
    assert st["completed"] == len(reqs) and st["failed"] == 0


# ---------------------------------------------------------------- overload

def test_engine_overload_sheds_at_bounded_queue():
    release = threading.Event()

    def slow_fn(x, k, beam):
        release.wait(30)
        n = x.shape[0]
        return (np.zeros((n, k), np.int32), np.zeros((n, k), np.float32))

    rows = np.zeros((1, 4), np.float32)
    eng = ServingEngine(slow_fn, row_budget=1, max_queue=4, max_wait_s=0.0)
    try:
        handles, sheds = [], 0
        # first submit occupies the dispatcher; queue then fills to max_queue
        for _ in range(12):
            try:
                handles.append(eng.submit(rows, k=3, beam=1))
            except EngineSaturated:
                sheds += 1
            time.sleep(0.01)
        st = eng.stats()
        assert sheds > 0 and st["shed"] == sheds
        assert st["max_queue_depth"] <= 4  # the bound held
        assert st["queue_depth"] <= 4
    finally:
        release.set()
        eng.close()
    # every admitted request still completes (close() drains)
    for h in handles:
        assert h.done()
        d, _ = h.result(timeout=1)
        assert d.shape == (1, 3)
    assert eng.stats()["completed"] == len(handles)


def test_engine_failure_propagates_to_handles():
    def bad_fn(x, k, beam):
        raise RuntimeError("engine exploded")

    with ServingEngine(bad_fn, row_budget=4, max_queue=8) as eng:
        h = eng.submit(np.zeros((2, 3), np.float32), k=2, beam=1)
        with pytest.raises(RuntimeError, match="engine exploded"):
            h.result(timeout=60)
    assert eng.stats()["failed"] == 1


# ---------------------------------------------------------------- deadlines

def test_engine_deadline_forces_early_dispatch():
    # max_wait is an eternity; a request deadline must force dispatch anyway
    tree, q = _mini_case()
    fn = make_search_fn(tree)
    fn(q[:1], 4, 2, chunk_rows=1)
    with ServingEngine(fn, row_budget=64, max_queue=8,
                       max_wait_s=30.0) as eng:
        t0 = time.perf_counter()
        h = eng.submit(q[:1], k=4, beam=2, deadline_s=0.05)
        got = h.result(timeout=10)
        waited = time.perf_counter() - t0
    assert waited < 5.0  # nowhere near max_wait_s
    _assert_bit_identical(got, _offline(tree, q[:1], 4, 2))


def test_engine_deadline_miss_flagged_answer_still_delivered():
    def slow_fn(x, k, beam):
        time.sleep(0.08)
        n = x.shape[0]
        return (np.zeros((n, k), np.int32), np.zeros((n, k), np.float32))

    with ServingEngine(slow_fn, row_budget=4, max_queue=8,
                       max_wait_s=0.0) as eng:
        h = eng.submit(np.zeros((1, 3), np.float32), k=2, beam=1,
                       deadline_s=0.001)
        d, s = h.result(timeout=60)
    assert h.deadline_missed
    assert d.shape == (1, 2)
    assert eng.stats()["deadline_misses"] == 1


def test_engine_waits_to_fill_until_forcing_point():
    # two staggered requests within max_wait coalesce into one batch
    tree, q = _mini_case()
    fn = make_search_fn(tree)
    fn(q[:1], 4, 2, chunk_rows=1)
    fn(q[:2], 4, 2, chunk_rows=1)
    with ServingEngine(fn, row_budget=64, max_queue=8,
                       max_wait_s=0.3) as eng:
        h1 = eng.submit(q[0:1], k=4, beam=2)
        time.sleep(0.02)
        h2 = eng.submit(q[1:2], k=4, beam=2)
        r1, r2 = h1.result(timeout=120), h2.result(timeout=120)
    st = eng.stats()
    assert st["n_batches"] == 1 and st["completed"] == 2
    _assert_bit_identical(r1, _offline(tree, q[0:1], 4, 2))
    _assert_bit_identical(r2, _offline(tree, q[1:2], 4, 2))


def _until(cond, timeout=10.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, "condition never held"
        time.sleep(0.005)


def test_engine_spans_on_fake_clock():
    """The dispatcher's spans on the engine's fake clock: ``engine_wait``
    until a request comes, ``engine_fill`` until the batch fills, one
    ``engine_queue`` record per request from admission to its batch's pop,
    and ``engine_batch``/``engine_call`` — all three under the batch's
    number."""
    clk = FakeClock()
    prof = Profiler(clock=clk)

    def fn(x, k, beam):  # a call takes 2 s of the fake clock
        clk.advance(2.0)
        return np.zeros((len(x), k), np.int32), np.zeros((len(x), k), np.float32)

    def named(name):
        return [(r.t0, r.t1, r.tag) for r in prof.records if r.name == name]

    row = np.zeros((1, 3), np.float32)
    eng = ServingEngine(fn, row_budget=2, max_queue=8, max_wait_s=1e9,
                        clock=clk, profiler=prof)
    try:
        clk.t = 1.0
        h1 = eng.submit(row, k=2, beam=1)
        _until(lambda: named("engine_wait"))
        time.sleep(0.05)  # the dispatcher now waits for the batch to fill
        clk.t = 3.0
        h2 = eng.submit(row, k=2, beam=1)
        h1.result(timeout=10), h2.result(timeout=10)
        _until(lambda: named("engine_batch"))
        clk.t = 6.0
        hs = [eng.submit(row, k=2, beam=1) for _ in range(2)]
        for h in hs:
            h.result(timeout=10)
        _until(lambda: len(named("engine_batch")) == 2)
    finally:
        eng.close()
    assert sorted(named("engine_queue")) == [
        (1.0, 3.0, 0), (3.0, 3.0, 0), (6.0, 6.0, 1), (6.0, 6.0, 1)]
    assert named("engine_batch") == [(3.0, 5.0, 0), (6.0, 8.0, 1)]
    calls = [r for r in prof.records if r.name == "engine_call"]
    assert [(r.t0, r.t1, r.tag, r.parent) for r in calls] == [
        (3.0, 5.0, 0, "engine_batch"), (6.0, 8.0, 1, "engine_batch")]
    assert named("engine_fill")[0] == (1.0, 3.0, None)
    waits = named("engine_wait")
    assert waits[0][1] == 1.0 and waits[1][1] == 6.0
    assert all(r.depth == 0 for r in prof.records if r.name != "engine_call")


# ------------------------------------------------------------ cache staging

def test_engine_cache_stage_hits_and_bit_identity():
    tree, q = _mini_case()
    fn = make_search_fn(tree)
    for m in (1, 2):  # cache misses run at single-row chunking
        fn(q[:m], 5, 2, chunk_rows=1)
    cache = AnswerCache(32)
    with ServingEngine(fn, row_budget=8, max_queue=32, cache=cache,
                       tree=tree) as eng:
        first = eng.submit(q[0:1], k=5, beam=2).result(timeout=120)
        again = eng.submit(q[0:1], k=5, beam=2).result(timeout=120)
        # duplicate rows inside one request dedup to one engine row
        dup = eng.submit(np.concatenate([q[0:1], q[0:1]]), k=5,
                         beam=2).result(timeout=120)
    _assert_bit_identical(first, _offline(tree, q[0:1], 5, 2))
    _assert_bit_identical(again, first)
    # cache entries are per-row answers, so the reference for the dup
    # request is the single-row offline answer scattered to both rows
    d1, s1 = _offline(tree, q[0:1], 5, 2)
    _assert_bit_identical(
        dup, (np.concatenate([d1, d1]), np.concatenate([s1, s1])))
    st = eng.stats()
    assert st["cache"]["hits"] >= 2  # the repeat + both dup rows
    assert cache.stats["misses"] >= 1


def test_engine_cache_requires_tree():
    with pytest.raises(ValueError, match="tree"):
        ServingEngine(lambda x, k, b: None, cache=AnswerCache(4))


# ---------------------------------------------------------------- lifecycle

def test_engine_submit_after_close_raises():
    fn = lambda x, k, b: (np.zeros((x.shape[0], k), np.int32),
                          np.zeros((x.shape[0], k), np.float32))
    eng = ServingEngine(fn, row_budget=4, max_queue=4)
    eng.close()
    eng.close()  # idempotent
    with pytest.raises(EngineClosed):
        eng.submit(np.zeros((1, 3), np.float32))


def test_engine_submit_validation():
    fn = lambda x, k, b: (np.zeros((x.shape[0], k), np.int32),
                          np.zeros((x.shape[0], k), np.float32))
    with ServingEngine(fn, row_budget=4, max_queue=4) as eng:
        with pytest.raises(ValueError):
            eng.submit(np.zeros((3,), np.float32))  # not [r, d]
        with pytest.raises(ValueError):
            eng.submit(np.zeros((0, 3), np.float32))  # r = 0
        with pytest.raises(ValueError):
            eng.submit(np.zeros((1, 3), np.float32), k=0)
        with pytest.raises(ValueError):
            eng.submit(np.zeros((1, 3), np.float32), beam=0)


def test_engine_ctor_validation():
    fn = lambda x, k, b: None
    with pytest.raises(ValueError):
        ServingEngine(fn, row_budget=0)
    with pytest.raises(ValueError):
        ServingEngine(fn, max_queue=0)
    with pytest.raises(ValueError):
        ServingEngine(fn, max_wait_s=-1.0)


def test_result_handle_timeout():
    release = threading.Event()

    def slow_fn(x, k, beam):
        release.wait(30)
        return (np.zeros((x.shape[0], k), np.int32),
                np.zeros((x.shape[0], k), np.float32))

    eng = ServingEngine(slow_fn, row_budget=4, max_queue=4, max_wait_s=0.0)
    try:
        h = eng.submit(np.zeros((1, 3), np.float32), k=2, beam=1)
        with pytest.raises(TimeoutError):
            h.result(timeout=0.05)
    finally:
        release.set()
        eng.close()
    assert h.result(timeout=1)[0].shape == (1, 2)


# --------------------------------------------------------------- load side

def test_open_loop_arrivals_poisson_seeded():
    a = open_loop_arrivals(100.0, 50, seed=7)
    b = open_loop_arrivals(100.0, 50, seed=7)
    np.testing.assert_array_equal(a, b)
    assert a[0] == 0.0 and (np.diff(a) >= 0).all()
    # mean gap ~ 1/rate
    assert np.mean(np.diff(a)) == pytest.approx(0.01, rel=0.6)
    with pytest.raises(ValueError):
        open_loop_arrivals(0.0, 5)
    with pytest.raises(ValueError):
        open_loop_arrivals(10.0, 0)


def test_run_load_end_to_end_and_report_lines():
    tree, q = _mini_case()
    fn = make_search_fn(tree)
    for s in (1, 2, 4, 8):
        fn(q[:s], 5, 2, chunk_rows=1)
    pool = request_pool(q, n_requests=24, rows_per_request=1, k=5, beam=2,
                        seed=1)
    with ServingEngine(fn, row_budget=8, max_queue=64,
                       max_wait_s=2e-3) as eng:
        stats = run_load(eng, pool, rate_qps=400.0, seed=2)
    assert stats["completed"] == stats["admitted"] == 24
    assert stats["shed"] == 0
    assert stats["target_qps"] == 400.0 and stats["offered_qps"] > 0
    assert stats["latency_ms"]["p50"] > 0 and stats["qps"] > 0
    lines = report_lines(stats, label="t")
    joined = "\n".join(lines)
    assert "t latency: p50=" in joined and "qps=" in joined
    assert "t batching:" in joined and "max_queue_depth=" in joined


def test_submit_all_counts_sheds_as_none():
    release = threading.Event()

    def slow_fn(x, k, beam):
        release.wait(30)
        return (np.zeros((x.shape[0], k), np.int32),
                np.zeros((x.shape[0], k), np.float32))

    pool = [(np.zeros((1, 3), np.float32), 2, 1) for _ in range(10)]
    eng = ServingEngine(slow_fn, row_budget=1, max_queue=2, max_wait_s=0.0)
    try:
        handles, stats = submit_all(eng, pool, rate_qps=1e6, seed=0)
    finally:
        release.set()
        eng.close()
    assert len(handles) == 10
    assert any(h is None for h in handles)  # sheds surfaced as None
    assert stats["target_qps"] == 1e6


def test_request_pool_shapes_and_validation():
    x = np.arange(40, dtype=np.float32).reshape(10, 4)
    pool = request_pool(x, n_requests=6, rows_per_request=3, k=4, beam=2,
                        seed=0)
    assert len(pool) == 6
    for rows, k, beam in pool:
        assert rows.shape == (3, 4) and (k, beam) == (4, 2)
    with pytest.raises(ValueError):
        request_pool(x, 3, rows_per_request=0)


# -------------------------------------------- sharded + store-backed paths

_SHARDED_SCRIPT = textwrap.dedent(
    """
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, {src!r})
    sys.path.insert(0, {tests!r})
    import numpy as np, jax, jax.numpy as jnp
    from fixtures import clustered_corpus, store_case
    from repro.core import ktree as kt
    from repro.core.backend import shard_from_store
    from repro.core.engine import ServingEngine, make_search_fn
    from repro.core.query import topk_search_sharded
    from repro.core.store import open_store
    from repro.launch.engine import request_pool, run_load

    out = {{}}
    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)

    def serve_and_compare(fn, q, tag, **eng_kw):
        for s in (2, 4, 8, 16):  # warm the chunk-aligned batch shapes
            fn(np.ascontiguousarray(q[:s]), 6, 3, chunk_rows=2)
        pool = request_pool(q, n_requests=20, rows_per_request=2, k=6,
                            beam=3, seed=5)
        with ServingEngine(fn, row_budget=16, max_queue=64,
                           max_wait_s=2e-3, **eng_kw) as eng:
            handles = [eng.submit(r, k=k, beam=b) for r, k, b in pool]
            res = [h.result(timeout=600) for h in handles]
        ok = True
        for (r, k, b), (d_e, s_e) in zip(pool, res):
            d_o, s_o = fn(r, k, b)
            ok = ok and bool((np.asarray(d_e) == np.asarray(d_o)).all())
            ok = ok and bool((np.asarray(s_e) == np.asarray(s_o)).all())
        st = eng.stats()
        out[tag] = dict(bit_identical=ok, completed=st["completed"],
                        failed=st["failed"],
                        peak_store=st["peak_batch_store_bytes"])

    # in-memory sharded corpus (uneven remainder over 8 shards)
    x = clustered_corpus(rng, n_clusters=5, per_cluster=60, d=8)
    tree = kt.build(jnp.asarray(x), order=8, batch_size=32)
    q = (x[:64] + 0.05 * rng.normal(0, 1, (64, 8))).astype(np.float32)
    serve_and_compare(make_search_fn(tree, mesh=mesh, corpus=x), q,
                      "sharded_mem")

    # store-backed sharded corpus: block caches report per-batch residency
    with tempfile.TemporaryDirectory() as td:
        case = store_case(td, sparse=False)
        store = open_store(case.path)
        sshards = shard_from_store(mesh, store, budget_bytes=1 << 16)
        fn = make_search_fn(case.tree, mesh=mesh, corpus=sshards)
        qs = case.x[:32].astype(np.float32)
        serve_and_compare(
            fn, qs, "sharded_store",
            block_caches=[p.store.cache for p in sshards.parts])
        out["budget_bound"] = dict(
            peak=out["sharded_store"]["peak_store"],
            bound=8 * (1 << 16),
        )
    print(json.dumps(out))
    """
)


@pytest.mark.slow
def test_engine_sharded_and_store_backed_bit_identity():
    script = _SHARDED_SCRIPT.format(src=_SRC, tests=_TESTS)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for tag in ("sharded_mem", "sharded_store"):
        assert out[tag]["bit_identical"], out[tag]
        assert out[tag]["completed"] == 20 and out[tag]["failed"] == 0
    # a store-backed batch touched disk and stayed within the budget bound
    assert out["sharded_store"]["peak_store"] > 0
    assert out["budget_bound"]["peak"] <= out["budget_bound"]["bound"]
