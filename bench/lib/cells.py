"""Set-up, measured window and check of one cell, for each kind of traffic.

A traffic file's ``kind`` picks the driver: ``open_loop`` (top-k requests
through ``ServingEngine`` at a fixed rate, shaped as ``lib/load.py`` reads
the file) or ``builds`` (whole ``ktree.build`` runs of the corpus, back to
back; ``corpus_seed`` fixes the corpus for every seed, ``shuffle`` permutes
its insertion order, and ``work_seed`` fixes that order and the build's key
too, so that every seed builds the same tree and does the same work).
Everything a driver needs is in the configuration file and the traffic file.
The configuration's ``representation`` picks the route: ``dense`` and
``sparse_medoid`` rows route as they are; ``rp`` (Random Indexing, DESIGN.md
§5.1) routes ELL rows through the projection its ``rp`` block states, and
search rescores the leaf pools from the ELL rows. The program is reached only
through ``make_backend``/``sparse_backend_from_csr``, ``make_projection``,
``RandomProjBackend.wrap`` (and its base's ``take``, to warm the rescore's
gathers), ``ktree.build``, ``make_search_fn`` and ``ServingEngine``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import queue
import sys
import threading
import time

import numpy as np

from lib import corpus as bcorpus, load, projection, reference
from lib import trace as btrace

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_hits",
    "/jax/compilation_cache/cache_misses",
)
RESULT_WAIT_S = 60.0  # how long past the window's close an answer may come
CHECK_SAMPLE = 256    # served answers the reference recomputes


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX's compile and compile-cache events while ``armed``."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.armed and name in COMPILE_EVENTS:
            self.count += 1

    def _duration(self, name, _secs, **_):
        self._event(name)


@dataclasses.dataclass
class Streams:
    """Independent generators drawn from one ``--seed``."""
    seed: int

    def __post_init__(self):
        ss = np.random.SeedSequence(self.seed % (1 << 64))
        kids = ss.spawn(10)
        self.split, self.arrivals, self.pool, self.sample = (
            np.random.default_rng(k) for k in kids[:4])
        self.key = int(kids[4].generate_state(1)[0] & 0x7FFFFFFF)
        (self.order, self.tenants, self.trace_arrivals, self.trace_pool,
         self.trace_tenants) = (np.random.default_rng(k) for k in kids[5:])


def round_bf16(m: bcorpus.Csr) -> bcorpus.Csr:
    """The control's input: every stored value rounded through bfloat16."""
    import ml_dtypes

    return dataclasses.replace(
        m, data=m.data.astype(ml_dtypes.bfloat16).astype(np.float32))


def is_rp(cfg: dict) -> bool:
    """A Random Indexing configuration: the tree routes in a projection."""
    return cfg["representation"] == "rp"


def route_dim(cfg: dict, n_cols: int) -> int:
    """Width of the space the tree routes in: the projection's, or the
    rows' own ``n_cols``."""
    return int(cfg["rp"]["dim"]) if is_rp(cfg) else n_cols


def base_backend(cfg: dict, m: bcorpus.Csr):
    """Hand the corpus rows to the program in the configuration's layout:
    dense, or ELL at the configuration's ``nnz_max`` (``sparse_medoid`` and
    the base rows of ``rp``)."""
    import jax.numpy as jnp

    from repro.core.backend import make_backend, sparse_backend_from_csr
    from repro.sparse.csr import Csr

    if cfg["representation"] == "dense":
        return make_backend(m.dense())
    longest = int(np.diff(m.indptr).max())
    if longest > cfg["nnz_max"]:
        raise ValueError(f"a document has {longest} terms, over the "
                         f"configuration's ELL width {cfg['nnz_max']}")
    csr = Csr(jnp.asarray(m.data), jnp.asarray(m.indices),
              jnp.asarray(m.indptr), m.n_cols)
    return sparse_backend_from_csr(csr, nnz_max=cfg["nnz_max"])


def routed(cfg: dict, base):
    """The backend the tree is built on: ``base`` itself, or for ``rp`` the
    base projected through the configuration's projection."""
    if not is_rp(cfg):
        return base
    from repro.core.backend import RandomProjBackend, make_projection

    rp = cfg["rp"]
    return RandomProjBackend.wrap(base, make_projection(
        base.dim, int(rp["dim"]), seed=int(rp["seed"]), kind=rp["kind"]))


def program_backend(cfg: dict, m: bcorpus.Csr):
    """The corpus rows as the backend the tree is built on."""
    return routed(cfg, base_backend(cfg, m))


def route(cfg: dict, docs: bcorpus.Csr):
    """The reference's route space over ``docs``: None where the tree routes
    in the rows' own space, else the float64 projection."""
    if not is_rp(cfg):
        return None
    return reference.Projected(projection.matrix(docs.n_cols, cfg["rp"]), docs)


def search_program(cfg: dict) -> str:
    """The jitted program that descends the tree for one chunk of queries."""
    return "_chunk_candidates_jit" if is_rp(cfg) else "_beam_search"


def rescore_gather_sizes(calls, chunk_cap: int, order: int) -> list:
    """Every row count of the RP rescore's gather (``_rp_row_fetcher``):
    the program pads a chunk's candidate union, at most ``chunk rows × beam
    × order`` documents, to a power of two, each of which compiles its own
    gather."""
    top = max(load.pow2((c or min(n, chunk_cap)) * beam * order)
              for _, beam, n, c in calls)
    return [1 << i for i in range(top.bit_length())]


def build_tree(cfg: dict, be, key: int):
    import jax

    from repro.core import ktree

    with jax.profiler.TraceAnnotation("bench.build"):
        tree = ktree.build(be, order=cfg["order"], key=jax.random.PRNGKey(key),
                           batch_size=cfg["batch_size"], medoid=cfg["medoid"])
        jax.block_until_ready(tree)
    return tree


def quiet_collector() -> None:
    """Collect, then freeze every object set-up made out of Python's cyclic
    collector, so that a full collection in the window scans only what the
    window allocates (a JAX process holds some 200,000 tracked objects after
    set-up; a full scan of them takes about 0.15 s)."""
    gc.collect()
    gc.freeze()


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank (inf counts as the largest)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(math.ceil(q / 100.0 * v.size) - 1, 0)])


class Tracer:
    """The traced sub-window of a ``--trace 1`` run."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.t0 = self.t1 = None
        self._span = None

    def start(self):
        import jax

        jax.profiler.start_trace(self.out_dir)
        self._span = jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self, kernels, programs) -> dict:
        tr = btrace.load(self.out_dir)
        spans = [e for e in tr.host if e.name == btrace.WINDOW_SPAN]
        window = ((spans[0].start_ns, spans[0].start_ns + spans[0].dur_ns)
                  if spans else None)
        return btrace.summarize(tr, kernels=kernels, programs=programs, window=window)


# ---------------------------------------------------------------------------
# open-loop serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeSetup:
    """A serve cell after set-up: the corpus and query pool (as generated,
    and as fed to the program), the program's tree and its search entry."""
    docs: bcorpus.Csr
    pool: np.ndarray
    fed_pool: np.ndarray
    tree: object
    search_fn: object
    streams: Streams
    cfg: dict


def serve_setup(cfg: dict, traffic: dict, seed: int, *, control: bool = False,
                n_docs: int | None = None) -> ServeSetup:
    """Corpus and held-out query pool from the seed, the tree built through
    ``ktree.build``, and every call shape the traffic can make compiled."""
    import jax

    from repro.core.engine import make_search_fn

    st = Streams(seed)
    n = n_docs or cfg["n_docs"]
    n_pool = traffic["query_pool"] if n_docs is None else min(traffic["query_pool"], n)
    # queries are held-out documents: the corpus and the pool are drawn apart
    # from one generated collection, by a stream of their own
    t = time.perf_counter()
    full, _ = bcorpus.prepared_corpus(bcorpus.spec_from_config(cfg, n + n_pool), seed)
    perm = st.split.permutation(n + n_pool)
    docs, pool = full.take(np.sort(perm[:n])), full.take(np.sort(perm[n:]))
    fed_docs, fed_pool = (round_bf16(docs), round_bf16(pool)) if control else (docs, pool)
    be = program_backend(cfg, fed_docs)
    log(f"set-up: corpus of {n} docs and {n_pool} queries {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    tree = build_tree(cfg, be, st.key)
    # a Random Indexing search rescores from the base rows the backend holds
    rp = be if is_rp(cfg) else None
    del be
    log(f"set-up: build {time.perf_counter() - t:.1f} s, depth {int(tree.depth)}, "
        f"{int(tree.n_nodes)} of {tree.max_nodes} nodes")
    fn = make_search_fn(tree, rp=rp)

    def search_fn(x, k, beam, chunk_rows=None):
        with jax.profiler.TraceAnnotation("bench.search_fn"):
            return fn(x, k, beam, chunk_rows=chunk_rows)
    search_fn.chunk = fn.chunk

    fed_pool = fed_pool.dense()
    t = time.perf_counter()
    calls = load.engine_calls(traffic, fn.chunk)
    for k, beam, rows, chunk_rows in calls:
        x = fed_pool[np.arange(rows) % len(fed_pool)]
        search_fn(x, k, beam, chunk_rows=chunk_rows)
    if rp is not None:
        import jax.numpy as jnp

        for size in rescore_gather_sizes(calls, fn.chunk, cfg["order"]):
            np.asarray(rp.base.take(jnp.asarray(np.zeros(size, np.int64), dtype=jnp.int32)))
    log(f"set-up: warm-up of {len(calls)} call shapes {time.perf_counter() - t:.1f} s")
    return ServeSetup(docs, pool.dense(), fed_pool, tree, search_fn, st, cfg)


def window_plan(s: ServeSetup, traffic: dict, seconds: float, rate=None, *,
                traced: bool = False) -> load.Plan:
    """The requests of a window from the seed's streams; the traced window of
    a ``--trace 1`` run draws from streams of its own."""
    st = s.streams
    arr, pool, ten = ((st.trace_arrivals, st.trace_pool, st.trace_tenants) if traced
                      else (st.arrivals, st.pool, st.tenants))
    return load.plan(traffic, traffic["rate_rows_per_s"] if rate is None else rate,
                     seconds, len(s.pool), arrivals_rng=arr, pool_rng=pool, tenant_rng=ten)


def offer_load(s: ServeSetup, traffic: dict, plan: load.Plan, seconds: float, *,
               counter: CompileCounter, prof=None, tracer=None) -> dict:
    """Submit the plan's requests through a fresh ``ServingEngine`` at their
    due times; collect every answer and when it came, waiting up to
    ``RESULT_WAIT_S`` past the window's close. A tracer is started before
    the window opens and stopped when it closes."""
    from repro.core.engine import EngineSaturated, ServingEngine
    from repro.core.profile import NULL_PROFILER
    from repro.core.query import AnswerCache

    due, which = plan.due, plan.which
    ks, beams = plan.k, plan.beam
    n_req = due.size
    t_sub = np.full(n_req, np.nan)
    t_done = np.full(n_req, np.inf)
    admitted = np.zeros(n_req, bool)
    answers: list = [None] * n_req
    done_q: "queue.Queue" = queue.Queue()

    def collect():
        while True:
            item = done_q.get()
            if item is None:
                return
            i, h = item
            try:
                answers[i] = h.result(timeout=seconds + RESULT_WAIT_S)
                t_done[i] = time.perf_counter()
            except Exception:  # failed, or no answer in time: it stays at +inf
                pass

    cache = traffic.get("answer_cache", 0)
    collector = threading.Thread(target=collect, daemon=True)
    engine = ServingEngine(s.search_fn, row_budget=traffic["row_budget"],
                           max_queue=traffic["max_queue"],
                           max_wait_s=traffic["max_wait_s"],
                           cache=AnswerCache(cache) if cache else None,
                           tree=s.tree if cache else None,
                           profiler=prof or NULL_PROFILER)
    collector.start()
    if tracer is not None:
        tracer.start()
    counter.armed = True
    t0 = time.perf_counter()
    try:
        for i in range(n_req):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            t_sub[i] = time.perf_counter()
            try:
                done_q.put((i, engine.submit(s.fed_pool[which[i]], int(ks[i]), int(beams[i]))))
                admitted[i] = True
            except EngineSaturated:
                pass
        end = t0 + seconds
        if time.perf_counter() < end:
            time.sleep(end - time.perf_counter())
        if tracer is not None:
            tracer.stop()
        counter.armed = False
        done_q.put(None)
        collector.join(timeout=RESULT_WAIT_S + seconds)
    finally:
        counter.armed = False
        engine.close(drain=False)
    return {"t0": t0, "due": t0 + due, "plan": plan, "t_sub": t_sub,
            "t_done": t_done, "admitted": admitted, "answers": answers,
            "stats": engine.stats()}


def check_served(s: ServeSetup, w: dict) -> dict:
    """``dist_gap`` and ``answers_differ`` over a sample, drawn from the
    seed, of the answered requests' rows (``CHECK_SAMPLE`` rows), each
    (k, beam) against the reference's own search."""
    plan = w["plan"]
    rows = plan.which.shape[1]
    answered = np.nonzero(np.isfinite(w["t_done"]))[0]
    pick = np.sort(s.streams.sample.choice(
        answered, min(max(CHECK_SAMPLE // rows, 1), answered.size), replace=False))
    host = reference.HostTree.from_device(s.tree, route_dim(s.cfg, s.docs.n_cols))
    s.tree = s.search_fn = None
    ref = reference.RefTree(host, lambda ids: s.docs.dense(ids, np.float64),
                            route(s.cfg, s.docs))
    gap, differ, n = 0.0, 0.0, 0
    for t in np.unique(plan.tenant[pick]):
        got = pick[plan.tenant[pick] == t]
        k, beam = plan.tenants[t]["k"], plan.tenants[t]["beam"]
        docs = np.concatenate([w["answers"][i][0] for i in got]).astype(np.int64)
        dist = np.concatenate([w["answers"][i][1] for i in got]).astype(np.float32)
        q64 = s.pool[plan.which[got].ravel()].astype(np.float64)
        c = reference.check_answers(ref, q64, docs.reshape(-1, k), dist.reshape(-1, k), k, beam)
        gap = max(gap, c["dist_gap"])
        differ += c["answers_differ"] * len(q64)
        n += len(q64)
    return {"dist_gap": gap, "answers_differ": differ / max(n, 1)}


def serve(cfg: dict, traffic: dict, seed: int, seconds: float, trace_dir, *,
          t_start: float, counter: CompileCounter, control: bool = False,
          n_docs: int | None = None) -> dict:
    """One run of an ``open_loop`` cell; returns the raw readings. A traced
    run adds, after the measured window, a window of ``trace_s`` of its own
    under the profiler; the client's tails, the engine's counters and the
    spans come from the untraced window."""
    from repro.core.profile import Profiler

    s = serve_setup(cfg, traffic, seed, control=control, n_docs=n_docs)
    prof = Profiler() if trace_dir else None
    quiet_collector()
    setup_s = time.perf_counter() - t_start
    w = offer_load(s, traffic, window_plan(s, traffic, seconds), seconds,
                   counter=counter, prof=prof)
    rows = w["plan"].which.shape[1]
    t_done = w["t_done"]
    lat = t_done - w["due"]
    lost = int((np.isinf(t_done) & w["admitted"]).sum())
    out = {
        "setup_s": setup_s,
        "attempted": int(t_done.size),
        "failed": int(np.isinf(t_done).sum()),
        "metrics": {
            "search_p50_ms": 1e3 * nearest_rank(lat, 50),
            "search_rows_per_s": rows * int((t_done <= w["t0"] + seconds).sum()) / seconds,
        },
        "layer": {
            "latency_s": lat,
            "lateness_s": w["t_sub"] - w["due"],
            "stats": w["stats"],
            "row_budget": traffic["row_budget"],
            "spans": prof.totals() if prof else {},
        },
    }
    if trace_dir:
        tracer = Tracer(trace_dir)
        trace_s = traffic["trace_s"]
        wt = offer_load(s, traffic, window_plan(s, traffic, trace_s, traced=True), trace_s,
                        counter=counter, tracer=tracer)
        done = wt["t_done"]
        out["layer"]["rows_traced"] = rows * int(((done >= tracer.t0) & (done < tracer.t1)).sum())
        program = search_program(cfg)
        out["layer"]["search_program"] = program
        out["layer"]["trace"] = tracer.summary(("nn_topk", "nn_assign", "ell_spmm"),
                                               (program,))
        shape = load.rows_per_call(traffic, s.search_fn.chunk)
        if shape is not None:
            out["layer"]["kernel_work"] = {"nn_topk": {
                "rows": shape[0], "centres": cfg["order"] + 1,
                "dim": route_dim(cfg, s.docs.n_cols), "k": shape[1]}}
    gc.unfreeze()
    memory_peak(out)

    # the check, once the windows have closed and the program's state is freed
    t = time.perf_counter()
    checks = check_served(s, w)
    checks["lost_requests"] = lost
    checks["compiles_in_window"] = counter.count
    out["checks"] = checks
    log(f"check of the served answers {time.perf_counter() - t:.1f} s")
    return out


def memory_peak(out: dict) -> None:
    """The process's peak device memory on its fullest device: the peak of
    the arrays it held (``memory_stats()["peak_bytes_in_use"]``) plus the
    memory the runtime holds back for the loaded programs' temporaries
    (``bytes_limit - bytes_reservable_limit``), which the arrays' peak leaves
    out; 0 where the backend keeps no statistics, as the CPU's. Logs every
    statistic of that device beside it."""
    import jax

    def peak(st):
        limit = st.get("bytes_limit", 0)
        scratch = max(limit - st.get("bytes_reservable_limit", limit), 0)
        return int(st.get("peak_bytes_in_use", 0)) + int(scratch)

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [peak(st) for st in stats]
    out["memory_peak_bytes"] = max(peaks)
    log(f"memory_stats of the fullest device: {stats[int(np.argmax(peaks))]}")


# ---------------------------------------------------------------------------
# whole builds
# ---------------------------------------------------------------------------

def build_inputs(cfg: dict, traffic: dict, seed: int, n: int):
    """The corpus of a ``builds`` cell, in its insertion order, and the
    build's key. ``work_seed``, where the mix sets it, stands in for the seed
    in every draw, so the seed no longer changes the tree or its work."""
    st = Streams(traffic.get("work_seed", seed))
    docs, _ = bcorpus.prepared_corpus(bcorpus.spec_from_config(cfg, n),
                                      traffic.get("corpus_seed", st.seed))
    if traffic.get("shuffle"):
        docs = docs.take(st.order.permutation(n))
    return docs, st.key


def builds(cfg: dict, traffic: dict, seed: int, seconds: float, trace_dir, *,
           t_start: float, counter: CompileCounter, control: bool = False,
           n_docs: int | None = None) -> dict:
    """One run of a ``builds`` cell; returns the raw readings. A Random
    Indexing build projects its corpus inside each timed build: the
    projection is part of what the RI K-tree's build costs."""

    n = n_docs or cfg["n_docs"]
    t = time.perf_counter()
    docs, key = build_inputs(cfg, traffic, seed, n)
    base = base_backend(cfg, round_bf16(docs) if control else docs)
    log(f"set-up: corpus of {n} docs {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    # the warm-up build runs the same corpus and key as the window's builds,
    # so it compiles every program (level and split-batch bucket) they use
    tree = build_tree(cfg, routed(cfg, base), key)
    del tree
    log(f"set-up: warm-up build {time.perf_counter() - t:.1f} s")
    tracer = Tracer(trace_dir) if trace_dir else None
    quiet_collector()
    setup_s = time.perf_counter() - t_start
    times = []
    counter.armed = True
    t0 = time.perf_counter()
    try:
        while True:
            if tracer is not None and not times:
                tracer.start()
            t = time.perf_counter()
            tree = build_tree(cfg, routed(cfg, base), key)
            times.append(time.perf_counter() - t)
            if tracer is not None and tracer.t1 is None:
                tracer.stop()
            if t0 + seconds - time.perf_counter() < np.mean(times):
                break
            del tree
        t_end = time.perf_counter()
    finally:
        counter.armed = False
        gc.unfreeze()
    compiles = counter.count
    out = {
        "setup_s": setup_s,
        "compiles_in_window": compiles,
        "attempted": len(times),
        "failed": 0,
        "metrics": {"build_docs_per_s": n * len(times) / (t_end - t0)},
        "layer": {"batches": len(times) * math.ceil(n / cfg["batch_size"]),
                  "builds_traced": 1, "batches_traced": math.ceil(n / cfg["batch_size"])},
    }
    memory_peak(out)
    if tracer is not None:
        kernel = "ell_spmm" if cfg["representation"] == "sparse_medoid" else "nn_assign"
        out["layer"]["trace"] = tracer.summary(
            ("nn_topk", "nn_assign", "ell_spmm"), ("_insert_wave", "split_node"))
        work = {"rows": cfg["batch_size"], "centres": cfg["order"] + 1,
                "dim": route_dim(cfg, docs.n_cols)}
        if kernel == "ell_spmm":
            work["nnz"] = cfg["nnz_max"]
        out["layer"]["kernel_work"] = {kernel: work}

    t = time.perf_counter()
    host = reference.HostTree.from_device(tree, route_dim(cfg, docs.n_cols))
    del tree, base
    ref = reference.RefTree(host, lambda ids: docs.dense(ids, np.float64), route(cfg, docs))
    checks = {"misplaced_docs": ref.misplaced(n)}
    checks.update(ref.leaf_check())
    checks.update(ref.centre_checks())
    checks["compiles_in_window"] = compiles
    out["checks"] = checks
    log(f"check of the tree {time.perf_counter() - t:.1f} s")
    return out


DRIVERS = {"open_loop": serve, "builds": builds}
