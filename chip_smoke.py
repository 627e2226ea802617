#!/usr/bin/env python3
"""Smoke run of the K-tree main path on a TPU: build → beam search →
ServingEngine, checked against plain references.

    python chip_smoke.py              # one chip: the RP, dense and ELL phases
    python chip_smoke.py --chips 4    # four chips: the shard-parallel path only

Everything runs in this one process (a chip belongs to one process), through
the entry points a user calls: ``corpus_backend`` → ``ktree.build`` →
``check_invariants`` → ``make_search_fn``/``topk_search`` → a
``ServingEngine`` under open-loop load. Corpora are generated from
``--seed`` at the published widths of each configuration; only the document
count is cut, and each cut is printed. Every phase checks:

- the tree's structural invariants;
- the phase's Pallas kernels, run on the chip on real corpus rows against
  the built tree's root centres, against the ``kernels/ref.py`` oracles run
  on the host's CPU: ids equal except ties within f32 tolerance, distances
  and scores within it;
- every returned distance against the float64 distance recomputed on the
  host from the corpus rows;
- recall@k against host brute force, no lower than the same search on the
  same tree gives through the oracle path on the host's CPU (in this
  process, under ``jax.default_device``) less ``RECALL_MARGIN``;
- engine answers against the offline answers on the same chip, and that no
  engine request failed, timed out or was shed;
- that the compiled build and search steps call each Pallas kernel the
  phase should use (``tpu_custom_call``), so no step fell back to the
  ``kernels/ref.py`` oracles or to interpret mode.

The ELL phase also answers its queries from a ``CorpusStore`` written to a
fresh directory, with a residency budget below the corpus size.

Times printed on the way are smoke wall times, not metrics. The last line of
standard output is ``{"ok": true, "device": {...}}``; any failed check, and any
platform other than ``tpu``, exits non-zero before it.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import os
import re
import shutil
import sys
import time
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".smoke_work")  # gitignored; emptied per run

K, BEAM = 10, 4
DIST_RTOL = 1e-5
"""f32 tolerance of a returned squared distance, relative to ‖q‖² + ‖x‖²
(a one-pass bf16 matmul is off by ~1e-3 of that)."""
RECALL_MARGIN = 0.01
"""Recall@K the chip's search may lose against the oracle path's search of
the same tree on the host's CPU. Both score in f32 and differ only in
summation order (the oracle check bounds the difference of each distance),
so their descents part only where two centres tie within that bound; a
kernel that picks wrong centres loses far more."""
ORACLE_ROWS = 256  # corpus rows of the kernel-vs-oracle check (one build batch)
ROW_BLOCK = 4096  # corpus rows per host brute-force block
KERNEL_RE = re.compile(r"\b(nn_assign|nn_topk|ell_spmm)_pallas")


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    arch: str                  # configs/registry.py entry
    n_docs: int
    n_queries: int
    cut: str                   # why n_docs is below the source's
    build_kernels: tuple       # Pallas kernels the build step must call
    search_kernels: tuple      # ... and the search step
    store: bool = False        # also answer the queries from a CorpusStore


PHASES = {
    "rp": Phase(
        "rp", "ktree-rcv1-rp", 193_844, 256,
        "none: the paper's full RCV1 subset",
        ("nn_assign",), ("nn_topk",),
    ),
    "dense": Phase(
        "dense", "ktree-inex", 20_000, 512,
        "114,366 → 20,000 docs: the tree holds a dense f32 copy of every doc "
        "(ROADMAP R1); 7,232 nodes × 21 × 8000 × 4 B ≈ 4.9 GB of centres",
        ("nn_assign",), ("nn_topk",),
    ),
    "ell": Phase(
        "ell", "ktree-rcv1", 20_000, 512,
        "193,844 → 20,000 docs: medoid centres are dense rows too "
        "(ROADMAP R1); ≈ 4.9 GB of centres",
        ("ell_spmm",), ("ell_spmm",), store=True,
    ),
}
SHARDED_DOCS = 8_000
"""--chips 4: corpus size of both sharded comparisons. The four-chip phase
checks placement, the collectives and the cross-shard merge, whose code and
per-row shapes (d = 8000, ELL nnz_max) do not depend on the doc count;
8,000 docs give each of four shards 2,000 rows, several store blocks each.
The one-chip phases cover the sizes."""


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------

def dense_rows(base, ids) -> np.ndarray:
    """Dense f32 host copies of corpus rows ``ids`` (any in-memory backend)."""
    import jax.numpy as jnp

    return np.asarray(base.take(jnp.asarray(np.asarray(ids, np.int32))))


def truth_topk(x_q: np.ndarray, base, n: int) -> np.ndarray:
    """Exact top-K doc ids by host brute force over the corpus, streamed in
    dense row blocks (``query.brute_force_topk_stream``)."""
    from repro.core.query import brute_force_topk_stream

    blocks = (
        (lo, dense_rows(base, np.arange(lo, min(lo + ROW_BLOCK, n))))
        for lo in range(0, n, ROW_BLOCK)
    )
    return brute_force_topk_stream(x_q, blocks, K)


def check_distances(label: str, x_q, docs, dist, base) -> None:
    """Every answer is K found docs, ascending, and each distance equals the
    float64 ‖q − x‖² of the rows it names."""
    check((docs >= 0).all(), f"{label}: a query found fewer than {K} docs")
    check((np.diff(dist, axis=1) >= 0).all(), f"{label}: distances not ascending")
    nq = docs.shape[0]
    rows = dense_rows(base, docs.ravel()).astype(np.float64).reshape(nq, K, -1)
    q64 = x_q.astype(np.float64)
    d64 = ((q64[:, None, :] - rows) ** 2).sum(-1)
    scale = (q64 ** 2).sum(-1)[:, None] + (rows ** 2).sum(-1)
    err = np.abs(dist.astype(np.float64) - d64)
    worst = float((err / scale).max())
    log(f"  {label}: max |dist − float64| / (‖q‖²+‖x‖²) = {worst:.2e} "
        f"(bound {DIST_RTOL:.0e})")
    check((err <= DIST_RTOL * scale + 1e-7).all(),
          f"{label}: distances differ from float64 by {worst:.2e} of scale")


def same_answers(label: str, x_q, a, b) -> None:
    """Two answer sets agree: distances within f32 tolerance, and equal ids
    except where the two distances at that rank tie within it. The
    tolerance is ``DIST_RTOL`` of a bound on ‖q‖² + ‖x‖² (‖x‖² ≤ 2‖q‖² +
    2‖x − q‖²), the scale of the rounding in ‖q‖² − 2q·x + ‖x‖²."""
    (da, sa), (db, sb) = a, b
    q_sq = (x_q.astype(np.float64) ** 2).sum(-1)[:, None]
    scale = 3 * q_sq + 2 * np.maximum(sa, sb)
    err = np.abs(sa.astype(np.float64) - sb)
    close = err <= DIST_RTOL * scale
    same_ids = da == db
    bits = bool(same_ids.all() and np.array_equal(sa, sb))
    log(f"  {label}: {int((~same_ids).sum())} of {da.size} ids differ, "
        f"max |Δdist| / scale = {float((err / scale).max()):.2e}"
        + (" (bit-identical)" if bits else ""))
    check(close.all(), f"{label}: distances differ")
    check((same_ids | close).all(), f"{label}: ids differ")


def kernel_calls(jitted, *args, **kw) -> collections.Counter:
    """Pallas kernels in the compiled program of ``jitted(*args, **kw)``."""
    text = jitted.lower(*args, **kw).compile().as_text()
    found = collections.Counter()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = KERNEL_RE.search(line)
            found[m.group(1) if m else "other"] += 1
    return found


def check_kernels(label: str, found, want) -> None:
    log(f"  {label} step: tpu_custom_call {dict(found)} (want {list(want)})")
    for name in want:
        check(found[name] >= 1, f"{label} step has no {name} Pallas kernel")


def host_cpu():
    import jax

    return jax.devices("cpu")[0]


def same_as_oracle(label: str, got, want, d64: np.ndarray, scale: np.ndarray) -> None:
    """A kernel's (ids, distances) against its oracle's: distances within
    ``DIST_RTOL`` of ``scale`` (per row), and equal ids except where the
    float64 distances ``d64`` [B, K] of the two ids tie within that bound."""
    (ids, dist), (ids_r, dist_r) = got, want
    ids, ids_r = ids.reshape(len(d64), -1), ids_r.reshape(len(d64), -1)
    dist = dist.reshape(ids.shape).astype(np.float64)
    dist_r = dist_r.reshape(ids.shape).astype(np.float64)
    tol = DIST_RTOL * scale[:, None] + 1e-7
    both_inf = np.isinf(dist) & np.isinf(dist_r)
    err = np.abs(np.where(both_inf, 0.0, dist) - np.where(both_inf, 0.0, dist_r))
    pick = lambda i: np.take_along_axis(d64, np.maximum(i, 0), axis=1)
    tie = (ids >= 0) & (ids_r >= 0) & (np.abs(pick(ids) - pick(ids_r)) <= tol)
    differ = ids != ids_r
    log(f"  oracle {label}: {int(differ.sum())} of {ids.size} ids differ "
        f"({int((differ & tie).sum())} tied), max |Δdist| / scale "
        f"{float((err / scale[:, None]).max()):.2e}")
    check((err <= tol).all(), f"oracle {label}: distances differ")
    check((~differ | tie).all(), f"oracle {label}: ids differ beyond ties")


def check_oracles(tree, be) -> None:
    """Run the phase's Pallas kernels on the chip on its first
    ``ORACLE_ROWS`` corpus rows (in the space the tree routes in) against
    the built tree's root centres, and hold them to the ``kernels/ref.py``
    oracles run on the host's CPU, and to float64 distances."""
    import jax
    import jax.numpy as jnp

    from repro.core.backend import EllSparseBackend
    from repro.kernels import ops, ref

    cpu = host_cpu()
    root = int(tree.root)
    c = tree.centers[root]
    valid = jnp.arange(tree.slots) < tree.n_entries[root]
    rows = jnp.arange(min(ORACLE_ROWS, be.n_docs), dtype=jnp.int32)
    x = be.take(rows)
    on_cpu = lambda *a: jax.device_put(a, cpu)
    x64, c64 = np.asarray(x, np.float64), np.asarray(c, np.float64)
    v = np.asarray(valid)
    d64 = ((x64[:, None, :] - c64[None]) ** 2).sum(-1)
    d64 = np.where(v[None, :], d64, np.inf)
    scale = (x64 ** 2).sum(-1) + (c64[v] ** 2).sum(-1).max()
    log(f"  oracle check: {len(x64)} rows vs the root's {int(v.sum())} centres "
        f"(d = {c64.shape[1]})")
    if isinstance(be, EllSparseBackend):
        vals, cols = be.values[rows], be.cols[rows]
        s = np.asarray(ops.ell_spmm(vals, cols, c), np.float64)
        s_ref = np.asarray(ref.ell_spmm_ref(*on_cpu(vals, cols, c)), np.float64)
        s64 = x64 @ c64.T
        for name, got in (("ell_spmm vs ref", s_ref), ("ell_spmm vs float64", s64)):
            err = np.abs(s - got)
            log(f"  oracle {name}: max |ΔS| / scale "
                f"{float((err / scale[:, None]).max()):.2e}")
            check((err <= DIST_RTOL * scale[:, None]).all(),
                  f"oracle {name}: scores differ")
        idx, dist = ops.medoid_assign_sparse(vals, cols, be.row_sq(rows), c, valid)
        want = ref.nn_assign_ref(*on_cpu(x, c, valid))
        same_as_oracle("medoid_assign_sparse (ell_spmm) vs ref",
                       (np.asarray(idx), np.asarray(dist)),
                       tuple(map(np.asarray, want)), d64, scale)
        return
    got = ops.nn_assign(x, c, valid)
    want = ref.nn_assign_ref(*on_cpu(x, c, valid))
    same_as_oracle("nn_assign vs ref", tuple(map(np.asarray, got)),
                   tuple(map(np.asarray, want)), d64, scale)
    for kq in (BEAM, K):
        got = ops.nn_topk(x, c, kq, valid)
        xc, cc, vc = on_cpu(x, c, valid)
        want = ref.nn_topk_ref(xc, cc, kq, vc)
        same_as_oracle(f"nn_topk kq={kq} vs ref", tuple(map(np.asarray, got)),
                       tuple(map(np.asarray, want)), d64, scale)


def oracle_search(tree, be, q, rp: bool):
    """The phase's search of the same tree through the oracle path on the
    host's CPU: inside a ``jax.default_device`` scope the backends call the
    ``kernels/ref.py`` oracles, and every operand is moved to the CPU."""
    import jax

    from repro.core.query import topk_search

    cpu = host_cpu()
    with jax.default_device(cpu):
        tree_c, be_c, q_c = jax.device_put((tree, be, q), cpu)
        return topk_search(tree_c, q_c, k=K, beam=BEAM,
                           rp=be_c if rp else None)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def make_corpus(phase: Phase, seed: int):
    """(backend, base backend, order, representation) for a phase."""
    from repro.configs import registry
    from repro.data.pipeline import corpus_backend

    cfg = registry.get(phase.arch).cfg
    spec = dataclasses.replace(cfg["corpus"], n_docs=phase.n_docs)
    rep = cfg["representation"]
    kw = {}
    if rep == "rp":
        kw = dict(rp_dim=cfg["rp_dim"], rp_seed=cfg["rp_seed"],
                  rp_kind=cfg["rp_kind"])
    be, _ = corpus_backend(spec, rep, seed=seed, **kw)
    base = be.base if rep == "rp" else be
    return be, base, cfg["orders"][0], rep, spec


def query_source(base, nq: int):
    """The first ``nq`` corpus rows as a query source: dense rows for a dense
    corpus, an ELL backend (so the search scores them with ``ell_spmm``)
    for a sparse one."""
    from repro.core.backend import EllSparseBackend

    if isinstance(base, EllSparseBackend):
        got = {"values": np.asarray(base.values[:nq]),
               "cols": np.asarray(base.cols[:nq])}
        return EllSparseBackend.from_rows(got, base.dim)
    return dense_rows(base, np.arange(nq))


def run_engine(label: str, search_fn, x_q: np.ndarray, seed: int) -> None:
    """Serve a short open-loop load through ``ServingEngine`` and hold its
    answers to the offline ones on the same device."""
    from repro.core.engine import ServingEngine
    from repro.launch.engine import request_pool, submit_all

    rows_per_req = 4
    pool = request_pool(x_q, n_requests=64, rows_per_request=rows_per_req,
                        k=K, beam=BEAM, seed=seed)
    search_fn(pool[0][0], K, BEAM, chunk_rows=rows_per_req)  # compile outside the load
    t0 = time.perf_counter()
    with ServingEngine(search_fn, row_budget=64, max_queue=256,
                       max_wait_s=2e-3, request_timeout_s=120.0) as eng:
        handles, load = submit_all(eng, pool, rate_qps=200.0, seed=seed)
        answers = [h.result(timeout=300) if h is not None else None
                   for h in handles]
        stats = eng.stats()
    lat = stats["latency_ms"]
    log(f"  {label} engine: {stats['completed']}/{stats['admitted']} completed, "
        f"shed={stats['shed']} failed={stats['failed']} "
        f"timeouts={stats['timeouts']} batches={stats['n_batches']}; "
        f"p50 {lat.get('p50', 0.0):.1f} ms, "
        f"{time.perf_counter() - t0:.1f} s (smoke wall time, not a metric)")
    check(stats["failed"] == 0 and stats["timeouts"] == 0,
          f"{label} engine: {stats['failed']} failed, {stats['timeouts']} timed out")
    check(stats["shed"] == 0 and all(a is not None for a in answers),
          f"{label} engine shed requests")
    check(stats["completed"] == len(pool), f"{label} engine left requests unanswered")
    eng_docs = np.concatenate([a[0] for a in answers])
    eng_dist = np.concatenate([a[1] for a in answers])
    off = [search_fn(rows, K, BEAM) for rows, _, _ in pool]
    x_eng = np.concatenate([rows for rows, _, _ in pool])
    same_answers(f"{label} engine vs offline", x_eng,
                 (eng_docs, eng_dist),
                 (np.concatenate([o[0] for o in off]),
                  np.concatenate([o[1] for o in off])))


def run_phase(phase: Phase, seed: int) -> None:
    """Run one one-chip phase and its checks."""
    import jax
    import jax.numpy as jnp

    from repro.core import ktree as kt
    from repro.core import query as qm
    from repro.core.backend import DenseBackend
    from repro.core.engine import make_search_fn

    t_phase = time.perf_counter()
    log(f"phase {phase.name} ({phase.arch}): {phase.n_docs} docs, "
        f"{phase.n_queries} queries, k={K} beam={BEAM}; cut: {phase.cut}")
    t0 = time.perf_counter()
    be, base, order, rep, spec = make_corpus(phase, seed)
    n = be.n_docs
    nnz = getattr(base, "nnz_max", None)
    log(f"  corpus {spec.name}: vocab {spec.vocab} culled to {base.dim}, "
        f"representation {rep}" + (f", ELL nnz_max {nnz}" if nnz else "")
        + (f", routed in {be.dim} dims" if rep == "rp" else "")
        + f"; order {order}; {time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    tree = kt.build(be, order=order, key=jax.random.PRNGKey(seed),
                    medoid=rep == "sparse_medoid")
    jax.block_until_ready(tree)
    log(f"  build: {n} docs in {time.perf_counter() - t0:.1f} s (smoke wall "
        f"time, not a metric), depth {int(tree.depth)}, "
        f"{int(tree.n_nodes)}/{tree.max_nodes} nodes, centres "
        f"{tree.centers.nbytes / 1e9:.2f} GB")
    kt.check_invariants(tree, n_docs=n)
    log("  check_invariants: ok")
    check_oracles(tree, be)

    nq = phase.n_queries
    q = query_source(base, nq)
    x_q = dense_rows(base, np.arange(nq))
    rp = be if rep == "rp" else None
    search_fn = make_search_fn(tree, rp=rp)
    t0 = time.perf_counter()
    docs, dist = search_fn(q, K, BEAM)
    log(f"  search: {nq} queries in {time.perf_counter() - t0:.1f} s with "
        "compile (smoke wall time, not a metric)")
    check_distances(f"{phase.name} search", x_q, docs, dist, base)

    t0 = time.perf_counter()
    ref_docs, _ = oracle_search(tree, be, q, rp=rep == "rp")
    log(f"  oracle search on the host CPU: {time.perf_counter() - t0:.1f} s "
        f"with compile; {int((ref_docs != docs).any(axis=1).sum())} of {nq} "
        "queries answered differently from the chip")
    t0 = time.perf_counter()
    truth = truth_topk(x_q, base, n)
    recall = qm.recall_at_k(docs, truth)
    recall_ref = qm.recall_at_k(ref_docs, truth)
    log(f"  recall@{K} = {recall:.4f} vs host brute force "
        f"({time.perf_counter() - t0:.1f} s); oracle search on the host CPU "
        f"{recall_ref:.4f}, margin {RECALL_MARGIN}")
    check(recall >= recall_ref - RECALL_MARGIN,
          f"{phase.name}: recall {recall:.4f} below the oracle search's "
          f"{recall_ref:.4f} − {RECALL_MARGIN}")

    levels = int(tree.depth) - 1
    mlev = kt._levels_bucket(levels)
    b = 256
    check_kernels("build", kernel_calls(
        kt._insert_wave, tree, be, jnp.arange(b, dtype=jnp.int32),
        jnp.arange(b, dtype=jnp.int32), jnp.ones(b, bool),
        jnp.int32(levels), max_levels=mlev,
    ), phase.build_kernels)
    if rep == "rp":
        zq = be.projection.apply(jnp.asarray(x_q))
        found = kernel_calls(
            qm._chunk_candidates_jit, tree, DenseBackend(zq),
            jnp.arange(nq, dtype=jnp.int32), jnp.int32(levels),
            max_levels=mlev, beam=BEAM,
        )
    else:
        from repro.core.backend import make_backend

        found = kernel_calls(
            qm._beam_search, tree, make_backend(q),
            jnp.arange(nq, dtype=jnp.int32), jnp.int32(levels),
            max_levels=mlev, beam=BEAM, k=K,
        )
    check_kernels("search", found, phase.search_kernels)

    if phase.store:
        run_store_queries(phase, tree, be, x_q, (docs, dist))
    run_engine(phase.name, search_fn, x_q, seed)
    log(f"phase {phase.name}: ok in {time.perf_counter() - t_phase:.1f} s "
        "(smoke wall time, not a metric)")
    del tree, be, base, q, search_fn
    gc.collect()


def fresh_dir(name: str) -> str:
    path = os.path.join(WORK, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(WORK, exist_ok=True)
    return path


def run_store_queries(phase: Phase, tree, be, x_q, mem_answers) -> None:
    """Answer the phase's queries from an on-disk store holding the corpus,
    with a residency budget of a quarter of it; answers must equal the
    in-memory ones."""
    from repro.core.query import topk_search
    from repro.core.store import open_store, save_store

    path = fresh_dir(f"store_{phase.name}")
    save_store(path, be, block_docs=1024)
    probe = open_store(path)
    budget = probe.nbytes // 4
    store = open_store(path, budget_bytes=budget)
    check(store.nbytes > budget, "store budget is not below the corpus size")
    t0 = time.perf_counter()
    docs, dist = topk_search(tree, store.view(0, phase.n_queries), k=K, beam=BEAM)
    cs = store.cache.stats
    log(f"  store: {store.n_docs} docs in {store.n_blocks} blocks, "
        f"{store.nbytes / 1e6:.1f} MB, budget {budget / 1e6:.1f} MB, "
        f"peak resident {cs['peak_resident_bytes'] / 1e6:.1f} MB, "
        f"{time.perf_counter() - t0:.1f} s (smoke wall time, not a metric)")
    check(cs["peak_resident_bytes"] <= max(budget, store.nbytes // store.n_blocks),
          "store residency exceeded its budget")
    same_answers(f"{phase.name} store-backed vs in-memory", x_q,
                 (docs, dist), mem_answers)
    shutil.rmtree(path, ignore_errors=True)


# ---------------------------------------------------------------------------
# four chips: shard-parallel serving vs single-device answers
# ---------------------------------------------------------------------------

def run_sharded(seed: int, n_chips: int) -> None:
    import jax

    from repro.core import ktree as kt
    from repro.core.backend import shard_from_store
    from repro.core.query import recall_at_k, topk_search, topk_search_sharded
    from repro.core.store import open_store, save_store
    from repro.launch.mesh import make_serving_mesh

    mesh = make_serving_mesh(n_chips)
    log(f"mesh: {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")
    for name in ("dense", "ell"):
        phase = dataclasses.replace(PHASES[name], n_docs=SHARDED_DOCS)
        t0 = time.perf_counter()
        be, base, order, rep, spec = make_corpus(phase, seed)
        tree = kt.build(be, order=order, key=jax.random.PRNGKey(seed),
                        medoid=rep == "sparse_medoid")
        kt.check_invariants(tree, n_docs=be.n_docs)
        log(f"sharded {name} ({phase.arch}): {be.n_docs} docs, built and "
            f"checked in {time.perf_counter() - t0:.1f} s (smoke wall time, "
            f"not a metric), depth {int(tree.depth)}")
        nq = phase.n_queries
        x_q = dense_rows(base, np.arange(nq))
        single = topk_search(tree, x_q, k=K, beam=BEAM)
        if name == "dense":
            corpus = be.shard(mesh)
            log(f"  corpus shards: x {corpus.x.shape} {corpus.x.sharding}")
            label = "in-memory"
        else:
            path = fresh_dir("store_sharded")
            save_store(path, be, block_docs=512)
            store = open_store(path)
            budget = store.nbytes // (4 * n_chips)
            corpus = shard_from_store(mesh, store, budget_bytes=budget)
            log(f"  store corpus: {store.nbytes / 1e6:.1f} MB in "
                f"{store.n_blocks} blocks, {n_chips} partitions of "
                f"{[p.n_docs for p in corpus.parts]} rows, "
                f"{budget / 1e6:.2f} MB budget each")
            label = "store-backed"
        _log_sharded_placement(mesh, tree, x_q, corpus, name)
        t0 = time.perf_counter()
        sharded = topk_search_sharded(mesh, tree, x_q, corpus=corpus, k=K, beam=BEAM)
        log(f"  sharded search: {nq} queries in {time.perf_counter() - t0:.1f} s "
            "with compile (smoke wall time, not a metric)")
        check_distances(f"sharded {name}", x_q, sharded[0], sharded[1], base)
        same_answers(f"{label} sharded×{n_chips} vs single-device", x_q,
                     sharded, single)
        truth = truth_topk(x_q, base, be.n_docs)
        r_sh, r_one = recall_at_k(sharded[0], truth), recall_at_k(single[0], truth)
        log(f"  recall@{K}: sharded {r_sh:.4f}, single-device {r_one:.4f}")
        check(r_sh >= r_one - 1e-9, f"sharded {name} recall below single-device")
        if name == "ell":
            peak = corpus.peak_resident_bytes
            log(f"  store residency peak across shards {peak / 1e6:.2f} MB")
            shutil.rmtree(path, ignore_errors=True)
        del tree, be, base, corpus
        gc.collect()


def _log_sharded_placement(mesh, tree, x_q, corpus, name: str) -> None:
    """Print where the sharded path's arrays live, as the compiled programs
    of one query chunk place them: the tree and query rows going in, the
    corpus shards, and the merged output."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import ktree as kt
    from repro.core import query as qm
    from repro.core.backend import make_backend

    levels = int(tree.depth) - 1
    mlev = kt._levels_bucket(levels)
    qbe = make_backend(x_q)
    rows = jnp.arange(x_q.shape[0], dtype=jnp.int32)
    log(f"  tree centres as built: {tree.centers.sharding}")
    if name == "dense":
        leaves, treedef = jax.tree_util.tree_flatten(corpus)
        specs = tuple(P(("data",), *([None] * (l.ndim - 1))) for l in leaves)
        fn = qm._get_sharded_chunk_fn(mesh, treedef, specs, mlev, BEAM, K)
        compiled = fn.lower(tree, qbe, rows, jnp.int32(levels), corpus).compile()
        ins, _ = compiled.input_shardings
        log(f"  chunk program inputs: tree centres {ins[0].centers}; "
            f"query rows {ins[1].x}; corpus block "
            f"{jax.tree_util.tree_leaves(ins[4])[0]}")
    else:
        cand, valid, xq, q_sq = qm._chunk_candidates_jit(
            tree, qbe, rows, jnp.int32(levels), max_levels=mlev, beam=BEAM)
        log(f"  descent outputs (candidates): {cand.sharding}")
        pools, pool_idx, owned, _ = corpus.chunk_pools(
            np.asarray(cand), np.asarray(valid))
        compiled = qm._get_store_merge_fn(mesh, "ell", K).lower(
            pools, pool_idx, owned, xq, q_sq, cand, valid).compile()
        ins, _ = compiled.input_shardings
        log(f"  merge program inputs: pools {ins[0][0]}; "
            f"query rows {ins[3]}")
    log(f"  merged output: {compiled.output_shardings[0]}")
    n_coll = compiled.as_text().count("all-gather-start") or \
        compiled.as_text().count("all-gather")
    log(f"  all-gathers in the program: {n_coll}")
    check(n_coll >= 1, f"sharded {name} program has no all-gather")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard-parallel path and the "
                    "single-device answers it is compared with")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no K-tree sources at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} × {len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            for phase in PHASES.values():
                run_phase(phase, args.seed)
        else:
            run_sharded(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s "
        "(smoke wall time, not a metric)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
