"""Batched top-k beam-search query engine over a built K-tree (DESIGN.md §7).

The paper uses the K-tree as a nearest-neighbour search tree for retrieval;
the greedy root→leaf descent (``nn_search``) visits exactly one leaf, so a
query that routes into the "wrong" subtree near the root can never recover.
Beam search keeps the best ``beam`` branches per level instead of the argmin:

- **level 0** — the root's entries are one flat centre set, so the top-``beam``
  entries come from the backend's fused flat path (``topk_flat``: the
  ``nn_topk`` Pallas kernel for dense queries, the ``ell_spmm`` scoring path +
  ``top_k`` for sparse queries).
- **levels 1..depth−2** — each of the ``beam`` frontier nodes contributes its
  ≤ m+1 entries; all ``beam·(m+1)`` candidates are scored in one
  ``cross_nodes`` call (per-query gathered centres — MXU einsum for dense
  rows, nnz-bounded column gather for sparse rows) and the best ``beam``
  children become the next frontier.
- **leaf level** — the union of the ``beam`` candidate leaves' documents
  (their entries *are* the inserted vectors) is scored the same way and
  reduced to ``(doc_ids, dists)[B, k]``, ascending, exact squared distances.

Everything after backend construction is one jitted call per query chunk;
descent depth is bucketed to powers of two exactly like ``route``
(DESIGN.md §6), so a growing tree triggers O(log depth) compiles per
(beam, k) setting, not one per depth.

``beam=1, k=1`` reproduces the greedy ``nn_search`` bit-for-bit: every level
scores the same tensors with the same expressions and ``top_k``'s
tie-breaking (lowest index first) matches ``argmin``'s.

Serving plane (DESIGN.md §8): ``topk_search`` streams chunks through a
dispatch-ahead pipeline (device compute overlaps D2H copy-out),
``topk_search_sharded`` runs the leaf scoring shard-parallel over a
row-sharded corpus with an exact O(B·k·n_shards) top-k merge, and
``AnswerCache``/``topk_search_cached`` put an LRU over repeated queries.

Out-of-core (DESIGN.md §9): ``topk_search`` also accepts a disk-backed
``CorpusStore``/``StoreSlice`` as the query source — each chunk's rows are
fetched from the store's block cache and materialised as a chunk-sized
backend, and the same dispatch-ahead pipeline overlaps the next chunk's disk
read with the previous chunk's device compute (``prefetch ≥ 1`` further moves
the read onto a ``store.Prefetcher`` reader thread, overlapping it with the
current chunk's D2H as well). ``topk_search_sharded`` accepts a store (or a
``backend.shard_from_store`` handle) as the *corpus*: the corpus stays on
disk behind per-shard block caches and each shard fetches only the beam
candidates it owns per chunk. Answers are bit-identical to the in-memory
paths throughout.

Random-projection routing (DESIGN.md §5.1): with ``rp=`` the tree was built
in a seeded low-dimensional projection (``backend.RandomProjBackend``) —
queries are projected per chunk, the beam descends in the projected space,
and the leaf candidate pool is **rescored from the original representation**
(in-memory base, ``CorpusStore.take_rows``, or per-shard partition caches)
at full precision. The rescore literally calls :func:`brute_force_topk_dist`
per query over its own candidate rows, so it is bit-identical to brute force
restricted to that pool by construction; the single-device, cached, and
sharded RP paths all extract pools through the same jitted
``_chunk_candidates`` and therefore bit-match each other.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.backend import (
    DenseBackend,
    DenseDocShards,
    DocShards,
    EllDocShards,
    ProjectionMismatch,
    RandomProjBackend,
    RandomProjection,
    StoreDocShards,
    VectorBackend,
    backend_from_rows,
    backend_from_store,
    is_store,
    make_backend,
    shard_from_store,
)
from repro.core.autotune import resolve_knobs
from repro.core.faults import FaultReport
from repro.core.ktree import (
    KTree, _levels_bucket, chunked_query_rows, leaf_nodes, padded_chunk_rows,
)
from repro.core.profile import NULL_PROFILER
from repro.core.store import check_on_fault
from repro.kernels.ref import EXACT, topk_from_dist, topk_merge_ref


def _score_entries(
    tree: KTree, backend: VectorBackend, rows: jax.Array,
    frontier: jax.Array, active: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Score every entry of every frontier node against each query.

    Returns (diff_sq f32[B, beam·m1] = ‖c‖² − 2·x·c with invalid slots and
    inactive beams masked +inf, child i32[B, beam·m1]). The ‖x‖² constant is
    deliberately dropped — it cannot change any per-query ordering and keeping
    it out preserves bit-exact agreement with the greedy descent."""
    b, beam = frontier.shape
    m1 = tree.slots
    c = tree.node_centers(frontier).reshape(b, beam * m1, tree.dim)
    c_sq = jnp.einsum("bmd,bmd->bm", c, c, precision=EXACT)
    diff_sq = c_sq - 2.0 * backend.cross_nodes(rows, c)
    slot_ok = (
        jnp.arange(m1)[None, None, :] < tree.n_entries[frontier][:, :, None]
    )                                                        # [B, beam, m1]
    ok = jnp.logical_and(slot_ok, active[:, :, None]).reshape(b, beam * m1)
    diff_sq = jnp.where(ok, diff_sq, jnp.inf)
    child = tree.child[frontier].reshape(b, beam * m1)
    return diff_sq, child


def _beam_frontier(
    tree: KTree,
    backend: VectorBackend,
    rows: jax.Array,
    levels: jax.Array,
    max_levels: int,
    beam: int,
) -> Tuple[jax.Array, jax.Array]:
    """Beam descent to the leaf level: (frontier i32[B, beam] candidate leaf
    ids, active bool[B, beam]). Levels ≥ ``levels`` are masked no-ops (bucketed
    compiles, DESIGN.md §6). Shared by the single-device leaf scoring
    (:func:`_beam_search`) and the shard-parallel path, so both descend through
    bit-identical frontiers."""
    b = rows.shape[0]
    frontier = jnp.full((b, beam), 1, jnp.int32) * tree.root
    active = jnp.broadcast_to(jnp.arange(beam) == 0, (b, beam))

    for l in range(max_levels):
        if l == 0:
            # root fast path: one flat centre set → fused top-beam
            valid = jnp.arange(tree.slots) < tree.n_entries[tree.root]
            idx, _ = backend.topk_flat(
                rows, tree.node_centers(tree.root), valid, beam
            )                                                # [B, beam]
            new_active = idx >= 0
            child_sel = tree.child[tree.root][jnp.maximum(idx, 0)]
        else:
            diff_sq, child = _score_entries(tree, backend, rows, frontier, active)
            negd, pos = jax.lax.top_k(-diff_sq, beam)
            new_active = jnp.isfinite(negd)
            child_sel = jnp.take_along_axis(child, pos, axis=1)
        child_sel = jnp.maximum(child_sel, 0)                # safe gather id
        act_l = jnp.asarray(l, jnp.int32) < levels
        frontier = jnp.where(act_l, child_sel, frontier)
        active = jnp.where(act_l, new_active, active)
    return frontier, active


@functools.partial(jax.jit, static_argnames=("max_levels", "beam", "k"))
def _beam_search(
    tree: KTree,
    backend: VectorBackend,
    rows: jax.Array,
    levels: jax.Array,
    max_levels: int,
    beam: int,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """One jitted beam-search descent + leaf scoring for a chunk of queries.

    Levels ≥ ``levels`` are masked no-ops (bucketed compiles, DESIGN.md §6).
    Returns (doc_ids i32[B, k], sqdist f32[B, k]) ascending; queries reaching
    fewer than k documents pad with (−1, +inf)."""
    frontier, active = _beam_frontier(tree, backend, rows, levels, max_levels, beam)

    # leaf level: the frontier's entries are the candidate documents
    diff_sq, child = _score_entries(tree, backend, rows, frontier, active)
    negd, pos = jax.lax.top_k(-diff_sq, min(k, diff_sq.shape[1]))
    if k > negd.shape[1]:                                    # k > beam·(m+1)
        negd = jnp.pad(negd, ((0, 0), (0, k - negd.shape[1])),
                       constant_values=-jnp.inf)
        pos = jnp.pad(pos, ((0, 0), (0, k - pos.shape[1])))
    found = jnp.isfinite(negd)
    docs = jnp.where(found, jnp.take_along_axis(child, pos, axis=1), -1)
    # the dropped ‖x‖² goes back in after selection (greedy does the same)
    dist = jnp.where(
        found, jnp.maximum(-negd + backend.row_sq(rows)[:, None], 0.0), jnp.inf
    )
    return docs.astype(jnp.int32), dist


def _pipeline_chunks(chunks, pipeline: int, dispatch, docs_out, dist_out,
                     prof=NULL_PROFILER):
    """Dispatch-ahead chunk loop (DESIGN.md §8): keep up to ``pipeline`` chunks
    in flight, copying out the oldest only once newer chunks are already
    dispatched — device compute overlaps the host-blocking D2H fetch instead of
    serialising behind it. ``pipeline=1`` reproduces the old synchronous loop
    (fetch immediately after each dispatch).

    ``chunks`` yields ``(rows_np, payload)`` pairs and ``dispatch(payload)``
    returns the chunk's in-flight device result. For store-backed queries the
    payload carries the chunk's global row ids and ``dispatch`` starts with a
    disk read — the same schedule then overlaps chunk i+1's block fetch with
    chunk i's device compute (DESIGN.md §9).

    ``prof`` (a ``repro.core.profile.Profiler``, DESIGN.md §11) records one
    ``"dispatch"`` span per chunk (H2D staging + jit dispatch) and one
    ``"compute"`` span per drain (the blocking device_get: device compute +
    D2H); the store iterator's ``"read"`` spans complete the picture."""
    depth = max(int(pipeline), 1)
    pending = collections.deque()

    def drain_one():
        rows_np, fut = pending.popleft()
        with prof.span("compute"):
            docs, dist = jax.device_get(fut)
        docs_out[rows_np] = docs[: rows_np.size]
        dist_out[rows_np] = dist[: rows_np.size]

    for rows_np, payload in chunks:
        with prof.span("dispatch"):
            fut = dispatch(payload)
        pending.append((rows_np, fut))
        while len(pending) >= depth:
            drain_one()
    while pending:
        drain_one()


def _store_chunk_iter(store, n: int, chunk: int, prefetch: int, dropped=None,
                      prof=NULL_PROFILER):
    """Yield ``(rows_np, fetched row arrays)`` per padded query chunk of a
    store source. ``prefetch=0``: the disk read happens inline, right before
    the chunk is dispatched (the §8 dispatch-ahead pipeline then overlaps it
    with the *previous* chunk's compute). ``prefetch ≥ 1``: the reads move to
    a ``store.Prefetcher`` reader thread of that depth, which additionally
    overlaps them with the current chunk's D2H copy-out — the yielded arrays
    (and hence the answers) are identical either way.

    ``dropped`` (degrade mode, DESIGN.md §10): a list that collects the
    global query-row ids whose store blocks were unreadable after retries —
    those rows are zero-filled in the yielded arrays and the caller must
    flag their answers (−1, +inf).

    ``prof`` records one ``"read"`` span per chunk fetch — on the consumer
    thread when ``prefetch=0``, on the reader thread when ≥ 1, so
    ``prof.overlap_seconds("read", "compute")`` measures whether the
    prefetch depth actually bought overlap (DESIGN.md §11)."""

    def fetch(req):
        rows_np, padded = req
        with prof.span("read"):
            if dropped is None:
                return store.take_rows(padded)
            got, ok = store.take_rows_masked(padded)
        if not ok.all():
            # padded[:rows_np.size] == rows_np (padding repeats the last row)
            dropped.extend(int(r) for r in rows_np[~ok[: rows_np.size]])
        return got

    if prefetch:
        from repro.core.store import Prefetcher

        with Prefetcher(
            padded_chunk_rows(n, chunk), fetch, depth=prefetch,
        ) as pf:
            for (rows_np, _), got in pf:
                yield rows_np, got
        return
    for req in padded_chunk_rows(n, chunk):
        yield req[0], fetch(req)


def topk_search(
    tree: KTree, q, k: int = 10, beam: int = 4, chunk: Optional[int] = None,
    pipeline: Optional[int] = None, prefetch: Optional[int] = None,
    on_fault: str = "raise", rp=None, rp_corpus=None, tuned=None,
    profiler=NULL_PROFILER,
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k ANN document search with beam-width recall control.

    ``q``: dense vectors, a Csr matrix, a backend, or a disk-backed
    ``CorpusStore``/``StoreSlice`` (DESIGN.md §9 — rows are fetched
    block-by-block from disk, chunk backends replace the monolithic array,
    and answers stay bit-identical to the in-memory path). Returns
    (doc_ids i32[B, k], sqdist f32[B, k]) ascending per query; padded entries
    are (−1, +inf). ``beam=1`` is the greedy single-path descent; wider beams
    trade ~beam× more scored candidates for recall (benchmarks/query_recall.py
    sweeps the trade-off). Queries are processed in chunks of ``chunk`` to
    bound the [chunk, beam·(m+1), d] gathered-centre buffers; ``pipeline``
    chunks stay in flight at once (2 = double-buffered dispatch-ahead, 1 = the
    old synchronous loop — benchmarks/query_throughput.py measures the gap).
    ``prefetch ≥ 1`` (store sources only) moves the disk reads onto an async
    ``store.Prefetcher`` reader thread of that depth, overlapping the next
    chunk's read with compute *and* the current D2H — answers unchanged.

    Fault handling (DESIGN.md §10): with the default ``on_fault="raise"`` a
    store block that exhausts its read retries surfaces a typed
    ``BlockCorrupt``/``BlockUnavailable``. ``on_fault="degrade"`` instead
    drops only the unreadable blocks' query rows — their answers become
    (−1, +inf), surviving rows stay bit-identical to a fault-free run — and
    returns a third element, a :class:`repro.core.faults.FaultReport`
    flagging ``degraded=True`` whenever anything was dropped.

    Random projection (DESIGN.md §5.1): ``rp=`` (a ``RandomProjBackend`` or
    bare ``RandomProjection``) switches to approximate-route, exact-rescore:
    queries are projected per chunk, the descent runs in the projected space
    the tree was built in, and the leaf candidate pool is rescored from the
    original representation — ``rp_corpus=`` (defaulting to the rp backend's
    in-memory base; pass the ``CorpusStore`` for an out-of-core base). The
    rescore is bit-identical to :func:`brute_force_topk_dist` restricted to
    each query's pool (it *is* that call); only the pool membership is
    approximate. Not composable with ``on_fault="degrade"`` yet.

    Knob resolution (DESIGN.md §11): ``chunk``/``pipeline``/``prefetch``
    left as ``None`` fall back to ``tuned=`` (a ``TunedKnobs`` from
    ``core/autotune.py``, typically loaded from the store's ``TUNE.json``
    sidecar) and then to the repo defaults (512 / 2 / 0) — explicit values
    always win, and since the knobs only reschedule work the answers are
    bit-identical whichever way they resolve. ``profiler=`` (a
    ``core.profile.Profiler``) records per-chunk "read"/"dispatch"/"compute"
    spans; the default ``NULL_PROFILER`` is free."""
    if k < 1 or beam < 1:
        raise ValueError(f"k and beam must be ≥ 1, got k={k} beam={beam}")
    chunk, pipeline, prefetch = resolve_knobs(
        tuned, chunk=chunk, pipeline=pipeline, prefetch=prefetch,
    )
    check_on_fault(on_fault)
    if rp is not None:
        if on_fault != "raise":
            raise ValueError(
                "rp= does not compose with on_fault='degrade' yet"
            )
        projection, src = _resolve_rp(rp, rp_corpus)
        return _topk_search_rp(
            tree, q, projection, src, k=k, beam=beam, chunk=chunk,
            pipeline=pipeline, prefetch=prefetch, prof=profiler,
        )
    store = q if is_store(q) else None
    degrade = on_fault == "degrade"
    dropped: Optional[list] = [] if (degrade and store is not None) else None
    be = None if store is not None else make_backend(q)
    src = store if store is not None else be
    if src.dim != tree.dim:
        raise ValueError(
            f"query dim {src.dim} != tree dim {tree.dim} "
            "(was the index built over a different corpus?)"
        )
    levels = int(tree.depth) - 1
    max_levels = _levels_bucket(levels)
    n = src.n_docs
    docs_out = np.full((n, k), -1, np.int32)
    dist_out = np.full((n, k), np.inf, np.float32)
    if n == 0:
        if degrade:
            return docs_out, dist_out, FaultReport()
        return docs_out, dist_out

    if store is not None:
        # out-of-core: the chunk's rows are read from the store's block cache
        # (a host disk fetch — inline, or on a Prefetcher reader thread) and
        # dispatched as a chunk-sized backend; with pipeline ≥ 2 the next
        # chunk's read overlaps this chunk's compute
        def dispatch(got):
            be_c = backend_from_rows(store, got)
            rows = jnp.arange(be_c.n_docs, dtype=jnp.int32)
            return _beam_search(
                tree, be_c, rows, jnp.int32(levels),
                max_levels=max_levels, beam=beam, k=k,
            )

        chunks = _store_chunk_iter(
            store, n, chunk, prefetch, dropped, prof=profiler,
        )
    else:
        def dispatch(rows):
            return _beam_search(
                tree, be, rows, jnp.int32(levels),
                max_levels=max_levels, beam=beam, k=k,
            )

        chunks = chunked_query_rows(n, chunk)

    _pipeline_chunks(chunks, pipeline, dispatch, docs_out, dist_out,
                     prof=profiler)
    if degrade:
        rows_lost = tuple(sorted(set(dropped))) if dropped else ()
        if rows_lost:
            idx = np.asarray(rows_lost, np.int64)
            docs_out[idx] = -1
            dist_out[idx] = np.inf
        qset = tuple(sorted(store.quarantined)) if store is not None else ()
        return docs_out, dist_out, FaultReport(
            degraded=bool(rows_lost), quarantined_blocks=qset,
            dropped_query_rows=rows_lost,
        )
    return docs_out, dist_out


# ---------------------------------------------------------------------------
# shard-parallel serving path (DESIGN.md §8): replicated tree + descent,
# row-sharded corpus at the leaf level, exact O(B·k·n_shards) top-k merge
# ---------------------------------------------------------------------------

def _tree_max_doc(tree: KTree) -> int:
    """Largest doc id stored in any leaf (host-side scan)."""
    child = np.asarray(tree.child)
    ne = np.asarray(tree.n_entries)
    return max(
        (int(child[leaf, : ne[leaf]].max()) for leaf in leaf_nodes(tree)),
        default=-1,
    )


def corpus_from_tree(tree: KTree) -> np.ndarray:
    """Recover the dense doc-vector corpus [n_docs, d] from the tree's own
    leaves (leaf entries *are* the inserted vectors). Default corpus for
    :func:`topk_search_sharded` when the build-time corpus isn't at hand; doc
    ids never inserted stay zero rows (the tree never addresses them)."""
    leaves = leaf_nodes(tree)
    child = np.asarray(tree.child)
    ne = np.asarray(tree.n_entries)
    centers = np.asarray(tree.centers)
    n_docs = _tree_max_doc(tree) + 1
    x = np.zeros((n_docs, tree.dim), np.float32)
    for leaf in leaves:
        x[child[leaf, : ne[leaf]]] = centers[leaf, : ne[leaf]]
    return x


_SHARDED_FN_CACHE: dict = {}


def _get_sharded_chunk_fn(mesh, shards_treedef, shards_specs, max_levels, beam, k):
    """Build (and cache) the jitted shard-map chunk function for one
    (mesh, corpus layout, level bucket, beam, k) setting.

    Per shard: translate the replicated beam candidates' global doc ids to
    local rows, score the owned ones against the local corpus block, take a
    local top-k, then all-gather the (k-wide) per-shard winners and merge with
    :func:`topk_merge_ref` — collective volume O(B·k·n_shards), never O(B·n)."""
    from repro.core.distributed import data_axes, flat_shard_index

    key = (mesh, shards_treedef, shards_specs, max_levels, beam, k)
    fn = _SHARDED_FN_CACHE.get(key)
    if fn is not None:
        return fn
    axes = data_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    spec_tree = jax.tree_util.tree_unflatten(shards_treedef, list(shards_specs))

    def leaf_merge(shards, xq, q_sq, cand, valid):
        # runs per shard: `shards` leaves are this shard's local corpus block
        del q_sq  # ordering is invariant to the per-query constant
        my_shard = flat_shard_index(mesh, axes)
        docs_per_shard = shards._rows0().shape[0]            # local block length
        local, owned = shards.to_local(cand, my_shard * docs_per_shard, docs_per_shard)
        owned = jnp.logical_and(owned, cand < shards.n_docs)  # pad rows own nothing
        part = shards.score_local(xq, local)                 # [B, C] ‖c‖²−2x·c
        part = jnp.where(jnp.logical_and(valid, owned), part, jnp.inf)
        pos, d_loc = topk_from_dist(part, k)                 # [B, k] local winners
        ids_loc = jnp.where(
            pos >= 0,
            jnp.take_along_axis(cand, jnp.clip(pos, 0, cand.shape[1] - 1), axis=1),
            -1,
        )
        # tiny collective: each shard contributes only its k-wide winner list
        g_d, g_i = d_loc, ids_loc
        for a in reversed(axes):
            g_d = jax.lax.all_gather(g_d, a)
            g_i = jax.lax.all_gather(g_i, a)
        b = xq.shape[0]
        g_d = g_d.reshape(n_shards, b, k).transpose(1, 0, 2)  # [B, S, k]
        g_i = g_i.reshape(n_shards, b, k).transpose(1, 0, 2)
        return topk_merge_ref(g_i, g_d, k)

    smap = jax.shard_map(
        leaf_merge,
        mesh=mesh,
        in_specs=(spec_tree, P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def chunk_fn(tree, qbe, rows, levels, shards):
        cand, valid, xq, q_sq = _chunk_candidates(
            tree, qbe, rows, levels, max_levels, beam
        )
        ids, part_d = smap(shards, xq, q_sq, cand, valid)
        found = ids >= 0
        # the dropped ‖x‖² goes back in after the merge, exactly like _beam_search
        dist = jnp.where(
            found, jnp.maximum(part_d + q_sq[:, None], 0.0), jnp.inf
        )
        return ids, dist

    fn = jax.jit(chunk_fn)
    _SHARDED_FN_CACHE[key] = fn
    return fn


def _chunk_candidates(
    tree: KTree, qbe: VectorBackend, rows: jax.Array, levels: jax.Array,
    max_levels: int, beam: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Descend one query chunk and expose its leaf-level candidate set:
    (cand i32[B, beam·m1] global doc ids, valid bool[B, beam·m1],
    xq f32[B, d] densified queries, q_sq f32[B]). Shared by the in-memory
    sharded chunk fn and the store-backed sharded path, so both score the
    exact same candidates for the exact same queries."""
    frontier, active = _beam_frontier(tree, qbe, rows, levels, max_levels, beam)
    b = rows.shape[0]
    m1 = tree.slots
    cand = tree.child[frontier].reshape(b, beam * m1)
    slot_ok = (
        jnp.arange(m1)[None, None, :] < tree.n_entries[frontier][:, :, None]
    )
    valid = jnp.logical_and(slot_ok, active[:, :, None]).reshape(b, beam * m1)
    xq = qbe.take(rows).astype(jnp.float32)                  # chunk-sized densify
    q_sq = qbe.row_sq(rows)
    return cand, valid, xq, q_sq


@functools.partial(jax.jit, static_argnames=("max_levels", "beam"))
def _chunk_candidates_jit(tree, qbe, rows, levels, max_levels, beam):
    """Jitted :func:`_chunk_candidates` — the device half of the store-backed
    sharded path (the host half fetches the owned candidates from disk)."""
    return _chunk_candidates(tree, qbe, rows, levels, max_levels, beam)


_STORE_MERGE_FN_CACHE: dict = {}


def _get_store_merge_fn(mesh, kind: str, k: int):
    """Build (and cache) the jitted shard-map pool-scoring merge for one
    (mesh, store layout, k) setting — the out-of-core counterpart of
    :func:`_get_sharded_chunk_fn`'s leaf merge (DESIGN.md §9).

    Each shard scores its fetched candidate *pool* with the exact
    ``DenseDocShards``/``EllDocShards.score_local`` expressions (pool rows
    are bit-identical to the corpus rows they were read from, so per-shard
    distances — and the all-gathered ``topk_merge_ref`` result — match the
    in-memory sharded path bit for bit); the collective stays
    O(B·k·n_shards)."""
    from repro.core.distributed import data_axes

    key = (mesh, kind, k)
    fn = _STORE_MERGE_FN_CACHE.get(key)
    if fn is not None:
        return fn
    axes = data_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    n_pools = 1 if kind == "dense" else 2
    pool_spec = tuple(P(axes, None, None) for _ in range(n_pools))

    def merge(pools, pool_idx, owned, xq, q_sq, cand, valid):
        # per shard: leading stacked axis is this shard's slot — squeeze it
        pool_idx, owned = pool_idx[0], owned[0]
        if kind == "dense":
            xd = pools[0][0][pool_idx].astype(jnp.float32)    # [B, C, d]
            c_sq = jnp.einsum("bcd,bcd->bc", xd, xd, precision=EXACT)
            part = c_sq - 2.0 * jnp.einsum(
                "bd,bcd->bc", xq.astype(jnp.float32), xd, precision=EXACT
            )
        else:
            pv, pc = pools[0][0], pools[1][0]                 # [U, nnz] each
            sq = jnp.sum(pv.astype(jnp.float32) ** 2, axis=1)
            v = pv[pool_idx].astype(jnp.float32)              # [B, C, nnz]
            c = pc[pool_idx]
            b_idx = jnp.arange(xq.shape[0])[:, None, None]
            g = xq.astype(jnp.float32)[b_idx, c]
            part = sq[pool_idx] - 2.0 * jnp.einsum("bcn,bcn->bc", v, g, precision=EXACT)
        part = jnp.where(jnp.logical_and(valid, owned), part, jnp.inf)
        pos, d_loc = topk_from_dist(part, k)
        ids_loc = jnp.where(
            pos >= 0,
            jnp.take_along_axis(cand, jnp.clip(pos, 0, cand.shape[1] - 1), axis=1),
            -1,
        )
        g_d, g_i = d_loc, ids_loc
        for a in reversed(axes):
            g_d = jax.lax.all_gather(g_d, a)
            g_i = jax.lax.all_gather(g_i, a)
        b = xq.shape[0]
        g_d = g_d.reshape(n_shards, b, k).transpose(1, 0, 2)  # [B, S, k]
        g_i = g_i.reshape(n_shards, b, k).transpose(1, 0, 2)
        ids, part_d = topk_merge_ref(g_i, g_d, k)
        # the dropped ‖x‖² goes back in after the merge, like _beam_search
        dist = jnp.where(
            ids >= 0, jnp.maximum(part_d + q_sq[:, None], 0.0), jnp.inf
        )
        return ids, dist

    smap = jax.shard_map(
        merge,
        mesh=mesh,
        in_specs=(pool_spec, P(axes, None, None), P(axes, None, None),
                  P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    fn = jax.jit(smap)
    _STORE_MERGE_FN_CACHE[key] = fn
    return fn


def _topk_search_sharded_store(
    mesh, tree: KTree, q, sshards: StoreDocShards, k: int, beam: int,
    chunk: int, on_fault: str = "raise", prefetch: int = 0,
    prof=NULL_PROFILER,
) -> Tuple[np.ndarray, np.ndarray]:
    """Shard-parallel top-k over a disk-backed corpus (DESIGN.md §9): per
    chunk, the jitted descent yields the beam candidate set, each shard's
    partition fetches only the candidates it owns through its own block
    cache (:meth:`StoreDocShards.chunk_pools`), and the shard-map pool merge
    returns the exact global top-k. The full corpus is never resident — peak
    store bytes stay within n_shards × per-shard budget.

    ``on_fault="degrade"`` (DESIGN.md §10) drops only the unreadable blocks'
    candidates (their docs score +inf, exactly as if no shard owned them) and
    unreadable *query* rows (flagged (−1, +inf)); surviving answers are
    bit-identical to a reference search over the surviving corpus subset.
    Returns a third :class:`repro.core.faults.FaultReport` element.

    ``prefetch ≥ 1`` moves store *query*-row reads onto a Prefetcher reader
    thread (the corpus-candidate fetches stay demand-driven — they depend on
    each chunk's descent); ``prof`` records "read" spans per query-chunk
    fetch, "dispatch" around the jitted descent, and "compute" around the
    host-sync pool fetch + shard-map merge (DESIGN.md §11)."""
    degrade = on_fault == "degrade"
    store_q = q if is_store(q) else None
    qbe = None if store_q is not None else make_backend(q)
    n = (store_q if store_q is not None else qbe).n_docs
    levels = int(tree.depth) - 1
    max_levels = _levels_bucket(levels)
    merge_fn = _get_store_merge_fn(mesh, sshards.kind, k)
    docs_out = np.full((n, k), -1, np.int32)
    dist_out = np.full((n, k), np.inf, np.float32)
    rows_lost: set = set()
    docs_lost: set = set()

    def _report() -> FaultReport:
        qset = set(sshards.parts[0].quarantined)
        if store_q is not None:
            qset |= set(store_q.quarantined)
        return FaultReport(
            degraded=bool(rows_lost or docs_lost),
            quarantined_blocks=tuple(sorted(qset)),
            dropped_query_rows=tuple(sorted(rows_lost)),
            dropped_docs=len(docs_lost),
        )

    if n == 0:
        return (docs_out, dist_out, _report()) if degrade \
            else (docs_out, dist_out)
    if store_q is not None:
        dropped_q: list = []

        def chunk_backends():
            for rows_np, got in _store_chunk_iter(
                store_q, n, chunk, prefetch,
                dropped_q if degrade else None, prof=prof,
            ):
                qbe_c = backend_from_rows(store_q, got)
                rows = jnp.arange(qbe_c.n_docs, dtype=jnp.int32)
                yield rows_np, qbe_c, rows
    else:
        def chunk_backends():
            for rows_np, padded in padded_chunk_rows(n, chunk):
                yield rows_np, qbe, jnp.asarray(padded.astype(np.int32))

    for rows_np, qbe_c, rows in chunk_backends():
        with prof.span("dispatch"):
            cand, valid, xq, q_sq = _chunk_candidates_jit(
                tree, qbe_c, rows, jnp.int32(levels),
                max_levels=max_levels, beam=beam,
            )
        with prof.span("compute"):
            # host sync: the candidate ids drive this chunk's disk fetches
            pools, pool_idx, owned, dropped_ids = sshards.chunk_pools(
                np.asarray(cand), np.asarray(valid), on_fault=on_fault
            )
            if dropped_ids.size:
                docs_lost.update(int(i) for i in dropped_ids)
            ids, dist = merge_fn(
                pools, pool_idx, owned, xq, q_sq, cand, valid
            )
            docs_out[rows_np] = np.asarray(ids)[: rows_np.size]
            dist_out[rows_np] = np.asarray(dist)[: rows_np.size]
    if store_q is not None and dropped_q:
        rows_lost.update(dropped_q)
    if degrade:
        if rows_lost:
            idx = np.asarray(sorted(rows_lost), np.int64)
            docs_out[idx] = -1
            dist_out[idx] = np.inf
        return docs_out, dist_out, _report()
    return docs_out, dist_out


def shard_corpus(mesh, corpus, axes=None) -> DocShards:
    """Normalise (corpus, mesh) into a row-sharded corpus view: accepts a dense
    array, Csr, backend, or an already-sharded ``*DocShards`` (passed through)."""
    if isinstance(corpus, (DenseDocShards, EllDocShards)):
        return corpus
    return make_backend(corpus).shard(mesh, axes)


def topk_search_sharded(
    mesh, tree: KTree, q, corpus=None, k: int = 10, beam: int = 4,
    chunk: Optional[int] = None, pipeline: Optional[int] = None,
    prefetch: Optional[int] = None, on_fault: str = "raise",
    rp=None, rp_corpus=None, tuned=None, profiler=NULL_PROFILER,
) -> Tuple[np.ndarray, np.ndarray]:
    """Shard-parallel top-k search: same answers as :func:`topk_search`, with
    the corpus row-sharded over ``mesh``'s data axes (DESIGN.md §8).

    The (small) tree is replicated and every shard descends the full query
    chunk (descent touches only internal-node centres); at the leaf level each
    shard scores just the beam candidates *it owns* against its local corpus
    block, reduces them to a k-wide winner list, and an all-gather +
    :func:`topk_merge_ref` merge produces the exact global (doc_ids, dists)
    [B, k] — the collective moves O(B·k·n_shards) scalars, never O(B·n).

    ``corpus``: the document corpus the tree was built over (array, Csr,
    backend, or a pre-sharded ``backend.shard(mesh)`` result — pass the latter
    when serving many batches so rows are placed once). Defaults to the dense
    vectors recovered from the tree's own leaves. Exact distance ties across
    shards resolve in shard-major (= doc-id-range) order, which can differ
    from the single-device candidate order; real-valued corpora are unaffected.

    Out-of-core (DESIGN.md §9): a ``CorpusStore`` corpus (or a pre-built
    ``backend.shard_from_store`` handle — pass that when serving many batches
    so the per-shard block caches persist) keeps the corpus on disk: each
    shard fetches only the beam candidates it owns through its own block
    cache, and answers stay bit-identical to the in-memory sharded path.
    ``q`` may itself be a store/slice (chunk rows fetched on demand), with
    either corpus kind. The store-corpus path runs one chunk at a time
    (``pipeline`` does not apply): the descent's candidate ids must return to
    the host to drive that chunk's disk fetches.

    Fault handling (DESIGN.md §10): ``on_fault="degrade"`` applies to store
    query sources and store corpora — unreadable query rows answer (−1, +inf)
    and quarantined corpus blocks' candidates are dropped (scored +inf, as
    if no shard owned them); surviving answers stay bit-identical to a
    reference search over the surviving subset. Degrade mode returns a third
    :class:`repro.core.faults.FaultReport` element; the default ``"raise"``
    keeps the two-tuple API and surfaces typed block errors.

    Random projection (DESIGN.md §5.1): with ``rp=``, descent is replicated
    work anyway (it touches only the small projected tree) and the exact
    rescore runs host-side against the original corpus — ``rp_corpus=``,
    the ``corpus`` argument, or the rp backend's base, in that order. A
    ``StoreDocShards`` corpus keeps the rescore fetches behind the
    per-shard partition caches (residency stays bounded). The candidate
    pools come from the same jitted descent as the single-device RP path,
    and the rescore is the same per-query ``brute_force_topk_dist`` call —
    so sharded RP answers are bit-identical to single-device RP answers by
    construction. Not composable with ``on_fault="degrade"`` yet.

    ``prefetch ≥ 1`` (store query sources and the RP route) moves the disk
    reads onto a ``store.Prefetcher`` reader thread, exactly as in
    :func:`topk_search` — answers unchanged. ``chunk``/``pipeline``/
    ``prefetch`` left ``None`` resolve through ``tuned=`` then the repo
    defaults (DESIGN.md §11); ``profiler=`` records the same
    "read"/"dispatch"/"compute" spans as the single-device path.
    """
    if k < 1 or beam < 1:
        raise ValueError(f"k and beam must be ≥ 1, got k={k} beam={beam}")
    chunk, pipeline, prefetch = resolve_knobs(
        tuned, chunk=chunk, pipeline=pipeline, prefetch=prefetch,
    )
    check_on_fault(on_fault)
    if rp is not None:
        if on_fault != "raise":
            raise ValueError(
                "rp= does not compose with on_fault='degrade' yet"
            )
        projection, src = _resolve_rp(
            rp, rp_corpus if rp_corpus is not None else corpus
        )
        return _topk_search_rp(
            tree, q, projection, src, k=k, beam=beam, chunk=chunk,
            pipeline=pipeline, prefetch=prefetch, prof=profiler,
        )
    degrade = on_fault == "degrade"
    store_q = q if is_store(q) else None
    qbe = None if store_q is not None else make_backend(q)
    q_src = store_q if store_q is not None else qbe
    if q_src.dim != tree.dim:
        raise ValueError(
            f"query dim {q_src.dim} != tree dim {tree.dim} "
            "(was the index built over a different corpus?)"
        )
    if isinstance(corpus, StoreDocShards) or is_store(corpus):
        sshards = (
            corpus if isinstance(corpus, StoreDocShards)
            else shard_from_store(mesh, corpus)
        )
        if sshards.dim != tree.dim:
            raise ValueError(
                f"corpus dim {sshards.dim} != tree dim {tree.dim}"
            )
        return _topk_search_sharded_store(
            mesh, tree, q, sshards, k=k, beam=beam, chunk=chunk,
            on_fault=on_fault, prefetch=prefetch, prof=profiler,
        )
    fresh = not isinstance(corpus, (DenseDocShards, EllDocShards))
    shards = shard_corpus(mesh, corpus_from_tree(tree) if corpus is None else corpus)
    if shards.dim != tree.dim:
        raise ValueError(f"corpus dim {shards.dim} != tree dim {tree.dim}")
    if fresh and corpus is not None:
        # sharding a raw corpus already walks the host arrays once — spend a
        # cheap extra scan making a wrong-corpus pairing loud instead of
        # silently dropping the doc ids the corpus can't address. Pre-sharded
        # corpora (the serving hot path) skip this; callers own the pairing.
        max_doc = _tree_max_doc(tree)
        if max_doc >= shards.n_docs:
            raise ValueError(
                f"tree addresses doc id {max_doc} but the corpus has only "
                f"{shards.n_docs} rows (was the index built over a different "
                "corpus?)"
            )
    from repro.core.distributed import data_axes

    axes = data_axes(mesh)
    leaves, treedef = jax.tree_util.tree_flatten(shards)
    specs = tuple(P(axes, *([None] * (l.ndim - 1))) for l in leaves)
    levels = int(tree.depth) - 1
    fn = _get_sharded_chunk_fn(
        mesh, treedef, specs, _levels_bucket(levels), beam, k
    )
    n = q_src.n_docs
    docs_out = np.full((n, k), -1, np.int32)
    dist_out = np.full((n, k), np.inf, np.float32)
    rows_lost: set = set()
    if n == 0:
        return (docs_out, dist_out, FaultReport()) if degrade \
            else (docs_out, dist_out)

    if store_q is not None:
        # store-sourced queries: fetch each chunk's rows from the block cache
        # (inline, or on a Prefetcher reader thread when prefetch ≥ 1) and
        # descend a chunk-sized backend, exactly like topk_search's §9 path
        dropped_q: Optional[list] = [] if degrade else None

        def dispatch(got):
            qbe_c = backend_from_rows(store_q, got)
            rows = jnp.arange(qbe_c.n_docs, dtype=jnp.int32)
            return fn(tree, qbe_c, rows, jnp.int32(levels), shards)

        chunks = _store_chunk_iter(
            store_q, n, chunk, prefetch, dropped_q, prof=profiler,
        )
    else:
        def dispatch(rows):
            return fn(tree, qbe, rows, jnp.int32(levels), shards)

        chunks = chunked_query_rows(n, chunk)

    _pipeline_chunks(chunks, pipeline, dispatch, docs_out, dist_out,
                     prof=profiler)
    if degrade:
        if store_q is not None and dropped_q:
            rows_lost.update(dropped_q)
        if rows_lost:
            idx = np.asarray(sorted(rows_lost), np.int64)
            docs_out[idx] = -1
            dist_out[idx] = np.inf
        qset = tuple(sorted(store_q.quarantined)) if store_q is not None else ()
        return docs_out, dist_out, FaultReport(
            degraded=bool(rows_lost), quarantined_blocks=qset,
            dropped_query_rows=tuple(sorted(rows_lost)),
        )
    return docs_out, dist_out


# ---------------------------------------------------------------------------
# random-projection routing (DESIGN.md §5.1): beam descent in the projected
# space, exact rescore of the leaf candidate pool from the original
# representation. Approximate-route, exact-rescore — the Random Indexing
# K-tree's serving path.
# ---------------------------------------------------------------------------


def _resolve_rp(rp, src):
    """Normalise the ``rp=``/``rp_corpus=`` pair into (projection, rescore
    source) with typed validation. ``rp``: a ``RandomProjection`` or a
    ``RandomProjBackend`` (whose in-memory ``base``, if any, is the default
    source); ``src``: an explicit original-representation corpus — array,
    Csr, backend, ``CorpusStore``/``StoreSlice``, ``*DocShards``, or a
    ``StoreDocShards`` handle (rescore rows then fetch through the per-shard
    partition caches)."""
    if isinstance(rp, RandomProjBackend):
        projection = rp.projection
        if src is None:
            src = rp.base
    elif isinstance(rp, RandomProjection):
        projection = rp
    else:
        raise TypeError(
            f"rp must be a RandomProjection or RandomProjBackend, "
            f"got {type(rp).__name__}"
        )
    if isinstance(src, RandomProjBackend):
        src = src.base
    if src is None:
        raise ValueError(
            "RP rescore needs the original corpus: pass rp_corpus= "
            "(array/backend/CorpusStore/shards) or an RandomProjBackend "
            "with an in-memory base"
        )
    return projection, src


def _ell_densify_rows(values, cols, dim: int) -> np.ndarray:
    """Densify fetched ELL rows host-side → f32[B, dim]. Value-0 slots are
    padding (the repo-wide ELL convention), so the scatter-add contributes
    exactly +0.0 for them — bit-identical to the device ``take`` densify."""
    values = np.asarray(values)
    cols = np.asarray(cols)
    out = np.zeros((values.shape[0], dim), np.float32)
    rows = np.repeat(np.arange(values.shape[0]), values.shape[1])
    np.add.at(
        out, (rows, cols.ravel().astype(np.intp)),
        values.astype(np.float32, copy=False).ravel(),
    )
    return out


def _rp_row_fetcher(src, in_dim: int):
    """Build ``fetch(sorted unique global ids) → f32[U, in_dim]`` over the
    original representation — the rescore stage's row source. The fetched
    bytes are pinned bit-identical across source kinds (store round-trips
    are exact; ELL densifies reproduce ``take``), which is what lets the
    single-device, store-backed, and sharded rescores agree exactly."""
    if isinstance(src, StoreDocShards):
        if src.dim != in_dim:
            raise ProjectionMismatch(
                f"rescore corpus dim {src.dim} != projection in_dim {in_dim}"
            )

        def fetch(ids):
            out = np.zeros((ids.size, in_dim), np.float32)
            dps = src.docs_per_shard
            for s, part in enumerate(src.parts):
                lo = s * dps
                m = np.logical_and(ids >= lo, ids < lo + part.n_docs)
                if not m.any():
                    continue
                got = part.take_rows(ids[m] - lo)
                if src.kind == "dense":
                    out[m] = np.asarray(got["x"]).astype(np.float32, copy=False)
                else:
                    out[m] = _ell_densify_rows(got["values"], got["cols"], in_dim)
            src.peak_resident_bytes = max(
                src.peak_resident_bytes,
                sum(p.store.cache.resident_bytes for p in src.parts),
            )
            return out

        return fetch
    if is_store(src):
        if src.dim != in_dim:
            raise ProjectionMismatch(
                f"rescore corpus dim {src.dim} != projection in_dim {in_dim}"
            )

        def fetch(ids):
            got = src.take_rows(ids)
            if src.kind == "dense":
                return np.asarray(got["x"]).astype(np.float32, copy=False)
            return _ell_densify_rows(got["values"], got["cols"], in_dim)

        return fetch
    if isinstance(src, (DenseDocShards, EllDocShards)):
        if src.dim != in_dim:
            raise ProjectionMismatch(
                f"rescore corpus dim {src.dim} != projection in_dim {in_dim}"
            )
        if isinstance(src, DenseDocShards):
            x_np = np.asarray(src.x)
            return lambda ids: x_np[ids].astype(np.float32, copy=False)
        v_np, c_np = np.asarray(src.values), np.asarray(src.cols)
        return lambda ids: _ell_densify_rows(v_np[ids], c_np[ids], in_dim)
    be = make_backend(src)
    if be.dim != in_dim:
        raise ProjectionMismatch(
            f"rescore corpus dim {be.dim} != projection in_dim {in_dim}"
        )

    def fetch(ids):
        # pad to a power-of-two length (repeating the last id): every union
        # size would otherwise compile its own gather
        pad = _levels_bucket(ids.size) - ids.size
        padded = np.concatenate([ids, np.repeat(ids[-1:], pad)])
        rows = be.take(jnp.asarray(padded, dtype=jnp.int32))
        return np.asarray(rows)[: ids.size].astype(np.float32, copy=False)

    return fetch


def _rescore_pool_chunk(
    x_q: np.ndarray, cand: np.ndarray, valid: np.ndarray, fetch_rows, k: int,
    prefetched=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact rescore of one chunk's leaf candidate pools.

    Per query: its valid candidates, deduplicated and sorted ascending, are
    gathered from the original representation and ranked by literally
    calling :func:`brute_force_topk_dist` over them — so the result *is*
    brute force restricted to the pool, bit for bit (the golden-equivalence
    tests make the same call). The union of the chunk's candidates is
    fetched once (one store round-trip per chunk); per-query rows are host
    gathers from that union. Distances clamp at 0 like every exact-path
    leaf distance.

    ``prefetched=(union, rows_u)`` hands in that union fetch done ahead of
    time (the ``prefetch ≥ 1`` rescore read-ahead in :func:`_topk_search_rp`)
    — the caller computed ``union`` by the exact expression below, so the
    ranking is bit-identical either way."""
    b = x_q.shape[0]
    docs = np.full((b, k), -1, np.int32)
    dist = np.full((b, k), np.inf, np.float32)
    if not valid.any():
        return docs, dist
    if prefetched is not None:
        union, rows_u = prefetched
    else:
        union = np.unique(cand[valid]).astype(np.int64)
        rows_u = fetch_rows(union)
    for i in range(b):
        ids_i = np.unique(cand[i][valid[i]]).astype(np.int64)
        if not ids_i.size:
            continue
        rows_i = rows_u[np.searchsorted(union, ids_i)]
        sel, d = brute_force_topk_dist(x_q[i : i + 1], rows_i, k)
        kk = sel.shape[1]
        docs[i, :kk] = ids_i[sel[0]]
        dist[i, :kk] = np.maximum(d[0], 0.0).astype(np.float32)
    return docs, dist


def rp_candidate_pools(
    tree: KTree, q, rp, beam: int = 4, chunk: int = 512,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The RP descent's leaf candidate pools, host-side: (cand i32[n,
    beam·m1] global doc ids, valid bool[n, beam·m1], x_q f32[n, in_dim]
    original query rows).

    These are *exactly* the pools ``topk_search(..., rp=...)`` rescores for
    the same ``(q, beam, chunk)`` — chunking affects which rows share a
    projection call, so pass the same ``chunk`` — produced by the same
    jitted ``_chunk_candidates`` descent. Exposed for the
    golden-equivalence tests (restrict ``brute_force_topk_dist`` to a pool
    and compare bit-for-bit) and for recall diagnostics."""
    projection = rp.projection if isinstance(rp, RandomProjBackend) else rp
    if not isinstance(projection, RandomProjection):
        raise TypeError(
            f"rp must be a RandomProjection or RandomProjBackend, "
            f"got {type(rp).__name__}"
        )
    store_q = q if is_store(q) else None
    qbe = None if store_q is not None else make_backend(q)
    q_src = store_q if store_q is not None else qbe
    if q_src.dim != projection.in_dim:
        raise ProjectionMismatch(
            f"query dim {q_src.dim} != projection in_dim {projection.in_dim}"
        )
    if tree.dim != projection.out_dim:
        raise ProjectionMismatch(
            f"tree dim {tree.dim} != projection out_dim {projection.out_dim} "
            "(was the tree built under a different projection?)"
        )
    levels = int(tree.depth) - 1
    max_levels = _levels_bucket(levels)
    n = q_src.n_docs
    cands, valids, xqs = [], [], []
    for rows_np, padded in padded_chunk_rows(n, chunk):
        if store_q is not None:
            qbe_c = backend_from_store(store_q, padded)
            rows = jnp.arange(padded.size, dtype=jnp.int32)
        else:
            qbe_c = qbe
            rows = jnp.asarray(padded.astype(np.int32))
        xq, cand, valid = _rp_chunk_candidates(
            tree, projection, qbe_c, rows, levels, max_levels, beam
        )
        b = rows_np.size
        cands.append(np.asarray(cand)[:b])
        valids.append(np.asarray(valid)[:b])
        xqs.append(np.asarray(xq)[:b].astype(np.float32, copy=False))
    if not cands:
        m1 = tree.slots
        return (np.zeros((0, beam * m1), np.int32),
                np.zeros((0, beam * m1), bool),
                np.zeros((0, projection.in_dim), np.float32))
    return (np.concatenate(cands), np.concatenate(valids), np.concatenate(xqs))


def _rp_chunk_candidates(
    tree: KTree, projection: RandomProjection, qbe_c, rows, levels: int,
    max_levels: int, beam: int,
):
    """One chunk of the RP descent: densify the original query rows, project
    them (one jitted matmul per row-bucket shape — replay-stable for equal
    chunking), and run the shared jitted candidate extraction over a dense
    backend of the projected rows. Returns device (x_q original, cand,
    valid). Single source of the RP pools: the single-device search, the
    sharded search, and :func:`rp_candidate_pools` all come through here."""
    xq = qbe_c.take(rows)                                     # original rows
    zq = projection.apply(xq)                                 # projected rows
    qbe_p = DenseBackend(zq)
    rows_p = jnp.arange(zq.shape[0], dtype=jnp.int32)
    cand, valid, _, _ = _chunk_candidates_jit(
        tree, qbe_p, rows_p, jnp.int32(levels),
        max_levels=max_levels, beam=beam,
    )
    return xq, cand, valid


def _topk_search_rp(
    tree: KTree, q, projection: RandomProjection, src, k: int, beam: int,
    chunk: int, pipeline: int, prefetch: int, prof=NULL_PROFILER,
) -> Tuple[np.ndarray, np.ndarray]:
    """The RP serving path: projected beam descent + exact host rescore.

    Same dispatch-ahead chunk schedule as :func:`topk_search` — the drain
    side runs the host rescore (a disk fetch + numpy ranking) instead of a
    plain D2H copy-out, so device descent of chunk i+1 overlaps chunk i's
    rescore. Every answer row depends only on its own query row and pool,
    so engine batching/caching compose exactly as for the exact path.

    ``prefetch ≥ 1`` applies at *both* disk seams: the store query-source
    reads move onto a ``store.Prefetcher`` reader thread (descent source),
    and the rescore's per-chunk candidate-union fetch moves onto a
    single-worker read-ahead executor so chunk i+1's rescore rows load
    while chunk i is still ranking. The union is computed by the same
    expression :func:`_rescore_pool_chunk` would use, so answers stay
    bit-identical (pinned in tests/test_rp.py)."""
    store_q = q if is_store(q) else None
    qbe = None if store_q is not None else make_backend(q)
    q_src = store_q if store_q is not None else qbe
    if q_src.dim != projection.in_dim:
        raise ProjectionMismatch(
            f"query dim {q_src.dim} != projection in_dim {projection.in_dim}"
        )
    if tree.dim != projection.out_dim:
        raise ProjectionMismatch(
            f"tree dim {tree.dim} != projection out_dim {projection.out_dim} "
            "(was the tree built under a different projection?)"
        )
    fetch_raw = _rp_row_fetcher(src, projection.in_dim)
    if prof.enabled:
        def fetch_rows(ids):
            with prof.span("read"):
                return fetch_raw(ids)
    else:
        fetch_rows = fetch_raw
    levels = int(tree.depth) - 1
    max_levels = _levels_bucket(levels)
    n = q_src.n_docs
    docs_out = np.full((n, k), -1, np.int32)
    dist_out = np.full((n, k), np.inf, np.float32)
    if n == 0:
        return docs_out, dist_out

    if store_q is not None:
        def dispatch(got):
            qbe_c = backend_from_rows(store_q, got)
            rows = jnp.arange(qbe_c.n_docs, dtype=jnp.int32)
            return _rp_chunk_candidates(
                tree, projection, qbe_c, rows, levels, max_levels, beam
            )

        chunks = _store_chunk_iter(store_q, n, chunk, prefetch, prof=prof)
    else:
        def dispatch(rows):
            return _rp_chunk_candidates(
                tree, projection, qbe, rows, levels, max_levels, beam
            )

        chunks = chunked_query_rows(n, chunk)

    depth = max(int(pipeline), 1)
    pending = collections.deque()
    ready = collections.deque()
    # rescore read-ahead (prefetch ≥ 1): a single-worker executor fetches
    # chunk i+1's candidate-union rows while chunk i's rescore is ranking
    executor = (
        ThreadPoolExecutor(max_workers=1) if int(prefetch or 0) >= 1
        else None
    )

    def harvest_one():
        # device→host sync of the oldest in-flight descent; with the
        # executor, also kick off its rescore union fetch in the background
        rows_np, (xq, cand, valid) = pending.popleft()
        b = rows_np.size
        with prof.span("compute"):
            xq_np = np.asarray(xq)[:b].astype(np.float32, copy=False)
            cand_np = np.asarray(cand)[:b]
            valid_np = np.asarray(valid)[:b]
        pre = None
        if executor is not None and valid_np.any():
            # exact expression _rescore_pool_chunk would use → bit-identical
            union = np.unique(cand_np[valid_np]).astype(np.int64)
            pre = (union, executor.submit(fetch_rows, union))
        ready.append((rows_np, xq_np, cand_np, valid_np, pre))

    def rank_one():
        rows_np, xq_np, cand_np, valid_np, pre = ready.popleft()
        prefetched = None
        if pre is not None:
            union, fut = pre
            prefetched = (union, fut.result())
        with prof.span("compute"):
            d, s = _rescore_pool_chunk(
                xq_np, cand_np, valid_np, fetch_rows, k,
                prefetched=prefetched,
            )
        docs_out[rows_np] = d
        dist_out[rows_np] = s

    try:
        for rows_np, payload in chunks:
            with prof.span("dispatch"):
                fut = dispatch(payload)
            pending.append((rows_np, fut))
            while len(pending) >= depth:
                harvest_one()
            while len(ready) >= 2:
                rank_one()
        while pending:
            harvest_one()
        while ready:
            rank_one()
    finally:
        if executor is not None:
            executor.shutdown(wait=True)
    return docs_out, dist_out


# ---------------------------------------------------------------------------
# answer cache (serving plane): LRU over content-hashed (query, k, beam)
# ---------------------------------------------------------------------------

class AnswerCache:
    """LRU top-k answer cache keyed by a content hash of (query row bytes,
    dtype, k, beam), with hit/miss counters for the serving QPS report.

    Exactness caveat: keys hash the raw float encoding, so only bit-identical
    queries hit (0.0 vs −0.0, or the same vector at a different dtype, miss);
    a blake2b-128 collision would alias two distinct queries — negligible
    (~2⁻⁶⁴ at any realistic cache size) but nonzero, hence "answer cache", not
    a correctness layer.

    Staleness: answers are valid for exactly one index. ``bind(index)`` clears
    the cache whenever a different index object shows up — KTree is an
    immutable pytree (``insert`` returns a *new* tree), so object identity is
    a sound invalidation token; :func:`topk_search_cached` binds on every
    call, making post-insert and cross-tree staleness impossible.

    Store-backed corpora add a second identity axis: the tree object can stay
    the same while the on-disk corpus it addresses is regenerated in place
    (same path, new blocks) — object identity alone would then serve answers
    whose doc ids point at different documents. ``bind(index, corpus_token)``
    closes that hole: pass the store's ``manifest_hash`` (a content hash over
    the per-block digests, DESIGN.md §9) and any token change flushes the
    cache.

    Thread safety: the serving engine (``core/engine.py``) consults the cache
    from its dispatcher thread while other threads admit requests, so
    ``get``/``put``/``bind`` (and the stats snapshot) run under a lock —
    matching the :class:`repro.core.store.BlockCache` treatment. Every call
    increments exactly one of hits/misses and LRU order stays consistent
    under concurrency."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"cache capacity must be ≥ 1, got {capacity}")
        self.capacity = int(capacity)
        self._entries: "collections.OrderedDict[bytes, Tuple[np.ndarray, np.ndarray]]" = (
            collections.OrderedDict()
        )
        self._index = None
        self._corpus_token = None
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def bind(self, index, corpus_token: Optional[str] = None) -> None:
        """Tie cached answers to one (index object, corpus content) pair.

        A different index object (a new tree after insert, another tree
        entirely) or a changed ``corpus_token`` (a store regenerated in place
        — pass the store's ``manifest_hash``) flushes all entries. The bound
        index is held strongly, so its id can never be recycled while
        bound."""
        with self._lock:
            if index is not self._index or corpus_token != self._corpus_token:
                self._entries.clear()
                self._index = index
                self._corpus_token = corpus_token

    @staticmethod
    def make_key(row: np.ndarray, k: int, beam: int) -> bytes:
        """Content key: blake2b-128 over (raw row bytes, dtype, k, beam)."""
        h = hashlib.blake2b(digest_size=16)
        row = np.ascontiguousarray(row)
        h.update(row.tobytes())
        h.update(f"|{row.dtype}|{k}|{beam}".encode())
        return h.digest()

    def get(self, key: bytes):
        """(docs, dists) for a key, refreshing its LRU position; None on miss."""
        with self._lock:
            val = self._entries.get(key)
            if val is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return val

    def put(self, key: bytes, value: Tuple[np.ndarray, np.ndarray]) -> None:
        """Insert (docs, dists) at ``key``, evicting LRU entries over
        capacity."""
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def stats(self) -> dict:
        """hits/misses/hit_rate/size/capacity for the serving report."""
        with self._lock:
            total = self.hits + self.misses
            return dict(
                hits=self.hits, misses=self.misses,
                hit_rate=self.hits / total if total else 0.0,
                size=len(self._entries), capacity=self.capacity,
            )


def cache_stage(
    cache: AnswerCache, x_q: np.ndarray, k: int, beam: int,
) -> Tuple[np.ndarray, np.ndarray, "collections.OrderedDict[bytes, list]"]:
    """Pre-batch cache stage: probe every row of ``x_q`` against ``cache``.

    Returns ``(docs, dist, miss_rows)`` where hit rows of the [n, k] answer
    arrays are already filled (misses stay (−1, +inf) until
    :func:`cache_fill`) and ``miss_rows`` maps each missing content key to the
    row indices sharing it, in first-appearance order — the in-batch dedup: one
    engine row per distinct missing query. The caller must have ``bind``-ed
    the cache; :func:`topk_search_cached` and the serving engine
    (``core/engine.py``) both stage through here so their hit/miss accounting
    and LRU traffic are identical."""
    n = x_q.shape[0]
    docs = np.full((n, k), -1, np.int32)
    dist = np.full((n, k), np.inf, np.float32)
    miss_rows: "collections.OrderedDict[bytes, list]" = collections.OrderedDict()
    for i in range(n):
        key = AnswerCache.make_key(x_q[i], k, beam)
        val = cache.get(key)
        if val is not None:
            docs[i], dist[i] = val
        else:
            miss_rows.setdefault(key, []).append(i)
    return docs, dist, miss_rows


def cache_fill(
    cache: AnswerCache,
    miss_rows: "collections.OrderedDict[bytes, list]",
    d_new: np.ndarray, s_new: np.ndarray,
    docs: np.ndarray, dist: np.ndarray,
) -> None:
    """Complete a :func:`cache_stage`: scatter the miss batch's answers
    (``d_new``/``s_new`` [n_miss, k], one row per ``miss_rows`` entry in
    order) back into the staged [n, k] arrays and insert each into the
    cache."""
    for j, (key, rows) in enumerate(miss_rows.items()):
        val = (d_new[j].copy(), s_new[j].copy())
        cache.put(key, val)
        for i in rows:
            docs[i], dist[i] = val


def concat_request_rows(
    rows_list: Sequence[np.ndarray],
) -> Tuple[np.ndarray, List[int]]:
    """Stack per-request query-row fragments into one engine batch.

    Returns ``(x [R_total, d], bounds)`` where ``bounds`` are the cumulative
    row offsets (len = n_requests + 1) that :func:`split_batch_answers` uses
    to demux the batched answers. The engine scores each row independently
    (descent and leaf top-k are per-row), so batching fragments this way
    changes no request's answer — the serving engine's scatter side."""
    bounds = [0]
    for r in rows_list:
        bounds.append(bounds[-1] + int(r.shape[0]))
    if not rows_list:
        raise ValueError("concat_request_rows needs at least one fragment")
    return np.concatenate([np.asarray(r) for r in rows_list], axis=0), bounds


def split_batch_answers(
    docs: np.ndarray, dist: np.ndarray, bounds: List[int],
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Demux one batched answer pair back into per-request fragments along
    the ``bounds`` offsets produced by :func:`concat_request_rows` (copies, so
    a request's result never aliases the batch buffer)."""
    return [
        (docs[lo:hi].copy(), dist[lo:hi].copy())
        for lo, hi in zip(bounds[:-1], bounds[1:])
    ]


def topk_search_cached(
    tree: KTree, q, cache: AnswerCache, k: int = 10, beam: int = 4,
    chunk: int = 512,
    search_fn: Optional[Callable[[np.ndarray], Tuple[np.ndarray, np.ndarray]]] = None,
    corpus_token: Optional[str] = None,
    rp=None, rp_corpus=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`topk_search` through an :class:`AnswerCache`: hit rows are served
    from the cache, miss rows (deduplicated within the batch) go through one
    engine call, and every computed answer is inserted. ``q`` must be dense
    rows (content hashing addresses raw bytes). ``search_fn`` overrides the
    engine for the miss batch — e.g. a :func:`topk_search_sharded` closure
    (it must answer over the *same* ``tree``: the cache binds to it).
    ``corpus_token``: pass the corpus store's ``manifest_hash`` when the
    served corpus lives on disk — answers then invalidate if the store is
    regenerated in place under an unchanged tree object (DESIGN.md §9).
    ``rp``/``rp_corpus`` route the miss batch through the RP
    approximate-route, exact-rescore path (DESIGN.md §5.1) — hashing still
    addresses the *original* query bytes, so cache keys are unchanged."""
    cache.bind(tree, corpus_token)
    x_q = np.asarray(q)
    docs, dist, miss_rows = cache_stage(cache, x_q, k, beam)
    if miss_rows:
        rep = np.asarray([rows[0] for rows in miss_rows.values()])
        if search_fn is None:
            d_new, s_new = topk_search(
                tree, x_q[rep], k=k, beam=beam, chunk=chunk,
                rp=rp, rp_corpus=rp_corpus,
            )
        else:
            d_new, s_new = search_fn(x_q[rep])
        cache_fill(cache, miss_rows, d_new, s_new, docs, dist)
    return docs, dist


# ---------------------------------------------------------------------------
# evaluation helpers (shared by benchmarks/query_recall.py, launch/serve.py
# and the examples — one definition of ground truth and recall)
# ---------------------------------------------------------------------------

def brute_force_topk(
    x_q: np.ndarray, x_all: np.ndarray, k: int,
    doc_block: int = 16384, q_block: int = 1024,
) -> np.ndarray:
    """Exact k-NN doc ids [nq, min(k, n_docs)] by squared distance (ties:
    lower id).

    Computed in ``q_block × doc_block`` tiles with a running top-k merge, so
    the full [n_q, n_docs] distance matrix never materialises — RCV1-scale
    ground truth fits in O(q_block·doc_block) memory. Stable tie order is
    preserved: per-tile stable argsorts keep equal-distance candidates in
    ascending doc-id order, and the running merge (stable argsort over
    [running | new-tile], where running ids always precede the tile's) keeps
    it — bit-identical to a stable argsort of the full matrix."""
    ids, _ = brute_force_topk_dist(
        x_q, x_all, k, doc_block=doc_block, q_block=q_block
    )
    return ids


def brute_force_topk_dist(
    x_q: np.ndarray, x_all: np.ndarray, k: int,
    doc_block: int = 16384, q_block: int = 1024,
) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`brute_force_topk` with the squared distances alongside —
    (ids [nq, min(k, n)], sqdist [nq, min(k, n)], same tiles, same running
    merge, so the two can never diverge). This is also the RP rescore
    primitive: ``topk_search(rp=...)`` calls it per query over the candidate
    pool's original-representation rows, which is what makes the rescore
    stage bit-identical to brute force restricted to that pool *by
    construction*."""
    x_q = np.asarray(x_q)
    x_all = np.asarray(x_all)
    nq, n = x_q.shape[0], x_all.shape[0]
    out = np.empty((nq, min(k, n)), dtype=np.intp)
    out_d = np.empty((nq, min(k, n)), dtype=x_q.dtype)
    q_sq = (x_q ** 2).sum(1)
    for qs in range(0, nq, q_block):
        qe = min(qs + q_block, nq)
        qb = x_q[qs:qe]
        run_ids = np.empty((qe - qs, 0), dtype=np.intp)
        run_d = np.empty((qe - qs, 0), dtype=x_q.dtype)
        for ds in range(0, n, doc_block):
            de = min(ds + doc_block, n)
            xb = x_all[ds:de]
            d = q_sq[qs:qe, None] - 2.0 * qb @ xb.T + (xb ** 2).sum(1)[None, :]
            run_ids, run_d = _merge_topk(run_ids, run_d, d, ds, k)
        out[qs:qe] = run_ids
        out_d[qs:qe] = run_d
    return out, out_d


def _merge_topk(run_ids, run_d, d, offset, k):
    """One running stable top-k merge step: fold a tile's distance matrix
    ``d`` [nq, C] (candidate ids ``offset + column``) into the running
    (ids, dists) [nq, ≤k]. Stable tie order is preserved — running entries
    precede the tile's, and per-tile stable argsorts keep equal-distance
    candidates in ascending id order. Shared by :func:`brute_force_topk` and
    :func:`brute_force_topk_stream` so the two ground truths cannot
    diverge."""
    sel = np.argsort(d, axis=1, kind="stable")[:, :k]
    run_ids = np.concatenate([run_ids, sel + offset], axis=1)
    run_d = np.concatenate([run_d, np.take_along_axis(d, sel, 1)], axis=1)
    keep = np.argsort(run_d, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(run_ids, keep, 1),
            np.take_along_axis(run_d, keep, 1))


def brute_force_topk_stream(x_q: np.ndarray, blocks, k: int) -> np.ndarray:
    """Exact k-NN doc ids [nq, ≤k] against a corpus streamed as
    ``(row_offset, dense block rows)`` pairs — the out-of-core ground truth
    (DESIGN.md §9): only one block is resident at a time.

    Same distances, ties, and running merge as :func:`brute_force_topk`
    (shared :func:`_merge_topk` step); block boundaries are invisible to the
    result. ``launch/serve.py --store`` feeds it store blocks (ELL blocks
    densified host-side)."""
    x_q = np.asarray(x_q)
    nq = x_q.shape[0]
    q_sq = (x_q ** 2).sum(1)
    run_ids = np.empty((nq, 0), dtype=np.intp)
    run_d = np.empty((nq, 0), dtype=np.float32)
    for lo, xb in blocks:
        xb = np.asarray(xb)
        d = (q_sq[:, None] - 2.0 * x_q @ xb.T + (xb ** 2).sum(1)[None, :]
             ).astype(np.float32)
        run_ids, run_d = _merge_topk(run_ids, run_d, d, lo, k)
    return run_ids


def recall_at_k(docs: np.ndarray, true_k: np.ndarray) -> float:
    """Mean |retrieved ∩ true| / k; −1 padding in ``docs`` never matches.

    One broadcast equality reduction (no per-query Python sets — O(n_q·k²)
    numpy instead of interpreter time; the old loop is pinned by a test)."""
    docs = np.asarray(docs)
    true_k = np.asarray(true_k)
    k = true_k.shape[1]
    hit = (true_k[:, :, None] == docs[:, None, :]).any(axis=2)   # [nq, k]
    return float((hit.sum(axis=1) / k).mean())
