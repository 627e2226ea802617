"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Demonstrates the serving paths end-to-end at reduced scale:
- LM: prefill a batch of prompts, then batched greedy decode with the KV cache.
- recsys retrieval: score a query against candidates brute-force and through
  the K-tree ANN index (the paper's NN-search-tree application) and report
  agreement + speed.
- paper (``--arch ktree-inex`` / ``ktree-rcv1``): the K-tree itself as the
  serving system — build **or restore** the index from a checkpoint
  (``--ckpt``, via ``ckpt.save_ktree``/``restore_ktree``), then answer
  batched top-k queries with the beam-search engine (DESIGN.md §7) and
  report QPS + recall@k vs brute force.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import reduced_cfg


def serve_lm(args):
    from repro.models import transformer as T

    spec = registry.get(args.arch)
    cfg = reduced_cfg(spec, args.scale)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)))
    max_seq = args.prompt_len + args.gen_len

    prefill = jax.jit(lambda p, t: T.prefill(p, t, cfg, max_seq=max_seq))
    decode = jax.jit(lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    toks = jnp.argmax(logits, -1)[:, None]
    out = [toks]
    t0 = time.perf_counter()
    for i in range(args.gen_len - 1):
        logits, cache = decode(params, cache, toks, jnp.int32(args.prompt_len + i))
        toks = jnp.argmax(logits, -1)[:, None]
        out.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.perf_counter() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"prefill {args.batch}x{args.prompt_len} in {t_prefill:.2f}s; "
          f"decoded {args.gen_len} tokens in {t_decode:.2f}s "
          f"({args.batch * args.gen_len / max(t_decode, 1e-9):.1f} tok/s)")
    print("sample output ids:", np.asarray(gen[0, :16]))


def serve_retrieval(args):
    from repro.models import recsys as R
    from repro.core import ktree as kt

    spec = registry.get(args.arch)
    cfg = reduced_cfg(spec, args.scale)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    items = params["tables"]["t0"]                      # candidate embeddings
    n = items.shape[0]
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(0, 0.3, (1, cfg.embed_dim)).astype(np.float32))

    t0 = time.perf_counter()
    scores, idx = R.retrieval_score(params, q, items, topk=10)
    jax.block_until_ready(scores)
    t_brute = time.perf_counter() - t0

    # K-tree ANN (paper's search tree): maximum inner product ≈ NN on the
    # unit sphere — normalise items for the index
    norm = items / jnp.maximum(jnp.linalg.norm(items, axis=1, keepdims=True), 1e-9)
    t0 = time.perf_counter()
    tree = kt.build(norm, order=32, batch_size=512)
    t_build = time.perf_counter() - t0
    qn = q / jnp.maximum(jnp.linalg.norm(q), 1e-9)
    t0 = time.perf_counter()
    doc, dist = kt.nn_search(tree, qn)
    t_ann = time.perf_counter() - t0
    in_topk = int(doc[0]) in set(np.asarray(idx[0]).tolist())
    print(f"brute-force top-10 in {t_brute*1e3:.1f}ms over {n} candidates; "
          f"K-tree build {t_build:.2f}s, ANN query {t_ann*1e3:.1f}ms, "
          f"ANN hit in brute top-10: {in_topk}")


def serve_paper_store(args):
    """Out-of-core K-tree serving (DESIGN.md §9): corpus in an on-disk block
    store (``--store DIR``) with ``--budget-mb`` of block-cache residency;
    the index streams in block-by-block (``build_from_store``) or restores by
    manifest reference (``--ckpt`` → ``save_index``/``restore_index``), and
    queries are answered straight from the store — the full corpus is never
    resident. ``--mesh N`` serves shard-parallel with per-shard block caches
    (``--budget-mb`` split evenly across the shards); ``--prefetch D`` moves
    the sequential disk scans (streaming build, single-device queries, the
    ground-truth block sweep) onto an async reader thread of that depth —
    sharded queries fetch candidates on demand and are unaffected.
    ``--engine`` composes with all of it: the store-backed (and sharded)
    search fn is handed to the continuous-batching ``ServingEngine``
    (DESIGN.md §8.1), whose per-batch report includes peak store residency
    from the shards' block caches.

    ``--autotune`` (DESIGN.md §11) measures-and-picks the three overlap
    knobs — query ``pipeline``, store ``prefetch``, ``chunk`` — for this
    (store layout, budget, backend) tuple, caching the decision in the
    store's ``TUNE.json`` sidecar; without the flag a valid sidecar entry is
    still consumed. Explicit ``--prefetch`` always wins, and tuned answers
    are bit-identical to the untuned ones (checked against the depth-1
    synchronous baseline every ``--autotune`` run)."""
    from repro.core import ktree as kt
    from repro.core.autotune import (
        autotune_store_search, load_tuned, sidecar_path,
    )
    from repro.core.engine import make_search_fn
    from repro.core.query import (
        AnswerCache, brute_force_topk_stream, recall_at_k, topk_search_cached,
    )
    from repro.ckpt import restore_index, save_index
    from repro.core.store import open_store
    from repro.data.pipeline import corpus_store
    from repro.data.synth_corpus import scaled

    spec = registry.get(args.arch)
    rep = spec.cfg.get("representation", "dense")
    rp_dim = _rp_dim_for(args, spec)
    corpus_spec = scaled(spec.cfg["corpus"], n_docs=args.n_docs, culled=args.culled)
    budget = max(int(args.budget_mb * 1024 * 1024), 1)
    projection = None

    if args.ckpt and os.path.isdir(args.ckpt):
        # restore by manifest reference: the checkpoint names the store it
        # was built over (and its content hash) — serve that one, don't
        # touch/describe the --store path it may or may not equal. An RP
        # index also records its projection spec; restore_index replays the
        # matrix bit-exactly from the stored seed (3-tuple return)
        t0 = time.perf_counter()
        out = restore_index(args.ckpt, budget_bytes=budget)
        tree, store = out[0], out[1]
        projection = out[2] if len(out) == 3 else None
        if rp_dim and (projection is None or projection.out_dim != rp_dim
                       or projection.seed != args.rp_seed):
            rec = projection.spec() if projection is not None else None
            raise SystemExit(
                f"index {args.ckpt} records projection {rec} but this serve "
                f"run expects rp_dim={rp_dim} seed={args.rp_seed}; match "
                "--rp-dim/--rp-seed to the checkpoint or rebuild"
            )
        print(f"restored store-backed index from {args.ckpt} in "
              f"{time.perf_counter()-t0:.2f}s (depth={int(tree.depth)}, "
              f"nodes={int(tree.n_nodes)}, store {store.path}: "
              f"{store.n_docs} docs, {store.n_blocks} blocks × "
              f"{store.block_docs}, budget {budget/1e6:.1f}MB"
              + (f", projection seed={projection.seed} "
                 f"{projection.in_dim}→{projection.out_dim}"
                 if projection is not None else "") + ")")
    else:
        t0 = time.perf_counter()
        corpus_store(corpus_spec, args.store, representation=rep,
                     block_docs=args.block_docs)
        store = open_store(args.store, budget_bytes=budget)
        print(f"store {args.store}: {store.n_docs} docs, {store.n_blocks} "
              f"blocks × {store.block_docs} docs ({store.nbytes/1e6:.1f}MB "
              f"on disk, budget {budget/1e6:.1f}MB) in {time.perf_counter()-t0:.2f}s")
        if rp_dim:
            from repro.core.backend import make_projection

            projection = make_projection(store.dim, rp_dim, seed=args.rp_seed)
        t0 = time.perf_counter()
        tree = kt.build_from_store(
            store, order=args.order,
            medoid=rep == "sparse_medoid" and projection is None,
            batch_size=256, prefetch=args.prefetch, projection=projection,
            # a prior sidecar decision feeds the build's prefetch when
            # --prefetch isn't explicit (build reads sequentially too)
            tuned=load_tuned(store, budget_bytes=budget,
                             backend=_backend_tag(projection)),
        )
        print(f"streaming-built K-tree over {store.n_docs} docs in "
              f"{time.perf_counter()-t0:.2f}s (depth={int(tree.depth)}, "
              f"nodes={int(tree.n_nodes)}, "
              f"cache: {store.cache.stats['evictions']} evictions, "
              f"resident {store.cache.resident_bytes/1e6:.1f}MB)")
        if args.ckpt:
            print(f"saved index by manifest reference to "
                  f"{save_index(args.ckpt, tree, store, projection=projection)}")

    nq = min(args.queries, store.n_docs)
    q_view = store.view(0, nq)
    on_fault = None if args.on_fault == "raise" else args.on_fault
    # cache keys + ground truth share these query rows; degrade mode
    # zero-fills rows whose block is quarantined instead of failing
    x_q = make_dense_rows(store, nq, on_fault=on_fault or "raise")
    if on_fault and args.cache:
        raise SystemExit(
            "--on-fault degrade does not compose with --cache (degraded "
            "answers must not be cached); drop one of the two"
        )
    if on_fault and projection is not None:
        raise SystemExit(
            "--on-fault degrade does not compose with random-projection "
            "routing (--rp-dim): the exact-rescore stage needs every "
            "candidate row readable; drop one of the two"
        )
    backend_tag = _backend_tag(projection)
    rp_kw = dict(rp=projection, rp_corpus=store if projection is not None
                 else None)
    if args.autotune:
        t0 = time.perf_counter()
        tuned = autotune_store_search(
            tree, store, k=args.k, beam=args.beam, budget_bytes=budget,
            backend=backend_tag, n_queries=nq, **rp_kw,
        )
        src = f"measured in {time.perf_counter() - t0:.2f}s"
    else:
        tuned = load_tuned(store, budget_bytes=budget, backend=backend_tag)
        src = "from sidecar"
    if tuned is not None:
        print(f"autotune: pipeline={tuned.pipeline} "
              f"prefetch={tuned.prefetch} chunk={tuned.chunk} "
              f"({tuned.qps:.0f} QPS vs depth-1 baseline "
              f"{tuned.baseline_qps:.0f}, read∩compute "
              f"{tuned.overlap_frac:.0%}; {src}, "
              f"sidecar {sidecar_path(store)})")
    if args.mesh > 1:
        # store-backed sharded serving: the corpus stays on disk — each mesh
        # shard fetches only the candidates it owns through its own block
        # cache (--budget-mb split evenly across the shards)
        from repro.core.backend import shard_from_store
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh)
        sshards = shard_from_store(
            mesh, store, budget_bytes=max(budget // args.mesh, 1)
        )
        mode = f"sharded×{args.mesh}"
        search_fn = make_search_fn(
            tree, mesh=mesh, corpus=sshards, on_fault=on_fault, rp=projection,
            prefetch=args.prefetch, tuned=tuned,
        )
        block_caches = [p.store.cache for p in sshards.parts]
    else:
        sshards = None
        mode = "single-device"
        search_fn = make_search_fn(
            tree, prefetch=args.prefetch, on_fault=on_fault,
            rp=projection, rp_corpus=store, tuned=tuned,
        )
        block_caches = [store.cache]
    if projection is not None:
        mode += f", rp{projection.out_dim}"
    run = lambda src: search_fn(src, args.k, args.beam)
    run(q_view)  # warm the jit cache
    if args.engine:
        return serve_engine_mode(
            args, search_fn, x_q, tree, mode=f"{mode}, out-of-core",
            corpus_token=store.manifest_hash, block_caches=block_caches,
        )
    if args.cache:
        # miss batches are dense rows (content hashing addresses raw bytes),
        # so the miss engine is the dense-row engine — warm it *outside* the
        # timed loop, or its first-compile cost lands in the QPS report
        run(x_q)
        cache = AnswerCache(args.cache)
        t0 = time.perf_counter()
        for _ in range(2):  # pass 1 cold-fills, pass 2 replays (hit path)
            docs, _ = topk_search_cached(
                tree, x_q, cache, k=args.k, beam=args.beam,
                search_fn=run, corpus_token=store.manifest_hash,
            )
        qps = 2 * nq / max(time.perf_counter() - t0, 1e-9)
        s = cache.stats
        print(f"cache: hits={s['hits']} misses={s['misses']} "
              f"hit_rate={s['hit_rate']:.2f} size={s['size']}/{s['capacity']}")
    else:
        t0 = time.perf_counter()
        out = run(q_view)
        qps = nq / max(time.perf_counter() - t0, 1e-9)
        docs = out[0]
        if len(out) == 3 and out[2].degraded:
            rep = out[2]
            print(f"DEGRADED answers: quarantined blocks "
                  f"{list(rep.quarantined_blocks)}, "
                  f"{len(rep.dropped_query_rows)} query rows dropped")
        if tuned is not None and on_fault is None:
            # knobs only reschedule work — pin it by re-answering with the
            # depth-1 synchronous schedule and explicit default chunking
            from repro.core.query import topk_search

            b_docs, b_dist = topk_search(
                tree, q_view, k=args.k, beam=args.beam,
                chunk=512, pipeline=1, prefetch=0, **rp_kw,
            )
            ok = bool(np.array_equal(np.asarray(docs), b_docs)
                      and np.array_equal(np.asarray(out[1]), b_dist))
            print("tuned answers vs depth-1 sync baseline: "
                  + ("bit-identical" if ok else "MISMATCH"))
            if not ok:
                raise SystemExit(
                    "tuned knobs changed answers — depths must never "
                    "change numerics"
                )

    cs = store.cache.stats
    print(f"store cache: hit_rate={cs['hit_rate']:.2f} "
          f"evictions={cs['evictions']} resident={cs['resident_bytes']/1e6:.1f}"
          f"/{cs['budget_bytes']/1e6:.1f}MB")
    if cs["read_retries"] or cs["verify_failures"] or cs["quarantined"]:
        print(f"store robustness: read_retries={cs['read_retries']} "
              f"read_errors={cs['read_errors']} "
              f"verify_failures={cs['verify_failures']} "
              f"quarantined={cs['quarantined']}")
    if sshards is not None:
        for s, st in enumerate(sshards.cache_stats):
            print(f"shard {s} cache: hit_rate={st['hit_rate']:.2f} "
                  f"misses={st['misses']} evictions={st['evictions']} "
                  f"resident={st['resident_bytes']/1e6:.2f}"
                  f"/{st['budget_bytes']/1e6:.2f}MB")
        print(f"peak store residency across shards: "
              f"{sshards.peak_resident_bytes/1e6:.2f}MB "
              f"(bound {args.mesh}×{max(budget // args.mesh, 1)/1e6:.2f}MB "
              f"+ one-block floors)")
    # ground truth streams block-by-block off the store (never fully
    # resident); degrade mode skips quarantined/excised blocks, so the
    # reference covers exactly the corpus the degraded index can answer from
    gt_prefetch = (
        args.prefetch if args.prefetch is not None
        else (tuned.prefetch if tuned is not None else 0)
    )
    true = brute_force_topk_stream(
        x_q,
        _dense_store_blocks(store, prefetch=gt_prefetch,
                            on_fault=on_fault or "raise"),
        args.k,
    )
    recall = recall_at_k(docs, true)
    print(f"{nq} queries: beam={args.beam} k={args.k} "
          f"recall@{args.k}={recall:.3f} {qps:.0f} QPS "
          f"({store.kind} store, out-of-core, {mode})")


def serve_engine_mode(args, search_fn, x_q, tree, mode,
                      corpus_token=None, block_caches=()):
    """``--engine``: continuous-batching service mode (DESIGN.md §8).

    Instead of replaying the query file as one closed batch, requests are
    generated **open-loop** at ``--rate`` arrivals/s (Poisson gaps, seeded)
    and admitted into a ``core.engine.ServingEngine`` — bounded queue
    (``--max-queue``, overload sheds instead of queueing unboundedly),
    dynamic batches up to ``--row-budget`` rows dispatched on fill or the
    oldest request's deadline forcing point (``--max-wait-ms`` /
    ``--deadline-ms``), optional ``--cache`` answer-cache pre-stage. The
    report is p50/p95/p99 latency + QPS + shed/occupancy/queue-depth — and a
    bit-identity check of one served request against the offline engine."""
    from repro.core.engine import ServingEngine
    from repro.core.query import AnswerCache
    from repro.launch.engine import report_lines, request_pool, run_load

    cache = AnswerCache(args.cache) if args.cache else None
    xw = np.asarray(x_q)
    pool = request_pool(
        xw, n_requests=args.requests,
        rows_per_request=args.rows_per_req, k=args.k, beam=args.beam,
    )
    # warm the chunk-aligned shapes dynamic batches hit: the engine pads each
    # request to its pow2 bucket and chunks fragments at the bucket, with the
    # fragment's chunk count also pow2-padded — so the compile ladder is
    # (bucket × pow2 chunk counts). First compiles land here, not in the
    # latency percentiles
    from repro.core.engine import pow2_bucket

    bucket = pow2_bucket(args.rows_per_req)
    cap = pow2_bucket(args.row_budget)

    def _warm(s, chunk_rows):
        reps = -(-s // xw.shape[0])  # ceil
        search_fn(np.tile(xw, (reps, 1))[:s], args.k, args.beam,
                  chunk_rows=chunk_rows)

    s = bucket
    while True:
        _warm(s, bucket)
        if s >= 2 * cap:
            break
        s *= 2
    if cache is not None:
        # cache miss batches run at single-row chunking (per-row-stable
        # answers); warm its pow2 miss-count ladder too
        m = 1
        while m <= cap:
            _warm(m, 1)
            m *= 2
    deadline = args.deadline_ms / 1e3 if args.deadline_ms else None
    print(f"engine serving ({mode}): rate={args.rate:.0f}/s "
          f"requests={args.requests} rows/req={args.rows_per_req} "
          f"row_budget={args.row_budget} max_queue={args.max_queue} "
          f"max_wait={args.max_wait_ms}ms"
          + (f" deadline={args.deadline_ms}ms" if deadline else ""))
    timeout = (args.request_timeout_ms / 1e3
               if args.request_timeout_ms else None)
    with ServingEngine(
        search_fn, row_budget=args.row_budget, max_queue=args.max_queue,
        max_wait_s=args.max_wait_ms / 1e3, request_timeout_s=timeout,
        cache=cache, tree=tree,
        corpus_token=corpus_token, block_caches=block_caches,
    ) as eng:
        stats = run_load(eng, pool, rate_qps=args.rate, deadline_s=deadline)
        rows, k, beam = pool[0]
        d_eng, s_eng = eng.submit(rows, k=k, beam=beam).result(timeout=120)
    if cache is None:
        out_off = search_fn(rows, k, beam)
        d_off, s_off = out_off[0], out_off[1]
    else:
        # cache entries are per-row answers (computed at single-row
        # chunking), so the offline reference is the per-row standalone calls
        parts = [search_fn(rows[i:i + 1], k, beam)
                 for i in range(rows.shape[0])]
        d_off = np.concatenate([np.asarray(p[0]) for p in parts])
        s_off = np.concatenate([np.asarray(p[1]) for p in parts])
    ok = bool((np.asarray(d_eng) == np.asarray(d_off)).all()
              and (np.asarray(s_eng) == np.asarray(s_off)).all())
    for line in report_lines(stats):
        print(line)
    print("engine answers vs offline engine: "
          + ("bit-identical" if ok else "MISMATCH"))
    if not ok:
        raise SystemExit("engine answers diverged from the offline engine")


def _backend_tag(projection) -> str:
    """The backend half of a ``core.autotune.tune_key``: ``"exact"`` for
    direct routing, ``"rp<out_dim>"`` for random-projection routing (the RP
    route's extra rescore stage can want different depths)."""
    return "exact" if projection is None else f"rp{projection.out_dim}"


def _rp_dim_for(args, spec) -> int:
    """Effective random-projection dim for this serve run: ``--rp-dim`` wins,
    else an arch whose representation is ``"rp"`` supplies its cfg default
    (``ktree-rcv1-rp``); 0 = exact routing (no projection)."""
    if args.rp_dim:
        return int(args.rp_dim)
    if spec.cfg.get("representation") == "rp":
        return int(spec.cfg.get("rp_dim", 128))
    return 0


def make_dense_rows(store, nq: int, on_fault: str = "raise") -> np.ndarray:
    """Densify the first ``nq`` store rows host-side (cache keys hash dense
    row bytes; ground truth needs dense queries). ``on_fault="degrade"``
    gathers through ``take_rows_masked`` — rows whose block is
    quarantined/excised come back as zero vectors instead of failing the
    whole serve run (DESIGN.md §10)."""
    from repro.core.backend import backend_from_store

    if on_fault != "degrade":
        be = backend_from_store(store, np.arange(nq))
        return np.asarray(be.take(jnp.arange(nq, dtype=jnp.int32)))
    got, _ = store.take_rows_masked(np.arange(nq))
    if store.kind == "dense":
        return np.asarray(got["x"], np.float32)
    v, c = got["values"], got["cols"]
    x = np.zeros((nq, store.dim), np.float32)
    # masked rows are zero-filled (values 0 → the scatter adds nothing)
    np.add.at(x, (np.arange(nq)[:, None], c), v)
    return x


def _dense_store_blocks(store, prefetch: int = 0, on_fault: str = "raise"):
    """Yield ``(row_offset, dense rows)`` per store block for
    ``brute_force_topk_stream`` — dense blocks as-is, ELL blocks densified by
    a host-side numpy scatter-add (padding slots are value 0, so they add
    nothing). One block resident at a time; ``prefetch ≥ 1`` reads the next
    block on an async reader thread while the current one is scored.
    ``on_fault="degrade"`` skips quarantined/excised blocks (the degraded
    ground-truth scan)."""
    for lo, hi, arrays in store.iter_blocks(prefetch=prefetch,
                                            on_fault=on_fault):
        if store.kind == "dense":
            yield lo, arrays["x"][: hi - lo].astype(np.float32)
        else:
            v, c = arrays["values"][: hi - lo], arrays["cols"][: hi - lo]
            xb = np.zeros((hi - lo, store.dim), np.float32)
            np.add.at(xb, (np.arange(hi - lo)[:, None], c), v)
            yield lo, xb


def _dense_backend_blocks(backend, n_docs: int, block: int = 16384):
    """Yield ``(row_offset, dense rows)`` per backend row block for
    ``brute_force_topk_stream`` — the in-memory counterpart of
    :func:`_dense_store_blocks`. Block size matches ``brute_force_topk``'s
    ``doc_block`` default, so the streamed ground truth merges at the same
    boundaries (shared ``_merge_topk`` step → identical ids); only one
    densified block is host-resident at a time instead of the whole corpus."""
    import jax.numpy as jnp

    for lo in range(0, n_docs, block):
        rows = jnp.arange(lo, min(lo + block, n_docs), dtype=jnp.int32)
        yield lo, np.asarray(backend.take(rows).astype(jnp.float32))


def serve_paper(args):
    """K-tree retrieval serving: build-or-restore the index, answer batched
    top-k beam-search queries (single-device, or shard-parallel with
    ``--mesh N``, optionally through an LRU answer cache with ``--cache C``),
    report recall@k vs brute force and QPS. ``--store DIR`` switches to the
    out-of-core path (:func:`serve_paper_store`)."""
    from repro.core import ktree as kt
    from repro.core.engine import make_search_fn
    from repro.core.query import (
        AnswerCache, brute_force_topk_stream, recall_at_k, topk_search_cached,
    )
    from repro.ckpt import restore_ktree, save_ktree
    from repro.data.pipeline import corpus_backend
    from repro.data.synth_corpus import scaled

    if args.store:
        return serve_paper_store(args)

    spec = registry.get(args.arch)
    rep = spec.cfg.get("representation", "dense")
    rp_dim = _rp_dim_for(args, spec)
    corpus_spec = scaled(spec.cfg["corpus"], n_docs=args.n_docs, culled=args.culled)
    base_rep = "sparse_medoid" if rep == "rp" else rep
    backend, _ = corpus_backend(corpus_spec, representation=base_rep)
    projection = None
    if rp_dim:
        # Random Indexing routing (DESIGN.md §5.1): build/route in the
        # projection, exact-rescore answers from the original backend rows
        from repro.core.backend import make_projection

        projection = make_projection(backend.dim, rp_dim, seed=args.rp_seed)
        rep = f"rp{rp_dim}/{base_rep}"
    medoid = base_rep == "sparse_medoid" and projection is None

    ckpt_file = (
        args.ckpt if not args.ckpt or args.ckpt.endswith(".npz")
        else args.ckpt + ".npz"
    )
    if ckpt_file and os.path.exists(ckpt_file):
        from repro.ckpt import load_ktree_projection

        t0 = time.perf_counter()
        tree = restore_ktree(args.ckpt)
        recorded = load_ktree_projection(args.ckpt)
        if not args.rp_dim and projection is None and recorded is not None:
            projection = recorded  # serve with the checkpointed projection
        if projection is not None or recorded is not None:
            # projection is part of the index identity: routing a tree built
            # under one projection with a different matrix (or none) silently
            # degrades every query — refuse, like a rewritten corpus
            exp = projection.spec() if projection is not None else None
            rec = recorded.spec() if recorded is not None else None
            if exp != rec:
                raise SystemExit(
                    f"checkpoint {ckpt_file} records projection {rec} but "
                    f"this serve run expects {exp}; match --rp-dim/--rp-seed "
                    "to the checkpoint or rebuild with a fresh --ckpt path"
                )
            projection = recorded  # replayed bit-exactly from the stored seed
        # guard against serving an index built over a different corpus: doc
        # ids in the tree must address rows of *this* corpus
        max_doc = max(
            (int(np.asarray(tree.child[leaf, : int(tree.n_entries[leaf])]).max())
             for leaf in kt.leaf_nodes(tree)), default=-1,
        )
        want_dim = projection.out_dim if projection is not None else backend.dim
        if tree.dim != want_dim or max_doc >= corpus_spec.n_docs:
            raise SystemExit(
                f"checkpoint {ckpt_file} does not match this corpus "
                f"(tree dim={tree.dim} max doc id={max_doc} vs expected "
                f"dim={want_dim} n_docs={corpus_spec.n_docs}); "
                "rebuild with a fresh --ckpt path or matching --n-docs/--culled"
            )
        print(f"restored K-tree from {ckpt_file} in {time.perf_counter()-t0:.2f}s "
              f"(depth={int(tree.depth)}, nodes={int(tree.n_nodes)}"
              + (f", projection seed={projection.seed} "
                 f"{projection.in_dim}→{projection.out_dim}"
                 if projection is not None else "") + ")")
    else:
        from repro.core.backend import RandomProjBackend

        build_be = (
            backend if projection is None
            else RandomProjBackend.wrap(backend, projection)
        )
        t0 = time.perf_counter()
        tree = kt.build(build_be, order=args.order, medoid=medoid, batch_size=256)
        print(f"built K-tree over {args.n_docs} docs in {time.perf_counter()-t0:.2f}s "
              f"(depth={int(tree.depth)}, nodes={int(tree.n_nodes)})")
        if args.ckpt:
            print(f"saved index to "
                  f"{save_ktree(args.ckpt, tree, projection=projection)}")

    # batched queries: corpus documents queried back against the index
    nq = min(args.queries, corpus_spec.n_docs)
    rows = jnp.arange(nq, dtype=jnp.int32)
    x_q = np.asarray(backend.take(rows))

    if args.mesh > 1:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh)
        shards = backend.shard(mesh)  # rows placed across shards once
        mode = f"sharded×{args.mesh}"
        search_fn = make_search_fn(tree, mesh=mesh, corpus=shards,
                                   rp=projection)
    else:
        mode = "single-device"
        search_fn = make_search_fn(tree, rp=projection, rp_corpus=backend)

    def run(xq):
        return search_fn(xq, args.k, args.beam)

    run(x_q)  # warm the jit cache
    if args.engine:
        return serve_engine_mode(args, search_fn, x_q, tree, mode=mode)
    if args.cache:
        # timed section answers the stream twice: pass 1 cold-fills the LRU,
        # pass 2 replays it — the hit path the report's hit_rate measures
        cache = AnswerCache(args.cache)
        t0 = time.perf_counter()
        docs, _ = topk_search_cached(
            tree, x_q, cache, k=args.k, beam=args.beam, search_fn=run
        )
        docs, _ = topk_search_cached(
            tree, x_q, cache, k=args.k, beam=args.beam, search_fn=run
        )
        qps = 2 * nq / max(time.perf_counter() - t0, 1e-9)
        s = cache.stats
        print(f"cache: hits={s['hits']} misses={s['misses']} "
              f"hit_rate={s['hit_rate']:.2f} size={s['size']}/{s['capacity']}")
    else:
        t0 = time.perf_counter()
        docs, _ = run(x_q)
        qps = nq / max(time.perf_counter() - t0, 1e-9)

    # brute-force ground truth on the query slice (exact squared distances),
    # streamed block-wise off the backend — densifying the whole corpus in one
    # take() defeated the blocked brute force for sparse/large corpora
    true = brute_force_topk_stream(
        x_q, _dense_backend_blocks(backend, corpus_spec.n_docs), args.k
    )
    recall = recall_at_k(docs, true)
    print(f"{nq} queries: beam={args.beam} k={args.k} "
          f"recall@{args.k}={recall:.3f} {qps:.0f} QPS ({rep} backend, {mode})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    # --- paper (K-tree) serving mode ---
    ap.add_argument("--ckpt", default="", help="K-tree index checkpoint path: "
                    "restore if present, else build and save here")
    ap.add_argument("--n-docs", type=int, default=2000)
    ap.add_argument("--culled", type=int, default=800)
    ap.add_argument("--order", type=int, default=16)
    ap.add_argument("--rp-dim", type=int, default=0,
                    help="Random Indexing routing (DESIGN.md §5.1): build and "
                    "descend the K-tree in an N-dim seeded random projection, "
                    "exact-rescoring answers from the original rows; 0 = "
                    "exact routing (default; archs with representation='rp' "
                    "fall back to their cfg rp_dim). Composes with --mesh/"
                    "--store/--cache/--engine; not with --on-fault degrade")
    ap.add_argument("--rp-seed", type=int, default=0,
                    help="projection seed for --rp-dim — the whole index "
                    "replays from it (checkpoints persist spec, not matrix)")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--beam", type=int, default=4)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--mesh", type=int, default=0, help="shard-parallel query "
                    "serving over N devices (topk_search_sharded); 0/1 = "
                    "single device. Composes with --store: the corpus stays "
                    "on disk behind per-shard block caches")
    ap.add_argument("--cache", type=int, default=0, help="LRU answer-cache "
                    "capacity (0 = off); the timed stream runs twice so the "
                    "report shows the hit path")
    ap.add_argument("--store", default="", help="out-of-core mode: corpus "
                    "block-store directory (written on first run, reused "
                    "after); builds stream from disk and queries fetch "
                    "blocks on demand (DESIGN.md §9). With --ckpt the index "
                    "checkpoints by manifest reference (save_index)")
    ap.add_argument("--budget-mb", type=float, default=64.0,
                    help="block-cache residency budget for --store, in MB "
                    "(with --mesh N: split evenly into N per-shard caches)")
    ap.add_argument("--block-docs", type=int, default=1024,
                    help="rows per store block (the disk I/O granule)")
    ap.add_argument("--prefetch", type=int, default=None,
                    help="async block-prefetch depth for --store (reader "
                    "thread ahead of the sequential disk scans: streaming "
                    "build, single-device + store-sourced sharded queries, "
                    "ground truth). Default: the store's TUNE.json decision "
                    "if present, else 0 (synchronous); an explicit value "
                    "always wins over --autotune")
    ap.add_argument("--autotune", action="store_true",
                    help="measure-and-pick (pipeline, prefetch, chunk) for "
                    "this (store layout, --budget-mb, backend) before "
                    "serving (DESIGN.md §11); the decision is cached in the "
                    "store's TUNE.json sidecar (invalidated when the "
                    "manifest hash rotates) and answers are checked "
                    "bit-identical to the depth-1 synchronous baseline")
    # --- continuous-batching engine mode (DESIGN.md §8) ---
    ap.add_argument("--engine", action="store_true",
                    help="serve through the continuous-batching engine: "
                    "open-loop request arrivals at --rate, bounded admission "
                    "queue, dynamic batches, p50/p95/p99 latency report. "
                    "Composes with --mesh/--cache/--store")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop arrival rate, requests/s (--engine)")
    ap.add_argument("--requests", type=int, default=512,
                    help="number of generated requests (--engine)")
    ap.add_argument("--rows-per-req", type=int, default=1,
                    help="query rows per generated request (--engine)")
    ap.add_argument("--row-budget", type=int, default=256,
                    help="max query rows per dispatched batch (--engine)")
    ap.add_argument("--max-queue", type=int, default=128,
                    help="admission-queue bound in requests; a full queue "
                    "sheds instead of growing (--engine)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="batch-formation wait cap, ms (--engine)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request completion deadline, ms; 0 = none. "
                    "The batcher dispatches no later than the oldest "
                    "request's deadline forcing point (--engine)")
    # --- robustness (DESIGN.md §10) ---
    ap.add_argument("--fsck", action="store_true",
                    help="verify the --store directory offline (digest-check "
                    "every block against the manifest) and exit: status 0 "
                    "clean, 1 damaged. With --fsck-repair, excise damaged "
                    "blocks and rewrite the manifest first")
    ap.add_argument("--fsck-repair", action="store_true",
                    help="with --fsck: excise damaged blocks (tombstone the "
                    "manifest entries, move files aside as <name>.damaged) "
                    "so the surviving rows serve degraded")
    ap.add_argument("--on-fault", choices=("raise", "degrade"),
                    default="raise",
                    help="store-read fault policy for --store serving: "
                    "'raise' fails the batch with a typed store error; "
                    "'degrade' drops only the quarantined blocks' rows and "
                    "flags the answers (DESIGN.md §10)")
    ap.add_argument("--request-timeout-ms", type=float, default=0.0,
                    help="engine-wide per-request time budget, ms; 0 = none. "
                    "The engine watchdog fails overdue requests with "
                    "EngineTimeout so no caller can hang (--engine)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.fsck:
        from repro.core.fsck import fsck_store, repair_store

        if not args.store:
            raise SystemExit("--fsck needs --store DIR")
        report = (repair_store(args.store) if args.fsck_repair
                  else fsck_store(args.store))
        for line in report.lines():
            print(line)
        raise SystemExit(0 if (report.clean or report.repaired) else 1)
    spec = registry.get(args.arch)
    if spec.family == "lm":
        serve_lm(args)
    elif spec.family == "recsys":
        serve_retrieval(args)
    elif spec.family == "paper":
        serve_paper(args)
    else:
        raise SystemExit("serving demo supports lm + recsys + paper archs")


if __name__ == "__main__":
    main()
