"""Benchmark entry point: ``python -m benchmarks.run`` (or
``python benchmarks/run.py``).

One section per paper table/figure + the system benches:
  paper_quality — Figures 1 & 2 (quality + runtime vs cluster count)
  sparse_dense  — §1 storage/speed observation
  scaling       — complexity claim (build time vs n)
  query_recall  — beam-search recall@k vs brute force + QPS (DESIGN.md §7)
  ri_recall     — Random Indexing routing: recall@k vs projection dim (§5.1)
  query_throughput — serving QPS/latency: chunk × pipeline × shards + cache
  serving       — continuous-batching engine: open-loop arrival-rate sweep
  oocore        — out-of-core store: build/query under a residency budget
  autotune      — measured-overlap knob tuner vs the depth-1 sync baseline
  chaos         — availability/latency under injected store + engine faults
  kernel_bench  — kernel micro-benches + oracle agreement
  roofline      — §Roofline terms from the dry-run artifacts (if present)

Output: ``name,us_per_call,derived`` CSV blocks.  Every leg also leaves a
``BENCH_<leg>.json`` artifact (stamped ``{"leg", "smoke"}``) so the perf
trajectory is populated even under ``--smoke``; ``BlockCache`` stats are
reset between legs so residency/hit-rate numbers don't bleed across sweeps.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # allow `python benchmarks/run.py` from anywhere
    sys.path.insert(0, _ROOT)
if os.path.join(_ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(_ROOT, "src"))


def _finish_leg(leg: str, smoke: bool, rows=None, json_path=None) -> None:
    """Close out one bench leg: make sure its ``BENCH_*.json`` exists and is
    stamped with ``{"leg", "smoke"}``, then reset every live ``BlockCache``'s
    counters so the next leg's residency/hit-rate numbers start clean.

    Legs with a native JSON writer pass the path they already wrote
    (``json_path``); the blob is stamped in place.  The rest pass their CSV
    ``rows`` and get a generic ``{"leg", "smoke", "rows"}`` blob.
    """
    if json_path is not None and os.path.exists(json_path):
        with open(json_path) as f:
            blob = json.load(f)
        blob["leg"], blob["smoke"] = leg, bool(smoke)
        with open(json_path, "w") as f:
            json.dump(blob, f, indent=2, sort_keys=True)
    else:
        path = json_path or f"BENCH_{leg}.json"
        blob = {"leg": leg, "smoke": bool(smoke),
                "rows": [list(r) if isinstance(r, tuple) else r
                         for r in (rows or [])]}
        with open(path, "w") as f:
            json.dump(blob, f, indent=2, sort_keys=True)
    from repro.core.store import BlockCache
    BlockCache.reset_all_stats()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=2000, help="paper-bench corpus size")
    ap.add_argument("--culled", type=int, default=800)
    ap.add_argument("--orders", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--skip", nargs="*", default=[])
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized run: tiny corpora, one order, small scaling sweep "
             "(CPU-friendly; Pallas kernels run via kernels/ref.py fallback / "
             "interpret mode)",
    )
    args = ap.parse_args()
    if args.smoke:
        args.docs, args.culled, args.orders = 400, 200, [8]
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    t_all = time.perf_counter()

    if "paper" not in args.skip:
        print("== paper_quality (Figures 1 & 2) ==", flush=True)
        from benchmarks import paper_quality
        rows = paper_quality.main(args.docs, args.culled, tuple(args.orders))
        _finish_leg("paper", args.smoke, rows=rows)

    if "sparse" not in args.skip:
        print("\n== sparse_dense (paper §1) ==", flush=True)
        from benchmarks import sparse_dense
        sd_args = (400, 200) if args.smoke else ()
        rows = sparse_dense.main(*sd_args)
        for name, us, extra in rows:
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("sparse_dense", args.smoke, rows=rows)

    if "scaling" not in args.skip:
        print("\n== scaling (complexity claim) ==", flush=True)
        from benchmarks import scaling
        sizes = (300, 600) if args.smoke else (1000, 2000, 4000)
        rows = scaling.main(sizes=sizes)
        for name, us, extra in rows:
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("scaling", args.smoke, rows=rows)

    if "query" not in args.skip:
        print("\n== query_recall (beam-search engine, DESIGN.md §7) ==", flush=True)
        from benchmarks import query_recall
        qr_kwargs = (
            dict(n_docs=500, culled=250, order=10, beams=(1, 2, 4), n_queries=96)
            if args.smoke else {}
        )
        rows = query_recall.main(**qr_kwargs)
        for name, us, extra in rows:
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("query_recall", args.smoke, rows=rows)

    if "ri" not in args.skip:
        print("\n== ri_recall (Random Indexing routing, DESIGN.md §5.1) ==", flush=True)
        from benchmarks import ri_recall
        ri_kwargs = (
            dict(n_docs=400, culled=200, order=8, rp_dims=(16, 64), n_queries=96)
            if args.smoke else {}
        )
        for name, us, extra in ri_recall.main(json_path="BENCH_ri.json", **ri_kwargs):
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("ri", args.smoke, json_path="BENCH_ri.json")

    if "throughput" not in args.skip:
        print("\n== query_throughput (serving plane, DESIGN.md §8) ==", flush=True)
        from benchmarks import query_throughput
        qt_kwargs = (
            dict(n_docs=600, culled=250, order=10, chunks=(64, 128),
                 n_queries=512, repeats=3)
            if args.smoke else {}
        )
        for name, us, extra in query_throughput.main(
                json_path="BENCH_query.json", **qt_kwargs):
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("throughput", args.smoke, json_path="BENCH_query.json")

    if "serving" not in args.skip:
        print("\n== serving (continuous-batching engine, DESIGN.md §8) ==", flush=True)
        from benchmarks import serving
        sv_kwargs = (
            dict(n_docs=600, culled=250, order=10, n_requests=160,
                 row_budget=32, max_queue=48)
            if args.smoke else {}
        )
        for name, us, extra in serving.main(
                json_path="BENCH_serving.json", **sv_kwargs):
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("serving", args.smoke, json_path="BENCH_serving.json")

    if "oocore" not in args.skip:
        print("\n== oocore (out-of-core store, DESIGN.md §9) ==", flush=True)
        from benchmarks import oocore
        oo_kwargs = (
            dict(n_docs=600, culled=250, order=10, chunk=128,
                 block_sizes=(64, 256), budget_fractions=(0.05, 0.5),
                 n_queries=256, repeats=2)
            if args.smoke else {}
        )
        for name, us, extra in oocore.main(
                json_path="BENCH_oocore.json", **oo_kwargs):
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("oocore", args.smoke, json_path="BENCH_oocore.json")

    if "autotune" not in args.skip:
        print("\n== autotune (measured-overlap knob tuner, DESIGN.md §11) ==",
              flush=True)
        from benchmarks import autotune
        at_kwargs = (
            dict(n_docs=600, culled=250, order=10, block_sizes=(64, 256),
                 budget_fractions=(0.05, 0.5), pipelines=(1, 2),
                 prefetches=(0, 2), chunks=(128, 512), n_queries=256,
                 repeats=2)
            if args.smoke else {}
        )
        for name, us, extra in autotune.main(
                json_path="BENCH_autotune.json", **at_kwargs):
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("autotune", args.smoke, json_path="BENCH_autotune.json")

    if "chaos" not in args.skip:
        print("\n== chaos (fault injection, DESIGN.md §10) ==", flush=True)
        from benchmarks import chaos
        ch_kwargs = (
            dict(n_docs=600, culled=250, order=10, block_docs=64,
                 engine_requests=96)
            if args.smoke else {}
        )
        for name, us, extra in chaos.main(
                json_path="BENCH_chaos.json", **ch_kwargs):
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("chaos", args.smoke, json_path="BENCH_chaos.json")

    if "kernels" not in args.skip:
        print("\n== kernel_bench ==", flush=True)
        from benchmarks import kernel_bench
        rows = kernel_bench.main()
        for name, us, extra in rows:
            print(f"{name},{us:.1f},{extra}", flush=True)
        _finish_leg("kernels", args.smoke, rows=rows)

    if "roofline" not in args.skip and os.path.isdir("experiments/dryrun"):
        print("\n== roofline (from dry-run artifacts) ==", flush=True)
        from benchmarks import roofline
        roofline.main()

    print(f"\nTOTAL_BENCH_SECONDS,{time.perf_counter()-t_all:.1f},", flush=True)


if __name__ == "__main__":
    main()
