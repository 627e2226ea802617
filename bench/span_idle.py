#!/usr/bin/env python3
"""Trace one window of a cell with the program's own spans on, and print the
device's idle inside each span (``lib/spans.py``) as one JSON line.

    python3 bench/span_idle.py --workload inex-dense.serve --seed 7

A serve cell's window is ``trace_s`` of its traffic after set-up, through a
``ServingEngine`` that holds a ``Profiler``; a build cell's is one whole
build after a warm-up build, through a ``ktree.build`` that holds one. The
line adds the profiler's totals, its records per request and per batch, the
share of the window's idle that the engine's (``engine_wait``,
``engine_fill``, ``engine_batch``) or the build's (``build_batch``) spans
cover, and the cost of one enabled span with no trace running. ``run.py
--trace 1`` reads none of this: it hands the builds and the traced serve
window no profiler, and its trace summary has no per-span idle. Needs a TPU,
as ``run.py`` does.
"""
import argparse
import json
import math
import os
import shutil
import sys
import time

import run

from lib import spans as bspans
from lib import trace as btrace

# per kind of cell, the spans whose union should hold the window's idle
COVER = {"open_loop": ("engine_wait", "engine_fill", "engine_batch"), "builds": ("build_batch",)}


def serve_window(spec: dict, seed: int, out_dir: str, n_docs=None):
    """Set up the serve cell and offer its traced window's load to an engine
    that holds a profiler; the profiler and the requests and batches run."""
    from lib import cells
    from repro.core.profile import Profiler

    cfg, traffic = spec["config"], spec["traffic"]
    s = cells.serve_setup(cfg, traffic, seed, n_docs=n_docs)
    prof = Profiler()
    secs = traffic["trace_s"]
    w = cells.offer_load(s, traffic, cells.window_plan(s, traffic, secs, traced=True), secs,
                         counter=cells.CompileCounter(), prof=prof,
                         tracer=cells.Tracer(out_dir))
    return prof, {"request": int(w["admitted"].sum()), "batch": w["stats"]["n_batches"]}


def build_window(spec: dict, seed: int, out_dir: str, n_docs=None):
    """The build cell's corpus from the seed, a warm-up build, then one
    traced build that holds a profiler; the profiler and the batches run."""
    import jax

    from lib import cells
    from repro.core import ktree
    from repro.core.profile import Profiler

    cfg, traffic = spec["config"], spec["traffic"]
    n = n_docs or cfg["n_docs"]
    docs, key = cells.build_inputs(cfg, traffic, seed, n)
    be = cells.program_backend(cfg, docs)
    cells.build_tree(cfg, be, key)  # compiles every program the build runs
    prof = Profiler()
    tracer = cells.Tracer(out_dir)
    tracer.start()
    tree = ktree.build(be, order=cfg["order"], key=jax.random.PRNGKey(key),
                       batch_size=cfg["batch_size"], medoid=cfg["medoid"], profiler=prof)
    jax.block_until_ready(tree)
    tracer.stop()
    return prof, {"batch": math.ceil(n / cfg["batch_size"])}


def span_cost_us(n: int = 100_000) -> float:
    """Microseconds of one enabled, empty span with no trace running."""
    from repro.core.profile import Profiler

    prof = Profiler()
    t = time.perf_counter()
    for _ in range(n):
        with prof.span("cost"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def measure(spec: dict, seed: int, out_dir: str, n_docs=None) -> dict:
    """Run the cell's traced window and reduce its trace over the window."""
    kind = spec["traffic"]["kind"]
    drive = serve_window if kind == "open_loop" else build_window
    prof, units = drive(spec, seed, out_dir, n_docs)
    tr = btrace.load(out_dir)
    (w,) = [e for e in tr.host if e.name == btrace.WINDOW_SPAN]
    out = bspans.program_spans(tr, (w.start_ns, w.start_ns + w.dur_ns), [COVER[kind]])
    out["cover"] = out["idle_by_span"]["+".join(COVER[kind])] / max(out["idle_s"], 1e-12)
    out["spans"] = prof.totals()
    out["records_per"] = {k: len(prof.records) / v for k, v in units.items() if v}
    out["span_cost_us"] = span_cost_us()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    out_dir = os.path.join(run.ROOT, ".bench_trace_spans")
    try:
        spec = run.cell_spec(args.workload, run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")))
        dev = run.configure_jax().devices()[0]
        if dev.platform != "tpu":
            raise run.BenchError(f"JAX found no TPU (platform {dev.platform!r})")
        shutil.rmtree(out_dir, ignore_errors=True)
        out = measure(spec, args.seed, out_dir)
    except run.BenchError as e:
        print(f"span_idle: {e}", file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps(dict(workload=args.workload, seed=args.seed, device=dev.device_kind, **out)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
