#!/usr/bin/env python3
"""Find the knee of a serve cell: the highest offered rate of query rows
that the system sustains without a growing backlog. Needs a TPU.

    python3 bench/sweep.py --workload inex-dense.serve --seed 5 --seconds 10 \\
        --rates 20 30 40 50 60

One set-up (corpus, tree, warm-up), then a window at each rate in turn, each
through a fresh ``ServingEngine`` and shaped by the cell's traffic file.
Prints one JSON line per rate: the rate offered, rows answered per second of
the window, requests shed, the largest queue depth, the backlog still
unanswered when the window closed, and the 50th and 95th percentile latency
from due time. The sweep is run once and recorded in PERF.md, not by the
benchmark's runs; a traffic file states its rate as a number, with the share
of the knee it is in its ``why``.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run
    from lib import cells

    run.configure_jax()
    bench = run.load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    counter = cells.CompileCounter()
    for name in args.workload:
        spec = run.cell_spec(name, bench)
        traffic = spec["traffic"]
        s = cells.serve_setup(spec["config"], traffic, args.seed)
        for rate in args.rates:
            plan = cells.window_plan(s, traffic, args.seconds, rate)
            w = cells.offer_load(s, traffic, plan, args.seconds, counter=counter)
            lat = w["t_done"] - w["due"]
            close = w["t0"] + args.seconds
            print(json.dumps({
                "workload": name, "rate": rate,
                "answered_rows_per_s": float(plan.which.shape[1] * (w["t_done"] <= close).sum()
                                             / args.seconds),
                "shed": int((~w["admitted"]).sum()),
                "max_queue_depth": w["stats"]["max_queue_depth"],
                "backlog_at_close": int(((w["t_sub"] <= close) & (w["t_done"] > close)).sum()),
                "p50_ms": 1e3 * cells.nearest_rank(lat, 50),
                "p95_ms": 1e3 * cells.nearest_rank(lat, 95),
                "rows_per_batch": w["stats"]["batch_occupancy"] * traffic["row_budget"],
                "late_p95_ms": 1e3 * cells.nearest_rank(w["t_sub"] - w["due"], 95),
                "compiles": counter.count,
            }), flush=True)
            del w
        del s
    return 0


if __name__ == "__main__":
    sys.exit(main())
