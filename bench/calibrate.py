#!/usr/bin/env python3
"""Read the numbers that ``correct`` compares, on many seeds in one process,
for the program and for its control. Needs a TPU.

    python3 bench/calibrate.py --workload inex-dense.serve --seconds 3 \\
        --seeds 11 12 13 --control-seeds 21 22 23

Each seed is one whole run of the cell at its own size and load (set-up,
window, check), as ``bench/run.py`` makes it. The control is the same run
with every generated row rounded through bfloat16 before it reaches the
program: the precision step below the configuration's float32. Prints one
JSON line per run with the checked numbers; the limits in a configuration
file are set between the largest reading of the program and the smallest of
the control (PERF.md gives both). The benchmark's own runs never run the
control.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    import run

    jax = run.configure_jax()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: JAX found no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    spec = run.cell_spec(args.workload, run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")))
    runs = [(s, False) for s in args.seeds] + [(s, True) for s in args.control_seeds]
    for seed, control in runs:
        t = time.perf_counter()
        res = run.run_cell(spec, seed, args.seconds, False, device=device, control=control)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": control,
            "correct": res["correct"], "attempted": res["attempted"], "failed": res["failed"],
            "checks": {k: v["value"] for k, v in res["checks"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items() if k != "setup_s"},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "run_s": time.perf_counter() - t,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
