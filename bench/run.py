#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload inex-dense.serve --seed 7 --seconds 10 --trace 0

Everything about a cell is found by name from ``BENCHMARK.json``: its
configuration file, its traffic file (``bench/traffic/<traffic>.json``,
whose ``kind`` picks the driver in ``bench/lib/cells.py``) and the reader of
each per-layer metric (``bench/metrics/<metric>.py``). The program comes
from ``src/`` of the same checkout.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics: the host's readings (the client's
tails, the engine's counters and spans) from the measured window, the device's
from a profiler trace (a serve cell traces a short window of its own after
the measured one, a build cell its first build). Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.

The last lines on standard error, and the ``checks`` key that ends the
result line, give each number the check compares with its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


class BenchError(Exception):
    """The cell cannot be run here."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str, bench: dict) -> dict:
    """The workload entry, its configuration and traffic, and the metrics it
    reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return {"workload": w, "config": cfg, "traffic": traffic, "end_to_end": e2e,
            "per_layer": layer}


def read_metric(name: str, layer: dict):
    """Run ``bench/metrics/<name>.py``'s ``read``; None where it found
    nothing to read."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(layer)


def check_limits(checks: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit. A number with no limit is an error in the configuration."""
    out, ok = {}, True
    for name, value in checks.items():
        if name not in limits:
            raise BenchError(f"no limit for check {name!r}")
        out[name] = {"value": value, "limit": limits[name]}
        ok = ok and value <= limits[name]
    return ok, out


def log_kernels(layer: dict) -> None:
    """Print, for each kernel a traced run measured, its work per call, the
    bound that applies, and its instruction in the compiled step as the
    trace names it, with the operand shapes the kernel's wrapper padded."""
    from lib import work

    for kernel, sizes in layer.get("kernel_work", {}).items():
        w = work.KERNELS[kernel](**sizes)
        line = f"kernel {kernel}: sizes {sizes}, {w['flops']} flop and {w['bytes']} B a call"
        if "peak" in layer:
            t, bound = work.roofline_seconds(w, layer["peak"])
            line += f", {bound}-bound, least {t * 1e6:.3f} us"
        tr = layer.get("trace") or {}
        line += (f"; {tr.get('kernel_calls', {}).get(kernel)} calls, "
                 f"{tr.get('kernel_s', {}).get(kernel)} s traced; "
                 f"{tr.get('kernel_op', {}).get(kernel)}")
        print(line, file=sys.stderr)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *, device: dict,
             control: bool = False, n_docs=None) -> dict:
    """Drive one run of the cell and return its result object."""
    from lib import cells
    from lib.peaks import peaks

    traffic = spec["traffic"]
    counter = cells.CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        raw = cells.DRIVERS[traffic["kind"]](
            spec["config"], traffic, seed, seconds, trace_dir, t_start=T_START,
            counter=counter, control=control, n_docs=n_docs)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    correct, checks = check_limits(raw["checks"], spec["config"]["limits"][traffic["kind"]])
    dev = dict(device, memory_peak_bytes=raw["memory_peak_bytes"])
    result = {"correct": correct, "attempted": raw["attempted"], "failed": raw["failed"]}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    if trace:
        layer = dict(raw["layer"])
        if device["platform"] == "tpu":
            layer["peak"] = peaks(device["kind"])
        metrics = {}
        for m in spec["per_layer"]:
            v = read_metric(m["name"], layer)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        log_kernels(layer)
        tr = raw["layer"].get("trace")
        if tr is not None:
            dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": raw["metrics"][m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"] if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": raw["setup_s"], "unit": units["setup_s"]}
    result.update(metrics=metrics, device=dev, checks=checks)
    return result


def configure_jax():
    """Put the program on the path and JAX's persistent compilation cache in
    the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every
    program however short its compile; return the ``jax`` module."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"no program sources at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    # libtpu would otherwise write its logs to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = cell_spec(args.workload, load_json(os.path.join(ROOT, "BENCHMARK.json")))
        devices = configure_jax().devices()
        chips = spec["workload"]["chips"]
        if devices[0].platform != "tpu":
            raise BenchError(f"JAX found no TPU (platform {devices[0].platform!r})")
        if len(devices) < chips:
            raise BenchError(f"the cell asks for {chips} chips; JAX found {len(devices)}")
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices)}
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace), device=device)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
