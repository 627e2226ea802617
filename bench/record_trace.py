#!/usr/bin/env python3
"""Record the small profiler trace that ``bench/tests/test_bench_trace.py``
reduces: a 2,000-document dense tree searched by ``CALLS`` one-row calls,
each inside the benchmark's ``bench.search_fn`` span, with a host pause of
``PAUSE_S`` between calls. Needs a TPU.

    python3 bench/record_trace.py OUT.xplane.pb
"""
import glob
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CALLS = 12
PAUSE_S = 0.002


def main(out: str) -> int:
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    from lib import corpus
    from lib.trace import WINDOW_SPAN
    from repro.core import ktree
    from repro.core.backend import make_backend
    from repro.core.engine import make_search_fn

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    with open(os.path.join(BENCH, "configs", "inex-dense.json")) as f:
        cfg = json.load(f)
    m, _ = corpus.prepared_corpus(corpus.spec_from_config(cfg, 2000 + CALLS), 0)
    x = m.dense()
    tree = ktree.build(make_backend(x[:2000]), order=cfg["order"],
                       key=jax.random.PRNGKey(0))
    fn = make_search_fn(tree)
    q = x[2000:]
    fn(q[:1], 10, 4, chunk_rows=1)  # compile outside the trace
    tmp = os.path.join(ROOT, ".bench_trace_record")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for i in range(CALLS):
            with jax.profiler.TraceAnnotation("bench.search_fn"):
                fn(q[i:i + 1], 10, 4, chunk_rows=1)
            time.sleep(PAUSE_S)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_trace: {CALLS} calls, {os.path.getsize(out)} bytes -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
