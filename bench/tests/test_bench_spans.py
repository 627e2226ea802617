"""The program's own spans on the trace: an enabled Profiler span lands on
the host plane of a real trace and a null one does not; the reduction of
the device's idle inside each span; and ``span_idle.py``'s window on small
cells on the CPU."""
import os

import pytest

import run
import span_idle
from lib import spans, trace
from lib.trace import Event, Trace


def _synthetic():
    # device busy [0, 5 us] and [20, 25 us]; the engine's spans around it
    ops = [Event("fusion.1", 0, 4_000), Event("copy.2", 3_000, 2_000),
           Event("nn_topk_pallas", 20_000, 5_000)]
    host = [Event(trace.WINDOW_SPAN, 0, 30_000), Event("bench.search_fn", 0, 26_000),
            Event("repro.engine_call", 0, 6_000), Event("repro.engine_call", 19_000, 7_000),
            Event("repro.engine_wait", 6_000, 13_000),
            Event("repro.engine_fill", 4_000, 2_000), Event("repro.engine_fill", 5_000, 3_000),
            Event("repro.engine_batch", 27_000, 4_000)]
    return Trace(ops={"/device:TPU:0": ops}, programs={}, host=host)


def test_idle_by_span_on_a_synthetic_trace():
    s = spans.program_spans(_synthetic(), (0, 30_000), unions=[("engine_wait", "engine_fill")])
    assert s["window_s"] == pytest.approx(30e-6) and s["idle_s"] == pytest.approx(20e-6)
    # by midpoint: the batch's [27, 31] counts, its idle only to the window's end
    assert s["span_calls"] == {"engine_batch": 1, "engine_call": 2, "engine_fill": 2,
                               "engine_wait": 1}
    assert s["idle_by_span"] == pytest.approx({
        "engine_call": 3e-6,                 # [5, 6], [19, 20], [25, 26]
        "engine_wait": 13e-6,                # [6, 19]
        "engine_fill": 3e-6,                 # [4, 8] less [4, 5]
        "engine_batch": 3e-6,                # [27, 30]
        "engine_wait+engine_fill": 14e-6,    # [5, 19]
    })
    assert "search_fn" not in s["span_calls"]  # the benchmark's own spans are not the program's


def test_idle_by_span_averages_over_devices():
    one = spans.program_spans(_synthetic(), (0, 30_000))
    tr = _synthetic()
    tr.ops["/device:TPU:1"] = [Event("fusion.9", 0, 30_000)]  # busy all window
    two = spans.program_spans(tr, (0, 30_000))
    assert two["idle_by_span"] == pytest.approx({k: v / 2 for k, v in one["idle_by_span"].items()})
    assert two["span_calls"] == one["span_calls"] and two["idle_s"] == pytest.approx(10e-6)


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A CPU trace holding an enabled Profiler span around a small jitted
    call, and a NULL_PROFILER span around another."""
    import jax
    import jax.numpy as jnp

    from repro.core.profile import NULL_PROFILER, Profiler

    out = str(tmp_path_factory.mktemp("xplane"))
    prof = Profiler()
    x = jnp.ones(8)
    jax.profiler.start_trace(out)
    try:
        with prof.span("traced_span", tag=3):
            (x * 2).block_until_ready()
        with NULL_PROFILER.span("null_span"):
            (x * 3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    return trace.load(out), prof


def test_enabled_span_lands_on_the_host_plane(cpu_trace):
    tr, prof = cpu_trace
    (ev,) = [e for e in tr.host if e.name == "repro.traced_span"]
    assert ev.dur_ns > 0
    (r,) = prof.records  # the record keeps the bare name
    assert (r.name, r.tag) == ("traced_span", 3)
    assert r.seconds * 1e9 >= 0.5 * ev.dur_ns


def test_null_span_emits_and_records_nothing(cpu_trace):
    from repro.core.profile import NULL_PROFILER

    tr, _ = cpu_trace
    assert not [e for e in tr.host if "null_span" in e.name]
    assert NULL_PROFILER.records == ()
    assert NULL_PROFILER.span("a") is NULL_PROFILER.span("b")


@pytest.mark.parametrize("name,names", [
    ("inex-dense.serve", {"engine_wait", "engine_fill", "engine_batch", "engine_call"}),
    ("rcv1-ell.build", {"build_batch", "insert_wave", "split_cascade", "split_scan",
                        "split_round"}),
])
def test_span_idle_window_on_a_small_cell(name, names, tmp_path):
    """On the CPU the trace has no device, so the whole window is idle and a
    span's idle is its length; the engine's or the build's spans hold most
    of it."""
    spec = run.cell_spec(name, run.load_json(os.path.join(run.ROOT, "BENCHMARK.json")))
    spec["traffic"] = dict(spec["traffic"], rate_rows_per_s=40.0, trace_s=1.0)
    out = span_idle.measure(spec, 2**31 + 41, str(tmp_path), n_docs=400)
    assert names <= set(out["span_calls"]) and names <= set(out["spans"])
    assert out["idle_s"] == pytest.approx(out["window_s"])
    assert 0.5 < out["cover"] <= 1.0 + 1e-9
    assert all(v > 0 for v in out["records_per"].values()) and out["span_cost_us"] > 0
