"""The trace reduction: busy time as the union of device op intervals,
kernel and program counts by name, idle gaps named by the host."""
import os

import pytest

from lib import trace
from lib.trace import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_clip_cuts_to_the_window():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert trace.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]


def _synthetic():
    # device: a kernel inside a program, a second overlapping op, then a gap
    ops = [Event("fusion.1", 0, 4_000), Event("nn_topk_pallas", 1_000, 2_000),
           Event("copy.2", 3_000, 2_000), Event("nn_topk_pallas", 20_000, 5_000)]
    programs = [Event("jit__beam_search(1)", 0, 5_000), Event("jit__beam_search(2)", 20_000, 5_000)]
    host = [Event(trace.WINDOW_SPAN, 0, 30_000), Event("bench.search_fn", 0, 26_000),
            Event("PjitFunction(_beam_search)", 6_000, 2_000),
            Event("time.sleep", 9_000, 10_000)]
    return Trace(ops={"/device:TPU:0": ops}, programs={"/device:TPU:0": programs}, host=host)


def test_summarize_synthetic_trace():
    s = trace.summarize(_synthetic(), kernels=("nn_topk", "ell_spmm"),
                        programs=("_beam_search",), window=(0, 30_000))
    assert s["window_s"] == pytest.approx(30e-6)
    assert s["busy_s"] == pytest.approx(10e-6)          # [0, 5 us] and [20, 25 us]
    assert s["kernel_calls"] == {"nn_topk": 2, "ell_spmm": 0}
    assert s["kernel_s"]["nn_topk"] == pytest.approx(7e-6)
    assert s["program_calls"] == {"_beam_search": 2}
    idle = dict(s["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(20e-6)
    # 5-20 us: the sleep covers 10 of its 15 us; 25-30 us: only the outer span
    assert idle == pytest.approx({"time.sleep": 15e-6, "bench.search_fn": 5e-6})


def test_window_counts_ops_by_midpoint():
    s = trace.summarize(_synthetic(), kernels=("nn_topk",), programs=("_beam_search",),
                        window=(0, 10_000))
    assert s["kernel_calls"]["nn_topk"] == 1 and s["program_calls"]["_beam_search"] == 1
    assert s["busy_s"] == pytest.approx(5e-6)


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on a TPU v5e by bench/record_trace.py: 12 one-row
    searches of a 2,000-doc tree, each in a bench.search_fn span, with a
    2 ms host sleep after each, inside the bench_window span."""
    tr = trace.load(os.path.join(DATA, "serve_small.xplane.pb"))
    span = [e for e in tr.host if e.name == trace.WINDOW_SPAN]
    assert len(span) == 1
    window = (span[0].start_ns, span[0].start_ns + span[0].dur_ns)
    return tr, window, trace.summarize(tr, kernels=("nn_topk", "nn_assign", "ell_spmm"),
                                       programs=("_beam_search",), window=window)


def test_recorded_trace_planes(recorded):
    tr, _, _ = recorded
    assert list(tr.ops) == ["/device:TPU:0"] and list(tr.programs) == ["/device:TPU:0"]
    assert sum(e.name == "bench.search_fn" for e in tr.host) == 12


def test_recorded_trace_counts(recorded):
    _, _, s = recorded
    assert s["n_devices"] == 1
    assert s["program_calls"] == {"_beam_search": 12}
    assert s["kernel_calls"] == {"nn_topk": 12, "nn_assign": 0, "ell_spmm": 0}
    assert 0 < s["kernel_s"]["nn_topk"] < s["program_s"]["_beam_search"]


def test_recorded_trace_busy_is_the_union_of_ops(recorded):
    tr, (lo, hi), s = recorded
    # a timeline at 100 ns resolution, built without the interval union
    n = (hi - lo) // 100 + 1
    busy = bytearray(int(n))
    for e in tr.ops["/device:TPU:0"]:
        a, b = max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi)
        for t in range(int((a - lo) // 100), int((b - lo) // 100)):
            busy[t] = 1
    assert s["busy_s"] == pytest.approx(sum(busy) * 100e-9, rel=0.01)
    assert s["program_s"]["_beam_search"] <= s["window_s"]
    assert 0 < s["busy_s"] < s["window_s"]


def test_recorded_trace_idle_gaps(recorded):
    _, _, s = recorded
    idle = dict(s["idle_gaps"])
    assert sum(idle.values()) <= s["window_s"] - s["busy_s"] + 1e-9
    assert sum(idle.values()) >= 0.95 * (s["window_s"] - s["busy_s"])
    # most of the idle time is the host's sleep between calls
    assert max(idle, key=idle.get) == "$time sleep"
    assert idle["$time sleep"] > 0.5 * sum(idle.values())
