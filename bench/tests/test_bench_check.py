"""``correct`` on small cells on the CPU: true for the program as it is, and
false for the control (every row rounded through bfloat16) and for each
fault a cell can have, planted in the timed path underneath the harness."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

import run

N_DOCS = 400
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _cell(name, rate=40.0, **traffic):
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    if name == "rcv1-ell.serve":  # the medoid tree served, not a cell of its own
        spec = run.cell_spec("inex-dense.serve", bench)
        spec["config"] = run.load_json(os.path.join(run.BENCH, "configs", "rcv1-ell.json"))
    else:
        spec = run.cell_spec(name, bench)
    spec["traffic"] = dict(spec["traffic"], rate_rows_per_s=rate, **traffic)
    return spec


def _correct(name, rate=40.0, control=False, seconds=1.5, trace=False, **traffic):
    res = run.run_cell(_cell(name, rate, **traffic), 2**31 + 17, seconds, trace,
                       device=DEVICE, control=control, n_docs=N_DOCS)
    return res["correct"], {k: v["value"] for k, v in res["checks"].items()}


CELLS = ["rcv1-ell.serve", "inex-dense.build", "rcv1-ell.build", "inex-dense.serve"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    ok, checks = _correct(name)
    assert ok, checks


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    ok, checks = _correct(name, control=True)
    assert not ok, checks


@pytest.mark.parametrize("traffic", [
    {"rows": 3, "tenants": [{"k": 10, "beam": 4, "share": 2}, {"k": 5, "beam": 2, "share": 1}]},
    {"queries": {"dist": "zipf", "s": 1.1}, "answer_cache": 64},
    {"arrivals": {"process": "poisson", "burst": 4}},
], ids=["rows3_two_tenants", "zipf_cached", "bursts"])
def test_traffic_shapes_are_correct(traffic):
    """Mixes a later cell may use, from its traffic file alone: each warms
    its own call shapes, compiles nothing in the window and checks correct."""
    ok, checks = _correct("inex-dense.serve", rate=60.0, **traffic)
    assert ok, checks


def _wrap_search(monkeypatch, alter):
    from repro.core import engine

    make = engine.make_search_fn

    def patched(tree, **kw):
        fn = make(tree, **kw)

        def wrong(x, k, beam, chunk_rows=None):
            docs, dist = fn(x, k, beam, chunk_rows=chunk_rows)
            return alter(x, docs.copy(), dist.copy())
        wrong.chunk = fn.chunk
        return wrong
    monkeypatch.setattr(engine, "make_search_fn", patched)


def test_altered_answer_is_not_correct(monkeypatch):
    def alter(x, docs, dist):
        docs[:, 0] = (docs[:, 0] + 1) % N_DOCS
        return docs, dist
    _wrap_search(monkeypatch, alter)
    ok, checks = _correct("rcv1-ell.serve")
    assert not ok and checks["answers_differ"] > 0.5, checks


def test_half_batch_left_out_is_not_correct(monkeypatch):
    def alter(x, docs, dist):  # odd rows of a batch get their neighbour's answer
        docs[1::2], dist[1::2] = docs[0:-1:2][: len(docs[1::2])], dist[0:-1:2][: len(dist[1::2])]
        return docs, dist
    _wrap_search(monkeypatch, alter)
    ok, checks = _correct("rcv1-ell.serve", rate=400.0)
    assert not ok, checks


def test_unchanged_state_is_not_correct(monkeypatch):
    from repro.core import ktree

    monkeypatch.setattr(ktree, "_insert_wave",
                        lambda tree, be, rows, ids, valid, levels, max_levels: (tree, valid))
    ok, checks = _correct("inex-dense.build")
    assert not ok and checks["misplaced_docs"] >= N_DOCS, checks


def test_half_wave_left_out_is_not_correct(monkeypatch):
    from repro.core import ktree

    wave = ktree._insert_wave

    def half(tree, be, rows, ids, valid, levels, max_levels):
        keep = valid & (jnp.arange(valid.shape[0]) < valid.shape[0] // 2)
        tree, _ = wave(tree, be, rows, ids, keep, levels, max_levels=max_levels)
        return tree, valid
    monkeypatch.setattr(ktree, "_insert_wave", half)
    ok, checks = _correct("inex-dense.build")
    assert not ok and checks["misplaced_docs"] > 0, checks


def test_altered_tree_is_not_correct(monkeypatch):
    from repro.core import ktree

    build = ktree.build

    def altered(*a, **kw):
        tree = build(*a, **kw)
        leaf = int(np.nonzero(np.asarray(tree.is_leaf)[: int(tree.n_nodes)]
                              & (np.asarray(tree.n_entries)[: int(tree.n_nodes)] > 1))[0][0])
        child = tree.child.at[leaf, 0].set(tree.child[leaf, 1])
        import dataclasses

        return dataclasses.replace(tree, child=child)
    monkeypatch.setattr(ktree, "build", altered)
    ok, checks = _correct("inex-dense.build")
    assert not ok and checks["misplaced_docs"] > 0, checks


def test_traced_run_reads_the_untraced_window():
    """A --trace 1 run reports per-layer metrics only; the host's readings
    come from the measured window, the trace from a window after it."""
    spec = _cell("inex-dense.serve", 40.0, trace_s=1.0)
    res = run.run_cell(spec, 2**31 + 29, 1.5, True, device=DEVICE, n_docs=N_DOCS)
    assert res["correct"], res["checks"]
    names = set(res["metrics"])
    assert {"client.search_p95_ms", "client.lateness_p95_ms", "engine.rows_per_batch",
            "search.call_ms"} <= names
    assert not names & {m["name"] for m in spec["end_to_end"]}
    assert res["device"]["window_s"] > 0 and "breakdown" in res
