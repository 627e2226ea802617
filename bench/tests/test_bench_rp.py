"""A Random Indexing configuration (``"representation": "rp"``) through the
harness, on small cells on the CPU: the tree routes in a seeded projection
of ELL rows and search rescores the leaf pools from the ELL rows. ``correct``
is true for the program as it is, with nothing compiled in the window, and
false for the control and for each fault planted in the route or the
rescore."""
import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

import run
from lib import corpus as bcorpus, projection, reference

N_DOCS = 400
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
RP = {"dim": 128, "seed": 0, "kind": "ternary"}
LIMITS = {
    "open_loop": {"dist_gap": 1e-05, "answers_differ": 0.03, "lost_requests": 0,
                  "compiles_in_window": 0},
    "builds": {"misplaced_docs": 0, "route_gap": 1e-05, "mean_gap": 3e-05, "count_gap": 0.0,
               "compiles_in_window": 0},
}


def _config():
    """``rcv1-ell.json`` as a Random Indexing K-tree: the same ELL rows,
    a mean tree routed in a 128-dim ternary projection."""
    cfg = run.load_json(os.path.join(run.BENCH, "configs", "rcv1-ell.json"))
    return dict(cfg, name="rcv1-rp", representation="rp", medoid=False, rp=RP, limits=LIMITS)


def _cell(kind):
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    spec = run.cell_spec("inex-dense.serve" if kind == "serve" else "rcv1-ell.build", bench)
    spec["config"] = _config()
    if kind == "serve":
        spec["traffic"] = dict(spec["traffic"], rate_rows_per_s=40.0)
    return spec


def _correct(kind, control=False):
    res = run.run_cell(_cell(kind), 2**31 + 23, 1.5, False, device=DEVICE,
                       control=control, n_docs=N_DOCS)
    return res["correct"], {k: v["value"] for k, v in res["checks"].items()}


@pytest.mark.parametrize("kind", ["gaussian", "ternary"])
def test_projection_is_the_programs(kind):
    from repro.core.backend import make_projection

    for seed in (0, 7):
        want = np.asarray(make_projection(300, 128, seed=seed, kind=kind).matrix)
        got = projection.matrix(300, {"dim": 128, "seed": seed, "kind": kind})
        assert got.dtype == want.dtype == np.float32
        assert np.array_equal(got, want)


def test_projected_route_rows_are_float64_products():
    docs, _ = bcorpus.prepared_corpus(
        bcorpus.spec_from_config(_config(), N_DOCS), 5)
    p = projection.matrix(docs.n_cols, RP)
    rt = reference.Projected(p, docs)
    ids = np.array([3, 0, 399])
    want = docs.dense(ids, np.float64) @ p.astype(np.float64)
    assert np.allclose(rt.rows(ids), want, rtol=1e-13, atol=1e-15)
    q = docs.dense([7], np.float64)
    assert np.allclose(rt.of(q), rt.rows([7]), rtol=1e-13, atol=1e-15)


@pytest.mark.parametrize("kind", ["serve", "build"])
def test_rp_cell_is_correct(kind):
    ok, checks = _correct(kind)
    assert ok and checks["compiles_in_window"] == 0, checks
    assert ("route_gap" in checks) == (kind == "build") and "leaf_gap" not in checks


def test_rp_control_is_not_correct():
    ok, checks = _correct("serve", control=True)
    assert not ok, checks


def _projected_ranking(monkeypatch):
    """The pool ranked by projected distances: the exact rescore skipped."""
    from repro.core import query

    rescore = query._rescore_pool_chunk
    p = projection.matrix(8000, RP)

    def ranked(x_q, cand, valid, fetch_rows, k, prefetched=None):
        return rescore(x_q @ p, cand, valid, lambda ids: fetch_rows(ids) @ p, k)
    monkeypatch.setattr(query, "_rescore_pool_chunk", ranked)
    return "dist_gap"


def _other_seed(monkeypatch):
    """Search descends through a projection of another seed than the
    build's."""
    from repro.core import engine

    make = engine.make_search_fn
    other = dict(RP, seed=RP["seed"] + 1)

    def patched(tree, rp=None, **kw):
        proj = dataclasses.replace(rp.projection, seed=other["seed"],
                                   matrix=jnp.asarray(projection.matrix(8000, other)))
        return make(tree, rp=dataclasses.replace(rp, projection=proj), **kw)
    monkeypatch.setattr(engine, "make_search_fn", patched)
    return "answers_differ"


def _half_pool(monkeypatch):
    """The rescore drops every other candidate of each pool."""
    from repro.core import query

    rescore = query._rescore_pool_chunk

    def half(x_q, cand, valid, fetch_rows, k, prefetched=None):
        valid = valid.copy()
        valid[:, 1::2] = False
        return rescore(x_q, cand, valid, fetch_rows, k)
    monkeypatch.setattr(query, "_rescore_pool_chunk", half)
    return "answers_differ"


@pytest.mark.parametrize("plant", [_projected_ranking, _other_seed, _half_pool],
                         ids=["projected_ranking", "other_seed", "half_pool"])
def test_rp_fault_is_not_correct(monkeypatch, plant):
    fails = plant(monkeypatch)
    ok, checks = _correct("serve")
    assert not ok and checks[fails] > LIMITS["open_loop"][fails], checks
