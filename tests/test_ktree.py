import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ktree as kt
from repro.core.metrics import micro_purity
from repro.core.sampling import sampled_ktree_clustering, select_sample_medoid


def planted(rng, k=6, per=50, d=10):
    means = rng.normal(0, 5, (k, d))
    x = np.concatenate([rng.normal(means[i], 1.0, (per, d)) for i in range(k)])
    return jnp.asarray(x.astype(np.float32)), np.repeat(np.arange(k), per)


@pytest.mark.parametrize("order,batch_size", [(4, 16), (8, 32), (16, 64)])
def test_build_invariants(order, batch_size):
    rng = np.random.default_rng(order)
    x, _ = planted(rng, k=4, per=40)
    tree = kt.build(x, order=order, batch_size=batch_size)
    kt.check_invariants(tree, n_docs=x.shape[0])


def test_sequential_build_matches_paper_semantics():
    """batch_size=1 is the exact one-vector-at-a-time algorithm."""
    rng = np.random.default_rng(0)
    x, _ = planted(rng, k=3, per=12, d=6)   # 36 docs
    tree = kt.build(x, order=4, batch_size=1)
    kt.check_invariants(tree, n_docs=x.shape[0])
    assert int(tree.depth) >= 2


def test_medoid_build_invariants_and_quality():
    rng = np.random.default_rng(1)
    x, labels = planted(rng)
    tree = kt.build(x, order=10, batch_size=32, medoid=True)
    kt.check_invariants(tree, n_docs=x.shape[0])
    assign, nc = kt.extract_assignment(tree, x.shape[0])
    p = float(micro_purity(jnp.asarray(assign), jnp.asarray(labels), nc, 6))
    assert p > 0.85


def test_assignment_covers_all_docs_once():
    rng = np.random.default_rng(2)
    x, _ = planted(rng, k=4, per=30)
    tree = kt.build(x, order=6, batch_size=16)
    assign, nc = kt.extract_assignment(tree, x.shape[0])
    assert (assign >= 0).all() and assign.max() < nc


def test_incremental_insert():
    rng = np.random.default_rng(3)
    x, _ = planted(rng, k=4, per=40)
    tree = kt.build(x[:100], order=8, batch_size=32)
    tree = kt.insert(tree, x[100:132], jnp.arange(100, 132))
    kt.check_invariants(tree, n_docs=132)


def test_profiled_build_equals_plain_and_counts_waves(monkeypatch):
    """A build handed a Profiler returns the tree of one without, field for
    field, and records one ``insert_wave`` span per wave it runs, inside one
    ``build_batch`` span per batch."""
    from repro.core.profile import Profiler

    rng = np.random.default_rng(4)
    x, _ = planted(rng, k=4, per=40)
    plain = kt.build(x, order=4, batch_size=32, key=jax.random.PRNGKey(3))
    waves = []
    wave = kt._insert_wave

    def counted(*a, **kw):
        waves.append(1)
        return wave(*a, **kw)
    monkeypatch.setattr(kt, "_insert_wave", counted)
    prof = Profiler()
    tree = kt.build(x, order=4, batch_size=32, key=jax.random.PRNGKey(3),
                    profiler=prof)
    for f in dataclasses.fields(kt.KTree):
        a, b = getattr(plain, f.name), getattr(tree, f.name)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f.name)
    tot = prof.totals()
    assert tot["insert_wave"]["count"] == len(waves) > tot["build_batch"]["count"]
    assert tot["build_batch"]["count"] == 5  # 160 docs in batches of 32
    assert tot["split_cascade"]["count"] == len(waves)
    assert tot["split_scan"]["count"] == tot["split_round"]["count"] + len(waves)
    parents = {r.name: r.parent for r in prof.records}
    assert parents == {"build_batch": None, "insert_wave": "build_batch",
                       "split_cascade": "build_batch", "split_scan": "split_cascade",
                       "split_round": "split_cascade"}


def test_nn_search_quality():
    rng = np.random.default_rng(4)
    x, _ = planted(rng, k=5, per=40, d=8)
    tree = kt.build(x, order=10, batch_size=32)
    doc, dist = kt.nn_search(tree, x[:60])
    # approximate search: the returned doc must be close (within 2x the true NN
    # dist on average) and often exact
    exact = (doc == np.arange(60)).mean()
    assert exact > 0.5
    assert (dist >= -1e-5).all()


def test_cluster_quality_beats_random():
    rng = np.random.default_rng(5)
    x, labels = planted(rng)
    tree = kt.build(x, order=12, batch_size=64)
    assign, nc = kt.extract_assignment(tree, x.shape[0])
    p = float(micro_purity(jnp.asarray(assign), jnp.asarray(labels), nc, 6))
    rand_assign = jnp.asarray(np.random.default_rng(0).integers(0, nc, x.shape[0]))
    pr = float(micro_purity(rand_assign, jnp.asarray(labels), nc, 6))
    assert p > pr + 0.2


def test_sampled_pipeline():
    rng = np.random.default_rng(6)
    x, labels = planted(rng, per=40)
    assign, nc, tree = sampled_ktree_clustering(x, order=8, fraction=0.2, batch_size=64)
    assert assign.shape[0] == x.shape[0] and (assign >= 0).all()
    p = float(micro_purity(jnp.asarray(assign), jnp.asarray(labels), nc, 6))
    assert p > 0.7


def test_medoid_sample_selection_size():
    rng = np.random.default_rng(7)
    x, _ = planted(rng, k=3, per=40, d=6)
    ids = select_sample_medoid(x, fraction=0.15, batch_size=32)
    frac = ids.size / x.shape[0]
    assert 0.03 < frac < 0.6
    assert len(np.unique(ids)) == ids.size


def test_level_centers_shrink_up_the_tree():
    rng = np.random.default_rng(8)
    x, _ = planted(rng, k=4, per=50)
    tree = kt.build(x, order=6, batch_size=32)
    if int(tree.depth) >= 3:
        c0 = kt.level_centers(tree, 0)
        c1 = kt.level_centers(tree, 1)
        assert c0.shape[0] <= c1.shape[0]


def test_ktree_checkpoint_roundtrip(tmp_path):
    from repro.ckpt import save_ktree, restore_ktree

    rng = np.random.default_rng(9)
    x, _ = planted(rng, k=3, per=20, d=5)
    tree = kt.build(x, order=5, batch_size=16)
    path = str(tmp_path / "tree.npz")
    save_ktree(path, tree)
    tree2 = restore_ktree(path)
    assert tree2.order == tree.order and tree2.medoid == tree.medoid
    np.testing.assert_array_equal(np.asarray(tree.child), np.asarray(tree2.child))
    a1, _ = kt.extract_assignment(tree, x.shape[0])
    a2, _ = kt.extract_assignment(tree2, x.shape[0])
    np.testing.assert_array_equal(a1, a2)


@settings(max_examples=10, deadline=None)
@given(
    st.integers(20, 120), st.integers(3, 10), st.integers(2, 8), st.integers(0, 9999)
)
def test_property_doc_conservation(n, order, d, seed):
    """Every inserted vector lives in exactly one leaf, for arbitrary data."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    tree = kt.build(x, order=order, batch_size=16, key=jax.random.PRNGKey(seed))
    kt.check_invariants(tree, n_docs=n)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 9999))
def test_property_duplicate_vectors(seed):
    """Degenerate inputs (many identical vectors) must still build a legal tree."""
    rng = np.random.default_rng(seed)
    base = rng.normal(0, 1, (4, 6)).astype(np.float32)
    x = jnp.asarray(np.repeat(base, 15, axis=0))
    tree = kt.build(x, order=5, batch_size=16, key=jax.random.PRNGKey(seed))
    kt.check_invariants(tree, n_docs=60)
