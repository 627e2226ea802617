"""K-tree checkpoint round-trips: dtype/static-field preservation for dense
and medoid trees (incl. the extended-dtype .npy descr bug), suffix handling,
atomicity, and restored trees staying fully live (further inserts + identical
query answers)."""
import dataclasses
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.ckpt import restore_ktree, save_ktree
from repro.core import ktree as kt
from repro.core.query import topk_search
from repro.sparse.csr import csr_from_dense


def planted(rng, n=90, d=8):
    x = rng.normal(0, 1, (n, d)).astype(np.float32)
    return jnp.asarray(x)


def assert_trees_equal(a, b):
    assert a.order == b.order and a.medoid == b.medoid
    assert isinstance(b.order, int) and isinstance(b.medoid, bool)
    for f in dataclasses.fields(a):
        if f.metadata.get("static"):
            continue
        fa, fb = getattr(a, f.name), getattr(b, f.name)
        assert fa.dtype == fb.dtype, f"{f.name}: {fa.dtype} != {fb.dtype}"
        assert fa.shape == fb.shape, f"{f.name}: {fa.shape} != {fb.shape}"
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb), err_msg=f.name)


@pytest.mark.parametrize("medoid", [False, True])
def test_roundtrip_preserves_everything(tmp_path, medoid):
    rng = np.random.default_rng(0 if medoid else 1)
    x = planted(rng)
    tree = kt.build(x, order=6, batch_size=16, medoid=medoid)
    path = str(tmp_path / "tree")
    out = save_ktree(path, tree)
    assert out.endswith(".npz") and os.path.exists(out)
    assert_trees_equal(tree, restore_ktree(path))


def test_roundtrip_sparse_medoid_tree(tmp_path):
    rng = np.random.default_rng(2)
    x = (rng.normal(0, 1, (80, 20)) * (rng.random((80, 20)) < 0.4)).astype(np.float32)
    m = csr_from_dense(x)
    tree = kt.build(m, order=7, medoid=True, batch_size=16)
    save_ktree(str(tmp_path / "t"), tree)
    tree2 = restore_ktree(str(tmp_path / "t"))
    assert_trees_equal(tree, tree2)
    # identical answers to sparse queries
    d1, s1 = topk_search(tree, m, k=5, beam=2)
    d2, s2 = topk_search(tree2, m, k=5, beam=2)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(s1, s2)


def test_npz_suffix_and_bare_path_agree(tmp_path):
    rng = np.random.default_rng(3)
    tree = kt.build(planted(rng, n=40), order=5, batch_size=16)
    p_bare = str(tmp_path / "a")
    p_npz = str(tmp_path / "b.npz")
    save_ktree(p_bare, tree)
    save_ktree(p_npz, tree)
    assert os.path.exists(p_bare + ".npz") and os.path.exists(p_npz)
    assert not os.path.exists(p_npz + ".npz")  # no double suffix
    assert_trees_equal(restore_ktree(p_bare), restore_ktree(p_npz))
    assert_trees_equal(restore_ktree(p_bare + ".npz"), restore_ktree(p_npz))


def test_extended_dtype_roundtrip(tmp_path):
    """bfloat16 tree pages survive the .npy descr limitation (stored upcast,
    restored to the recorded dtype)."""
    tree = kt.ktree_init(16, 4, 8, dtype=jnp.bfloat16)
    tree = dataclasses.replace(
        tree,
        centers=tree.centers.at[0, 0].set(jnp.asarray(0.25, jnp.bfloat16)),
        n_entries=tree.n_entries.at[0].set(1),
        child=tree.child.at[0, 0].set(0),
    )
    save_ktree(str(tmp_path / "bf16"), tree)
    tree2 = restore_ktree(str(tmp_path / "bf16"))
    assert tree2.centers.dtype == jnp.bfloat16
    assert tree2.counts.dtype == jnp.bfloat16
    assert_trees_equal(tree, tree2)


def test_no_tmp_residue(tmp_path):
    rng = np.random.default_rng(4)
    tree = kt.build(planted(rng, n=30), order=4, batch_size=8)
    save_ktree(str(tmp_path / "t"), tree)
    assert [f for f in os.listdir(tmp_path) if "tmp" in f] == []


@pytest.mark.parametrize("medoid", [False, True])
def test_restored_tree_accepts_insert_and_queries(tmp_path, medoid):
    rng = np.random.default_rng(5 if medoid else 6)
    x = np.asarray(planted(rng, n=120))
    tree = kt.build(jnp.asarray(x[:90]), order=6, batch_size=16, medoid=medoid)
    save_ktree(str(tmp_path / "t"), tree)
    tree2 = restore_ktree(str(tmp_path / "t"))

    # identical query answers before any mutation
    q = jnp.asarray(x[:25])
    np.testing.assert_array_equal(
        topk_search(tree, q, k=3, beam=2)[0], topk_search(tree2, q, k=3, beam=2)[0]
    )
    # a restored tree is fully live: insert more docs, invariants hold, and
    # the same growth applied to the original gives the identical tree
    key = jax.random.PRNGKey(7)
    grown = kt.insert(tree, jnp.asarray(x[90:]), np.arange(90, 120), key=key)
    grown2 = kt.insert(tree2, jnp.asarray(x[90:]), np.arange(90, 120), key=key)
    kt.check_invariants(grown2, n_docs=120)
    assert_trees_equal(grown, grown2)


def test_padded_tree_roundtrip(tmp_path):
    """Centres stored 24 slots wide for order 20 (the sublane tile pad,
    DESIGN.md §3) come back 24 wide, pad slots zero, answers unchanged."""
    rng = np.random.default_rng(8)
    x = planted(rng, n=160)
    tree = kt.build(x, order=20, batch_size=32)
    assert tree.centers.shape[1] == 24 and int(tree.depth) >= 2
    save_ktree(str(tmp_path / "t"), tree)
    tree2 = restore_ktree(str(tmp_path / "t"))
    assert_trees_equal(tree, tree2)
    kt.check_invariants(tree2, n_docs=160)
    np.testing.assert_array_equal(
        topk_search(tree, x, k=5, beam=2)[0], topk_search(tree2, x, k=5, beam=2)[0]
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_unpadded_checkpoint_restores_padded(tmp_path, dtype):
    """A snapshot written with m+1-wide centres (before the slot axis was
    padded) restores to the stored width, padded with zeros on load."""
    rng = np.random.default_rng(9)
    x = planted(rng, n=160)
    tree = kt.build(x, order=20, batch_size=32)
    if dtype != jnp.float32:
        tree = dataclasses.replace(
            tree, centers=jnp.pad(tree.centers, ((0, 0), (0, 8), (0, 0)))
            .astype(dtype))
    width = kt.stored_slots(21, dtype)
    assert tree.centers.shape[1] == width
    path = save_ktree(str(tmp_path / "t"), tree)
    with np.load(path) as data:
        arrays = dict(data)
    arrays["centers"] = arrays["centers"][:, :21]   # the old m+1-wide format
    np.savez(path, **arrays)

    tree2 = restore_ktree(path)
    assert tree2.centers.shape == tree.centers.shape
    assert_trees_equal(tree, tree2)
    np.testing.assert_array_equal(
        topk_search(tree, x, k=5, beam=2)[0], topk_search(tree2, x, k=5, beam=2)[0]
    )
