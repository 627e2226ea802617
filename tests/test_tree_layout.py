"""The tree's stored slot axis (DESIGN.md §3).

``tree.centers`` stores ``stored_slots(m+1)`` slots a node: m+1 rounded up
to the sublane tile, so a TPU keeps the array row-major and no program
copies the whole tree to another layout. At order 7 (m+1 = 8) there is no
pad; at order 20 the stored width is 24. Each case builds the dense mean
tree or the ELL medoid tree and holds it, and its search answers, to
digests taken from the same builds with m+1-wide storage: the pad changes
no bit of the tree's entries or of an answer.
"""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fixtures import random_corpus
from repro.core import ktree as kt
from repro.core.query import topk_search
from repro.sparse.csr import csr_from_dense

N_DOCS, DIM, N_QUERIES = 500, 48, 40
STORED = {7: 8, 20: 24}
CASES = [(7, "dense"), (7, "ell"), (20, "dense"), (20, "ell")]

# sha256[:16] of each case's tree cut to its m+1 entry slots, and of its
# topk_search answers, from builds whose centres were stored m+1 wide
TREE_DIGEST = {
    (7, "dense"): "96b9d5aab3954327",
    (7, "ell"): "1e6cfe2a21e3fb35",
    (20, "dense"): "b3b24963081a9f82",
    (20, "ell"): "088aefc8b78f37b4",
}
SEARCH_DIGEST = {
    (7, "dense"): "38b629c65e08e94f",
    (7, "ell"): "07f8a2a355ec7f80",
    (20, "dense"): "7de01d0d1d53992f",
    (20, "ell"): "e8b0c85eba9de71c",
}


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def tree_digest(tree) -> str:
    """Digest of every data field, ``centers`` cut to the m+1 entry slots."""
    return digest(
        np.asarray(tree.centers)[:, : tree.order + 1], tree.counts, tree.child,
        tree.n_entries, tree.is_leaf, tree.parent, tree.parent_slot,
        tree.height, tree.root, tree.n_nodes, tree.depth,
    )


@functools.lru_cache(maxsize=None)
def built(order: int, kind: str):
    """(tree, queries) of one case: a dense mean tree over dense rows, or a
    medoid tree over the same rows as ELL (sparse backend)."""
    rng = np.random.default_rng(14)
    x = random_corpus(rng, n=N_DOCS, d=DIM, sparse=True)
    xq = x[:N_QUERIES] + rng.normal(0, 0.1, (N_QUERIES, DIM)).astype(np.float32)
    if kind == "dense":
        data, q = jnp.asarray(x), jnp.asarray(xq)
    else:
        data, q = csr_from_dense(x), csr_from_dense(xq)
    tree = kt.build(data, order=order, batch_size=64, medoid=kind == "ell",
                    key=jax.random.PRNGKey(order))
    return tree, q


@pytest.mark.parametrize("order,kind", CASES)
def test_tree_layout_matches_unpadded_build(order, kind):
    tree, _ = built(order, kind)
    m1 = order + 1
    assert int(tree.depth) >= 3  # the build split its root at least twice
    assert tree.centers.shape[1] == tree.stored_slots == STORED[order]
    assert tree.slots == m1
    assert tree.counts.shape[1] == tree.child.shape[1] == m1
    assert not np.asarray(tree.centers)[:, m1:].any(), "pad slots written"
    kt.check_invariants(tree, n_docs=N_DOCS)
    assert tree_digest(tree) == TREE_DIGEST[order, kind]


@pytest.mark.parametrize("order,kind", CASES)
def test_search_on_padded_tree_matches_unpadded(order, kind):
    tree, q = built(order, kind)
    docs, dist = topk_search(tree, q, k=10, beam=4)
    assert (docs[:, 0] >= 0).all()
    assert digest(docs, dist) == SEARCH_DIGEST[order, kind]


@pytest.mark.parametrize("dtype,m1,want", [
    (jnp.float32, 8, 8), (jnp.float32, 21, 24), (jnp.float32, 5, 8),
    (jnp.bfloat16, 21, 32), (jnp.bfloat16, 16, 16),
])
def test_stored_slots_round_to_the_sublane_tile(dtype, m1, want):
    assert kt.stored_slots(m1, dtype) == want
    tree = kt.ktree_init(4, m1 - 1, 3, dtype=dtype)
    assert tree.centers.shape == (4, want, 3)
    assert tree.slots == m1
    assert tree.node_centers(jnp.array([0, 2])).shape == (2, m1, 3)
