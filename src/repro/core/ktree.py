"""K-tree — height-balanced cluster tree of order m (the paper's contribution).

TPU-native array layout (DESIGN.md §3): the whole tree lives in preallocated
device arrays; node ids are row indices. Entry arrays have ``order+1`` slots so
a node can transiently hold m+1 entries (the paper's overflow state) before the
k-means split. The control plane (which node to split next, wave scheduling) is
thin host Python; every data-touching step is a jitted batched op.

Semantics (paper §1):
- leaves hold 1..m data vectors (``child`` = document id),
- internal nodes hold 1..m (cluster mean, child node) pairs,
- insertion = NN search root→leaf, updating weighted means along the path,
- a node that reaches m+1 entries is split with k-means (k=2), the two means
  are promoted to the parent; the root split grows the tree by one level,
- the tree is a nearest-neighbour search tree over the inserted vectors.

Medoid variant (paper §2): centres are document exemplars (nearest entry to
each 2-means mean), entries are *not* weighted and means are *not* updated on
insertion — ``medoid=True``.

Vector backends (DESIGN.md §5): documents reach the tree through a
:mod:`repro.core.backend` instance — dense rows (seed behaviour) or the
paper's sparse representation (ELL + CSR; distances via the ``ell_spmm`` /
``nn_assign`` Pallas kernels on TPU, ``kernels/ref.py`` oracles on CPU).
Node centres are always dense; the sparse corpus is densified only one
routed wave at a time (leaf appends and node splits), never wholesale.

Control plane (DESIGN.md §6): ``route`` compilations are bucketed by level
count (one compile per power-of-two descent depth, with inactive levels
masked), and all overflowing nodes of one height are split in a single
jitted ``split_nodes_batch`` call (vmapped 2-means) instead of one jit call
per node.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.autotune import resolve_knobs
from repro.core.backend import VectorBackend, make_backend
from repro.core.kmeans import kmeans
from repro.core.profile import NULL_PROFILER
from repro.kernels.ref import EXACT


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KTree:
    # --- data fields (device arrays) ---
    centers: jax.Array       # f32[N, m+1, d] entry vectors/means (zeros invalid)
    counts: jax.Array        # f32[N, m+1]    subtree weight per entry
    child: jax.Array         # i32[N, m+1]    doc id (leaf) / node id (internal)
    n_entries: jax.Array     # i32[N]
    is_leaf: jax.Array       # bool[N]
    parent: jax.Array        # i32[N]         -1 for root
    parent_slot: jax.Array   # i32[N]
    height: jax.Array        # i32[N]         0 at leaves (stable under root growth)
    root: jax.Array          # i32[]
    n_nodes: jax.Array       # i32[]
    depth: jax.Array         # i32[]          levels; 1 = root is a leaf
    # --- meta fields (static) ---
    order: int = dataclasses.field(metadata=dict(static=True))
    medoid: bool = dataclasses.field(metadata=dict(static=True))

    @property
    def max_nodes(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[2]

    @property
    def slots(self) -> int:  # order + 1
        return self.centers.shape[1]


def ktree_init(
    max_nodes: int, order: int, dim: int, medoid: bool = False, dtype=jnp.float32
) -> KTree:
    m1 = order + 1
    return KTree(
        centers=jnp.zeros((max_nodes, m1, dim), dtype),
        counts=jnp.zeros((max_nodes, m1), dtype),
        child=jnp.full((max_nodes, m1), -1, jnp.int32),
        n_entries=jnp.zeros((max_nodes,), jnp.int32),
        is_leaf=jnp.ones((max_nodes,), bool).at[0].set(True),
        parent=jnp.full((max_nodes,), -1, jnp.int32),
        parent_slot=jnp.full((max_nodes,), -1, jnp.int32),
        height=jnp.zeros((max_nodes,), jnp.int32),
        root=jnp.int32(0),
        n_nodes=jnp.int32(1),
        depth=jnp.int32(1),
        order=order,
        medoid=medoid,
    )


CAPACITY_HEADROOM = 1.8
"""Node-capacity multiplier over the worst-case leaf count in
:func:`suggested_max_nodes`. Internal nodes of an order-m tree add at most
~1/(⌈m/2⌉−1) ≈ 0.5× more nodes on top of the leaves, and the split cascade
transiently allocates the new sibling before the parent absorbs it — 1.8×
covers both with margin (pinned by the capacity property test)."""


def suggested_max_nodes(n_docs: int, order: int) -> int:
    """Preallocation capacity: worst-case ~2·N/(m/2) half-full leaves, times
    :data:`CAPACITY_HEADROOM` for internal nodes + split headroom, plus
    constant slack for tiny corpora."""
    leaves = max(2 * n_docs // max(order // 2, 1), 8)
    return int(leaves * CAPACITY_HEADROOM) + 32


def _levels_bucket(levels: int) -> int:
    """Round a descent depth up to a power of two — ``route``/``_insert_wave``
    compile once per bucket (inactive levels are masked), so a growing tree
    triggers O(log depth) compiles instead of one per depth."""
    if levels <= 0:
        return 0
    b = 1
    while b < levels:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# routing (NN search root→leaf) — the hot path
# ---------------------------------------------------------------------------

def _node_nearest_slot(
    tree: KTree, node_ids: jax.Array, backend: VectorBackend, rows: jax.Array
) -> jax.Array:
    """For each (node, query-row) pick the nearest *valid* entry slot → i32[B].

    Distances drop the ‖x‖² constant (same argmin). Per-query gathered node
    centres: the backend supplies the cross term — MXU einsum for dense rows,
    an nnz-bounded column gather for sparse rows."""
    c = tree.centers[node_ids]                                   # [B, m1, d]
    c_sq = jnp.einsum("bmd,bmd->bm", c, c, precision=EXACT)
    cross = backend.cross_nodes(rows, c)
    dist = c_sq - 2.0 * cross
    valid = jnp.arange(tree.slots)[None, :] < tree.n_entries[node_ids][:, None]
    dist = jnp.where(valid, dist, jnp.inf)
    return jnp.argmin(dist, axis=1).astype(jnp.int32)


def _root_nearest_slot(
    tree: KTree, backend: VectorBackend, rows: jax.Array
) -> jax.Array:
    """Level-0 descent: every query is at the root, so its entries form one
    flat centre set — the fused flat-NN path (``nn_assign`` / ``ell_spmm``
    Pallas kernels on TPU, ref oracles elsewhere)."""
    c = tree.centers[tree.root]                                  # [m1, d]
    valid = jnp.arange(tree.slots) < tree.n_entries[tree.root]
    idx, _ = backend.nn_flat(rows, c, valid)
    return idx


def _route_descend(
    tree: KTree,
    backend: VectorBackend,
    rows: jax.Array,
    levels: jax.Array,
    max_levels: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Descend up to ``max_levels`` internal levels; levels ≥ ``levels`` are
    masked no-ops (the node sticks once the true leaf level is reached).

    Returns (leaf_ids i32[B], path_nodes i32[max_levels, B],
    path_slots i32[max_levels, B]); path rows at l ≥ levels are stale and must
    be masked by the caller."""
    b = rows.shape[0]
    node = jnp.full((b,), 1, jnp.int32) * tree.root
    nodes_l, slots_l = [], []
    for l in range(max_levels):
        if l == 0:
            slot = _root_nearest_slot(tree, backend, rows)
        else:
            slot = _node_nearest_slot(tree, node, backend, rows)
        nodes_l.append(node)
        slots_l.append(slot)
        active = jnp.asarray(l, jnp.int32) < levels
        node = jnp.where(active, tree.child[node, slot], node)
    path_nodes = jnp.stack(nodes_l) if max_levels else jnp.zeros((0, b), jnp.int32)
    path_slots = jnp.stack(slots_l) if max_levels else jnp.zeros((0, b), jnp.int32)
    return node, path_nodes, path_slots


@functools.partial(jax.jit, static_argnames=("max_levels",))
def _route_jit(tree, backend, rows, levels, max_levels):
    return _route_descend(tree, backend, rows, levels, max_levels)


def route(
    tree: KTree, x, levels: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Descend ``levels`` internal levels from the root.

    ``x``: dense array, Csr, or a backend instance. Returns (leaf_ids i32[B],
    path_nodes i32[levels, B], path_slots i32[levels, B]). ``levels = depth-1``
    reaches the leaf level (the tree is height-balanced, so every query
    descends the same number of steps). Compilation is bucketed: one compile
    per power-of-two level count, not one per depth."""
    backend = make_backend(x)
    rows = jnp.arange(backend.n_docs, dtype=jnp.int32)
    leaf, pn, ps = _route_jit(
        tree, backend, rows, jnp.int32(levels), max_levels=_levels_bucket(levels)
    )
    return leaf, pn[:levels], ps[:levels]


@jax.jit
def _nearest_in_leaf_backend(
    tree: KTree, leaf_ids: jax.Array, backend: VectorBackend, rows: jax.Array
):
    """(doc_id i32[B], sqdist f32[B]) — exact NN among the reached leaf's
    vectors, for any backend."""
    c = tree.centers[leaf_ids]                                   # [B, m1, d]
    c_sq = jnp.einsum("bmd,bmd->bm", c, c, precision=EXACT)
    diff_sq = c_sq - 2.0 * backend.cross_nodes(rows, c)
    valid = jnp.arange(tree.slots)[None, :] < tree.n_entries[leaf_ids][:, None]
    diff_sq = jnp.where(valid, diff_sq, jnp.inf)
    slot = jnp.argmin(diff_sq, axis=1).astype(jnp.int32)
    best = jnp.take_along_axis(diff_sq, slot[:, None], 1)[:, 0] + backend.row_sq(rows)
    return tree.child[leaf_ids, slot], jnp.maximum(best, 0.0)


def nearest_in_leaf(tree: KTree, leaf_ids: jax.Array, x: jax.Array):
    """(doc_id i32[B], sqdist f32[B]) — dense-query convenience wrapper."""
    backend = make_backend(x)
    rows = jnp.arange(backend.n_docs, dtype=jnp.int32)
    return _nearest_in_leaf_backend(tree, leaf_ids, backend, rows)


# ---------------------------------------------------------------------------
# batched insertion wave
# ---------------------------------------------------------------------------

def _group_rank(leaf_ids: jax.Array) -> jax.Array:
    """rank of each element within its equal-leaf group (stable, 0-based)."""
    b = leaf_ids.shape[0]
    perm = jnp.argsort(leaf_ids, stable=True)
    sorted_leaf = leaf_ids[perm]
    first = jnp.searchsorted(sorted_leaf, sorted_leaf, side="left")
    rank_sorted = jnp.arange(b, dtype=jnp.int32) - first.astype(jnp.int32)
    return jnp.zeros((b,), jnp.int32).at[perm].set(rank_sorted)


@functools.partial(jax.jit, static_argnames=("max_levels",), donate_argnums=0)
def _insert_wave(
    tree: KTree,
    backend: VectorBackend,
    rows: jax.Array,
    doc_ids: jax.Array,
    valid: jax.Array,
    levels: jax.Array,
    max_levels: int,
) -> Tuple[KTree, jax.Array]:
    """One insertion wave at the current tree shape.

    Routes every (valid) backend row to its leaf, accepts per-leaf up to the
    m+1 overflow capacity, applies the paper's weighted-mean updates along the
    accepted paths (dense mode), and appends accepted vectors to leaves —
    densifying *only this wave's rows* via the backend. Returns
    (tree, accepted bool[B]). Callers split overflowing nodes and loop until
    nothing is pending (see :func:`build`).

    The input tree is donated — its buffers become the output's, so a wave
    never holds two trees (4.9 GB each at INEX width and 20k docs) — and
    must not be used after the call; so are the split ops'."""
    m1 = tree.slots
    nmax = tree.max_nodes
    leaf_ids, path_nodes, path_slots = _route_descend(
        tree, backend, rows, levels, max_levels
    )

    # ---- acceptance: per leaf, up to (m+1 − n_entries) new vectors this wave.
    # Invalid (already-inserted / padding) vectors must not consume capacity:
    # park them in a sentinel group before ranking.
    rank = _group_rank(jnp.where(valid, leaf_ids, nmax))
    free = (m1 - tree.n_entries[leaf_ids]).astype(jnp.int32)
    accepted = jnp.logical_and(valid, rank < free)

    # the only densification point: one wave's worth of rows
    x = backend.take(rows).astype(tree.centers.dtype)

    # ---- path mean updates for accepted vectors (dense K-tree only)
    if not tree.medoid:
        centers, counts = tree.centers, tree.counts
        for l in range(max_levels):
            upd = jnp.logical_and(accepted, jnp.asarray(l, jnp.int32) < levels)
            wa = upd.astype(x.dtype)
            n_l, s_l = path_nodes[l], path_slots[l]
            # rows that update one (node, slot) entry form a group: each row
            # gets its group's vector and weight sums from a [B, B] matmul,
            # and only the touched entries are rewritten — no tree-sized
            # temporary (every row of a group writes the same value)
            group = jnp.logical_and(n_l[:, None] == n_l[None, :],
                                    s_l[:, None] == s_l[None, :])
            group = group.astype(x.dtype) * wa[None, :]
            sum_x = jnp.matmul(group, x, precision=EXACT)         # [B, d]
            old_counts = counts[n_l, s_l]
            new_counts = old_counts + group.sum(axis=1)
            new_centers = (
                centers[n_l, s_l] * old_counts[:, None] + sum_x
            ) / jnp.maximum(new_counts, 1e-12)[:, None]
            n_safe = jnp.where(upd, n_l, nmax)  # OOB rows are dropped
            centers = centers.at[n_safe, s_l].set(new_centers)
            counts = counts.at[n_safe, s_l].set(new_counts)
        tree = dataclasses.replace(tree, centers=centers, counts=counts)

    # ---- leaf append
    slot = tree.n_entries[leaf_ids] + rank
    leaf_safe = jnp.where(accepted, leaf_ids, nmax)
    centers = tree.centers.at[leaf_safe, slot].set(x)
    counts = tree.counts.at[leaf_safe, slot].set(1.0)
    child = tree.child.at[leaf_safe, slot].set(doc_ids.astype(jnp.int32))
    n_entries = tree.n_entries.at[leaf_safe].add(accepted.astype(jnp.int32))
    tree = dataclasses.replace(
        tree, centers=centers, counts=counts, child=child, n_entries=n_entries
    )
    return tree, accepted


# ---------------------------------------------------------------------------
# node split (k-means k=2) + promotion — the B+-tree machinery
# ---------------------------------------------------------------------------

class _SplitParts(NamedTuple):
    """Pure per-node split computation (no tree writes) — shared by the scalar
    root split and the batched same-height split."""
    left_centers: jax.Array   # [m1, d]
    left_counts: jax.Array    # [m1]
    left_child: jax.Array     # [m1]
    n_left: jax.Array         # i32[]
    right_centers: jax.Array  # [m1, d]
    right_counts: jax.Array   # [m1]
    right_child: jax.Array    # [m1]
    n_right: jax.Array        # i32[]
    mean_l: jax.Array         # [d] promoted centre (mean or exemplar)
    mean_r: jax.Array         # [d]
    w_l: jax.Array            # f32[] promoted weight
    w_r: jax.Array            # f32[]


def _split_parts(
    key: jax.Array,
    e_centers: jax.Array,
    e_counts: jax.Array,
    e_child: jax.Array,
    n_e: jax.Array,
    medoid: bool,
) -> _SplitParts:
    """2-means an overflowing node's entries and partition them into the
    (stay, move) halves plus the two promoted summaries."""
    m1 = e_centers.shape[0]
    validm = jnp.arange(m1) < n_e

    w = jnp.where(validm, jnp.ones_like(e_counts) if medoid else e_counts, 0.0)
    # n_init=2: one retry guards against a degenerate k-means++ draw without
    # doubling the split cascade's cost the way the standalone default would
    res = kmeans(key, e_centers, 2, w=w, max_iters=50, init="kmeanspp", n_init=2)
    grp = res.assign.astype(jnp.int32)

    # enforce two non-empty groups (degenerate data / identical vectors)
    n1 = jnp.sum(jnp.where(validm, grp, 0))
    n0 = n_e - n1
    d_to_c0 = jnp.sum((e_centers - res.centers[0]) ** 2, axis=1)
    far = jnp.argmax(jnp.where(validm, d_to_c0, -jnp.inf)).astype(jnp.int32)
    near = jnp.argmin(jnp.where(validm, d_to_c0, jnp.inf)).astype(jnp.int32)
    grp = jnp.where(n1 == 0, grp.at[far].set(1), grp)
    grp = jnp.where(n0 == 0, grp.at[near].set(0), grp)
    grp = jnp.where(validm, grp, 1)  # invalid slots sort to the right group tail

    # stable partition: group-0 entries first (stay), group-1 entries (move)
    perm = jnp.argsort(grp, stable=True)
    n_left = jnp.sum(jnp.where(validm, (grp == 0).astype(jnp.int32), 0))
    n_right = n_e - n_left
    p_centers, p_counts, p_child = e_centers[perm], e_counts[perm], e_child[perm]
    pos = jnp.arange(m1, dtype=jnp.int32)
    left_sel = pos < n_left
    right_sel = pos < n_right

    left_centers = jnp.where(left_sel[:, None], p_centers, 0.0)
    left_counts = jnp.where(left_sel, p_counts, 0.0)
    left_child = jnp.where(left_sel, p_child, -1)
    # right entries compacted to the front of the new node
    r_perm = jnp.where(pos + n_left < m1, pos + n_left, m1 - 1)
    right_centers = jnp.where(right_sel[:, None], p_centers[r_perm], 0.0)
    right_counts = jnp.where(right_sel, p_counts[r_perm], 0.0)
    right_child = jnp.where(right_sel, p_child[r_perm], -1)

    # subtree summaries to promote
    w_l = jnp.sum(left_counts)
    w_r = jnp.sum(right_counts)
    mean_l = jnp.sum(left_centers * left_counts[:, None], 0) / jnp.maximum(w_l, 1e-12)
    mean_r = jnp.sum(right_centers * right_counts[:, None], 0) / jnp.maximum(w_r, 1e-12)
    if medoid:
        # exemplar = nearest entry vector to each mean (k-medoids, paper §2)
        def exemplar(entry_c, sel, mean):
            d = jnp.sum((entry_c - mean) ** 2, axis=1)
            i = jnp.argmin(jnp.where(sel, d, jnp.inf))
            return entry_c[i]
        mean_l = exemplar(left_centers, left_sel, mean_l)
        mean_r = exemplar(right_centers, right_sel, mean_r)

    return _SplitParts(
        left_centers, left_counts, left_child, n_left,
        right_centers, right_counts, right_child, n_right,
        mean_l, mean_r, w_l, w_r,
    )


@functools.partial(jax.jit, donate_argnums=0)
def split_node(tree: KTree, node_id: jax.Array, key: jax.Array) -> KTree:
    """Split one overflowing node (n_entries == m+1) into two with 2-means and
    promote the two means (or exemplars, medoid mode) to the parent. The caller
    guarantees the parent has a free slot. This scalar path also handles the
    root split (the only split that grows the tree); same-height non-root
    splits go through :func:`split_nodes_batch`."""
    m1 = tree.slots
    nmax = tree.max_nodes
    node_id = jnp.asarray(node_id, jnp.int32)
    parts = _split_parts(
        key,
        tree.centers[node_id],
        tree.counts[node_id],
        tree.child[node_id],
        tree.n_entries[node_id],
        tree.medoid,
    )
    leaf = tree.is_leaf[node_id]
    new_id = tree.n_nodes
    pos = jnp.arange(m1, dtype=jnp.int32)

    centers = tree.centers.at[node_id].set(parts.left_centers).at[new_id].set(parts.right_centers)
    counts = tree.counts.at[node_id].set(parts.left_counts).at[new_id].set(parts.right_counts)
    child = tree.child.at[node_id].set(parts.left_child).at[new_id].set(parts.right_child)
    n_entries = tree.n_entries.at[node_id].set(parts.n_left).at[new_id].set(parts.n_right)
    is_leaf = tree.is_leaf.at[new_id].set(leaf)
    height = tree.height.at[new_id].set(tree.height[node_id])

    # children of an internal node follow their entries
    int_node = jnp.logical_not(leaf)
    lc_safe = jnp.where(
        jnp.logical_and(int_node, pos < parts.n_left), parts.left_child, nmax
    )
    rc_safe = jnp.where(
        jnp.logical_and(int_node, pos < parts.n_right), parts.right_child, nmax
    )
    parent = tree.parent.at[lc_safe].set(node_id).at[rc_safe].set(new_id)
    parent_slot = tree.parent_slot.at[lc_safe].set(pos).at[rc_safe].set(pos)

    is_root = tree.parent[node_id] < 0
    p_id = jnp.where(is_root, tree.n_nodes + 1, tree.parent[node_id])
    p_slot_l = jnp.where(is_root, 0, tree.parent_slot[node_id])
    p_slot_r = jnp.where(is_root, 1, tree.n_entries[p_id])

    centers = centers.at[p_id, p_slot_l].set(parts.mean_l).at[p_id, p_slot_r].set(parts.mean_r)
    counts = counts.at[p_id, p_slot_l].set(parts.w_l).at[p_id, p_slot_r].set(parts.w_r)
    child = child.at[p_id, p_slot_l].set(node_id).at[p_id, p_slot_r].set(new_id)
    n_entries = n_entries.at[p_id].set(jnp.where(is_root, 2, n_entries[p_id] + 1))
    is_leaf = is_leaf.at[p_id].set(jnp.where(is_root, False, is_leaf[p_id]))
    height = height.at[p_id].set(
        jnp.where(is_root, tree.height[node_id] + 1, height[p_id])
    )
    parent = parent.at[node_id].set(p_id).at[new_id].set(p_id)
    parent = parent.at[p_id].set(jnp.where(is_root, -1, parent[p_id]))
    parent_slot = parent_slot.at[node_id].set(p_slot_l).at[new_id].set(p_slot_r)

    return dataclasses.replace(
        tree,
        centers=centers,
        counts=counts,
        child=child,
        n_entries=n_entries,
        is_leaf=is_leaf,
        parent=parent,
        parent_slot=parent_slot,
        height=height,
        root=jnp.where(is_root, p_id, tree.root).astype(jnp.int32),
        n_nodes=tree.n_nodes + jnp.where(is_root, 2, 1).astype(jnp.int32),
        depth=jnp.where(is_root, tree.depth + 1, tree.depth).astype(jnp.int32),
    )


@functools.partial(jax.jit, donate_argnums=0)
def split_nodes_batch(
    tree: KTree, node_ids: jax.Array, valid: jax.Array, keys: jax.Array
) -> KTree:
    """Split a batch of overflowing *same-height, non-root* nodes in one jitted
    call: vmapped 2-means + one set of fused scatters.

    ``node_ids`` i32[S] (padding rows have ``valid=False``), ``keys`` [S]-batch
    of PRNG keys. Splits whose parent lacks free slots are deferred (their
    ``valid`` drops) — the driver loop picks them up after the parent itself
    splits. The caller must exclude the root (its split grows the tree; use
    :func:`split_node`)."""
    m1 = tree.slots
    nmax = tree.max_nodes
    node_ids = jnp.asarray(node_ids, jnp.int32)
    read = jnp.where(valid, node_ids, 0)                 # safe gather index
    p_id = tree.parent[read]                             # [S] ≥ 0 for valid rows
    p_read = jnp.maximum(p_id, 0)

    # per-parent capacity: rank splits sharing a parent; only the first
    # (m+1 − n_entries[parent]) proceed this round
    rank = _group_rank(jnp.where(valid, p_id, nmax))
    free = (m1 - tree.n_entries[p_read]).astype(jnp.int32)
    valid = jnp.logical_and(valid, rank < free)

    parts = jax.vmap(
        functools.partial(_split_parts, medoid=tree.medoid)
    )(
        keys,
        tree.centers[read],
        tree.counts[read],
        tree.child[read],
        tree.n_entries[read],
    )

    leaf = tree.is_leaf[read]                            # [S]
    new_id = (tree.n_nodes + jnp.cumsum(valid) - valid).astype(jnp.int32)
    node_safe = jnp.where(valid, node_ids, nmax)
    new_safe = jnp.where(valid, new_id, nmax)

    centers = tree.centers.at[node_safe].set(parts.left_centers).at[new_safe].set(parts.right_centers)
    counts = tree.counts.at[node_safe].set(parts.left_counts).at[new_safe].set(parts.right_counts)
    child = tree.child.at[node_safe].set(parts.left_child).at[new_safe].set(parts.right_child)
    n_entries = tree.n_entries.at[node_safe].set(parts.n_left).at[new_safe].set(parts.n_right)
    is_leaf = tree.is_leaf.at[new_safe].set(leaf)
    height = tree.height.at[new_safe].set(tree.height[read])

    # children of internal nodes follow their entries
    pos = jnp.arange(m1, dtype=jnp.int32)[None, :]       # [1, m1]
    ok = jnp.logical_and(valid, jnp.logical_not(leaf))[:, None]
    lc_safe = jnp.where(jnp.logical_and(ok, pos < parts.n_left[:, None]), parts.left_child, nmax)
    rc_safe = jnp.where(jnp.logical_and(ok, pos < parts.n_right[:, None]), parts.right_child, nmax)
    node_b = jnp.broadcast_to(node_ids[:, None], lc_safe.shape)
    new_b = jnp.broadcast_to(new_id[:, None], rc_safe.shape)
    pos_b = jnp.broadcast_to(pos, lc_safe.shape)
    parent = tree.parent.at[lc_safe].set(node_b).at[rc_safe].set(new_b)
    parent_slot = tree.parent_slot.at[lc_safe].set(pos_b).at[rc_safe].set(pos_b)

    # promotion: left keeps the node's (parent, slot); right appends after the
    # parent's current entries, ordered by the per-parent rank
    p_safe = jnp.where(valid, p_id, nmax)
    p_slot_l = tree.parent_slot[read]
    p_slot_r = tree.n_entries[p_read] + rank
    centers = centers.at[p_safe, p_slot_l].set(parts.mean_l).at[p_safe, p_slot_r].set(parts.mean_r)
    counts = counts.at[p_safe, p_slot_l].set(parts.w_l).at[p_safe, p_slot_r].set(parts.w_r)
    child = child.at[p_safe, p_slot_l].set(node_ids).at[p_safe, p_slot_r].set(new_id)
    n_entries = n_entries.at[p_safe].add(valid.astype(jnp.int32))
    parent = parent.at[node_safe].set(p_id).at[new_safe].set(p_id)
    parent_slot = parent_slot.at[node_safe].set(p_slot_l).at[new_safe].set(p_slot_r)

    return dataclasses.replace(
        tree,
        centers=centers,
        counts=counts,
        child=child,
        n_entries=n_entries,
        is_leaf=is_leaf,
        parent=parent,
        parent_slot=parent_slot,
        height=height,
        n_nodes=tree.n_nodes + jnp.sum(valid).astype(jnp.int32),
    )


_SPLIT_BATCH_CAP = 64  # bounds vmapped-kmeans memory (S · m1 · d fp32)


def _split_batch_size(n: int) -> int:
    """Pad split batches to powers of two so ``split_nodes_batch`` compiles
    once per bucket."""
    b = 1
    while b < n:
        b *= 2
    return min(b, _SPLIT_BATCH_CAP)


def _split_all_overflowing(tree: KTree, key: jax.Array) -> Tuple[KTree, jax.Array]:
    """Host control plane: split overflowing nodes shallowest (max height)
    first — all overflowing nodes of one height in a single jitted call — until
    the m-order invariant holds everywhere. Splitting top-down guarantees a
    parent has spare capacity before its children promote into it (splits that
    would overflow a full parent are deferred one round by the batch op)."""
    while True:
        n_nodes = int(tree.n_nodes)
        # slice on the host: a device slice of a new length compiles anew
        n_entries = np.asarray(tree.n_entries)[:n_nodes]
        over = np.nonzero(n_entries > tree.order)[0]
        if over.size == 0:
            return tree, key
        root = int(tree.root)
        if n_entries[root] > tree.order:
            # the root split grows the tree — scalar path
            key, sub = jax.random.split(key)
            tree = split_node(tree, jnp.int32(root), sub)
            continue
        heights = np.asarray(tree.height)[over]
        batch = over[heights == heights.max()][:_SPLIT_BATCH_CAP]
        size = _split_batch_size(batch.size)
        ids = np.zeros(size, np.int32)
        ids[: batch.size] = batch[:size]
        valid = np.arange(size) < batch.size
        key, sub = jax.random.split(key)
        tree = split_nodes_batch(
            tree, jnp.asarray(ids), jnp.asarray(valid), jax.random.split(sub, size)
        )


# ---------------------------------------------------------------------------
# build drivers
# ---------------------------------------------------------------------------

def build(
    x,
    order: int,
    key: Optional[jax.Array] = None,
    batch_size: int = 256,
    medoid: bool = False,
    max_nodes: Optional[int] = None,
    backend: str = "auto",
) -> KTree:
    """Online batched construction (paper §1 semantics; ``batch_size=1`` is the
    exact sequential algorithm). Host loop: waves of route→accept→insert, then
    the split cascade, until the batch is fully inserted.

    ``x``: dense f[N, d] array, a :class:`repro.sparse.Csr` corpus, or a
    prebuilt backend. ``backend``: "auto" follows the input layout; "sparse"
    builds the paper's sparse-document tree (§2 — typically with
    ``medoid=True``) even from a dense input; "dense" densifies a sparse
    input. A prebuilt ``backend.RandomProjBackend`` passes through and builds
    the Random Indexing tree (DESIGN.md §5.1): every wave routes, appends,
    and splits in the projected space, so ``tree.dim`` is the projection's
    ``out_dim``. The pending set between waves is derived from the fetched
    ``accepted`` mask — no extra device→host sync per wave."""
    be = make_backend(x, backend)
    n = be.n_docs
    if key is None:
        key = jax.random.PRNGKey(0)
    if max_nodes is None:
        max_nodes = suggested_max_nodes(n, order)
    tree = ktree_init(max_nodes, order, be.dim, medoid=medoid, dtype=jnp.float32)

    for start in range(0, n, batch_size):
        idx = np.arange(start, min(start + batch_size, n))
        pad = batch_size - idx.size
        ids_np = np.concatenate([idx, np.full(pad, -1)]).astype(np.int32)
        rows = jnp.asarray(np.where(ids_np >= 0, ids_np, 0))
        doc_ids = jnp.asarray(ids_np)
        valid_np = ids_np >= 0
        while valid_np.any():
            levels = int(tree.depth) - 1
            tree, accepted = _insert_wave(
                tree, be, rows, doc_ids, jnp.asarray(valid_np),
                jnp.int32(levels), max_levels=_levels_bucket(levels),
            )
            valid_np &= ~np.asarray(accepted)
            tree, key = _split_all_overflowing(tree, key)
    return tree


def build_from_store(
    store,
    order: int,
    key: Optional[jax.Array] = None,
    batch_size: int = 256,
    medoid: bool = False,
    max_nodes: Optional[int] = None,
    prefetch: Optional[int] = None,
    projection=None,
    tuned=None,
    profiler=NULL_PROFILER,
) -> KTree:
    """Streaming out-of-core build: insert an on-disk corpus batch-by-batch
    (paper §1: "this tree structure allows for efficient disk based
    implementations where space requirements exceed that of main memory";
    DESIGN.md §9).

    ``store``: a ``repro.core.store.CorpusStore`` (dense or ELL blocks) or a
    ``StoreSlice``. Each batch's rows are fetched from disk through the
    store's LRU block cache and materialised as a *batch-sized* backend — at
    any moment the resident state is the tree arrays (centroids + structure),
    one batch of document vectors, and the store's bounded block cache. The
    K-tree's incremental insert is what makes this possible: leaves absorb
    each batch and the split cascade runs on resident tree pages only.

    Runs the exact wave/split schedule of :func:`build` (same batching, same
    PRNG consumption), so the resulting tree is **bit-identical** to an
    in-memory ``build(corpus, ...)`` over the same corpus and arguments —
    tests pin this for both block layouts.

    ``prefetch ≥ 1`` moves each batch's disk read onto an async
    ``store.Prefetcher`` reader thread of that depth, so the next batch's
    block fetch overlaps the current batch's insert waves; the fetched rows
    (and hence the tree) are identical to the synchronous path.

    ``projection`` (a ``backend.RandomProjection``, DESIGN.md §5.1) builds
    the Random Indexing tree instead: store blocks stream once through the
    fixed-chunk ``project_corpus`` pass (the sparse corpus is never
    materialised — only the small ``f32[N, out_dim]`` projected matrix stays
    resident, which is the RI premise) and the build runs entirely in the
    projected space. Bit-identical to ``build(RandomProjBackend.wrap(corpus,
    projection), ...)`` over the same corpus, by the shared fixed projection
    granularity.

    ``prefetch=None`` resolves through ``tuned=`` (a ``TunedKnobs`` from the
    store's ``TUNE.json`` sidecar, DESIGN.md §11) and then the repo default
    0 — explicit values win, and the knob never changes the tree.
    ``profiler=`` records one ``"read"`` span per batch fetch and one
    ``"insert"`` span per batch's insert waves."""
    from repro.core.backend import RandomProjBackend, backend_from_rows

    _, _, prefetch = resolve_knobs(tuned, prefetch=prefetch)
    if projection is not None:
        be = RandomProjBackend.from_store(store, projection, prefetch=prefetch)
        return build(
            be, order=order, key=key, batch_size=batch_size, medoid=medoid,
            max_nodes=max_nodes,
        )
    n = store.n_docs
    if key is None:
        key = jax.random.PRNGKey(0)
    if max_nodes is None:
        max_nodes = suggested_max_nodes(n, order)
    tree = ktree_init(max_nodes, order, store.dim, medoid=medoid, dtype=jnp.float32)

    batches = []
    for start in range(0, n, batch_size):
        idx = np.arange(start, min(start + batch_size, n))
        pad = batch_size - idx.size
        batches.append(np.concatenate([idx, np.full(pad, -1)]).astype(np.int32))

    def fetch(ids_np):
        # padding rows fetch corpus row 0, exactly like build's safe gather
        with profiler.span("read"):
            return store.take_rows(np.where(ids_np >= 0, ids_np, 0))

    import contextlib

    with contextlib.ExitStack() as stack:
        if prefetch:
            from repro.core.store import Prefetcher

            # registered on the stack so a failing insert wave (or an
            # interrupt) stops the reader thread instead of leaking it
            fetched = stack.enter_context(
                Prefetcher(batches, fetch, depth=prefetch)
            )
        else:
            fetched = ((ids_np, fetch(ids_np)) for ids_np in batches)
        for ids_np, got in fetched:
            be = backend_from_rows(store, got)
            rows = jnp.arange(batch_size, dtype=jnp.int32)
            doc_ids = jnp.asarray(ids_np)
            valid_np = ids_np >= 0
            with profiler.span("insert"):
                while valid_np.any():
                    levels = int(tree.depth) - 1
                    tree, accepted = _insert_wave(
                        tree, be, rows, doc_ids, jnp.asarray(valid_np),
                        jnp.int32(levels), max_levels=_levels_bucket(levels),
                    )
                    valid_np &= ~np.asarray(accepted)
                    tree, key = _split_all_overflowing(tree, key)
    return tree


def insert(
    tree: KTree, x, doc_ids, key: Optional[jax.Array] = None
) -> KTree:
    """Incremental insertion into an existing tree (paper §5: "clusters can be
    produced incrementally ... easy updates as new documents arrive").

    ``x``: the new documents (dense array, Csr, or backend); ``doc_ids``: their
    global ids (−1 = padding)."""
    if key is None:
        key = jax.random.PRNGKey(1)
    # the waves and splits donate their tree: work on a copy, the caller's
    # tree stays valid
    tree = jax.tree_util.tree_map(jnp.copy, tree)
    be = make_backend(x)
    doc_ids = jnp.asarray(doc_ids, jnp.int32)
    rows = jnp.arange(be.n_docs, dtype=jnp.int32)
    valid_np = np.asarray(doc_ids) >= 0
    while valid_np.any():
        levels = int(tree.depth) - 1
        tree, accepted = _insert_wave(
            tree, be, rows, doc_ids, jnp.asarray(valid_np),
            jnp.int32(levels), max_levels=_levels_bucket(levels),
        )
        valid_np &= ~np.asarray(accepted)
        tree, key = _split_all_overflowing(tree, key)
    return tree


def insert_into_store(
    tree: KTree, store, x, key: Optional[jax.Array] = None, projection=None
) -> KTree:
    """Incremental insertion into a **store-backed** index (DESIGN.md §9):
    route the new documents into the tree *and* spill their vectors to the
    on-disk corpus, closing the out-of-core loop for ever-growing corpora
    (paper §5's incremental updates, without the corpus ever being resident).

    ``x`` (dense array / Csr / backend) is normalised once into the store's
    exact block layout (``backend.backend_for_store_layout`` — ELL rows re-laid
    at the store's ``nnz_max`` width), so the vectors the tree inserts and the
    vectors the store serves afterwards are bit-identical; the new documents
    take global ids ``[store.n_docs, store.n_docs + B)``. The tree insert runs
    first (a failure leaves the store untouched), then ``store.append`` fills
    the last block's padding tail, appends new block files, and atomically
    replaces the manifest — rotating ``manifest_hash``, so answer caches and
    ``save_index`` checkpoints keyed on the old token correctly invalidate.

    Returns the new tree; ``store`` (an open ``CorpusStore``) is mutated in
    place and immediately serves the grown corpus. Equivalence contract: the
    returned tree bit-matches ``insert`` of the same normalised rows into an
    in-memory shadow tree (property-tested for both layouts).

    ``projection`` (a ``backend.RandomProjection``, DESIGN.md §5.1): the
    store still appends the *original* normalised rows — the rescore
    representation — while the tree inserts their projection (the routing
    representation), keeping the RI index's two spaces in lockstep. The
    inserted projected rows bit-match
    ``RandomProjBackend.wrap(normalised_rows, projection)``'s, which is what
    the shadow-tree property test pins."""
    from repro.core.backend import RandomProjBackend, backend_for_store_layout

    be = backend_for_store_layout(store, x)
    n0 = store.n_docs
    doc_ids = np.arange(n0, n0 + be.n_docs, dtype=np.int32)
    ins = be if projection is None else RandomProjBackend.wrap(be, projection)
    tree = insert(tree, ins, doc_ids, key=key)
    store.append(be)
    return tree


# ---------------------------------------------------------------------------
# read APIs
# ---------------------------------------------------------------------------

def leaf_nodes(tree: KTree) -> np.ndarray:
    n = int(tree.n_nodes)
    is_leaf = np.asarray(tree.is_leaf[:n])
    ne = np.asarray(tree.n_entries[:n])
    return np.nonzero(np.logical_and(is_leaf, ne > 0))[0]


def extract_assignment(tree: KTree, n_docs: int) -> Tuple[np.ndarray, int]:
    """(cluster i32[n_docs], n_clusters) — cluster = compact id of the containing
    leaf (the paper's leaf-level clustering solution). Unseen docs get −1."""
    leaves = leaf_nodes(tree)
    child = np.asarray(tree.child)
    ne = np.asarray(tree.n_entries)
    out = np.full(n_docs, -1, np.int32)
    for ci, leaf in enumerate(leaves):
        docs = child[leaf, : ne[leaf]]
        out[docs] = ci
    return out, len(leaves)


def padded_chunk_rows(n: int, chunk: int):
    """Yield (rows_np, padded host row ids) slices covering [0, n): each
    chunk's ids padded (repeating the last row) to the next power-of-two
    bucket ≤ ``chunk`` — same bucketing trick as :func:`_levels_bucket`, so
    jitted consumers compile once per bucket instead of once per remainder
    size. Single source of truth for chunk slicing: the in-memory query path
    (:func:`chunked_query_rows`) and the store-backed path (DESIGN.md §9)
    both derive from it, which is what keeps their chunk shapes — and hence
    answers — bit-identical."""
    for s in range(0, n, chunk):
        rows_np = np.arange(s, min(s + chunk, n))
        pad = _levels_bucket(rows_np.size) - rows_np.size
        yield rows_np, np.concatenate([rows_np, np.full(pad, rows_np[-1])])


def chunked_query_rows(n: int, chunk: int):
    """Yield (rows_np, rows_dev i32) slices covering [0, n) for batched query
    consumers — :func:`padded_chunk_rows` with the padded ids placed on
    device."""
    for rows_np, padded in padded_chunk_rows(n, chunk):
        yield rows_np, jnp.asarray(padded.astype(np.int32))


def assign_via_tree(tree: KTree, x, chunk: int = 1024) -> np.ndarray:
    """Cluster new vectors by NN search to the leaf level (sampled K-tree path,
    paper §3: tree built on a sample classifies the full corpus). ``x`` may be
    dense, a Csr corpus, or a backend."""
    be = make_backend(x)
    leaves = leaf_nodes(tree)
    remap = np.full(tree.max_nodes, -1, np.int32)
    remap[leaves] = np.arange(leaves.size, dtype=np.int32)
    levels = int(tree.depth) - 1
    max_levels = _levels_bucket(levels)
    outs = []
    for rows_np, rows in chunked_query_rows(be.n_docs, chunk):
        leaf_ids, _, _ = _route_jit(
            tree, be, rows, jnp.int32(levels), max_levels=max_levels
        )
        outs.append(remap[np.asarray(leaf_ids)][: rows_np.size])
    return np.concatenate(outs)


def nn_search(tree: KTree, q) -> Tuple[np.ndarray, np.ndarray]:
    """Approximate NN doc ids for queries (the search-tree application).
    ``q`` may be dense vectors, a Csr matrix, or a backend.

    Thin ``beam=1, k=1`` wrapper over the query engine
    (:func:`repro.core.query.topk_search`) — use that directly for top-k
    results or wider beams. The pre-engine greedy descent is kept as
    :func:`nn_search_greedy` (golden baseline for the equivalence tests)."""
    from repro.core.query import topk_search

    doc, dist = topk_search(tree, q, k=1, beam=1)
    return doc[:, 0], dist[:, 0]


def nn_search_greedy(tree: KTree, q) -> Tuple[np.ndarray, np.ndarray]:
    """The original greedy single-path descent (1-NN): route to one leaf, then
    exact NN among that leaf's vectors. ``topk_search(beam=1, k=1)`` must
    reproduce this exactly; tests pin the equivalence."""
    be = make_backend(q)
    levels = int(tree.depth) - 1
    rows = jnp.arange(be.n_docs, dtype=jnp.int32)
    leaf_ids, _, _ = _route_jit(
        tree, be, rows, jnp.int32(levels), max_levels=_levels_bucket(levels)
    )
    doc, dist = _nearest_in_leaf_backend(tree, leaf_ids, be, rows)
    return np.asarray(doc), np.asarray(dist)


def level_centers(tree: KTree, level: int) -> np.ndarray:
    """Centres at a given level below the root (0 = root entries) — "a smaller
    number of clusters higher in the tree" (paper §4) and the §5 browsing API."""
    n = int(tree.n_nodes)
    nodes = [int(tree.root)]
    for _ in range(level):
        nxt = []
        child = np.asarray(tree.child[:n])
        ne = np.asarray(tree.n_entries[:n])
        leaf = np.asarray(tree.is_leaf[:n])
        for nd in nodes:
            if leaf[nd]:
                continue
            nxt.extend(child[nd, : ne[nd]].tolist())
        nodes = nxt
    cs, ne_all = np.asarray(tree.centers[:n]), np.asarray(tree.n_entries[:n])
    return np.concatenate([cs[nd, : ne_all[nd]] for nd in nodes], axis=0)


def check_invariants(tree: KTree, n_docs: Optional[int] = None, rtol: float = 1e-3):
    """Structural invariants (tests + post-build validation):
    1. every allocated node obeys 1 ≤ n_entries ≤ m (an internal root ≥ 2),
    2. leaves all sit at height 0 and the tree is height-balanced,
    3. parent/child pointers are mutually consistent (incl. root parent −1,
       root height == depth−1, is_leaf ⇔ height 0, child ids allocated),
    4. internal entry count == total weight of the child's entries,
    5. dense mode: internal entry centre ≈ weighted mean of child entries,
    6. every allocated node is reachable from the root and slots past
       n_entries are cleared (child −1, zero weight),
    7. every inserted doc appears in exactly one leaf slot, with in-range id.
    Raises AssertionError on violation."""
    n = int(tree.n_nodes)
    ne = np.asarray(tree.n_entries[:n])
    child = np.asarray(tree.child[:n])
    counts = np.asarray(tree.counts[:n])
    centers = np.asarray(tree.centers[:n])
    is_leaf = np.asarray(tree.is_leaf[:n])
    parent = np.asarray(tree.parent[:n])
    parent_slot = np.asarray(tree.parent_slot[:n])
    height = np.asarray(tree.height[:n])
    root = int(tree.root)

    assert parent[root] == -1 and parent_slot[root] == -1, "root has a parent"
    assert height[root] == int(tree.depth) - 1, (
        f"root height {height[root]} != depth-1 ({int(tree.depth) - 1})"
    )
    reachable = set()
    stack = [root]
    while stack:
        nd = stack.pop()
        reachable.add(nd)
        if not is_leaf[nd]:
            stack.extend(int(c) for c in child[nd, : ne[nd]])
    assert reachable == set(range(n)), (
        f"allocated nodes unreachable from root: {sorted(set(range(n)) - reachable)}"
    )
    for nd in sorted(reachable):
        assert 1 <= ne[nd] <= tree.order, f"node {nd}: {ne[nd]} entries (m={tree.order})"
        assert is_leaf[nd] == (height[nd] == 0), f"is_leaf/height mismatch at {nd}"
        assert (child[nd, ne[nd]:] == -1).all(), f"stale child ids past n_entries at {nd}"
        assert (counts[nd, ne[nd]:] == 0).all(), f"stale weights past n_entries at {nd}"
        if is_leaf[nd]:
            assert (counts[nd, : ne[nd]] == 1).all(), f"leaf {nd} entry weight != 1"
        if not is_leaf[nd]:
            if nd == root:
                assert ne[nd] >= 2, f"internal root has {ne[nd]} < 2 entries"
            for s in range(ne[nd]):
                c = int(child[nd, s])
                assert 0 <= c < n, f"child id {c} of {nd} not allocated"
                assert parent[c] == nd and parent_slot[c] == s, f"bad pointer {nd}->{c}"
                assert height[c] == height[nd] - 1, "height mismatch"
                if not tree.medoid:
                    # medoid centres/counts are frozen at split time (paper §2)
                    assert abs(counts[nd, s] - counts[c, : ne[c]].sum()) <= max(
                        rtol * counts[nd, s], 1e-2
                    ), f"count mismatch at {nd}:{s}"
                    w = counts[c, : ne[c]]
                    mean = (centers[c, : ne[c]] * w[:, None]).sum(0) / max(w.sum(), 1e-12)
                    err = np.abs(centers[nd, s] - mean).max()
                    scale = max(np.abs(mean).max(), 1e-3)
                    assert err <= max(rtol * scale, 1e-3), f"mean mismatch {nd}:{s} err={err}"
    leaf_heights = {height[nd] for nd in reachable if is_leaf[nd]}
    assert leaf_heights == {0}, f"unbalanced leaves: {leaf_heights}"
    if n_docs is not None:
        seen = np.zeros(n_docs, np.int32)
        for nd in reachable:
            if is_leaf[nd]:
                docs = child[nd, : ne[nd]]
                assert ((docs >= 0) & (docs < n_docs)).all(), (
                    f"leaf {nd} holds out-of-range doc ids {docs}"
                )
                np.add.at(seen, docs, 1)
        assert (seen == 1).all(), f"doc conservation broken: {np.unique(seen)}"
