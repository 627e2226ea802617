"""The plain reference: what a K-tree and a beam search over it must give,
computed in float64 with NumPy from the benchmark's own corpus rows.

It imports nothing of the program. From the program it takes only the shape
of the tree it built (which node holds which entries), never a vector the
program computed: internal centres are recomputed here, as the mean of the
documents under an entry (mean K-tree) or as the document an entry names
(medoid K-tree, identified as the subtree document nearest to the entry).

The route and the score are apart. The tree routes in a space of its own
(``Identity``: the rows themselves, for dense and ELL configurations;
``Projected``: ``x·P`` in float64, for a Random Indexing one), and answers
are scored on the original rows. Build checks (``RefTree.misplaced``,
``leaf_check``, ``centre_checks``) hold the program's tree to the K-tree's
guarantees in the route space: every document in exactly one leaf, all
leaves at one depth, each leaf entry the document's own route row, each
internal entry the mean (and count) of its subtree, or one of its subtree's
documents. Serve checks (``check_answers``) hold served answers to the
float64 distances of the original rows they name, and to the reference's own
beam search over the same tree: a descent in the route space, then the exact
original-space distances of the documents in the final beam's leaves.
"""
from __future__ import annotations

import dataclasses

import numpy as np

TIE_RTOL = 1e-5
"""Two distances closer than this share of ‖q‖² + ‖x‖² tie: the reference
takes either order (f32 rounding of a squared distance is ~1e-7 of it)."""


class Identity:
    """The route space of a tree built on the rows themselves."""
    leaf_name = "leaf_gap"  # a leaf entry is a copy of its row: exact

    def __init__(self, rows64):
        self.rows = rows64

    @staticmethod
    def of(x64: np.ndarray) -> np.ndarray:
        return x64


class Projected:
    """The route space of a Random Indexing tree: ``x ↦ x·P`` in float64,
    with the route rows of the whole corpus computed once from its sparse
    rows (never densified whole)."""
    leaf_name = "route_gap"  # the program projects in float32: not a copy

    def __init__(self, p32: np.ndarray, csr):
        """``p32``: P, ``f32[n_cols, dim]``; ``csr``: the corpus rows
        (``data``, ``indices``, ``indptr``)."""
        import scipy.sparse

        self.p64 = p32.astype(np.float64)
        m = scipy.sparse.csr_matrix(
            (csr.data.astype(np.float64), csr.indices, csr.indptr),
            shape=(csr.indptr.size - 1, self.p64.shape[0]))
        self.z64 = np.asarray(m @ self.p64)

    def rows(self, ids) -> np.ndarray:
        return self.z64[np.asarray(ids, np.int64)]

    def of(self, x64: np.ndarray) -> np.ndarray:
        return x64 @ self.p64


@dataclasses.dataclass
class HostTree:
    """The program's tree as host arrays (``KTree`` field for field)."""
    centers: np.ndarray    # f32[N, m+1, d]
    counts: np.ndarray     # f32[N, m+1]
    child: np.ndarray      # i32[N, m+1]
    n_entries: np.ndarray  # i32[N]
    is_leaf: np.ndarray    # bool[N]
    root: int
    n_nodes: int
    depth: int
    medoid: bool

    @classmethod
    def from_device(cls, tree, dim: int) -> "HostTree":
        """The tree's arrays on the host; ``centers`` cut to the ``dim``
        features of the route space, whatever the program pads it to."""
        return cls(
            centers=np.asarray(tree.centers)[:, :, :dim], counts=np.asarray(tree.counts),
            child=np.asarray(tree.child), n_entries=np.asarray(tree.n_entries),
            is_leaf=np.asarray(tree.is_leaf), root=int(tree.root),
            n_nodes=int(tree.n_nodes), depth=int(tree.depth),
            medoid=bool(tree.medoid),
        )


class RefTree:
    """The reference's view of a built tree: its walk from the root, each
    node's documents, and float64 centres of every internal entry, in the
    route space."""

    def __init__(self, t: HostTree, rows64, route=None):
        """``rows64(ids) -> f64[len(ids), d]``: the benchmark's corpus rows;
        ``route``: the space the tree routes in (default ``Identity``)."""
        self.t = t
        self.rows64 = rows64
        self.route = route or Identity(rows64)
        self.faults = 0           # structural faults found on the walk
        self.node_docs: dict[int, np.ndarray] = {}
        self.leaf_of: dict[int, int] = {}
        self.dup_docs = 0
        self._walk()

    def _entries(self, node: int) -> np.ndarray:
        return self.t.child[node, : self.t.n_entries[node]]

    def _walk(self) -> None:
        t = self.t
        seen = set()
        order = []                # nodes in pre-order
        stack = [(t.root, 1)]
        while stack:
            node, level = stack.pop()
            if node < 0 or node >= t.n_nodes or node in seen:
                self.faults += 1
                continue
            seen.add(node)
            order.append(node)
            if not 1 <= t.n_entries[node] <= t.child.shape[1] - 1:
                self.faults += 1  # an order-m node holds 1..m entries
            if t.is_leaf[node]:
                if level != t.depth:
                    self.faults += 1  # height balance: every leaf at the bottom
                continue
            stack.extend((int(c), level + 1) for c in self._entries(node))
        for node in reversed(order):
            if t.is_leaf[node]:
                docs = self._entries(node).astype(np.int64)
                for d in docs:
                    if d in self.leaf_of:
                        self.dup_docs += 1
                    self.leaf_of[int(d)] = node
            else:
                parts = [self.node_docs.get(int(c), np.zeros(0, np.int64))
                         for c in self._entries(node)]
                docs = np.concatenate(parts) if parts else np.zeros(0, np.int64)
            self.node_docs[node] = docs
        self.order = order

    def misplaced(self, n_docs: int) -> int:
        """Documents not held by exactly one leaf, ids out of range, and
        structural faults."""
        ids = np.fromiter(self.leaf_of.keys(), np.int64, len(self.leaf_of))
        bad_ids = int(((ids < 0) | (ids >= n_docs)).sum())
        missing = n_docs - int(((ids >= 0) & (ids < n_docs)).sum())
        return missing + bad_ids + self.dup_docs + self.faults

    def leaf_check(self) -> dict:
        """``{route.leaf_name: leaf_gap()}``: ``leaf_gap`` where a leaf entry
        copies its row, ``route_gap`` where the program computed it."""
        return {self.route.leaf_name: self.leaf_gap()}

    def leaf_gap(self) -> float:
        """Largest |leaf entry − the route row of the document it names|, as
        a share of that row's largest element. A copy is exact: 0."""
        worst = 0.0
        for node in self.order:
            if not self.t.is_leaf[node]:
                continue
            docs = self._entries(node)
            ok = (docs >= 0)
            if not ok.any():
                continue
            x = self.route.rows(docs[ok])
            c = self.t.centers[node, : docs.size][ok].astype(np.float64)
            scale = np.abs(x).max(axis=1)
            worst = max(worst, float((np.abs(c - x).max(axis=1) / scale).max()))
        return worst

    def internal_entries(self):
        """(node, slot, child, docs under the child) of every internal entry."""
        for node in self.order:
            if self.t.is_leaf[node]:
                continue
            for s, c in enumerate(self._entries(node)):
                yield node, s, int(c), self.node_docs.get(int(c), np.zeros(0, np.int64))

    def centre_checks(self) -> dict:
        """Mean tree: the largest gap of an internal centre to the float64
        mean of its subtree (as a share of that mean's largest element), and
        of its count to the subtree's size. Medoid tree: the largest gap of
        an internal centre to the nearest document of its subtree (as a share
        of that row's largest element). Also fills ``self.ref_centres``."""
        self.ref_centres: dict[tuple[int, int], np.ndarray] = {}
        if self.t.medoid:
            worst = 0.0
            for node, s, _, docs in self.internal_entries():
                c = self.t.centers[node, s].astype(np.float64)
                best, best_row = np.inf, None
                for lo in range(0, docs.size, 2048):
                    x = self.route.rows(docs[lo: lo + 2048])
                    gap = np.abs(x - c).max(axis=1) / np.maximum(np.abs(x).max(axis=1), 1e-300)
                    i = int(gap.argmin())
                    if gap[i] < best:
                        best, best_row = float(gap[i]), x[i]
                worst = max(worst, best)
                self.ref_centres[(node, s)] = best_row
            return {"medoid_gap": worst}
        sums: dict[int, np.ndarray] = {}
        for node in reversed(self.order):
            if self.t.is_leaf[node]:
                docs = self._entries(node)
                sums[node] = self.route.rows(docs[docs >= 0]).sum(axis=0)
            else:
                sums[node] = sum(sums.get(int(c), 0.0) for c in self._entries(node))
        mean_gap, count_gap = 0.0, 0.0
        for node, s, c, docs in self.internal_entries():
            mean = sums.get(c, 0.0) / max(docs.size, 1)
            self.ref_centres[(node, s)] = mean
            got = self.t.centers[node, s].astype(np.float64)
            mean_gap = max(mean_gap, float(np.abs(got - mean).max() / np.abs(mean).max()))
            count_gap = max(count_gap, abs(float(self.t.counts[node, s]) - docs.size))
        return {"mean_gap": mean_gap, "count_gap": count_gap}

    def beam_search(self, q64: np.ndarray, k: int, beam: int):
        """Reference top-k: beam descent over the reference centres in the
        route space, then the exact float64 distances of the original rows of
        the documents in the final beam's leaves (brute force restricted to
        that pool). Returns (doc ids [k], distances [k]) ascending."""
        t = self.t
        qr = self.route.of(q64)
        frontier = [t.root]
        for _ in range(t.depth - 1):
            cands = []
            for node in frontier:
                for s, c in enumerate(self._entries(node)):
                    d = float(((self.ref_centres[(node, s)] - qr) ** 2).sum())
                    cands.append((d, int(c)))
            cands.sort(key=lambda dc: dc[0])
            frontier = [c for _, c in cands[:beam]]
        docs = np.concatenate([self._entries(n) for n in frontier]).astype(np.int64)
        d = ((self.rows64(docs) - q64) ** 2).sum(axis=1)
        o = np.argsort(d, kind="stable")[:k]
        return docs[o], d[o]


def check_answers(ref: RefTree, q64: np.ndarray, docs: np.ndarray, dist: np.ndarray,
                  k: int, beam: int) -> dict:
    """Served answers (``docs``, ``dist`` [n, k]) to queries ``q64`` [n, d]
    against float64: ``dist_gap``, the largest |served − float64 distance|
    of a named document as a share of ‖q‖² + ‖x‖²; ``answers_differ``, the
    share of answers whose documents differ from the reference search's
    beyond ties within ``TIE_RTOL``, or that name fewer than k documents."""
    if not hasattr(ref, "ref_centres"):
        ref.centre_checks()
    n = len(q64)
    dist_gap, differ = 0.0, 0
    for i in range(n):
        q = q64[i]
        q_sq = float(q @ q)
        got = docs[i]
        if (got < 0).any() or not np.isfinite(dist[i]).all():
            differ += 1
            continue
        x = ref.rows64(got)
        d64 = ((x - q) ** 2).sum(axis=1)
        scale = q_sq + (x ** 2).sum(axis=1)
        dist_gap = max(dist_gap, float((np.abs(dist[i].astype(np.float64) - d64) / scale).max()))
        want, want_d = ref.beam_search(q, k, beam)
        tol = TIE_RTOL * (3 * q_sq + 2 * np.maximum(d64, want_d))
        same = (got == want) | (np.abs(d64 - want_d) <= tol)
        if len(want) != len(got) or not same.all():
            differ += 1
    return {"dist_gap": dist_gap, "answers_differ": differ / max(n, 1)}
