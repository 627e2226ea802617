"""k-means variants used by the K-tree system and the CLUTO-style baselines.

- :func:`kmeans`              — Lloyd to convergence (what K-tree runs on node
                                splits; paper §4: "K-tree runs k-means to
                                convergence using dense vectors").
- :func:`kmeans_fixed_iters`  — fixed-iteration variant ("CLUTO stops after a
                                specified number of iterations").
- :func:`bisecting_kmeans`    — CLUTO's repeated-bisecting baseline.
- :func:`minibatch_kmeans`    — web-scale variant used by the bulk tree builder.

Everything is weighted (weights = subtree sizes when clustering tree entries)
and mask-aware (invalid rows carry weight 0), so the same jitted code serves
full-corpus clustering and the K-tree's tiny node splits via vmap.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import EXACT


# ---------------------------------------------------------------------------
# distances + assignment (the hot path — Pallas kernel behind a flag)
# ---------------------------------------------------------------------------

def pairwise_sqdist(
    x: jax.Array,
    centers: jax.Array,
    x_sq: Optional[jax.Array] = None,
    c_sq: Optional[jax.Array] = None,
) -> jax.Array:
    """‖x−c‖² = ‖x‖² − 2·x·cᵀ + ‖c‖² — [B,K]. The matmul is the MXU hot spot."""
    if x_sq is None:
        x_sq = jnp.einsum("nd,nd->n", x, x, precision=EXACT)
    if c_sq is None:
        c_sq = jnp.einsum("kd,kd->k", centers, centers, precision=EXACT)
    cross = jnp.matmul(x, centers.T, precision=EXACT)
    return jnp.maximum(x_sq[:, None] - 2.0 * cross + c_sq[None, :], 0.0)


def assign(
    x: jax.Array,
    centers: jax.Array,
    valid: Optional[jax.Array] = None,
    use_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Nearest-centre assignment: (idx i32[B], sqdist f32[B]).

    ``valid``: bool[K] — masked centres are never chosen. ``use_kernel``
    dispatches to the Pallas ``nn_assign`` kernel (TPU; interpret-mode on CPU).
    """
    if use_kernel:
        from repro.kernels.ops import nn_assign

        return nn_assign(x, centers, valid=valid)
    d = pairwise_sqdist(x, centers)
    if valid is not None:
        d = jnp.where(valid[None, :], d, jnp.inf)
    idx = jnp.argmin(d, axis=1).astype(jnp.int32)
    return idx, jnp.take_along_axis(d, idx[:, None], axis=1)[:, 0]


# ---------------------------------------------------------------------------
# Lloyd iterations
# ---------------------------------------------------------------------------

class KMeansResult(NamedTuple):
    centers: jax.Array    # f32[k, d]
    assign: jax.Array     # i32[n]
    counts: jax.Array     # f32[k] (weighted)
    sse: jax.Array        # f32[] weighted sum of squared distances
    iters: jax.Array      # i32[]


def _centroid_update(
    x: jax.Array, idx: jax.Array, w: jax.Array, k: int, via: str = "matmul"
) -> Tuple[jax.Array, jax.Array]:
    """(sums f32[k,d], counts f32[k]). ``matmul`` = one-hot einsum (MXU-friendly,
    what the TPU path uses); ``segment`` = segment_sum scatter."""
    if via == "matmul":
        onehot = jax.nn.one_hot(idx, k, dtype=x.dtype) * w[:, None]   # [n,k]
        sums = jnp.einsum("nk,nd->kd", onehot, x, precision=EXACT)
        counts = onehot.sum(axis=0)
    else:
        sums = jax.ops.segment_sum(x * w[:, None], idx, num_segments=k)
        counts = jax.ops.segment_sum(w, idx, num_segments=k)
    return sums, counts


def lloyd_step(
    x: jax.Array,
    centers: jax.Array,
    w: Optional[jax.Array] = None,
    update_via: str = "matmul",
    use_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One weighted Lloyd step → (new_centers, idx, counts, sse).
    Empty clusters keep their previous centre (standard)."""
    k = centers.shape[0]
    if w is None:
        w = jnp.ones(x.shape[0], x.dtype)
    idx, dist = assign(x, centers, use_kernel=use_kernel)
    sums, counts = _centroid_update(x, idx, w, k, via=update_via)
    new_centers = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-12), centers)
    sse = jnp.sum(w * dist)
    return new_centers, idx, counts, sse


def kmeans_pp_init(key: jax.Array, x: jax.Array, k: int, w: Optional[jax.Array] = None) -> jax.Array:
    """k-means++ seeding (weighted). O(k) sequential rounds, each a matvec."""
    n = x.shape[0]
    if w is None:
        w = jnp.ones(n, x.dtype)
    key0, key = jax.random.split(key)
    first = jax.random.categorical(key0, jnp.log(jnp.maximum(w, 1e-30)))
    centers0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    mind0 = jnp.sum((x - x[first]) ** 2, axis=1)

    def body(i, carry):
        centers, mind, key = carry
        key, sub = jax.random.split(key)
        logits = jnp.log(jnp.maximum(mind * w, 1e-30))
        nxt = jax.random.categorical(sub, logits)
        c = x[nxt]
        centers = centers.at[i].set(c)
        mind = jnp.minimum(mind, jnp.sum((x - c) ** 2, axis=1))
        return centers, mind, key

    centers, _, _ = jax.lax.fori_loop(1, k, body, (centers0, mind0, key))
    return centers


def _kmeans_single(
    key: jax.Array,
    x: jax.Array,
    k: int,
    w: jax.Array,
    max_iters: int,
    tol: float,
    init: str,
    init_centers: Optional[jax.Array],
    update_via: str,
    use_kernel: bool,
) -> KMeansResult:
    """One Lloyd-to-convergence run from one seeding (see :func:`kmeans`)."""
    n = x.shape[0]
    if init_centers is not None:
        centers = init_centers
    elif init == "kmeanspp":
        centers = kmeans_pp_init(key, x, k, w)
    else:  # random rows
        sel = jax.random.choice(key, n, (k,), replace=False, p=w / w.sum())
        centers = x[sel]

    def cond(state):
        _, _, _, _, i, done = state
        return jnp.logical_and(i < max_iters, jnp.logical_not(done))

    def body(state):
        centers, idx_old, counts, sse_old, i, _ = state
        centers_new, idx, counts, sse = lloyd_step(
            x, centers, w, update_via=update_via, use_kernel=use_kernel
        )
        done = jnp.all(idx == idx_old)
        if tol > 0.0:
            done = jnp.logical_or(done, jnp.abs(sse_old - sse) <= tol * jnp.maximum(sse_old, 1e-30))
        return centers_new, idx, counts, sse, i + 1, done

    idx0 = jnp.full((n,), -1, jnp.int32)
    state = (centers, idx0, jnp.zeros((k,), x.dtype), jnp.inf, jnp.int32(0), jnp.bool_(False))
    centers, idx, counts, sse, iters, _ = jax.lax.while_loop(cond, body, state)
    # final consistent assignment against the converged centres
    idx, dist = assign(x, centers, use_kernel=use_kernel)
    sums, counts = _centroid_update(x, idx, w, k, via=update_via)
    centers = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-12), centers)
    return KMeansResult(centers, idx, counts, jnp.sum(w * dist), iters)


@functools.partial(
    jax.jit,
    static_argnames=("k", "max_iters", "update_via", "use_kernel", "init", "n_init"),
)
def kmeans(
    key: jax.Array,
    x: jax.Array,
    k: int,
    w: Optional[jax.Array] = None,
    max_iters: int = 300,
    tol: float = 0.0,
    init: str = "kmeanspp",
    init_centers: Optional[jax.Array] = None,
    update_via: str = "matmul",
    use_kernel: bool = False,
    n_init: int = 4,
) -> KMeansResult:
    """Weighted Lloyd **to convergence** (assignments fixed-point) — the k-means
    the paper runs inside K-tree. ``tol=0`` means exact assignment convergence;
    ``max_iters`` is a safety cap.

    ``n_init`` independent seedings run and the lowest-SSE solution wins
    (standard Lloyd restarts — k-means++ alone still lands in local optima on
    a bad draw). Explicit ``init_centers`` forces a single run."""
    if w is None:
        w = jnp.ones(x.shape[0], x.dtype)
    if init_centers is not None or n_init <= 1:
        return _kmeans_single(
            key, x, k, w, max_iters, tol, init, init_centers, update_via, use_kernel
        )
    runs = [
        _kmeans_single(kk, x, k, w, max_iters, tol, init, None, update_via, use_kernel)
        for kk in jax.random.split(key, n_init)
    ]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *runs)
    best = jnp.argmin(stacked.sse)
    return jax.tree.map(lambda a: a[best], stacked)


@functools.partial(jax.jit, static_argnames=("k", "iters", "update_via", "use_kernel"))
def kmeans_fixed_iters(
    key: jax.Array,
    x: jax.Array,
    k: int,
    iters: int = 10,
    w: Optional[jax.Array] = None,
    update_via: str = "matmul",
    use_kernel: bool = False,
) -> KMeansResult:
    """CLUTO-style: stop after ``iters`` Lloyd iterations (paper §4)."""
    n = x.shape[0]
    if w is None:
        w = jnp.ones(n, x.dtype)
    centers = kmeans_pp_init(key, x, k, w)

    def body(_, centers):
        c, _, _, _ = lloyd_step(x, centers, w, update_via=update_via, use_kernel=use_kernel)
        return c

    centers = jax.lax.fori_loop(0, iters, body, centers)
    idx, dist = assign(x, centers, use_kernel=use_kernel)
    _, counts = _centroid_update(x, idx, w, k, via=update_via)
    return KMeansResult(centers, idx, counts, jnp.sum(w * dist), jnp.int32(iters))


def bisecting_kmeans(
    key: jax.Array,
    x: jax.Array,
    k: int,
    w: Optional[jax.Array] = None,
    inner_iters: int = 20,
    use_kernel: bool = False,
) -> KMeansResult:
    """Repeated bisecting k-means (CLUTO ``rbr``-style): repeatedly 2-means-split
    the cluster with the largest weighted SSE until k clusters exist.

    Host loop over k−1 splits; each split is a *masked* jitted 2-means over the
    full array (weights zeroed outside the target cluster) so shapes stay
    static — no dynamic gathers.
    """
    n = x.shape[0]
    if w is None:
        w = jnp.ones(n, x.dtype)
    assign_full = jnp.zeros(n, jnp.int32)
    centers = jnp.zeros((k, x.shape[1]), x.dtype)
    centers = centers.at[0].set((x * w[:, None]).sum(0) / jnp.maximum(w.sum(), 1e-12))

    @functools.partial(jax.jit, static_argnames=())
    def split(key, assign_full, centers, target, n_current):
        mask = (assign_full == target).astype(x.dtype) * w
        # n_init=1: this is the CLUTO-style baseline the paper benchmarks
        # against — keep its per-split cost at one Lloyd run, not best-of-N
        res = kmeans(key, x, 2, w=mask, max_iters=inner_iters, init="kmeanspp",
                     use_kernel=use_kernel, n_init=1)
        sel = jnp.logical_and(assign_full == target, res.assign == 1)
        assign_full = jnp.where(sel, n_current, assign_full)
        centers = centers.at[target].set(res.centers[0]).at[n_current].set(res.centers[1])
        return assign_full, centers

    @jax.jit
    def cluster_sse(assign_full, centers):
        d = pairwise_sqdist(x, centers)
        dist = jnp.take_along_axis(d, assign_full[:, None], axis=1)[:, 0]
        return jax.ops.segment_sum(dist * w, assign_full, num_segments=k)

    for n_current in range(1, k):
        sse = cluster_sse(assign_full, centers)
        target = int(jnp.argmax(sse[:n_current]))
        key, sub = jax.random.split(key)
        assign_full, centers = split(sub, assign_full, centers, target, n_current)

    idx, dist = assign(x, centers)  # final refit assignment (CLUTO refines too)
    counts = jax.ops.segment_sum(w, assign_full, num_segments=k)
    return KMeansResult(centers, assign_full, counts, jnp.sum(w * dist), jnp.int32(k - 1))


def minibatch_kmeans(
    key: jax.Array,
    x: jax.Array,
    k: int,
    batch: int = 4096,
    steps: int = 200,
    use_kernel: bool = False,
) -> KMeansResult:
    """Sculley-style mini-batch k-means — the bulk tree builder's workhorse at
    corpus scale (per-centre 1/count learning rates)."""
    n = x.shape[0]
    key, sub = jax.random.split(key)
    sel = jax.random.choice(sub, n, (k,), replace=False)
    centers0 = x[sel]

    @jax.jit
    def step(carry, key):
        centers, counts = carry
        bidx = jax.random.randint(key, (batch,), 0, n)
        xb = x[bidx]
        idx, _ = assign(xb, centers, use_kernel=use_kernel)
        sums, bc = _centroid_update(xb, idx, jnp.ones(batch, x.dtype), k)
        counts_new = counts + bc
        lr = bc / jnp.maximum(counts_new, 1.0)
        means_b = sums / jnp.maximum(bc, 1e-12)[:, None]
        centers = jnp.where(bc[:, None] > 0, centers + lr[:, None] * (means_b - centers), centers)
        return (centers, counts_new), None

    keys = jax.random.split(key, steps)
    (centers, counts), _ = jax.lax.scan(step, (centers0, jnp.zeros(k, x.dtype)), keys)
    idx, dist = assign(x, centers, use_kernel=use_kernel)
    return KMeansResult(centers, idx, counts, jnp.sum(dist), jnp.int32(steps))
