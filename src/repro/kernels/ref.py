"""Pure-jnp oracles for every Pallas kernel (the correctness references the
per-kernel tests sweep against)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

EXACT = jax.lax.Precision.HIGHEST
"""Matmul precision of every distance computation in the repo. On a TPU the
default f32 matmul is one bf16 pass (~3 significant digits), which would
make returned distances, centre means and routing differ from the f32
semantics the paper and the reference implementations define; ``HIGHEST``
keeps f32 accuracy on the MXU. CPU backends compute f32 either way."""


def _full_sqdist(
    x: jax.Array, centers: jax.Array, valid: Optional[jax.Array] = None
) -> jax.Array:
    """Clamped squared distances f32[B, K] (masked centres → +inf) — the shared
    distance matrix behind ``nn_assign_ref`` and ``nn_topk_ref`` (so their
    argmin / top-1 agree bit-for-bit)."""
    x32 = x.astype(jnp.float32)
    c32 = centers.astype(jnp.float32)
    d = (
        jnp.einsum("bd,bd->b", x32, x32, precision=EXACT)[:, None]
        - 2.0 * jnp.matmul(x32, c32.T, precision=EXACT)
        + jnp.einsum("kd,kd->k", c32, c32, precision=EXACT)[None, :]
    )
    d = jnp.maximum(d, 0.0)
    if valid is not None:
        d = jnp.where(valid[None, :], d, jnp.inf)
    return d


def nn_assign_ref(
    x: jax.Array, centers: jax.Array, valid: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array]:
    """(argmin idx i32[B], sqdist f32[B]) against every centre row."""
    d = _full_sqdist(x, centers, valid)
    idx = jnp.argmin(d, axis=1).astype(jnp.int32)
    return idx, jnp.take_along_axis(d, idx[:, None], axis=1)[:, 0]


def topk_from_dist(dist: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """(idx i32[B,k], dist f32[B,k]) — k smallest per row, ascending, ties by
    lower column (``lax.top_k`` stability). Rows with fewer than k finite
    entries pad with (−1, +inf); ``k`` may exceed the column count."""
    b, n = dist.shape
    if k > n:
        dist = jnp.pad(dist, ((0, 0), (0, k - n)), constant_values=jnp.inf)
    neg, idx = jax.lax.top_k(-dist, k)
    d = -neg
    idx = jnp.where(jnp.isfinite(d), idx.astype(jnp.int32), -1)
    return idx, d


def nn_topk_ref(
    x: jax.Array, centers: jax.Array, k: int, valid: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array]:
    """(idx i32[B,k], sqdist f32[B,k]) — the k nearest centres per query,
    ascending; oracle for the ``nn_topk`` Pallas kernel."""
    return topk_from_dist(_full_sqdist(x, centers, valid), k)


def topk_merge_ref(
    ids: jax.Array, dist: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Merge per-shard top-k candidate lists into one exact global top-k.

    ``ids`` i32[B, S, k_s], ``dist`` f32[B, S, k_s] — each shard's ascending
    (id, dist) list over a *disjoint* id subset, padded with (−1, +inf).
    Returns (ids i32[B, k], dist f32[B, k]) ascending — the top-k of the union,
    by composing :func:`topk_from_dist` over the flattened S·k_s candidates.
    Exact ties across shards resolve in shard-major order (the serving merge's
    documented tie rule)."""
    b = ids.shape[0]
    ids_f = ids.reshape(b, -1)
    dist_f = dist.reshape(b, -1)
    pos, d = topk_from_dist(dist_f, k)
    out = jnp.take_along_axis(ids_f, jnp.maximum(pos, 0), axis=1)
    return jnp.where(pos >= 0, out, -1).astype(jnp.int32), d


def ell_spmm_ref(values: jax.Array, cols: jax.Array, centers: jax.Array) -> jax.Array:
    """S[b,k] = Σ_j values[b,j] · centers[k, cols[b,j]] — densify + matmul."""
    b, nz = values.shape
    d = centers.shape[1]
    x_dense = jnp.zeros((b, d), jnp.float32)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], cols.shape)
    x_dense = x_dense.at[rows, cols].add(values.astype(jnp.float32))
    return jnp.matmul(x_dense, centers.astype(jnp.float32).T, precision=EXACT)
