"""Jitted public wrappers around the Pallas kernels: padding to hardware-aligned
block shapes, centre-mask bias construction, backend dispatch (interpret mode on
CPU so the TPU kernel bodies are validated everywhere)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.nn_assign import nn_assign_pallas
from repro.kernels.nn_topk import nn_topk_pallas
from repro.kernels.ell_spmm import ell_spmm_pallas
from repro.kernels.ref import EXACT


def _interpret() -> bool:
    return not on_tpu()


def _pad_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _centre_bias(k: int, kp: int, valid: Optional[jax.Array]) -> jax.Array:
    """f32[1, kp] additive centre mask: 0 on valid centres, +inf on masked
    ones and on the padded rows kp − k (which must never win)."""
    bias = jnp.zeros((k,), jnp.float32)
    if valid is not None:
        bias = jnp.where(valid, 0.0, jnp.inf)
    return jnp.pad(bias, (0, kp - k), constant_values=jnp.inf)[None, :]


def nn_assign(
    x: jax.Array,
    centers: jax.Array,
    valid: Optional[jax.Array] = None,
    bm: int = 128,
    bk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """(idx i32[B], sqdist f32[B]) — drop-in for repro.core.kmeans.assign.

    Pads B→bm·⌈⌉, K→bk·⌈⌉, D→128·⌈⌉ (zero padding leaves distances unchanged;
    padded centres are masked +inf inside the kernel)."""
    b, d = x.shape
    k = centers.shape[0]
    bp, kp, dp = _pad_to(b, bm), _pad_to(k, bk), _pad_to(d, 128)
    xq = jnp.pad(x, ((0, bp - b), (0, dp - d)))
    cq = jnp.pad(centers, ((0, kp - k), (0, dp - d)))
    bias = _centre_bias(k, kp, valid)
    dist, idx = nn_assign_pallas(xq, cq, bias, bm=bm, bk=bk, interpret=_interpret())
    return idx[:b, 0], dist[:b, 0]


def nn_topk(
    x: jax.Array,
    centers: jax.Array,
    k: int,
    valid: Optional[jax.Array] = None,
    bm: int = 128,
    bk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """(idx i32[B,k], sqdist f32[B,k]) — k nearest centres per query, ascending
    (ties by lower centre id, matching ``lax.top_k``). Generalises
    :func:`nn_assign`; padding follows the same scheme, and queries with fewer
    than k reachable centres pad with (−1, +inf) — ``k`` may exceed K."""
    b, d = x.shape
    kc = centers.shape[0]
    bp, kp, dp = _pad_to(b, bm), _pad_to(kc, bk), _pad_to(d, 128)
    xq = jnp.pad(x, ((0, bp - b), (0, dp - d)))
    cq = jnp.pad(centers, ((0, kp - kc), (0, dp - d)))
    bias = _centre_bias(kc, kp, valid)
    dist, idx = nn_topk_pallas(
        xq, cq, bias, kq=k, bm=bm, bk=bk, interpret=_interpret()
    )
    return idx[:b], dist[:b]


def ell_spmm(
    values: jax.Array,
    cols: jax.Array,
    centers: jax.Array,
    bm: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Sparse-doc × dense-centre scores S f32[B,K] (see ell_spmm kernel)."""
    b, nnz = values.shape
    k, d = centers.shape
    bp, kp, dp = _pad_to(b, bm), _pad_to(k, bk), _pad_to(d, 128)
    # slot-major (transposed) ELL, so the kernel reads one nnz slot of a
    # row tile as a sublane row; padded rows are all-padding (value 0)
    vt = jnp.pad(values, ((0, bp - b), (0, 0))).T
    ct = jnp.pad(cols, ((0, bp - b), (0, 0))).T
    ctq = jnp.pad(centers, ((0, kp - k), (0, dp - d)))
    s_t = ell_spmm_pallas(vt, ct, ctq, bm=bm, bk=bk, interpret=_interpret())
    return s_t[:k, :b].T


def medoid_assign_sparse(
    values: jax.Array,
    cols: jax.Array,
    row_sq: jax.Array,
    centers: jax.Array,
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """NN assignment where *documents are sparse* (ELL) and centres dense —
    the medoid K-tree scoring path: ‖x‖² − 2·S + ‖c‖² with S from ell_spmm."""
    s = ell_spmm(values, cols, centers)
    c32 = centers.astype(jnp.float32)
    c_sq = jnp.einsum("kd,kd->k", c32, c32, precision=EXACT)
    dist = jnp.maximum(row_sq[:, None] - 2.0 * s + c_sq[None, :], 0.0)
    if valid is not None:
        dist = jnp.where(valid[None, :], dist, jnp.inf)
    idx = jnp.argmin(dist, axis=1).astype(jnp.int32)
    return idx, jnp.take_along_axis(dist, idx[:, None], axis=1)[:, 0]
