"""Pallas TPU kernel: fused masked top-k nearest-centre search.

Generalises ``nn_assign`` (hard-min online accumulator, DESIGN.md §3.3) to the
k smallest distances per query — the beam-search / top-k retrieval hot spot
(DESIGN.md §7). dist[b,k] = ‖x_b‖² − 2·x_b·c_k + ‖c_k‖² as before; the running
state per query row is now a sorted length-``kq`` buffer of (dist, centre id)
pairs instead of a scalar (min, argmin).

Grid: (B/bm, K/bk) with the k axis inner/sequential so the output buffers
(indexed by b only) stay resident in VMEM across centre tiles. Each tile is
merged into the running buffer by ``kq`` select-min-and-mask passes over the
concatenated [bm, kq + bk] candidates — O(kq·(kq+bk)) VPU work per tile,
negligible next to the [bm,D]×[D,bk] MXU matmul for the beam widths the query
engine uses (kq ≤ 64).

Tie-breaking matches ``jax.lax.top_k``: ascending distance, ties by lower
centre index (the running buffer holds earlier tiles' entries and
concatenates before the current tile, and argmin takes the first occurrence).
Masked / padded centres carry +inf distance; exhausted buffer slots report
centre id −1.

VMEM per step (bm=bk=128, D=8064 fp32): x 4.1 MiB + c 4.1 MiB, each
double-buffered by the pipeline → 16.5 MiB, plus the unrolled merge's live
[bm, kq+bk] candidate copies (~2·kq of them: 0.7 MiB at kq=4, 11 MiB at
kq=64). That is over Mosaic's 16 MiB default scoped limit — the compiler
refused kq ≥ 10 at 17.56 MiB — so the call sets ``vmem_limit_bytes`` from
these sizes (``kernels.scoped_vmem_limit``: 25 MiB at kq=4, 36 MiB at
kq=64). The bias is a 2-D ``[1, K]`` row: Mosaic refuses a 1-D
block whose tiling differs from XLA's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import scoped_vmem_limit
from repro.kernels.ref import EXACT


def _nn_topk_kernel(
    x_ref, c_ref, bias_ref, dist_ref, arg_ref, *, bk: int, kq: int
):
    k = pl.program_id(1)
    x = x_ref[...]
    c = c_ref[...]
    cross = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=EXACT,
    )                                                        # [bm, bk]
    x32 = x.astype(jnp.float32)
    c32 = c.astype(jnp.float32)
    x_sq = jnp.sum(x32 * x32, axis=1, keepdims=True)         # [bm, 1]
    c_sq = jnp.sum(c32 * c32, axis=1)                        # [bk]
    dist = jnp.maximum(x_sq - 2.0 * cross + c_sq[None, :], 0.0)
    # masked AND padded centres both carry +inf bias (built in ops.nn_topk)
    dist = dist + bias_ref[...]
    col = k * bk + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)

    @pl.when(k == 0)
    def _init():
        dist_ref[...] = jnp.full(dist_ref.shape, jnp.inf, jnp.float32)
        arg_ref[...] = jnp.full(arg_ref.shape, -1, jnp.int32)

    # merge the tile into the running sorted buffer: kq select-min passes over
    # the [bm, kq + bk] candidate set (buffer first → earlier tiles win ties)
    comb_d = jnp.concatenate([dist_ref[...], dist], axis=1)
    comb_i = jnp.concatenate([arg_ref[...], col], axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, comb_d.shape, 1)
    out_d = jnp.zeros(dist_ref.shape, jnp.float32)
    out_i = jnp.zeros(arg_ref.shape, jnp.int32)
    slot = jax.lax.broadcasted_iota(jnp.int32, out_d.shape, 1)
    for t in range(kq):
        m = jnp.min(comb_d, axis=1)                          # [bm]
        a = jnp.argmin(comb_d, axis=1).astype(jnp.int32)     # first occurrence
        sel = pos == a[:, None]
        win = jnp.sum(jnp.where(sel, comb_i, 0), axis=1)     # gather winner id
        win = jnp.where(jnp.isinf(m), -1, win)               # exhausted → −1
        out_d = jnp.where(slot == t, m[:, None], out_d)
        out_i = jnp.where(slot == t, win[:, None], out_i)
        comb_d = jnp.where(sel, jnp.inf, comb_d)             # consume the winner
    dist_ref[...] = out_d
    arg_ref[...] = out_i


@functools.partial(jax.jit, static_argnames=("kq", "bm", "bk", "interpret"))
def nn_topk_pallas(
    x: jax.Array,
    centers: jax.Array,
    bias: jax.Array,
    *,
    kq: int,
    bm: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """Padded entry point — callers use repro.kernels.ops.nn_topk, which pads
    B/K/D and builds the centre-mask bias. x: [B,D], centers: [K,D],
    bias: f32[1,K].
    Returns (dist f32[B,kq] ascending, idx i32[B,kq]; −1 id on padding)."""
    b, d = x.shape
    k, _ = centers.shape
    assert b % bm == 0 and k % bk == 0, "pad B and K first"
    grid = (b // bm, k // bk)
    kernel = functools.partial(_nn_topk_kernel, bk=bk, kq=kq)
    block_bytes = (bm * d * x.dtype.itemsize + bk * d * centers.dtype.itemsize
                   + 4 * bk + 8 * bm * kq)
    # the unrolled merge keeps ~2·kq live [bm, kq+bk] candidate copies
    merge_bytes = 2 * kq * bm * (kq + bk) * 4
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, kq), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, kq), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kq), jnp.float32),
            jax.ShapeDtypeStruct((b, kq), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=scoped_vmem_limit(block_bytes, merge_bytes)
        ),
        interpret=interpret,
    )(x, centers, bias)
