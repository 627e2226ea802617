"""Pallas TPU kernel: fused nearest-centre search (the K-tree hot spot).

dist[b,k] = ‖x_b‖² − 2·x_b·c_k + ‖c_k‖², reduced to (min, argmin) over k with an
*online* accumulator across centre tiles — flash-attention's online-softmax
pattern specialised to hard-min (DESIGN.md §3.3). The cross term is a
[bm,D]×[D,bk] MXU matmul per tile at full f32 precision; block dims are
multiples of 128.

Grid: (B/bm, K/bk) — the k axis is the inner (sequential, "arbitrary") axis so
the output block (indexed by b only) stays resident in VMEM and is revisited.
Every block is 2-D: the centre-mask bias is a ``[1, K]`` row and the outputs
are ``[B, 1]`` columns (Mosaic refuses 1-D blocks whose tiling differs from
XLA's).

VMEM per step (defaults bm=bk=128, D=8064 fp32): x 4.1 MiB + c 4.1 MiB, each
double-buffered by the pipeline → 16.5 MiB, plus dist 64 KiB — over Mosaic's
16 MiB default scoped limit, so the call sets ``vmem_limit_bytes`` from the
block sizes (``kernels.scoped_vmem_limit``: 24 MiB at D=8064; v5e has
128 MiB of VMEM per core).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import scoped_vmem_limit
from repro.kernels.ref import EXACT


def _nn_assign_kernel(x_ref, c_ref, bias_ref, min_ref, arg_ref, *, bk: int):
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
    dist = dist + bias_ref[...]                              # +inf on masked/padded centres

    local_min = jnp.min(dist, axis=1, keepdims=True)         # [bm, 1]
    local_arg = (k * bk + jnp.argmin(dist, axis=1)).astype(jnp.int32)[:, None]

    @pl.when(k == 0)
    def _init():
        min_ref[...] = local_min
        arg_ref[...] = local_arg

    @pl.when(k > 0)
    def _accum():
        prev = min_ref[...]
        better = local_min < prev                            # strict: keeps first occurrence
        min_ref[...] = jnp.where(better, local_min, prev)
        arg_ref[...] = jnp.where(better, local_arg, arg_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def nn_assign_pallas(
    x: jax.Array,
    centers: jax.Array,
    bias: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """Padded entry point — callers use repro.kernels.ops.nn_assign, which pads
    B/K/D and builds the centre-mask bias. x: [B,D], centers: [K,D],
    bias: f32[1,K]. Returns (min f32[B,1], argmin i32[B,1])."""
    b, d = x.shape
    k, _ = centers.shape
    assert b % bm == 0 and k % bk == 0, "pad B and K first"
    grid = (b // bm, k // bk)
    block_bytes = (bm * d * x.dtype.itemsize + bk * d * centers.dtype.itemsize
                   + 4 * bk + 8 * bm)
    return pl.pallas_call(
        functools.partial(_nn_assign_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=scoped_vmem_limit(block_bytes)
        ),
        interpret=interpret,
    )(x, centers, bias)
