"""Pallas TPU kernel: padded-sparse (ELL) documents × dense centres scores.

S[b,k] = Σ_j vals[b,j] · C[k, cols[b,j]] — the medoid/sparse K-tree scoring path
(paper §2: documents stay sparse; only centres are dense).

TPU adaptation (DESIGN.md §3.4): instead of per-element gathers (GPU-style),
each row tile is **densified once into a VMEM scratch buffer** and then hits
the MXU as a plain matmul against every centre tile. The densify cost is
amortised over all K tiles because the k grid axis is inner/sequential and
the scratch persists across it. HBM traffic stays proportional to the
*sparse* bytes — the paper's point.

Mosaic has no scatter, so the densify is a compare-and-select per nnz slot:
the tile is laid out transposed, ``xᵀ[D, bm]``, and built 128 vocabulary rows
at a time — for each slot j, ``acc += where(row_id == cols[j, :], vals[j, :],
0)``, where ``cols[j, :]`` is one sublane row of the slot-major
``colsᵀ[nnz_max, bm]`` block broadcast down the accumulator. Slots add in
j order, so duplicate columns sum and padding slots (value 0) add nothing,
exactly like a scatter-add. The kernel then emits ``Sᵀ`` tiles as the
standard matmul ``C[bk, D] · xᵀ[D, bm]``; ``ops.ell_spmm`` transposes the
small ``[K, B]`` result back.

VMEM per step (bm=bk=128, D=8064, nnz_max=184 fp32): scratch xᵀ 4.1 MiB
(single) + c 4.1 MiB and valsᵀ/colsᵀ 0.18 MiB, double-buffered → 8.6 MiB, plus
the out tile → ~12.8 MiB; the call sets ``vmem_limit_bytes`` from these sizes
(``kernels.scoped_vmem_limit``: 21 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import scoped_vmem_limit
from repro.kernels.ref import EXACT

_D_BLK = 128  # vocabulary rows densified per accumulator (16 f32 vregs at bm=128)


def _ell_spmm_kernel(vals_ref, cols_ref, c_ref, out_ref, xt_ref, *, nnz_max: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _densify():
        d, bm = xt_ref.shape
        row0 = jax.lax.broadcasted_iota(jnp.int32, (_D_BLK, bm), 0)

        def vocab_block(t, carry):
            base = pl.multiple_of(t * _D_BLK, _D_BLK)
            row = row0 + base

            def slot(j, acc):
                cj = cols_ref[pl.ds(j, 1), :]                       # [1, bm]
                vj = vals_ref[pl.ds(j, 1), :].astype(jnp.float32)   # [1, bm]
                return acc + jnp.where(row == cj, vj, 0.0)

            xt_ref[pl.ds(base, _D_BLK), :] = jax.lax.fori_loop(
                0, nnz_max, slot, jnp.zeros((_D_BLK, bm), jnp.float32)
            )
            return carry

        jax.lax.fori_loop(0, d // _D_BLK, vocab_block, 0)

    out_ref[...] = jax.lax.dot_general(
        c_ref[...].astype(jnp.float32),
        xt_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=EXACT,
    )                                                               # Sᵀ [bk, bm]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def ell_spmm_pallas(
    values_t: jax.Array,  # f[nnz_max, B]  (ELL values, slot-major)
    cols_t: jax.Array,    # i32[nnz_max, B]
    centers: jax.Array,   # f[K, D]
    *,
    bm: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Padded, transposed entry point (see repro.kernels.ops.ell_spmm, which
    pads B/K/D and transposes). D must be a multiple of 128. Returns
    Sᵀ f32[K, B]."""
    nnz_max, b = values_t.shape
    k, d = centers.shape
    assert b % bm == 0 and k % bk == 0, "pad B and K first"
    assert d % _D_BLK == 0, "pad D to a multiple of 128 first"
    block_bytes = (nnz_max * bm * (values_t.dtype.itemsize + 4)
                   + bk * d * centers.dtype.itemsize + 4 * bk * bm)
    return pl.pallas_call(
        functools.partial(_ell_spmm_kernel, nnz_max=nnz_max),
        grid=(b // bm, k // bk),
        in_specs=[
            pl.BlockSpec((nnz_max, bm), lambda i, j: (0, i)),
            pl.BlockSpec((nnz_max, bm), lambda i, j: (0, i)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bk, bm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((k, b), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, bm), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=scoped_vmem_limit(block_bytes, 4 * d * bm)
        ),
        interpret=interpret,
    )(values_t, cols_t, centers)
