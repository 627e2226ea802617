"""Padded (ELL) sparse layout — the TPU-friendly form.

Every row is padded to ``nnz_max`` (column id 0, value 0). Static shapes, so the
``ell_spmm`` Pallas kernel can tile it into VMEM. The paper's medoid K-tree keeps
documents sparse; ELL is how those documents feed the MXU.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.csr import Csr
from repro.kernels.ref import EXACT


class Ell(NamedTuple):
    values: jax.Array   # f[n_rows, nnz_max]   (0 on padding)
    cols: jax.Array     # i32[n_rows, nnz_max] (0 on padding — value 0 nullifies)
    n_cols: int         # static

    @property
    def n_rows(self) -> int:
        return self.values.shape[0]

    @property
    def nnz_max(self) -> int:
        return self.values.shape[1]

    @property
    def shape(self):
        return (self.n_rows, self.n_cols)


def ell_from_csr(m: Csr, nnz_max: int | None = None, pad_to: int = 8) -> Ell:
    """Host-side CSR → ELL. ``nnz_max`` defaults to the longest row, rounded up
    to a multiple of ``pad_to`` (lane-friendly)."""
    indptr = np.asarray(m.indptr)
    lengths = np.diff(indptr)
    if nnz_max is None:
        nnz_max = int(lengths.max()) if lengths.size else 1
    nnz_max = max(pad_to, int(-(-nnz_max // pad_to) * pad_to))
    vals = np.zeros((m.n_rows, nnz_max), dtype=np.asarray(m.data).dtype)
    cols = np.zeros((m.n_rows, nnz_max), dtype=np.int32)
    data = np.asarray(m.data)
    indices = np.asarray(m.indices)
    for i in range(m.n_rows):
        k = min(int(lengths[i]), nnz_max)
        vals[i, :k] = data[indptr[i] : indptr[i] + k]
        cols[i, :k] = indices[indptr[i] : indptr[i] + k]
    return Ell(values=jnp.asarray(vals), cols=jnp.asarray(cols), n_cols=m.n_cols)


def ell_to_dense(e: Ell) -> jax.Array:
    out = jnp.zeros(e.shape, e.values.dtype)
    r = jnp.broadcast_to(jnp.arange(e.n_rows)[:, None], e.cols.shape)
    return out.at[r, e.cols].add(e.values)


def ell_dot_dense(e: Ell, dense_t: jax.Array) -> jax.Array:
    """Scores S[i,k] = Σ_j values[i,j] · dense_t[cols[i,j], k].

    ``dense_t``: f[n_cols, K] (centres transposed). This is the pure-XLA
    reference path; the Pallas kernel (repro.kernels.ell_spmm) is the TPU
    version. Memory: n_rows × nnz_max × K intermediate — callers tile rows.
    """
    gathered = jnp.take(dense_t, e.cols, axis=0)           # [n, nnz, K]
    return jnp.einsum("nj,njk->nk", e.values, gathered, precision=EXACT)


def ell_row_norms(e: Ell) -> jax.Array:
    return (e.values * e.values).sum(axis=1)
