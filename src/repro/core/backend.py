"""Vector backends — how the K-tree touches document vectors (DESIGN.md §5).

The tree itself is representation-agnostic: node centres are always dense
rows of ``tree.centers`` (means in dense mode, document exemplars in medoid
mode). What varies is the *corpus side* — how a batch of documents, addressed
by row id, is scored against centres, norm'ed, and densified for leaf
appends / node splits. That seam is a backend:

- :class:`DenseBackend` — the seed behaviour: documents are rows of a dense
  ``f32[N, d]`` matrix. Scoring is an MXU matmul; the flat-centre path goes
  through the ``nn_assign`` Pallas kernel (interpret mode on CPU).
- :class:`EllSparseBackend` — the paper's sparse extension (§2): documents
  stay in ELL layout (values/cols padded to ``nnz_max``) with CSR alongside
  for exact-length dense row gathers. Scoring against a flat centre set goes
  through the ``ell_spmm`` Pallas kernel; scoring against per-query gathered
  node centres uses an ``nnz``-sized column gather (compute ∝ nnz, not d).
- :class:`RandomProjBackend` — the Random Indexing K-tree (PAPERS.md,
  arxiv 1001.0833): a base corpus (dense or ELL) plus a seeded random
  projection. Build, descent, and insert run entirely in the projected
  space (``dim == rp_dim`` — small dense centres, ~order-of-magnitude fewer
  descent FLOPs); the query engine rescores final candidates from the
  *original* representation at full precision (``query.topk_search(rp=...)``).

All are registered dataclass pytrees, so they cross jit boundaries and the
jitted tree ops (`route`, `_insert_wave`) specialise per backend type.

Distances everywhere drop the ‖x‖² constant: ``‖c‖² − 2·x·c`` has the same
argmin. ``row_sq`` supplies the constant back when a true distance is needed
(leaf NN search).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import on_tpu
from repro.kernels.ref import EXACT
from repro.sparse.csr import Csr, csr_from_dense, csr_row_gather_dense
from repro.sparse.ell import Ell, ell_from_csr


def _ell_csr_arrays(vals: np.ndarray, cols: np.ndarray, pad_to: int | None = None):
    """Rebuild host-side CSR arrays (data, indices, indptr) from padded ELL
    rows — the one place the load-bearing convention lives: value-0 slots are
    padding. ``pad_to`` zero-pads data/indices to a static capacity (chunk
    backends need fixed leaf shapes for compile-cache stability; ``indptr``
    bounds every read, so the padding is inert)."""
    mask = vals != 0
    indptr = np.zeros(vals.shape[0] + 1, dtype=np.int32)
    np.cumsum(mask.sum(axis=1), out=indptr[1:])
    data, indices = vals[mask], cols[mask].astype(np.int32)
    if pad_to is not None:
        nnz = int(indptr[-1])
        data_p = np.zeros(pad_to, vals.dtype)
        data_p[:nnz] = data
        idx_p = np.zeros(pad_to, np.int32)
        idx_p[:nnz] = indices
        data, indices = data_p, idx_p
    return data, indices, indptr


def _use_pallas() -> bool:
    """Kernel dispatch: the Pallas kernels are compiled on TPU; elsewhere the
    pure-jnp oracles in :mod:`repro.kernels.ref` serve as the fallback (the
    kernels themselves stay testable off-TPU through interpret mode in
    :mod:`repro.kernels.ops`). Follows ``jax.default_device`` scopes
    (:func:`repro.kernels.on_tpu`)."""
    return on_tpu()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseBackend:
    """Documents are rows of a dense matrix — the seed K-tree's representation."""

    x: jax.Array  # f[N, d]

    @classmethod
    def from_store(cls, source, rows=None) -> "DenseBackend":
        """Chunk backend over rows of an on-disk dense corpus store
        (DESIGN.md §9).

        ``source``: a ``repro.core.store.CorpusStore`` (``kind="dense"``) or a
        ``StoreSlice``; ``rows``: global row ids to materialise (default: all
        — only sensible for small stores; out-of-core consumers pass
        chunk-sized row sets). The materialised rows are bit-identical to the
        corresponding rows of an in-memory backend over the same corpus, so
        every per-row op (``cross_nodes``/``topk_flat``/``row_sq``) agrees
        exactly with the monolithic path."""
        if rows is None:
            rows = np.arange(source.n_docs)
        return cls.from_rows(source.take_rows(rows))

    @classmethod
    def from_rows(cls, got) -> "DenseBackend":
        """Chunk backend over already-fetched dense store rows
        (``{"x": f[B, d]}`` — the :meth:`from_store` construction with the
        disk read factored out, so a ``store.Prefetcher`` can run it on a
        reader thread and hand the arrays over bit-identically)."""
        return cls(x=jnp.asarray(got["x"]))

    @property
    def n_docs(self) -> int:
        """Corpus row count N."""
        return self.x.shape[0]

    @property
    def dim(self) -> int:
        """Vector dimensionality d."""
        return self.x.shape[1]

    @property
    def dtype(self):
        """Document element dtype."""
        return self.x.dtype

    def take(self, rows: jax.Array) -> jax.Array:
        """Dense vectors for a batch of row ids — f[B, d]."""
        return self.x[rows]

    def row_sq(self, rows: jax.Array) -> jax.Array:
        """‖x‖² per row — f32[B] (the constant term of squared distances)."""
        xb = self.x[rows].astype(jnp.float32)
        return jnp.einsum("bd,bd->b", xb, xb, precision=EXACT)

    def cross_nodes(self, rows: jax.Array, centers: jax.Array) -> jax.Array:
        """x_b · c_bm for per-query gathered node centres ``centers`` [B, m1, d]."""
        return jnp.einsum("bd,bmd->bm", self.x[rows], centers, precision=EXACT)

    def cross_flat(self, rows: jax.Array, centers: jax.Array) -> jax.Array:
        """x_b · c_k against a flat centre set [K, d] → [B, K]."""
        return jnp.matmul(self.x[rows], centers.T, precision=EXACT)

    def nn_flat(
        self, rows: jax.Array, centers: jax.Array, valid: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """(idx i32[B], sqdist f32[B]) vs a flat centre set — Pallas ``nn_assign``
        kernel on TPU, ``ref.nn_assign_ref`` oracle elsewhere."""
        if _use_pallas():
            from repro.kernels.ops import nn_assign

            return nn_assign(self.x[rows], centers, valid=valid)
        from repro.kernels.ref import nn_assign_ref

        return nn_assign_ref(self.x[rows], centers, valid=valid)

    def topk_flat(
        self, rows: jax.Array, centers: jax.Array, valid: jax.Array, k: int
    ) -> Tuple[jax.Array, jax.Array]:
        """(idx i32[B,k], sqdist f32[B,k]) — k nearest flat centres per query,
        ascending (DESIGN.md §7). Pallas ``nn_topk`` kernel on TPU, the
        ``ref.nn_topk_ref`` oracle elsewhere; rows with fewer than k valid
        centres pad with (−1, +inf)."""
        if _use_pallas():
            from repro.kernels.ops import nn_topk

            return nn_topk(self.x[rows], centers, k, valid=valid)
        from repro.kernels.ref import nn_topk_ref

        return nn_topk_ref(self.x[rows], centers, k, valid=valid)

    def shard(self, mesh, axes=None) -> "DenseDocShards":
        """Row-shard this corpus over the mesh's data axes (DESIGN.md §8)."""
        from repro.core.distributed import shard_rows

        (x,), n_shards, _ = shard_rows(mesh, [self.x], axes)
        return DenseDocShards(x=x, n_docs=self.n_docs, n_shards=n_shards)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EllSparseBackend:
    """Documents stay sparse (paper §2): ELL for kernel scoring, CSR for exact
    dense row gathers (leaf appends densify only the routed batch, never the
    corpus)."""

    values: jax.Array      # f[N, nnz_max] (0 on padding)
    cols: jax.Array        # i32[N, nnz_max] (0 on padding)
    sq: jax.Array          # f32[N] row squared norms
    csr_data: jax.Array    # f[nnz]
    csr_indices: jax.Array # i32[nnz]
    csr_indptr: jax.Array  # i32[N+1]
    n_cols: int = dataclasses.field(metadata=dict(static=True))

    @classmethod
    def from_store(cls, source, rows=None) -> "EllSparseBackend":
        """Chunk backend over rows of an on-disk ELL corpus store
        (DESIGN.md §9).

        ``source``: a ``repro.core.store.CorpusStore`` (``kind="ell"``) or a
        ``StoreSlice``; ``rows``: global row ids (default: all). The CSR side
        is rebuilt host-side from the fetched ELL rows (value-0 slots are
        padding, same convention as ``make_backend(Ell)``), and ``sq`` is
        computed from the ELL values exactly like
        :func:`sparse_backend_from_csr` — so chunk backends score, densify,
        and norm bit-identically to an in-memory backend over the same
        corpus.

        The CSR arrays are zero-padded to the static ``B·nnz_max`` capacity:
        a chunk's true nnz varies chunk-to-chunk, and a varying leaf shape
        would retrace the jitted consumers (``_beam_search``/``_insert_wave``)
        on *every* chunk — padded, all chunks of one bucket size share one
        compile, like the in-memory path. ``indptr`` bounds every CSR read,
        so the padding is never addressed."""
        if rows is None:
            rows = np.arange(source.n_docs)
        return cls.from_rows(source.take_rows(rows), source.dim)

    @classmethod
    def from_rows(cls, got, n_cols: int) -> "EllSparseBackend":
        """Chunk backend over already-fetched ELL store rows
        (``{"values", "cols"}`` — the :meth:`from_store` construction with
        the disk read factored out for ``store.Prefetcher`` consumers; same
        CSR rebuild, same static padding, bit-identical scoring)."""
        vals, cols = got["values"], got["cols"]
        data, indices, indptr = _ell_csr_arrays(vals, cols, pad_to=vals.size)
        return cls(
            values=jnp.asarray(vals),
            cols=jnp.asarray(cols),
            sq=jnp.sum(jnp.asarray(vals).astype(jnp.float32) ** 2, axis=1),
            csr_data=jnp.asarray(data),
            csr_indices=jnp.asarray(indices),
            csr_indptr=jnp.asarray(indptr),
            n_cols=n_cols,
        )

    @property
    def n_docs(self) -> int:
        """Corpus row count N."""
        return self.values.shape[0]

    @property
    def dim(self) -> int:
        """Logical vector dimensionality (the culled vocabulary size)."""
        return self.n_cols

    @property
    def nnz_max(self) -> int:
        """ELL padding width — max stored nonzeros per row."""
        return self.values.shape[1]

    @property
    def dtype(self):
        """Document element dtype."""
        return self.values.dtype

    def _csr(self) -> Csr:
        return Csr(self.csr_data, self.csr_indices, self.csr_indptr, self.n_cols)

    def take(self, rows: jax.Array) -> jax.Array:
        """Densify a batch of rows — f[B, d]. O(B·nnz_max) scatter; this is the
        only densification point in the sparse K-tree (wave-sized, not
        corpus-sized)."""
        return csr_row_gather_dense(self._csr(), rows, self.nnz_max)

    def row_sq(self, rows: jax.Array) -> jax.Array:
        """‖x‖² per row — f32[B], from the precomputed ELL norms."""
        return self.sq[rows]

    def cross_nodes(self, rows: jax.Array, centers: jax.Array) -> jax.Array:
        """Per-query gathered node centres [B, m1, d]: gather only the nnz
        touched columns of each query's own centre block — compute is
        B·m1·nnz, not B·m1·d."""
        v = self.values[rows]                                  # [B, nnz]
        c = self.cols[rows]                                    # [B, nnz]
        gathered = jnp.take_along_axis(centers, c[:, None, :], axis=2)  # [B, m1, nnz]
        return jnp.einsum("bn,bmn->bm", v, gathered, precision=EXACT)

    def cross_flat(self, rows: jax.Array, centers: jax.Array) -> jax.Array:
        """Flat centre set [K, d] → scores via the ``ell_spmm`` Pallas kernel
        on TPU, the ``ref.ell_spmm_ref`` oracle elsewhere."""
        if _use_pallas():
            from repro.kernels.ops import ell_spmm

            return ell_spmm(self.values[rows], self.cols[rows], centers)
        from repro.kernels.ref import ell_spmm_ref

        return ell_spmm_ref(self.values[rows], self.cols[rows], centers)

    def nn_flat(
        self, rows: jax.Array, centers: jax.Array, valid: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """(idx, sqdist) vs a flat centre set: ‖x‖² − 2·S + ‖c‖² with S from
        the ``ell_spmm`` scoring path (``medoid_assign_sparse`` on TPU)."""
        if _use_pallas():
            from repro.kernels.ops import medoid_assign_sparse

            return medoid_assign_sparse(
                self.values[rows], self.cols[rows], self.sq[rows], centers,
                valid=valid,
            )
        s = self.cross_flat(rows, centers)
        dist = self._flat_sqdist(rows, s, centers, valid)
        idx = jnp.argmin(dist, axis=1).astype(jnp.int32)
        return idx, jnp.take_along_axis(dist, idx[:, None], axis=1)[:, 0]

    def _flat_sqdist(
        self, rows: jax.Array, scores: jax.Array, centers: jax.Array, valid: jax.Array
    ) -> jax.Array:
        """‖x‖² − 2·S + ‖c‖², clamped, masked → f32[B, K] (shared by the nn/topk
        flat paths so their top-1 agree bit-for-bit)."""
        c32 = centers.astype(jnp.float32)
        c_sq = jnp.einsum("kd,kd->k", c32, c32, precision=EXACT)
        dist = jnp.maximum(self.sq[rows][:, None] - 2.0 * scores + c_sq[None, :], 0.0)
        return jnp.where(valid[None, :], dist, jnp.inf)

    def topk_flat(
        self, rows: jax.Array, centers: jax.Array, valid: jax.Array, k: int
    ) -> Tuple[jax.Array, jax.Array]:
        """(idx i32[B,k], sqdist f32[B,k]) — k nearest flat centres per query,
        ascending. The cross term reuses the ``ell_spmm`` scoring path (Pallas
        on TPU via ``cross_flat``); the k-selection is a dense ``top_k`` over
        the K scores, which are already materialised."""
        from repro.kernels.ref import topk_from_dist

        s = self.cross_flat(rows, centers)
        return topk_from_dist(self._flat_sqdist(rows, s, centers, valid), k)

    def shard(self, mesh, axes=None) -> "EllDocShards":
        """Row-shard this corpus over the mesh's data axes (DESIGN.md §8).

        Only the ELL arrays + norms travel (the kernel-scoring layout); the CSR
        side stays host-global — the sharded serving path never densifies."""
        from repro.core.distributed import shard_rows

        (values, cols, sq), n_shards, _ = shard_rows(
            mesh, [self.values, self.cols, self.sq], axes
        )
        return EllDocShards(
            values=values, cols=cols, sq=sq,
            n_cols=self.n_cols, n_docs=self.n_docs, n_shards=n_shards,
        )


# ---------------------------------------------------------------------------
# random-projection backend (DESIGN.md §5.1): the Random Indexing K-tree.
# The tree is built and routed in a low-dimensional dense projection of the
# corpus while documents keep their original (possibly sparse, possibly
# on-disk) representation; the query engine's final rescore stage goes back
# to the original rows at full precision.
# ---------------------------------------------------------------------------


class ProjectionMismatch(ValueError):
    """A restored index's recorded random projection does not match what the
    caller (or the paired tree/store) expects — seed, dims, kind, or dtype
    differ, or one side has a projection and the other does not. Raised
    instead of silently serving answers routed through the wrong projection,
    the same refusal discipline as a rewritten store's ``manifest_hash``."""


PROJECT_CHUNK = 1024  # fixed projection granularity — see project_corpus


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RandomProjection:
    """A seeded random projection ``f32[in_dim] → f32[out_dim]`` — the part of
    an RP index that must replay exactly.

    The matrix is a pure function of ``(seed, in_dim, out_dim, kind)`` via
    jax's counter-based PRNG (:func:`make_projection`), so checkpoints persist
    only the spec (plus the dtype, verified on restore) and rebuild the
    matrix bit-identically; :meth:`spec` / ``checkpoint.restore_index`` carry
    it. Kinds: ``"gaussian"`` (dense N(0, 1/out_dim) — the JL default),
    ``"ternary"`` (sparse ±1 index vectors, the Random Indexing construction
    of arxiv 1001.0833, density 1/8, variance-normalised), ``"identity"``
    (requires ``out_dim == in_dim``; makes the RP pipeline reproduce the
    dense exact path — the equivalence anchor the tests pin)."""

    matrix: jax.Array  # f32[in_dim, out_dim]
    seed: int = dataclasses.field(metadata=dict(static=True))
    kind: str = dataclasses.field(metadata=dict(static=True))

    @property
    def in_dim(self) -> int:
        """Original (document) dimensionality."""
        return self.matrix.shape[0]

    @property
    def out_dim(self) -> int:
        """Projected (routing) dimensionality — the tree's ``dim``."""
        return self.matrix.shape[1]

    @property
    def dtype(self):
        """Projection matrix dtype (always float32 today; recorded in
        checkpoints so a future widening can't silently alias)."""
        return self.matrix.dtype

    def spec(self) -> dict:
        """The replayable description ``{seed, in_dim, out_dim, kind, dtype}``
        — everything :func:`projection_from_spec` needs to rebuild
        ``matrix`` bit-identically."""
        return dict(
            seed=int(self.seed), in_dim=int(self.in_dim),
            out_dim=int(self.out_dim), kind=str(self.kind),
            dtype=str(np.dtype(self.matrix.dtype)),
        )

    def apply(self, x: jax.Array) -> jax.Array:
        """Project dense rows ``f[B, in_dim] → f32[B, out_dim]`` (jitted; one
        compile per row-bucket shape, so equal-shaped calls are bit-stable)."""
        return _apply_projection(self.matrix, jnp.asarray(x))


@jax.jit
def _apply_projection(matrix: jax.Array, x: jax.Array) -> jax.Array:
    return jnp.matmul(x.astype(jnp.float32), matrix, precision=EXACT)


def make_projection(
    in_dim: int, out_dim: int, seed: int = 0, kind: str = "gaussian"
) -> RandomProjection:
    """Deterministically generate a :class:`RandomProjection` from its spec.

    Same (seed, dims, kind) → bit-identical matrix on every call and every
    process (jax threefry PRNG), which is what makes a checkpointed RP index
    replayable from the stored seed alone."""
    if in_dim < 1 or out_dim < 1:
        raise ValueError(f"projection dims must be ≥ 1, got {in_dim}→{out_dim}")
    key = jax.random.PRNGKey(seed)
    if kind == "gaussian":
        matrix = jax.random.normal(
            key, (in_dim, out_dim), jnp.float32
        ) * jnp.float32(1.0 / math.sqrt(out_dim))
    elif kind == "ternary":
        # Random Indexing index vectors (arxiv 1001.0833): sparse ±1 at
        # density 1/8, scaled so E‖Px‖² ≈ ‖x‖²
        density = 1.0 / 8.0
        u = jax.random.uniform(key, (in_dim, out_dim), jnp.float32)
        scale = jnp.float32(1.0 / math.sqrt(density * out_dim))
        matrix = jnp.where(
            u < density / 2, scale, jnp.where(u > 1.0 - density / 2, -scale, 0.0)
        )
    elif kind == "identity":
        if out_dim != in_dim:
            raise ValueError(
                f"identity projection needs out_dim == in_dim, got "
                f"{in_dim}→{out_dim}"
            )
        matrix = jnp.eye(in_dim, dtype=jnp.float32)
    else:
        raise ValueError(
            f"unknown projection kind {kind!r}; use gaussian|ternary|identity"
        )
    return RandomProjection(matrix=matrix, seed=int(seed), kind=kind)


def projection_from_spec(spec: dict) -> RandomProjection:
    """Rebuild a projection from a :meth:`RandomProjection.spec` record,
    verifying the recorded dtype still matches what :func:`make_projection`
    produces (a silent dtype drift would un-replay every checkpoint)."""
    try:
        proj = make_projection(
            int(spec["in_dim"]), int(spec["out_dim"]),
            seed=int(spec["seed"]), kind=str(spec["kind"]),
        )
    except KeyError as e:
        raise ProjectionMismatch(f"projection spec missing field {e}") from e
    want = str(spec.get("dtype", "float32"))
    if str(np.dtype(proj.matrix.dtype)) != want:
        raise ProjectionMismatch(
            f"projection dtype {np.dtype(proj.matrix.dtype)} != recorded {want}"
        )
    return proj


def project_corpus(projection: RandomProjection, source, prefetch: int = 0):
    """Project a whole corpus → ``f32[N, out_dim]`` (host array), in fixed
    :data:`PROJECT_CHUNK`-row chunks.

    ``source``: an in-memory corpus/backend or a ``CorpusStore``/``StoreSlice``
    (rows stream through the block cache — only one densified chunk is ever
    resident, so the sparse corpus is never materialised; ``prefetch ≥ 1``
    moves store reads onto a ``store.Prefetcher`` thread). The chunk
    granularity is deliberately *fixed* — independent of the caller's batch
    size — so the in-memory and streaming constructions project every row at
    the same jitted shape and the two resulting backends (and every tree
    built over them) are bit-identical by construction."""
    from repro.core.ktree import padded_chunk_rows

    n = source.n_docs
    out_dim = projection.out_dim
    if n == 0:
        return np.zeros((0, out_dim), np.float32)
    if source.dim != projection.in_dim:
        raise ProjectionMismatch(
            f"corpus dim {source.dim} != projection in_dim {projection.in_dim}"
        )
    outs = []
    if is_store(source):
        def fetch(req):
            _, padded = req
            return source.take_rows(padded)

        import contextlib

        with contextlib.ExitStack() as stack:
            if prefetch:
                from repro.core.store import Prefetcher

                fetched = stack.enter_context(Prefetcher(
                    padded_chunk_rows(n, PROJECT_CHUNK), fetch, depth=prefetch,
                ))
            else:
                fetched = (
                    (req, fetch(req)) for req in padded_chunk_rows(n, PROJECT_CHUNK)
                )
            for (rows_np, padded), got in fetched:
                be_c = backend_from_rows(source, got)
                x = be_c.take(jnp.arange(padded.size, dtype=jnp.int32))
                outs.append(np.asarray(projection.apply(x))[: rows_np.size])
    else:
        be = make_backend(source)
        for rows_np, padded in padded_chunk_rows(n, PROJECT_CHUNK):
            x = be.take(jnp.asarray(padded.astype(np.int32)))
            outs.append(np.asarray(projection.apply(x))[: rows_np.size])
    return np.concatenate(outs, axis=0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RandomProjBackend:
    """The Random Indexing K-tree's corpus side (arxiv 1001.0833): a base
    corpus (dense or ELL — possibly left on disk) routed through a seeded
    random projection.

    Every tree-facing op (``take``/``cross_nodes``/``nn_flat``/…) delegates to
    ``proj`` — a :class:`DenseBackend` over the projected rows — so build,
    descent, and insert run entirely in the ``out_dim``-dimensional space
    (``dim == projection.out_dim``; the tree's centres are small and dense and
    bit-match a plain dense tree built over the same projected rows). What the
    projection *costs* is exactness: projected distances only approximate
    original-space distances, so the query engine treats the tree as a
    candidate generator and rescores the leaf pool from ``base`` (or the
    store) at full precision — ``query.topk_search(..., rp=...)``.

    ``base`` keeps the original in-memory representation for that rescore;
    it is ``None`` when the original rows live in a ``CorpusStore`` (the
    out-of-core construction — pass the store as ``rp_corpus=`` at query
    time)."""

    proj: DenseBackend
    projection: RandomProjection
    base: Optional[Union[DenseBackend, EllSparseBackend]]

    @classmethod
    def wrap(cls, corpus, projection: RandomProjection) -> "RandomProjBackend":
        """Wrap an in-memory corpus (dense array, Csr, Ell, or backend):
        normalises it via :func:`make_backend`, projects it with
        :func:`project_corpus`'s fixed chunking, and keeps the base for the
        exact rescore."""
        base = make_backend(corpus)
        z = project_corpus(projection, base)
        return cls(
            proj=DenseBackend(jnp.asarray(z)), projection=projection, base=base
        )

    @classmethod
    def from_store(
        cls, source, projection: RandomProjection, prefetch: int = 0
    ) -> "RandomProjBackend":
        """Project an on-disk corpus without ever materialising it
        (DESIGN.md §9): rows stream through the store's block cache in
        :data:`PROJECT_CHUNK` chunks, and only the projected ``f32[N,
        out_dim]`` matrix — the Random Indexing premise's *small*
        representation — stays resident. ``base`` is ``None``; rescore
        fetches original rows back through the store
        (``query.topk_search(..., rp_corpus=store)``). Bit-identical to
        :meth:`wrap` of the same corpus, by the shared fixed-chunk
        projection."""
        z = project_corpus(projection, source, prefetch=prefetch)
        return cls(
            proj=DenseBackend(jnp.asarray(z)), projection=projection, base=None
        )

    @property
    def n_docs(self) -> int:
        """Corpus row count N."""
        return self.proj.n_docs

    @property
    def dim(self) -> int:
        """Routing dimensionality — the *projected* dim (the tree's dim)."""
        return self.proj.dim

    @property
    def base_dim(self) -> int:
        """Original document dimensionality (the rescore space)."""
        return self.projection.in_dim

    @property
    def dtype(self):
        """Projected element dtype (f32)."""
        return self.proj.dtype

    def take(self, rows: jax.Array) -> jax.Array:
        """Projected vectors for a batch of row ids — f32[B, out_dim] (what
        leaf appends store: the tree holds projected rows)."""
        return self.proj.take(rows)

    def row_sq(self, rows: jax.Array) -> jax.Array:
        """‖Px‖² per row — norms in the projected space."""
        return self.proj.row_sq(rows)

    def cross_nodes(self, rows: jax.Array, centers: jax.Array) -> jax.Array:
        """Projected-space ``x·c`` against per-query gathered centres."""
        return self.proj.cross_nodes(rows, centers)

    def cross_flat(self, rows: jax.Array, centers: jax.Array) -> jax.Array:
        """Projected-space ``x·c`` against a flat centre set."""
        return self.proj.cross_flat(rows, centers)

    def nn_flat(self, rows, centers, valid):
        """Nearest flat centre per row, in the projected space."""
        return self.proj.nn_flat(rows, centers, valid)

    def topk_flat(self, rows, centers, valid, k):
        """Top-k flat centres per row, in the projected space."""
        return self.proj.topk_flat(rows, centers, valid, k)


VectorBackend = Union[DenseBackend, EllSparseBackend, RandomProjBackend]


# ---------------------------------------------------------------------------
# sharded corpus views — the serving plane's document side (DESIGN.md §8).
# A `*DocShards` is a backend row-sharded over a mesh's data axes: shard s owns
# the contiguous global doc ids [s·L, (s+1)·L) where L = n_pad / n_shards
# (rows zero-padded to the shard multiple). `score_local` and `to_local` are
# shard_map-body views: inside shard_map the array leaves ARE the local block.
# ---------------------------------------------------------------------------


class _DocShardsBase:
    n_docs: int
    n_shards: int

    @staticmethod
    def to_local(global_ids: jax.Array, lo, docs_per_shard: int):
        """Global→local doc-id translation: (local row ids clipped safe for
        gathering, owned mask). ``lo`` = flat_shard_index · docs_per_shard."""
        local = global_ids - lo
        owned = jnp.logical_and(local >= 0, local < docs_per_shard)
        return jnp.clip(local, 0, docs_per_shard - 1), owned


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseDocShards(_DocShardsBase):
    """Dense corpus rows sharded P(data_axes, None) for shard-parallel query
    serving."""

    x: jax.Array  # f[n_pad, d] (local block [L, d] inside shard_map)
    n_docs: int = dataclasses.field(metadata=dict(static=True))
    n_shards: int = dataclasses.field(metadata=dict(static=True))

    def _rows0(self) -> jax.Array:
        return self.x

    @property
    def dim(self) -> int:
        """Vector dimensionality d."""
        return self.x.shape[1]

    def score_local(self, xq: jax.Array, ids: jax.Array) -> jax.Array:
        """‖c‖² − 2·x·c for local doc row ids ``ids`` [B, C] against dense
        queries ``xq`` [B, d] — shard_map-body view (same expressions as the
        single-device `_score_entries`, so distances agree)."""
        xd = self.x[ids].astype(jnp.float32)                   # [B, C, d]
        c_sq = jnp.einsum("bcd,bcd->bc", xd, xd, precision=EXACT)
        return c_sq - 2.0 * jnp.einsum(
            "bd,bcd->bc", xq.astype(jnp.float32), xd, precision=EXACT
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EllDocShards(_DocShardsBase):
    """ELL-sparse corpus rows sharded P(data_axes, None): the sharded scorer
    stays sparse-first — per-candidate compute is O(nnz), never a densify."""

    values: jax.Array  # f[n_pad, nnz_max]
    cols: jax.Array    # i32[n_pad, nnz_max]
    sq: jax.Array      # f32[n_pad]
    n_cols: int = dataclasses.field(metadata=dict(static=True))
    n_docs: int = dataclasses.field(metadata=dict(static=True))
    n_shards: int = dataclasses.field(metadata=dict(static=True))

    def _rows0(self) -> jax.Array:
        return self.values

    @property
    def dim(self) -> int:
        """Logical vector dimensionality (the culled vocabulary size)."""
        return self.n_cols

    def score_local(self, xq: jax.Array, ids: jax.Array) -> jax.Array:
        """‖c‖² − 2·x·c for local doc row ids [B, C] against dense queries
        [B, d]: nnz-bounded column gather from the query rows (compute is
        B·C·nnz, not B·C·d) — shard_map-body view."""
        v = self.values[ids].astype(jnp.float32)               # [B, C, nnz]
        c = self.cols[ids]                                     # [B, C, nnz]
        b_idx = jnp.arange(xq.shape[0])[:, None, None]
        g = xq.astype(jnp.float32)[b_idx, c]                   # [B, C, nnz]
        return self.sq[ids] - 2.0 * jnp.einsum("bcn,bcn->bc", v, g, precision=EXACT)


DocShards = Union[DenseDocShards, EllDocShards]


class StoreDocShards:
    """Row-sharded view of an **on-disk** corpus for shard-parallel serving
    (DESIGN.md §8/§9) — the out-of-core sibling of ``*DocShards``.

    Shard ``s`` owns the same contiguous global rows the in-memory layout
    gives it (``distributed.shard_row_extent``), but instead of holding its
    corpus block on device it holds a ``CorpusStore.partition`` slice — its
    own ``BlockCache`` under a per-shard residency budget. Per query chunk,
    :meth:`chunk_pools` fetches only the beam candidates each shard owns
    (deduplicated, via that shard's cache) into a padded per-shard *pool*;
    the sharded engine scores pools with the exact ``score_local``
    expressions, so answers stay bit-identical to the in-memory sharded path
    while peak store residency stays ≤ n_shards × per-shard budget (plus the
    per-cache one-block floor). A host-side handle — never crosses jit.
    """

    def __init__(self, mesh, store, budget_bytes=None, axes=None):
        from repro.core.distributed import data_axes, n_row_shards, shard_row_extent

        self.mesh = mesh
        self.axes = data_axes(mesh) if axes is None else tuple(axes)
        self.n_shards = n_row_shards(mesh, self.axes)
        self.kind = store.kind
        self.dim = store.dim
        self.nnz_max = store.nnz_max
        self.n_docs = store.n_docs
        self.manifest_hash = store.manifest_hash
        self.docs_per_shard = shard_row_extent(store.n_docs, self.n_shards)
        self._dtype = np.dtype(store.dtype)
        self.parts = store.partition(self.n_shards, budget_bytes=budget_bytes)
        self.peak_resident_bytes = 0

    def _pool_fields(self):
        """(name, per-row trailing shape, dtype) of the pool arrays."""
        if self.kind == "dense":
            return (("x", (self.dim,), self._dtype),)
        return (("values", (self.nnz_max,), self._dtype),
                ("cols", (self.nnz_max,), np.int32))

    def chunk_pools(self, cand: np.ndarray, valid: np.ndarray,
                    on_fault: str = "raise"):
        """Fetch one chunk's owned candidate rows into per-shard pools.

        ``cand`` i32[B, C] global candidate doc ids, ``valid`` bool[B, C].
        Returns ``(pools, pool_idx, owned, dropped_ids)``: ``pools`` is a
        tuple of stacked arrays ``[S, U, …]`` (each shard's deduplicated
        owned candidate rows, zero-padded to a shared power-of-two ``U``),
        ``pool_idx`` i32[S, B, C] maps each candidate slot to its pool row
        (0 where unowned — masked), ``owned`` bool[S, B, C] marks the slots
        shard ``s`` must score — the same ownership predicate the in-memory
        ``to_local`` computes — and ``dropped_ids`` is the global doc ids
        this chunk could not fetch (always empty with ``on_fault="raise"``,
        where an unreadable block raises its typed ``BlockError`` instead).

        ``on_fault="degrade"`` (DESIGN.md §10): candidates whose store block
        exhausted its read retries are removed from ``owned`` — they score
        +inf exactly as if no shard owned them, so answers are bit-identical
        to a search over the surviving corpus subset. Updates
        :attr:`peak_resident_bytes` from the partition caches."""
        from repro.core.store import check_on_fault

        check_on_fault(on_fault)
        s_count, (b, c) = self.n_shards, cand.shape
        per_shard = []
        u_max = 1
        for s, part in enumerate(self.parts):
            lo = s * self.docs_per_shard
            own = np.logical_and(
                valid, np.logical_and(cand >= lo, cand < lo + part.n_docs)
            )
            ids = np.unique(cand[own])
            per_shard.append((lo, own, ids))
            u_max = max(u_max, ids.size)
        u = 1
        while u < u_max:
            u *= 2
        pools = tuple(
            np.zeros((s_count, u) + shape, dtype)
            for _, shape, dtype in self._pool_fields()
        )
        pool_idx = np.zeros((s_count, b, c), np.int32)
        owned = np.zeros((s_count, b, c), bool)
        dropped: list = []
        for s, (lo, own, ids) in enumerate(per_shard):
            owned[s] = own
            if ids.size:
                if on_fault == "degrade":
                    got, ok = self.parts[s].take_rows_masked(ids - lo)
                    if not ok.all():
                        bad = ids[~ok]
                        dropped.append(bad)
                        # drop only the unreadable blocks' candidates: they
                        # score +inf, exactly as if no shard owned them
                        owned[s] &= ~np.isin(cand, bad)
                else:
                    got = self.parts[s].take_rows(ids - lo)
                for pool, (name, _, _) in zip(pools, self._pool_fields()):
                    pool[s, : ids.size] = got[name]
                pool_idx[s][own] = np.searchsorted(ids, cand[own]).astype(np.int32)
        self.peak_resident_bytes = max(
            self.peak_resident_bytes,
            sum(p.store.cache.resident_bytes for p in self.parts),
        )
        dropped_ids = (
            np.concatenate(dropped) if dropped else np.empty(0, cand.dtype)
        )
        return pools, pool_idx, owned, dropped_ids

    @property
    def cache_stats(self) -> list:
        """Per-shard block-cache stats dicts (serve report + tests)."""
        return [p.store.cache.stats for p in self.parts]


def shard_from_store(mesh, store, budget_bytes=None, axes=None) -> StoreDocShards:
    """Row-shard an on-disk corpus over ``mesh``'s data axes **without
    materialising it** (DESIGN.md §8/§9).

    ``store``: an open ``CorpusStore`` (not a slice — shard ownership is
    defined over the full global row range the tree addresses);
    ``budget_bytes``: per-shard block-cache budget (default: the store
    handle's own budget), so total residency is bounded by
    n_shards × budget. The result plugs into
    ``query.topk_search_sharded(..., corpus=...)`` — pass it when serving
    many batches so the partitions (and their caches) are created once. A
    full-range ``StoreSlice`` is unwrapped to its parent; a partial slice is
    rejected (the tree addresses global doc ids, so a sharded corpus must
    cover the whole store)."""
    from repro.core.store import CorpusStore, StoreSlice

    if isinstance(store, StoreSlice):
        if store.lo == 0 and store.hi == store.store.n_docs:
            store = store.store
        else:
            raise ValueError(
                f"sharded corpus slice [{store.lo}, {store.hi}) must cover "
                f"the full store row range [0, {store.store.n_docs}) — the "
                "tree addresses global doc ids"
            )
    if not isinstance(store, CorpusStore):
        raise TypeError(
            f"shard_from_store wants an open CorpusStore, got {type(store).__name__}"
        )
    return StoreDocShards(mesh, store, budget_bytes=budget_bytes, axes=axes)


def sparse_backend_from_csr(
    m: Csr, nnz_max: int | None = None, pad_to: int = 8
) -> EllSparseBackend:
    """Build the ELL+CSR backend from a CSR corpus (host-side layout pass).

    ``sq`` is computed from the ELL values so that when an explicit ``nnz_max``
    truncates long rows, norms stay consistent with what ``cross_*``/``take``
    actually see (``take`` also clips at ``nnz_max``). ``pad_to`` is
    ``ell_from_csr``'s lane rounding — pass 1 to honour an explicit
    ``nnz_max`` exactly (the store-append path must match a store's recorded
    width, DESIGN.md §9)."""
    e = ell_from_csr(m, nnz_max=nnz_max, pad_to=pad_to)
    return EllSparseBackend(
        values=e.values,
        cols=e.cols,
        sq=jnp.sum(e.values.astype(jnp.float32) ** 2, axis=1),
        csr_data=m.data,
        csr_indices=m.indices,
        csr_indptr=m.indptr,
        n_cols=m.n_cols,
    )


def backend_from_store(source, rows=None) -> VectorBackend:
    """Materialise store rows as the matching in-memory backend
    (DESIGN.md §9).

    ``source``: a ``repro.core.store.CorpusStore`` or ``StoreSlice`` —
    ``kind="dense"`` → :class:`DenseBackend`, ``kind="ell"`` →
    :class:`EllSparseBackend`. ``rows`` (global ids, default all) is the
    residency knob: out-of-core consumers (store-backed ``topk_search``,
    ``build_from_store``) pass one chunk's rows at a time, so only
    chunk-sized backends ever exist on device."""
    if source.kind == "dense":
        return DenseBackend.from_store(source, rows)
    return EllSparseBackend.from_store(source, rows)


def backend_from_rows(source, got) -> VectorBackend:
    """Materialise **already-fetched** store rows as the matching backend.

    ``got`` is a ``take_rows`` result (``{"x"}`` dense / ``{"values",
    "cols"}`` ELL) for ``source``'s layout — the seam that lets a
    ``store.Prefetcher`` move the disk read onto a reader thread
    (DESIGN.md §9) while the backend construction (and hence every answer)
    stays bit-identical to :func:`backend_from_store`."""
    if source.kind == "dense":
        return DenseBackend.from_rows(got)
    return EllSparseBackend.from_rows(got, source.dim)


def backend_for_store_layout(source, corpus) -> VectorBackend:
    """Normalise new corpus rows into ``source``'s exact block layout.

    ``source``: a ``CorpusStore``/``StoreSlice``; ``corpus``: a dense array,
    Csr, or backend. Returns a backend whose rows can be appended to the
    store verbatim (``CorpusStore.append``) *and* inserted into the tree
    (``ktree.insert_into_store``) — one normalisation, so the vectors the
    tree holds and the vectors the store serves are bit-identical. Dense
    stores: densify + cast to the store dtype. ELL stores: re-lay the rows at
    the store's recorded ``nnz_max`` width (longer rows truncate exactly like
    an explicit-``nnz_max`` backend). Dimension mismatches raise.

    Idempotent: a backend already in the store's exact layout (same kind,
    dim, dtype — and ``nnz_max`` width for ELL) passes through untouched, so
    ``insert_into_store`` normalising once and ``append`` normalising its
    argument again costs one layout pass, not two."""
    if is_store(corpus):
        raise TypeError("append source must be in-memory rows, not a store")
    dtype = np.dtype(source.dtype)
    if source.kind == "dense":
        be = make_backend(corpus, "dense")
        if be.dim != source.dim:
            raise ValueError(
                f"appended rows have dim {be.dim} != store dim {source.dim}"
            )
        x = be.x if be.dtype == dtype else be.x.astype(dtype)
        return DenseBackend(x=x)
    if (
        isinstance(corpus, EllSparseBackend)
        and corpus.dim == source.dim
        and corpus.nnz_max == source.nnz_max
        and np.dtype(corpus.dtype) == dtype
    ):
        return corpus
    if isinstance(corpus, Csr):
        m = corpus
    elif isinstance(corpus, EllSparseBackend):
        m = corpus._csr()
    elif isinstance(corpus, DenseBackend):
        m = csr_from_dense(np.asarray(corpus.x))
    elif isinstance(corpus, Ell):
        data, indices, indptr = _ell_csr_arrays(
            np.asarray(corpus.values), np.asarray(corpus.cols)
        )
        m = Csr(data=jnp.asarray(data), indices=jnp.asarray(indices),
                indptr=jnp.asarray(indptr), n_cols=corpus.n_cols)
    else:
        m = csr_from_dense(np.asarray(corpus))
    if m.n_cols != source.dim:
        raise ValueError(
            f"appended rows have dim {m.n_cols} != store dim {source.dim}"
        )
    if np.asarray(m.data).dtype != dtype:
        m = Csr(data=jnp.asarray(np.asarray(m.data).astype(dtype)),
                indices=m.indices, indptr=m.indptr, n_cols=m.n_cols)
    return sparse_backend_from_csr(m, nnz_max=source.nnz_max, pad_to=1)


def is_store(x) -> bool:
    """True when ``x`` is an out-of-core corpus handle (a ``CorpusStore`` or
    ``StoreSlice``) rather than an in-memory corpus/backend."""
    from repro.core.store import CorpusStore, StoreSlice

    return isinstance(x, (CorpusStore, StoreSlice))


def make_backend(x, backend: str = "auto") -> VectorBackend:
    """Normalise (corpus, backend-name) into a backend instance.

    ``x``: dense array, :class:`Csr`, :class:`Ell`-producing Csr, an
    existing backend, or an out-of-core store handle (``CorpusStore`` /
    ``StoreSlice`` — materialised **whole**; out-of-core paths check
    :func:`is_store` before calling this). ``backend``: "auto" (follow the
    input layout), "dense", or "sparse".
    """
    if backend not in ("auto", "dense", "sparse"):
        raise ValueError(f"unknown backend {backend!r}; use auto|dense|sparse")
    if is_store(x):
        x = backend_from_store(x)
        if backend == "dense" and isinstance(x, EllSparseBackend):
            x = DenseBackend(x.take(jnp.arange(x.n_docs)))
        elif backend == "sparse" and isinstance(x, DenseBackend):
            x = sparse_backend_from_csr(csr_from_dense(np.asarray(x.x)))
    if isinstance(x, (DenseBackend, EllSparseBackend, RandomProjBackend)):
        return x
    if isinstance(x, Csr):
        if backend == "dense":
            from repro.sparse.csr import csr_to_dense

            return DenseBackend(csr_to_dense(x))
        return sparse_backend_from_csr(x)
    if isinstance(x, Ell):
        if backend == "dense":
            from repro.sparse.ell import ell_to_dense

            return DenseBackend(ell_to_dense(x))
        # rebuild CSR host-side straight from the padded layout (O(nnz);
        # never materialises the dense corpus) via the shared ELL→CSR helper
        data, indices, indptr = _ell_csr_arrays(
            np.asarray(x.values), np.asarray(x.cols)
        )
        m = Csr(
            data=jnp.asarray(data),
            indices=jnp.asarray(indices),
            indptr=jnp.asarray(indptr),
            n_cols=x.n_cols,
        )
        return sparse_backend_from_csr(m, nnz_max=x.nnz_max)
    # array-like
    xa = jnp.asarray(x)
    if backend == "sparse":
        return sparse_backend_from_csr(csr_from_dense(np.asarray(xa)))
    return DenseBackend(xa)
