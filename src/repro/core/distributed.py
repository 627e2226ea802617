"""Distributed clustering primitives (beyond the single-threaded paper).

Documents are sharded over the ``data`` (and ``pod``) mesh axes; centres are
replicated (small) or sharded over ``model`` (huge leaf-level K). Centroid
updates are (sum, count) psums — a hierarchical all-reduce: ICI within a pod,
DCI across pods, exactly the collective the roofline analysis prices.

These functions are written with ``shard_map`` so the collective schedule is
explicit (not left to GSPMD), which is what we tune in §Perf.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.kmeans import assign as _assign
from repro.kernels.ref import EXACT


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes that shard documents: ('pod','data') when multi-pod."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_row_shards(mesh: Mesh, axes: Optional[Tuple[str, ...]] = None) -> int:
    """Number of row shards a corpus splits into over the data axes."""
    axes = data_axes(mesh) if axes is None else tuple(axes)
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def flat_shard_index(mesh: Mesh, axes: Tuple[str, ...]):
    """Flattened shard index of the executing device *inside a shard_map body*
    — row-major over ``axes``, matching how ``P(axes, ...)`` splits rows."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * int(mesh.shape[a]) + jax.lax.axis_index(a)
    return idx


def shard_row_extent(n: int, n_shards: int) -> int:
    """Rows per shard after zero-padding ``n`` rows to the shard multiple —
    shard ``s`` owns the contiguous global row range ``[s·L, (s+1)·L)``.
    Single source of truth for row ownership: :func:`shard_rows` (in-memory
    ``*DocShards``) and ``store.CorpusStore.partition`` (out-of-core, §9)
    both derive from it, which is what keeps disk-backed shard ownership
    aligned with the device layout."""
    return -(-n // n_shards)


def shard_rows(mesh: Mesh, arrays, axes: Optional[Tuple[str, ...]] = None):
    """Device-put arrays row-sharded over the mesh's data axes.

    Rows are zero-padded up to the shard multiple so every shard holds the same
    block length (shard_map needs even splits); callers mask the pad rows via
    the true row count. Returns (sharded arrays list, n_shards, n_pad)."""
    axes = data_axes(mesh) if axes is None else tuple(axes)
    n_shards = n_row_shards(mesh, axes)
    n = int(arrays[0].shape[0])
    n_pad = shard_row_extent(n, n_shards) * n_shards
    out = []
    for a in arrays:
        a_np = np.asarray(a)
        assert a_np.shape[0] == n, "row-sharded arrays must share the row count"
        if n_pad > n:
            pad = np.zeros((n_pad - n, *a_np.shape[1:]), a_np.dtype)
            a_np = np.concatenate([a_np, pad], axis=0)
        spec = P(axes, *([None] * (a_np.ndim - 1)))
        out.append(jax.device_put(a_np, NamedSharding(mesh, spec)))
    return out, n_shards, n_pad


def distributed_lloyd_step(mesh: Mesh, use_kernel: bool = False):
    """Returns a jitted step: (x_sharded [N,d], centers [k,d]) →
    (centers', assign, sse). Centres replicated; docs sharded over data axes."""
    axes = data_axes(mesh)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axes, None), P(None, None)),
        out_specs=(P(None, None), P(axes), P()),
        check_vma=False,
    )
    def step(xs, c):
        k = c.shape[0]
        idx, dist = _assign(xs, c, use_kernel=use_kernel)
        onehot = jax.nn.one_hot(idx, k, dtype=xs.dtype)
        sums = jnp.einsum("nk,nd->kd", onehot, xs, precision=EXACT)
        counts = onehot.sum(axis=0)
        for ax in axes:  # hierarchical all-reduce: ICI first, then DCI
            sums = jax.lax.psum(sums, ax)
            counts = jax.lax.psum(counts, ax)
        new_c = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1e-12), c)
        sse = dist.sum()
        for ax in axes:
            sse = jax.lax.psum(sse, ax)
        return new_c, idx, sse

    return jax.jit(step)


def distributed_kmeans(
    mesh: Mesh,
    x: jax.Array,
    k: int,
    iters: int = 20,
    key: Optional[jax.Array] = None,
    use_kernel: bool = False,
):
    """Fixed-iteration distributed Lloyd. ``x`` may be host-global; it is placed
    with a data-sharded NamedSharding. Returns (centers, assign, sse)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    axes = data_axes(mesh)
    x = jax.device_put(x, NamedSharding(mesh, P(axes, None)))
    # k-means++ on a bounded subsample (cheap, replicated) — full-data ++ would
    # serialise k rounds of global argmax; the sample is the standard remedy.
    from repro.core.kmeans import kmeans_pp_init

    k1, k2 = jax.random.split(key)
    n_sample = min(x.shape[0], max(8 * k, 2048))
    picks = jax.random.choice(k1, x.shape[0], (n_sample,), replace=False)
    # a gather from row-sharded x must name its output layout: replicated
    sample = x.at[picks].get(out_sharding=NamedSharding(mesh, P(None, None)))
    centers = jax.device_put(
        kmeans_pp_init(k2, sample, k), NamedSharding(mesh, P(None, None))
    )
    step = distributed_lloyd_step(mesh, use_kernel=use_kernel)
    idx = sse = None
    for _ in range(iters):
        # one step in flight at a time: XLA:CPU's in-process all-reduce can
        # deadlock when two steps' collectives wait on the same thread pool
        # (host-device meshes); on an accelerator it costs a round trip
        centers, idx, sse = jax.block_until_ready(step(x, centers))
    return centers, idx, sse


def distributed_assign_sharded_centers(mesh: Mesh, k_global: int, use_kernel: bool = False):
    """NN assignment when the centre set itself is sharded over ``model``
    (leaf-level K in the tens of thousands): each device scores its centre
    shard, then a tiny (min, argmin) all-gather+reduce combines — collective
    volume is O(B·n_model_shards), not O(B·K).

    Returns jitted fn: (x [B,d] sharded over data axes, centers [K,d] sharded
    over model) → (global idx i32[B], sqdist f32[B]), both data-sharded.
    """
    axes = data_axes(mesh)
    n_shards = mesh.shape["model"]
    k_local = k_global // n_shards

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axes, None), P("model", None)),
        out_specs=(P(axes), P(axes)),
        check_vma=False,
    )
    def assign_fn(xs, cs):
        my_shard = jax.lax.axis_index("model")
        idx_local, dist_local = _assign(xs, cs, use_kernel=use_kernel)
        idx_global = idx_local + my_shard * k_local
        # gather the per-shard winners: [n_shards, B] each — tiny collective
        all_dist = jax.lax.all_gather(dist_local, "model")
        all_idx = jax.lax.all_gather(idx_global, "model")
        w = jnp.argmin(all_dist, axis=0)
        best_idx = jnp.take_along_axis(all_idx, w[None, :], axis=0)[0]
        best_dist = jnp.take_along_axis(all_dist, w[None, :], axis=0)[0]
        return best_idx.astype(jnp.int32), best_dist

    return jax.jit(assign_fn)


def sampled_tree_assign_distributed(mesh: Mesh, tree, x, chunk: int = 4096):
    """Paper §3 at fleet scale: the (small) sample-built tree is replicated and
    every data shard routes its own documents — embarrassingly parallel; the
    only collective is the final result layout. Returns cluster ids [N]."""
    from repro.core import ktree as kt

    axes = data_axes(mesh)
    x = jax.device_put(x, NamedSharding(mesh, P(axes, None)))
    # tree arrays are small (m·#nodes); replicate
    tree = jax.device_put(tree, NamedSharding(mesh, P()))
    return kt.assign_via_tree(tree, x, chunk=chunk)
