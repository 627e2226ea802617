"""Sharded pytree checkpointing with atomic renames and elastic restore."""
from __future__ import annotations

import os
import re
import shutil
from typing import Any, Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

_STEP_RE = re.compile(r"^step_(\d{9})$")


def _leaf_paths(tree: Any):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def save(ckpt_dir: str, tree: Any, step: int) -> str:
    """Atomic save: write to step_xxx.tmp, fsync, rename."""
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:09d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    leaves, treedef = _leaf_paths(tree)
    manifest = {"treedef": str(treedef), "n_leaves": len(leaves), "step": step, "leaves": []}
    for i, leaf in enumerate(leaves):
        arr = np.asarray(jax.device_get(leaf))
        np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), arr)
        manifest["leaves"].append({"i": i, "dtype": str(arr.dtype), "shape": list(arr.shape)})
    with open(os.path.join(tmp, "MANIFEST.msgpack"), "wb") as f:
        f.write(msgpack.packb(manifest))
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    _gc(ckpt_dir, keep=3)
    return final


def _gc(ckpt_dir: str, keep: int):
    steps = sorted(
        (int(m.group(1)), name)
        for name in os.listdir(ckpt_dir)
        if (m := _STEP_RE.match(name))
    )
    for _, name in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, name), ignore_errors=True)


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Highest step number with a completed ``step_xxx`` directory in
    ``ckpt_dir`` (None when none exist)."""
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(m.group(1)) for n in os.listdir(ckpt_dir) if (m := _STEP_RE.match(n))]
    return max(steps) if steps else None


def restore(ckpt_dir: str, like: Any, step: Optional[int] = None, shardings: Any = None) -> Any:
    """Restore into the structure of ``like``. ``shardings`` (optional pytree of
    NamedShardings) re-places leaves against the *current* mesh — elastic
    restore across fleet-size changes."""
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:09d}")
    like_leaves, treedef = jax.tree.flatten(like)
    mpath = os.path.join(path, "MANIFEST.msgpack")
    try:
        with open(mpath, "rb") as f:
            manifest = msgpack.unpackb(f.read())
    except (ValueError, msgpack.exceptions.ExtraData,
            msgpack.exceptions.UnpackException) as e:
        from repro.core.store import ManifestError

        raise ManifestError(
            mpath, f"corrupt or truncated checkpoint manifest ({e})"
        ) from e
    assert manifest["n_leaves"] == len(like_leaves), (
        f"checkpoint has {manifest['n_leaves']} leaves, expected {len(like_leaves)}"
    )
    shard_leaves = (
        treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(like_leaves)
    )
    out = []
    for i, (ref, shard) in enumerate(zip(like_leaves, shard_leaves)):
        arr = np.load(os.path.join(path, f"leaf_{i:05d}.npy"))
        if shard is not None:
            out.append(jax.device_put(arr, shard))
        else:
            out.append(jnp.asarray(arr, dtype=getattr(ref, "dtype", None)))
    return treedef.unflatten(out)


# --- K-tree persistence (paper: "efficient disk based implementations") -----

def save_ktree(path: str, tree, projection=None) -> str:
    """Atomic single-file K-tree snapshot (tmp + rename, like :func:`save`).

    Extended dtypes (bfloat16 & friends) are not understood by the .npy
    format's descr — ``np.save`` silently writes them as opaque void bytes
    that ``jnp.asarray`` then rejects on load. Each field's true dtype is
    recorded in the meta blob and non-native float dtypes are stored upcast
    to float32 (lossless); :func:`restore_ktree` casts back.

    ``projection`` (a ``repro.core.backend.RandomProjection``) records the
    random-projection *spec* — seed, dims, kind, dtype — in the meta blob.
    The matrix itself is never written: jax's threefry PRNG is deterministic,
    so the spec replays it bit-exactly (DESIGN.md §5.1). Read it back with
    :func:`load_ktree_projection`."""
    import dataclasses

    final = path if path.endswith(".npz") else path + ".npz"
    os.makedirs(os.path.dirname(final) or ".", exist_ok=True)
    arrays, dtypes = {}, {}
    for f in dataclasses.fields(tree):
        if f.metadata.get("static"):
            continue
        arr = np.asarray(jax.device_get(getattr(tree, f.name)))
        dtypes[f.name] = str(arr.dtype)
        if arr.dtype.kind == "V":  # extended float (e.g. bfloat16)
            arr = arr.astype(np.float32)
        arrays[f.name] = arr
    meta = {"order": tree.order, "medoid": tree.medoid, "dtypes": dtypes}
    if projection is not None:
        meta["projection"] = projection.spec()
    tmp = final + ".tmp.npz"
    np.savez(tmp, **arrays, _meta=np.frombuffer(msgpack.packb(meta), dtype=np.uint8))
    os.replace(tmp, final)
    return final


def restore_ktree(path: str):
    """Load a :func:`save_ktree` snapshot back into a live ``KTree`` (accepts
    the path with or without its ``.npz`` suffix; per-field dtypes restored
    from the meta blob). A snapshot whose ``centers`` hold only the m+1
    entry slots (written before the slot axis was padded) is padded to the
    stored width on load."""
    from repro.core.ktree import KTree, stored_slots

    data = np.load(path if path.endswith(".npz") else path + ".npz")
    meta = msgpack.unpackb(data["_meta"].tobytes())
    dtypes = meta.get("dtypes", {})  # absent in pre-fix checkpoints
    arrays = {k: v for k, v in data.items() if k != "_meta"}
    order = int(meta["order"])
    c = arrays["centers"]
    pad = stored_slots(order + 1, dtypes.get("centers", c.dtype)) - c.shape[1]
    if pad:
        arrays["centers"] = np.pad(c, ((0, 0), (0, pad), (0, 0)))
    kwargs = {k: jnp.asarray(v, dtype=dtypes.get(k)) for k, v in arrays.items()}
    return KTree(order=order, medoid=bool(meta["medoid"]), **kwargs)


def load_ktree_projection(path: str):
    """Replay the ``RandomProjection`` recorded by
    ``save_ktree(..., projection=...)`` (None when the snapshot was saved
    without one). The matrix is rebuilt from the stored spec via
    ``projection_from_spec`` — bit-identical to the one used at save time."""
    from repro.core.backend import projection_from_spec

    data = np.load(path if path.endswith(".npz") else path + ".npz")
    meta = msgpack.unpackb(data["_meta"].tobytes())
    spec = meta.get("projection")
    return None if spec is None else projection_from_spec(spec)


# --- store-backed index persistence (DESIGN.md §9) ---------------------------

INDEX_META_NAME = "INDEX.json"


def save_index(path: str, tree, store, projection=None) -> str:
    """Checkpoint a store-backed index **by manifest reference**: the tree's
    array pages are snapshotted (``tree.npz``, via :func:`save_ktree`) next to
    a small JSON that records the corpus store's path and
    ``manifest_hash`` — the corpus itself (the large side of the index) is
    never copied or materialised.

    ``path`` becomes a directory ``{tree.npz, INDEX.json}``; the write lands
    in a tmp dir and installs by rename (an existing checkpoint is moved
    aside and removed only after the replacement is in place, so a crash
    never destroys the previous restore point). Restore with
    :func:`restore_index`, which re-opens the store and refuses to pair the
    tree with a corpus whose manifest content changed (regenerated in place →
    stale doc ids). A store grown by ``ktree.insert_into_store`` rotates its
    ``manifest_hash`` the same way: re-checkpoint the grown (tree, store)
    pair afterwards — the pre-insert checkpoint correctly refuses to restore
    against the extended corpus.

    ``projection`` (a ``RandomProjection``) records the random-projection
    spec in both the tree snapshot and ``INDEX.json`` for an RP-routed index
    (tree built over ``RandomProjBackend.from_store``). Restore rebuilds the
    matrix bit-exactly from the spec and refuses a caller-expected projection
    that differs (``ProjectionMismatch``), the same contract as a rewritten
    store."""
    import json

    from repro.core.store import _install_dir

    tmp = path.rstrip("/") + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    save_ktree(os.path.join(tmp, "tree"), tree, projection=projection)
    ref = {
        "store_path": os.path.abspath(store.path),
        "manifest_hash": store.manifest_hash,
        "kind": store.kind,
        "n_docs": store.n_docs,
    }
    if projection is not None:
        ref["projection"] = projection.spec()
    with open(os.path.join(tmp, INDEX_META_NAME), "w") as f:
        json.dump(ref, f, indent=1, sort_keys=True)
    _install_dir(tmp, path)
    return path


def restore_index(
    path: str,
    budget_bytes: Optional[int] = None,
    check: bool = True,
    projection=None,
):
    """Restore a :func:`save_index` checkpoint → ``(tree, store)``, or
    ``(tree, store, projection)`` when the checkpoint recorded a
    random-projection spec (``save_index(..., projection=...)``).

    The store is re-opened from the recorded path with ``budget_bytes`` of
    block-cache residency (default: the store module's default budget).
    ``check=True`` (default) verifies the store's current ``manifest_hash``
    against the one recorded at save time and raises ``ValueError`` on
    mismatch — the corpus was regenerated in place (or grown by
    ``insert_into_store`` after the save), so the tree's doc ids would
    silently address different (or fewer) documents than the tree that was
    checkpointed alongside them. One mismatch is allowed: a store *repaired*
    by ``store_fsck`` records its pre-repair hashes in the manifest's
    ``fsck_lineage`` chain — excision keeps blocks positional, so the tree's
    doc ids still address the same rows and the pair restores (reads of the
    excised blocks fail typed / degrade, DESIGN.md §10). A corrupt or
    truncated ``INDEX.json`` raises a typed
    ``repro.core.store.ManifestError`` naming the file.

    ``projection`` states the projection the caller *expects* (a
    ``RandomProjection`` or a spec dict). A recorded projection that differs
    from the expectation in any field — seed, dims, kind, dtype — raises
    ``repro.core.backend.ProjectionMismatch``: routing a tree built under one
    projection with a different matrix silently degrades every query, the
    exact analogue of pairing a tree with a rewritten corpus. Expecting a
    projection when none was recorded (or vice versa when the checkpoint
    carries one and dims disagree with the tree/store) is refused the same
    way. The returned projection's matrix is replayed bit-exactly from the
    stored seed."""
    from repro.core.backend import ProjectionMismatch, projection_from_spec
    from repro.core.store import (
        DEFAULT_BUDGET_BYTES, ManifestError, load_manifest, open_store,
    )

    ipath = os.path.join(path, INDEX_META_NAME)
    if not os.path.exists(ipath):
        raise FileNotFoundError(
            f"no store-backed index checkpoint at {path} "
            f"(missing {INDEX_META_NAME})"
        )
    ref = load_manifest(ipath)
    for key in ("store_path", "manifest_hash"):
        if key not in ref:
            raise ManifestError(
                ipath, f"index reference is missing the {key!r} field "
                       "(corrupt or not a save_index checkpoint)"
            )
    tree = restore_ktree(os.path.join(path, "tree"))
    store = open_store(
        ref["store_path"],
        budget_bytes=DEFAULT_BUDGET_BYTES if budget_bytes is None else budget_bytes,
    )
    if check and store.manifest_hash != ref["manifest_hash"]:
        if ref["manifest_hash"] not in store.manifest.get("fsck_lineage", ()):
            raise ValueError(
                f"index {path} references corpus store {ref['store_path']} "
                f"with manifest hash {ref['manifest_hash']}, but the store on "
                f"disk now hashes to {store.manifest_hash} — the corpus was "
                "rewritten in place; rebuild the index (or pass check=False "
                "to pair anyway)"
            )
    expected = projection.spec() if hasattr(projection, "spec") else projection
    recorded = ref.get("projection")
    if recorded is None:
        if expected is not None:
            raise ProjectionMismatch(
                f"index {path} records no random projection but the caller "
                f"expects one ({expected}) — this checkpoint was built on the "
                "exact (unprojected) path"
            )
        return tree, store
    if expected is not None and dict(expected) != dict(recorded):
        raise ProjectionMismatch(
            f"index {path} records projection {recorded} but the caller "
            f"expects {expected} — routing this tree under a different "
            "projection silently degrades every query; rebuild the index"
        )
    proj = projection_from_spec(recorded)
    if proj.out_dim != tree.dim or proj.in_dim != store.dim:
        raise ProjectionMismatch(
            f"index {path} records projection "
            f"{proj.in_dim}→{proj.out_dim} but the restored tree has dim "
            f"{tree.dim} and the store has dim {store.dim} — checkpoint and "
            "corpus disagree; rebuild the index"
        )
    return tree, store, proj
