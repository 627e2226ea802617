"""Pallas TPU kernels for the K-tree hot spots (DESIGN.md §3.3/§3.4): fused
nearest-centre search (``nn_assign``), its top-k generalisation
(``nn_topk``), and sparse-document scoring (``ell_spmm``). ``ops`` pads and
dispatches them; ``ref`` holds the pure-jnp oracles."""
from __future__ import annotations

import jax

_MIB = 1 << 20
DEFAULT_SCOPED_VMEM = 16 * _MIB
"""Mosaic's default scoped-VMEM limit per kernel on v5e (the core has
128 MiB of VMEM; a kernel may ask for more than the default)."""


def scoped_vmem_limit(pipelined_bytes: int, resident_bytes: int = 0) -> int:
    """``vmem_limit_bytes`` for a kernel whose BlockSpec operands total
    ``pipelined_bytes`` per grid step (the pipeline double-buffers each of
    them) plus ``resident_bytes`` of scratch, with 8 MiB of headroom for
    in-kernel temporaries; never below the default limit. The full-``D``
    blocks at the culled vocabulary (d = 8000 → 8064) need ~17 MiB, just
    over the default."""
    need = 2 * pipelined_bytes + resident_bytes + 8 * _MIB
    return max(DEFAULT_SCOPED_VMEM, -(-need // _MIB) * _MIB)


def on_tpu() -> bool:
    """Whether the computation being traced runs on a TPU: the platform of an
    enclosing ``jax.default_device`` scope, else the default backend's. It
    picks the Pallas kernels over the ``ref`` oracles, and compiled over
    interpret mode. ``jax_default_device`` is part of the jit cache key, so
    a trace made under one scope is never reused under another: on a TPU
    host, ``with jax.default_device(jax.devices("cpu")[0])`` runs the oracle
    path on the host's CPU."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend() == "tpu"
    return (dev if isinstance(dev, str) else dev.platform) == "tpu"
