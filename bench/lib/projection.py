"""The random projection of a Random Indexing configuration, made by the
benchmark itself from the configuration's ``rp`` spec.

A configuration with ``"representation": "rp"`` states ``"rp": {"dim",
"seed", "kind"}``: the tree is built and descended in ``x ↦ x·P``, P an
``f32[n_cols, dim]`` matrix drawn by jax's threefry generator from
``PRNGKey(seed)`` (DESIGN.md §5.1):

- ``gaussian``: N(0, 1) entries times ``1/sqrt(dim)``;
- ``ternary``: the Random Indexing index vectors of arXiv:1001.0833, ±1 at
  density 1/8 (half each sign) times ``1/sqrt(dim/8)``, from one uniform
  draw: below 1/16 is +, above 15/16 is −.

The reference uses it in float64 (``reference.Projected``). It is drawn on
the host's CPU device, so that the check puts nothing on the chip.
"""
from __future__ import annotations

import math

import numpy as np

KINDS = ("gaussian", "ternary")
DENSITY = 1.0 / 8.0


def matrix(n_cols: int, spec: dict) -> np.ndarray:
    """P, ``f32[n_cols, spec["dim"]]``, for the spec's seed and kind."""
    import jax
    import jax.numpy as jnp

    dim, kind = int(spec["dim"]), spec["kind"]
    if kind not in KINDS:
        raise ValueError(f"no projection kind {kind!r}; have {KINDS}")
    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.random.PRNGKey(int(spec["seed"]))
        if kind == "gaussian":
            p = jax.random.normal(key, (n_cols, dim), jnp.float32) * jnp.float32(
                1.0 / math.sqrt(dim))
        else:
            u = jax.random.uniform(key, (n_cols, dim), jnp.float32)
            scale = jnp.float32(1.0 / math.sqrt(DENSITY * dim))
            p = jnp.where(u < DENSITY / 2, scale,
                          jnp.where(u > 1.0 - DENSITY / 2, -scale, 0.0))
        return np.asarray(p)
