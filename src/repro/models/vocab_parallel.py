"""Vocab-parallel embedding (Megatron-LM style) via shard_map.

GSPMD's gather partitioner (CPU backend especially) falls back to replicating
a vocab-sharded embedding table for ``jnp.take`` — measured as a full
bf16[V,D] + fp32 grad copy per device on the 131k-vocab configs. The classic
fix is explicit: each shard masks ids outside its vocab range, looks up
locally, zero-fills, and psums over the vocab axis. The VJP is then a purely
local scatter-add into the local shard — no replicated [V,D] buffers anywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

shard_map = jax.shard_map

from repro.models import sharding as sh


def embed_lookup(
    embed: jax.Array, tokens: jax.Array, tok_logical=("batch", "seq")
) -> jax.Array:
    """tokens [B,S] → [B,S,D]. Uses the vocab-parallel path when a sharding
    rules context is active and the vocab axis is actually sharded; plain
    take() otherwise (single-device tests). ``tok_logical`` is the tokens'
    logical sharding (decode passes (batch, None) — a length-1 dim can't
    shard)."""
    rules = sh.current_rules()
    mesh = sh._MESH.get()
    if rules is None or mesh is None:
        return jnp.take(embed, tokens, axis=0)
    vocab_ax = rules.table.get("vocab")
    if vocab_ax is None:
        return jnp.take(embed, tokens, axis=0)
    vocab_ax = vocab_ax if isinstance(vocab_ax, str) else vocab_ax[0]
    n_shards = mesh.shape[vocab_ax]
    if embed.shape[0] % n_shards != 0:
        return jnp.take(embed, tokens, axis=0)
    vshard = embed.shape[0] // n_shards
    tok_spec = rules.spec(*tok_logical)

    fn = shard_map(
        lambda etab, toks: _local_lookup(etab, toks, vocab_ax, vshard),
        mesh=mesh,
        in_specs=(P(vocab_ax, None), tok_spec),
        out_specs=P(*(tuple(tok_spec) + (None,))),
        check_vma=False,
    )
    return fn(embed, tokens)


def _local_lookup(etab, toks, vocab_ax, vshard):
    idx = jax.lax.axis_index(vocab_ax)
    local = toks - idx * vshard
    ok = jnp.logical_and(local >= 0, local < vshard)
    x = jnp.take(etab, jnp.clip(local, 0, vshard - 1), axis=0)
    x = jnp.where(ok[..., None], x, jnp.zeros((), x.dtype))
    return jax.lax.psum(x, vocab_ax)
