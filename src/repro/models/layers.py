"""Common NN layers in pure JAX (no flax): params are nested dicts of arrays;
every layer is (init, apply) pairs. Matmul-heavy ops take an optional
``dtype`` for bf16 compute with fp32 params/accumulation.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sharding import constrain


def dense_init(key, in_dim: int, out_dim: int, scale: Optional[float] = None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


def rmsnorm_init(dim: int, dtype=jnp.float32):
    return jnp.ones((dim,), dtype)


def rmsnorm(x: jax.Array, g: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g


def mlp_stack_init(key, dims: Sequence[int], dtype=jnp.float32):
    """[(w,b), ...] for a plain ReLU MLP with the given layer widths."""
    params = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        key, sub = jax.random.split(key)
        params.append(
            {"w": dense_init(sub, a, b, dtype=dtype), "b": jnp.zeros((b,), dtype)}
        )
    return params


def mlp_stack_apply(params, x: jax.Array, act=jax.nn.relu, final_act: bool = False) -> jax.Array:
    for i, p in enumerate(params):
        x = x @ p["w"] + p["b"]
        if i < len(params) - 1 or final_act:
            x = act(x)
    return x


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    freqs = rope_freqs(x.shape[-1], theta)                      # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]                         # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention — chunked (flash-style) causal for train/prefill, cached for decode
# ---------------------------------------------------------------------------

def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B,S,Hk,hd] → [B,S,Hk*n_rep,hd] (GQA head sharing)."""
    if n_rep == 1:
        return k
    b, s, hk, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, hk, n_rep, hd)).reshape(
        b, s, hk * n_rep, hd
    )


def flash_attention(
    q: jax.Array,      # [B, Sq, H, hd]
    k: jax.Array,      # [B, Skv, Hk, hd]
    v: jax.Array,      # [B, Skv, Hk, hd]
    q_offset: jax.Array | int = 0,   # global position of q[0] (seq-sharded q)
    causal: bool = True,
    kv_chunk: int = 1024,
    unroll: bool = False,            # dry-run probes: unroll so cost_analysis
                                     # counts every KV chunk (scan bodies are
                                     # otherwise costed once)
) -> jax.Array:
    """Memory-O(chunk) causal attention: lax.scan over KV chunks with the
    online-softmax accumulator. Peak intermediate = [B,H,Sq,kv_chunk] instead of
    [B,H,Sq,Skv] — what makes the 32k-prefill cells fit (DESIGN §5)."""
    b, sq, h, hd = q.shape
    skv, hk = k.shape[1], k.shape[2]
    n_rep = h // hk
    kv_chunk = min(kv_chunk, skv)
    n_chunks = -(-skv // kv_chunk)
    pad = n_chunks * kv_chunk - skv
    if pad:  # internal padding; padded keys are masked below via k_pos >= skv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / np.sqrt(hd)

    kc = k.reshape(b, n_chunks, kv_chunk, hk, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, hk, hd).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(sq)                            # [Sq] global

    def step(carry, inp):
        m, l, o = carry                                          # [B,H,Sq],[B,H,Sq],[B,H,Sq,hd]
        kb, vb, c_idx = inp                                      # [B,ck,Hk,hd]
        kb = _repeat_kv(kb, n_rep)
        vb = _repeat_kv(vb, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
        k_pos = c_idx * kv_chunk + jnp.arange(kv_chunk)          # [ck] global
        mask = k_pos[None, :] < skv                              # padded tail
        if causal:
            mask = jnp.logical_and(mask, q_pos[:, None] >= k_pos[None, :])
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows (exp(-inf - -inf))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        step, (m0, l0, o0), (kc, vc, jnp.arange(n_chunks)),
        unroll=n_chunks if unroll else 1,
    )
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 2, 1, 3).astype(q.dtype)               # [B,Sq,H,hd]


def dense_attention(
    q: jax.Array,      # [B, Sq, H, hd]
    k: jax.Array,      # [B, Skv, Hk, hd]
    v: jax.Array,
    causal: bool = True,
) -> jax.Array:
    """Materialised-scores attention — the *training* path. Under full remat
    the [B,H,Sq,Skv] scores are transient in fwd and recomputed in bwd, which
    beats flash-scan's per-chunk VJP residuals at train seq lengths (the
    hypothesis→measure log for this choice is in EXPERIMENTS.md §Perf)."""
    b, sq, h, hd = q.shape
    n_rep = h // k.shape[2]
    kb = _repeat_kv(k, n_rep)
    vb = _repeat_kv(v, n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) / np.sqrt(hd)
    if causal:
        q_pos = jnp.arange(sq)
        k_pos = jnp.arange(kb.shape[1])
        s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vb)
    return o


def flash_decode_attention(
    q: jax.Array,        # [B, 1, H, hd]
    k_cache: jax.Array,  # [B, S, Hk, hd]  (seq sharded over kv axes)
    v_cache: jax.Array,
    length: jax.Array,   # i32[] valid cache prefix
) -> jax.Array:
    """Flash-decoding (§Perf iteration 2): explicit shard_map over the cache's
    sharded seq axis — each shard computes a partial softmax over its KV slice
    and the combine is a tiny (pmax, psum) of per-query stats. Left to GSPMD,
    the einsum gets resharded onto kv-heads and the repeated-KV broadcast is
    *replicated* (measured 2.9 GiB/layer on qwen decode_32k).

    Requires an active sharding-rules context; callers fall back to
    :func:`decode_attention` otherwise."""
    from repro.models import sharding as sh
    from jax.sharding import PartitionSpec as P

    rules, mesh = sh.current_rules(), sh._MESH.get()
    b, s, hk, hd = k_cache.shape
    h = q.shape[2]
    kv_name = "kv_seq_b1" if b == 1 else "kv_seq"
    kv_ax = rules.table.get(kv_name)
    if kv_ax is None:
        return decode_attention(q, k_cache, v_cache, length)
    kv_axes = (kv_ax,) if isinstance(kv_ax, str) else tuple(kv_ax)
    b_ax = None if b == 1 else rules.table.get("batch")
    n_shards = 1
    for a in kv_axes:
        n_shards *= mesh.shape[a]
    s_loc = s // n_shards
    scale = 1.0 / np.sqrt(hd)

    def local(qb, kb, vb):
        # shard-local partial attention over [B_loc, S_loc]
        idx = jax.lax.axis_index(kv_axes)          # flattened shard id
        k_pos = idx * s_loc + jnp.arange(s_loc)
        kb_r = _repeat_kv(kb, h // hk)
        vb_r = _repeat_kv(vb, h // hk)
        sL = jnp.einsum("bqhd,bkhd->bhqk", qb, kb_r).astype(jnp.float32) * scale
        valid = (k_pos < length)[None, None, None, :]
        sL = jnp.where(valid, sL, -jnp.inf)
        m = sL.max(axis=-1)                         # [B,H,1]
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(jnp.isfinite(sL), jnp.exp(sL - m_safe[..., None]), 0.0)
        l = p.sum(axis=-1)
        o = jnp.einsum("bhqk,bkhd->bhqd", p, vb_r.astype(jnp.float32))
        # combine partial softmaxes across shards
        gm = jax.lax.pmax(m, kv_axes)
        gm_safe = jnp.where(jnp.isfinite(gm), gm, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - gm_safe), 0.0)
        L = jax.lax.psum(l * alpha, kv_axes)
        O = jax.lax.psum(o * alpha[..., None], kv_axes)
        out = O / jnp.maximum(L, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(qb.dtype)   # [B,1,H,hd]

    fn = shard_map_fn(
        local,
        mesh=mesh,
        in_specs=(
            P(b_ax, None, None, None),
            P(b_ax, kv_axes, None, None),
            P(b_ax, kv_axes, None, None),
        ),
        out_specs=P(b_ax, None, None, None),
    )
    return fn(q, k_cache, v_cache)


def shard_map_fn(f, mesh, in_specs, out_specs):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def decode_attention(
    q: jax.Array,        # [B, 1, H, hd]
    k_cache: jax.Array,  # [B, S, Hk, hd]
    v_cache: jax.Array,  # [B, S, Hk, hd]
    length: jax.Array,   # i32[] or i32[B] — valid cache prefix
) -> jax.Array:
    """One-token attention against the cache. The cache seq dim may be sharded
    (flash-decoding): the max/sum reductions below become partial-reduce +
    tiny all-reduce under GSPMD."""
    b, s, hk, hd = k_cache.shape
    h = q.shape[2]
    kb = _repeat_kv(k_cache, h // hk)
    vb = _repeat_kv(v_cache, h // hk)
    scale = 1.0 / np.sqrt(hd)
    s_logits = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.broadcast_to(jnp.atleast_1d(length)[:, None], (b, s))
    s_logits = jnp.where(valid[:, None, None, :], s_logits, -jnp.inf)
    p = jax.nn.softmax(s_logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# cross-entropy with sharded vocab
# ---------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, labels: jax.Array, valid: Optional[jax.Array] = None):
    """Mean token cross-entropy, safe for a vocab-sharded last dim: the gold
    logit is selected with an iota-compare reduction (fusable, partial+psum
    under GSPMD) instead of take_along_axis (which would gather the shard)."""
    logits32 = logits.astype(jnp.float32)
    m = logits32.max(axis=-1, keepdims=True)
    z = jnp.log(jnp.sum(jnp.exp(logits32 - m), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(labels.dtype, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == labels[..., None], logits32, 0.0), axis=-1)
    nll = z - gold
    if valid is None:
        return nll.mean()
    w = valid.astype(jnp.float32)
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)
