"""Grouped-query attention with train / prefill / decode paths.

One implementation serves every attention-bearing architecture:
* GQA with arbitrary (n_heads, n_kv_heads) — MHA when equal;
* causal or bidirectional masking;
* optional sliding window (Mixtral / long-context dense variants);
* KV cache for prefill (fill) and decode (single-token append);
* cross-attention (keys/values from encoder memory).

Layout conventions: activations (B, S, d); q/k/v (B, S, H, hd); KV cache
(B, S_max, H_kv, hd).  Scores run in fp32.  The decode path writes the cache
at ``pos`` via dynamic_update_slice (donated in serve_step).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels import flash_attention, ops as kernel_ops
from .layers import apply_rope, dense, dense_init

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array        # (B, S_max, H_kv, hd)
    v: jax.Array        # (B, S_max, H_kv, hd)


def attn_init(rng: jax.Array, d: int, n_heads: int, n_kv: int, head_dim: int,
              dtype=jnp.float32, qkv_bias: bool = False) -> Dict[str, Any]:
    kq, kk, kv, ko = jax.random.split(rng, 4)
    p = {"wq": dense_init(kq, d, n_heads * head_dim, dtype),
         "wk": dense_init(kk, d, n_kv * head_dim, dtype),
         "wv": dense_init(kv, d, n_kv * head_dim, dtype),
         "wo": dense_init(ko, n_heads * head_dim, d, dtype,
                          scale=1.0 / math.sqrt(n_heads * head_dim))}
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def _project_qkv(p, x_q, x_kv, n_heads, n_kv, head_dim):
    B, Sq = x_q.shape[:2]
    Skv = x_kv.shape[1]
    q = dense({"w": p["wq"]["w"]}, x_q)
    k = dense({"w": p["wk"]["w"]}, x_kv)
    v = dense({"w": p["wv"]["w"]}, x_kv)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, Sq, n_heads, head_dim),
            k.reshape(B, Skv, n_kv, head_dim),
            v.reshape(B, Skv, n_kv, head_dim))


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
         mask: Optional[jax.Array], scale: Optional[float] = None) -> jax.Array:
    """Grouped-query attention without materializing expanded KV.

    q: (B,Sq,H,hd); k/v: (B,Skv,Hkv,hd) with H = g·Hkv; mask broadcastable
    to (B,1/H,Sq,Skv) (True = attend).  The query heads are reshaped into
    (Hkv, g) groups and contracted against the *unexpanded* KV — a
    ``jnp.repeat`` expansion costs rep× KV memory and forces GSPMD to
    rematerialize sharded caches (measured 2 GiB all-gather per decode
    layer)."""
    B, Sq, H, hd = q.shape
    hkv = k.shape[2]
    g = H // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, hkv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        # mask comes in as (B,1,Sq,Skv)-ish; insert the group axis
        m = jnp.expand_dims(mask, 2) if mask.ndim == 4 else mask
        scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, hd)


def make_mask(Sq: int, Skv: int, causal: bool, window: Optional[int],
              q_offset: int = 0) -> Optional[jax.Array]:
    """(1,1,Sq,Skv) boolean mask.  ``q_offset`` shifts query positions (for
    prefill continuation); ``window`` keeps keys within [pos-window+1, pos]."""
    if not causal and window is None:
        return None
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Skv)
    rel = qpos[:, None] - kpos[None, :]
    m = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        m &= rel >= 0
    if window is not None:
        m &= rel < window
    return m[None, None]


def attn_train(p, x: jax.Array, *, n_heads: int, n_kv: int, head_dim: int,
               causal: bool = True, window: Optional[int] = None,
               rope_fraction: float = 1.0, rope_theta: float = 10_000.0,
               x_kv: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence attention (training / encoder).  ``x_kv`` switches to
    cross-attention (no RoPE on keys of encoder memory by convention here —
    both sides get positions of their own sequence).

    On a TPU, self-attention without a window whose length the kernel can
    block runs the blocked kernel (``kernels/flash_attention``); everything
    else, and every path off a TPU, runs ``sdpa``."""
    src = x if x_kv is None else x_kv
    q, k, v = _project_qkv(p, x, src, n_heads, n_kv, head_dim)
    if rope_fraction > 0:
        qpos = jnp.arange(x.shape[1])[None]
        kpos = jnp.arange(src.shape[1])[None]
        q = apply_rope(q, qpos, rope_fraction, rope_theta)
        k = apply_rope(k, kpos, rope_fraction, rope_theta)
    B, S = x.shape[:2]
    if (x_kv is None and window is None and not kernel_ops.off_tpu()
            and flash_attention.block_size(S, head_dim)):
        out = flash_attention.attention(q, k, v, causal)
    else:
        out = sdpa(q, k, v, make_mask(S, src.shape[1],
                                      causal and x_kv is None, window))
    return dense({"w": p["wo"]["w"]}, out.reshape(B, S, n_heads * head_dim))


def attn_prefill(p, x: jax.Array, *, n_heads: int, n_kv: int, head_dim: int,
                 cache_len: int, window: Optional[int] = None,
                 rope_fraction: float = 1.0, rope_theta: float = 10_000.0
                 ) -> Tuple[jax.Array, KVCache]:
    """Causal attention over the prompt, emitting a KV cache of cache_len
    (>= S; right-padded)."""
    B, S = x.shape[:2]
    q, k, v = _project_qkv(p, x, x, n_heads, n_kv, head_dim)
    if rope_fraction > 0:
        pos = jnp.arange(S)[None]
        q = apply_rope(q, pos, rope_fraction, rope_theta)
        k = apply_rope(k, pos, rope_fraction, rope_theta)
    mask = make_mask(S, S, True, window)
    out = sdpa(q, k, v, mask)
    pad = [(0, 0), (0, cache_len - S), (0, 0), (0, 0)]
    cache = KVCache(k=jnp.pad(k, pad), v=jnp.pad(v, pad))
    return (dense({"w": p["wo"]["w"]}, out.reshape(B, S, n_heads * head_dim)),
            cache)


def attn_decode(p, x: jax.Array, cache: KVCache, pos: jax.Array, *,
                n_heads: int, n_kv: int, head_dim: int,
                window: Optional[int] = None,
                rope_fraction: float = 1.0, rope_theta: float = 10_000.0
                ) -> Tuple[jax.Array, KVCache]:
    """One-token decode. x: (B, 1, d); ``pos`` scalar int32 — the index of
    this token; cache holds positions [0, pos)."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, x, n_heads, n_kv, head_dim)
    if rope_fraction > 0:
        pvec = jnp.full((1, 1), pos, dtype=jnp.int32)
        q = apply_rope(q, pvec, rope_fraction, rope_theta)
        k = apply_rope(k, pvec, rope_fraction, rope_theta)
    new_k = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                         (0, pos, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                         (0, pos, 0, 0))
    # Pin the decode layout: cache + query stay head_dim-sharded so the
    # score contraction psums a (B,H,1,S) f32 instead of GSPMD re-gathering
    # the whole cache (measured 1 GiB/layer without these).
    q = constrain(q, ("batch", None, None, "model"))
    new_k = constrain(new_k, ("batch", None, None, "model"))
    new_v = constrain(new_v, ("batch", None, None, "model"))
    S_max = new_k.shape[1]
    kpos = jnp.arange(S_max)
    valid = kpos <= pos
    if window is not None:
        valid &= kpos > pos - window
    mask = valid[None, None, None, :]      # (1,1,1,S_max)
    out = sdpa(q, new_k.astype(q.dtype), new_v.astype(q.dtype), mask)
    y = dense({"w": p["wo"]["w"]}, out.reshape(B, 1, n_heads * head_dim))
    return y, KVCache(k=new_k, v=new_v)


def attn_flops(tokens: int, kv_tokens: int, d: int, n_heads: int, n_kv: int,
               head_dim: int) -> float:
    """Forward FLOPs: projections + scores + value mix."""
    proj = 2.0 * tokens * d * (n_heads * head_dim) \
        + 2.0 * 2.0 * kv_tokens * d * (n_kv * head_dim) \
        + 2.0 * tokens * (n_heads * head_dim) * d
    scores = 2.0 * 2.0 * tokens * kv_tokens * n_heads * head_dim
    return proj + scores
