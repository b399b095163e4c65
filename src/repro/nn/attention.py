"""GQA attention: train/prefill (blocked-causal flash), decode (KV cache),
local-window (RecurrentGemma), bidirectional (encoder) and cross attention.

Train and prefill attention goes through ``kernels.ops.self_attention``:
on a TPU, causal attention over whole kernel blocks runs the splash
kernel; everywhere else it runs ``flash_attention`` below, a blocked scan
with the flash kernels' structure (block decomposition, online softmax):
only lower-triangle (q_block, kv_block) pairs are computed, giving ~2x
FLOP savings over naive causal attention and O(S·C) live memory instead
of O(S^2).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.ops import self_attention
from repro.nn import params as prm
from repro.nn.layers import apply_rope, def_headnorm, headnorm
from repro.nn.policy import interior_pref
from repro.parallel import shard

NEG_INF = -1e30


def def_gqa(d_model, n_heads, n_kv_heads, head_dim, qkv_bias=False, qk_norm=False):
    d = {
        "wq": prm.ParamDef((d_model, n_heads, head_dim), ("embed", "heads", "head_dim"),
                           init="scaled_fan_in"),
        "wk": prm.ParamDef((d_model, n_kv_heads, head_dim), ("embed", "kv_heads", "head_dim"),
                           init="scaled_fan_in"),
        "wv": prm.ParamDef((d_model, n_kv_heads, head_dim), ("embed", "kv_heads", "head_dim"),
                           init="scaled_fan_in"),
        "wo": prm.ParamDef((n_heads, head_dim, d_model), ("heads", "head_dim", "embed"),
                           init="scaled_fan_in"),
    }
    if qkv_bias:
        d["bq"] = prm.ParamDef((n_heads, head_dim), ("heads", "head_dim"), init="zeros")
        d["bk"] = prm.ParamDef((n_kv_heads, head_dim), ("kv_heads", "head_dim"), init="zeros")
        d["bv"] = prm.ParamDef((n_kv_heads, head_dim), ("kv_heads", "head_dim"), init="zeros")
    if qk_norm:
        d["q_norm"] = def_headnorm(head_dim)
        d["k_norm"] = def_headnorm(head_dim)
    return d


class KVCache(NamedTuple):
    k: jax.Array  # (B, n_kv, S_max, head_dim)
    v: jax.Array  # (B, n_kv, S_max, head_dim)


def _project_qkv(p, x, positions, rope_theta, use_rope=True):
    """x: (B, S, d) → q (B, H, S, hd), k/v (B, KV, S, hd)."""
    pref = interior_pref()
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"], preferred_element_type=pref)
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"], preferred_element_type=pref)
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"], preferred_element_type=pref)
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)[None, :, None, :]
        k = k + p["bk"].astype(k.dtype)[None, :, None, :]
        v = v + p["bv"].astype(v.dtype)[None, :, None, :]
    q, k, v = q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype)
    if "q_norm" in p:
        q = headnorm(p["q_norm"], q)
        k = headnorm(p["k_norm"], k)
    if use_rope:
        q = apply_rope(q, positions[:, None, :], rope_theta)
        k = apply_rope(k, positions[:, None, :], rope_theta)
    q = shard(q, "batch_attn", "heads", "attn_seq", "head_dim")
    k = shard(k, "batch_attn", "kv_heads", "attn_seq", "head_dim")
    v = shard(v, "batch_attn", "kv_heads", "attn_seq", "head_dim")
    return q, k, v


def _group_q(q, n_kv):
    """(B, H, S, D) → (B, KV, G, S, D) grouping query heads per kv head."""
    b, h, s, d = q.shape
    return q.reshape(b, n_kv, h // n_kv, s, d)


def _flash_block(q, k, v, m, l, o, mask):
    """One online-softmax accumulation step.

    q: (B, KV, G, Sq, D); k/v: (B, KV, C, D); mask: broadcastable (Sq, C) or None.
    m/l: (B, KV, G, Sq); o: (B, KV, G, Sq, D); all fp32 accumulators.
    """
    s = jnp.einsum("bkgsd,bkcd->bkgsc", q, k, preferred_element_type=jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p_ = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p_, axis=-1)
    pv = jnp.einsum("bkgsc,bkcd->bkgsd", p_.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * alpha[..., None] + pv
    return m_new, l_new, o_new


def _finish(m, l, o, dtype):
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def _pick_chunk(s: int, want: int) -> int:
    """Largest divisor of s that is <= want (handles seqs like 1500)."""
    c = min(want, s)
    while s % c:
        c -= 1
    return c


def flash_attention(q, k, v, *, causal=True, window=0, chunk=512,
                    q_offset=0):
    """Blocked flash attention over (B, H, S, D) q and (B, KV, Skv, D) k/v.

    ``window > 0`` restricts each query to the last ``window`` keys (local
    attention). ``q_offset`` is the absolute position of q[0] relative to
    k[0] (used when q is a suffix of the kv sequence).
    Returns (B, H, S, D) in q.dtype.
    """
    b, h, sq, d = q.shape
    n_kv = k.shape[1]
    skv = k.shape[2]
    scale = d ** -0.5
    qg = _group_q((q * scale).astype(q.dtype), n_kv)

    cq = _pick_chunk(sq, chunk)
    ck = _pick_chunk(skv, chunk)
    n_qc, n_kc = sq // cq, skv // ck

    outs = []
    for i in range(n_qc):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * cq, cq, axis=3)
        q_pos = q_offset + i * cq + jnp.arange(cq)
        # Static kv-block range for this q block: causal upper bound and
        # local-window lower bound (both resolved at trace time).
        hi = n_kc if not causal else min(n_kc, (q_offset + (i + 1) * cq + ck - 1) // ck)
        lo = 0
        if window > 0:
            lo = max(0, (q_offset + i * cq - window) // ck)
        m = jnp.full((b, n_kv, h // n_kv, cq), NEG_INF, jnp.float32)
        l = jnp.zeros((b, n_kv, h // n_kv, cq), jnp.float32)
        o = jnp.zeros((b, n_kv, h // n_kv, cq, d), jnp.float32)

        def body(carry, j):
            m, l, o = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * ck, ck, axis=2)
            vj = jax.lax.dynamic_slice_in_dim(v, j * ck, ck, axis=2)
            k_pos = j * ck + jnp.arange(ck)
            mask = None
            if causal or window > 0:
                ok = jnp.ones((cq, ck), bool)
                if causal:
                    ok &= q_pos[:, None] >= k_pos[None, :]
                if window > 0:
                    ok &= q_pos[:, None] - k_pos[None, :] < window
                mask = ok[None, None, None]
            m, l, o = _flash_block(qi, kj, vj, m, l, o, mask)
            return (m, l, o), None

        (m, l, o), _ = jax.lax.scan(body, (m, l, o), jnp.arange(lo, hi))
        outs.append(_finish(m, l, o, q.dtype).reshape(b, h, cq, d))
    return jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]


def naive_attention(q, k, v, *, causal=True, window=0, q_offset=0):
    """Reference O(S^2)-memory attention (oracle for tests)."""
    b, h, sq, d = q.shape
    n_kv = k.shape[1]
    skv = k.shape[2]
    qg = _group_q(q, n_kv) * (d ** -0.5)
    s = jnp.einsum("bkgsd,bkcd->bkgsc", qg, k, preferred_element_type=jnp.float32)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(skv)
    if causal:
        s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, None, None], s, NEG_INF)
    if window > 0:
        s = jnp.where((q_pos[:, None] - k_pos[None, :] < window)[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgsc,bkcd->bkgsd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, sq, d).astype(q.dtype)


def decode_attention(q, cache: KVCache, cache_len, *, window=0):
    """Single-step attention against a KV cache.

    q: (B, H, 1, D); cache.k/v: (B, KV, S_max, D); cache_len: () int32 —
    number of valid cache entries (the new token's k/v must already be
    written at cache_len - 1).
    """
    b, h, _, d = q.shape
    n_kv = cache.k.shape[1]
    s_max = cache.k.shape[2]
    qg = _group_q(q * (d ** -0.5), n_kv)
    # Scores einsum reads the cache in ITS dtype (bf16): requesting an f32
    # output here makes XLA upcast the entire multi-GB cache (§Perf llama3
    # decode it.8). Softmax runs in f32 on the small scores tensor; the MXU
    # accumulates dots in f32 internally regardless.
    s = jnp.einsum("bkgsd,bkcd->bkgsc", qg, cache.k)  # (B,KV,G,1,S_max)
    s = s.astype(jnp.float32)
    pos = jnp.arange(s_max)
    valid = pos < cache_len
    if window > 0:
        valid &= pos >= cache_len - window
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgsc,bkcd->bkgsd", p.astype(cache.v.dtype), cache.v)
    return o.reshape(b, h, 1, d).astype(q.dtype)


def gqa_attention(
    p,
    x,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    positions,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    causal: bool = True,
    window: int = 0,
    chunk: int = 512,
    impl: str = "flash",
    cache: Optional[KVCache] = None,
    cache_len=None,
    mode: str = "train",  # train | prefill | decode
):
    """Full GQA attention block. Returns (y, new_cache_or_None)."""
    del n_heads  # implied by param shapes
    q, k, v = _project_qkv(p, x, positions, rope_theta, use_rope)
    new_cache = None
    if mode == "decode":
        assert cache is not None and cache_len is not None
        # Write this step's k/v at position cache_len, then attend over
        # cache_len+1 valid entries.
        k_new = jax.lax.dynamic_update_slice_in_dim(cache.k, k, cache_len, axis=2)
        v_new = jax.lax.dynamic_update_slice_in_dim(cache.v, v, cache_len, axis=2)
        new_cache = KVCache(k_new, v_new)
        o = decode_attention(q, new_cache, cache_len + 1, window=window)
    else:
        if impl == "flash":
            o = self_attention(q, k, v, causal=causal, window=window,
                               chunk=chunk)
        else:
            o = naive_attention(q, k, v, causal=causal, window=window)
        if mode == "prefill":
            new_cache = KVCache(k, v)
    o = shard(o, "batch_attn", "heads", "attn_seq", "head_dim")
    y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"],
                   preferred_element_type=interior_pref())
    return y.astype(x.dtype), new_cache


# --------------------------------------------------------------------------
# Cross attention (whisper decoder → encoder memory)
# --------------------------------------------------------------------------

def def_cross_attention(d_model, n_heads, head_dim):
    return {
        "wq": prm.ParamDef((d_model, n_heads, head_dim), ("embed", "heads", "head_dim"),
                           init="scaled_fan_in"),
        "wk": prm.ParamDef((d_model, n_heads, head_dim), ("embed", "kv_heads", "head_dim"),
                           init="scaled_fan_in"),
        "wv": prm.ParamDef((d_model, n_heads, head_dim), ("embed", "kv_heads", "head_dim"),
                           init="scaled_fan_in"),
        "wo": prm.ParamDef((n_heads, head_dim, d_model), ("heads", "head_dim", "embed"),
                           init="scaled_fan_in"),
    }


def cross_attention(p, x, memory=None, mem_kv=None):
    """x: (B, S, d) queries; memory: (B, S_enc, d) or precomputed mem_kv."""
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"], preferred_element_type=jnp.float32).astype(x.dtype)
    if mem_kv is None:
        k = jnp.einsum("bsd,dhk->bhsk", memory, p["wk"], preferred_element_type=jnp.float32).astype(x.dtype)
        v = jnp.einsum("bsd,dhk->bhsk", memory, p["wv"], preferred_element_type=jnp.float32).astype(x.dtype)
    else:
        k, v = mem_kv
    o = naive_attention(q, k, v, causal=False)
    y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"], preferred_element_type=jnp.float32)
    return y.astype(x.dtype), (k, v)
