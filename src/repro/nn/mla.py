"""Multi-head latent attention (DeepSeek-V2/V3), in training, run in its
expanded form: the latent is projected back to per-head keys and values
and attended as ordinary multi-head attention.

With no q compression (``q_lora_rank`` null), per token:

* q = W_q x, ``n_heads`` x (``qk_nope_dim`` + ``qk_rope_dim``);
* [c_kv, k_rope] = W_kv_a x, ``kv_lora_rank`` + ``qk_rope_dim``, and
  c_kv = RMSNorm(c_kv);
* [k_nope, v] = W_kv_b c_kv, ``n_heads`` x (``qk_nope_dim`` +
  ``v_head_dim``);
* k = [k_nope, rope(k_rope) broadcast to every head], q's rope part takes
  rope too; scores scaled by 1/sqrt(q/k width); o = W_o(heads x v).

The q/k width (192 for Moonlight) differs from v's (128);
``kernels.ops.self_attention`` takes both. The rope dimensions rotate as
two halves, as ``nn.layers.apply_rope`` does. Prefill and decode through a
latent cache are not built: ``nn.blocks.stack_apply`` refuses those modes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ops import self_attention
from repro.nn import params as prm
from repro.nn.layers import apply_rope, def_rmsnorm, rmsnorm
from repro.nn.policy import interior_pref
from repro.parallel import shard


def def_mla(d_model, n_heads, kv_lora_rank, qk_nope_dim, qk_rope_dim,
            v_head_dim):
    qk = qk_nope_dim + qk_rope_dim
    return {
        "wq": prm.ParamDef((d_model, n_heads, qk),
                           ("embed", "heads", "head_dim"), init="scaled_fan_in"),
        "wkv_a": prm.matrix(d_model, kv_lora_rank + qk_rope_dim, "embed", None),
        "kv_norm": def_rmsnorm(kv_lora_rank),
        "wkv_b": prm.ParamDef((kv_lora_rank, n_heads, qk_nope_dim + v_head_dim),
                              (None, "heads", "head_dim"), init="scaled_fan_in"),
        "wo": prm.ParamDef((n_heads, v_head_dim, d_model),
                           ("heads", "head_dim", "embed"), init="scaled_fan_in"),
    }


def mla_attention(p, x, *, qk_nope_dim: int, qk_rope_dim: int, positions,
                  rope_theta: float, eps: float, chunk: int = 512):
    """x: (B, S, d) → (B, S, d), causal, over the whole sequence."""
    pref = interior_pref()
    rank = p["kv_norm"]["scale"].shape[0]
    with jax.named_scope("mla"):
        q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"],
                       preferred_element_type=pref).astype(x.dtype)
        kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"],
                          preferred_element_type=pref).astype(x.dtype)
        c_kv = rmsnorm(p["kv_norm"], kv_a[..., :rank], eps)
        k_rope = apply_rope(kv_a[:, None, :, rank:], positions[:, None, :],
                            rope_theta)  # (B, 1, S, rope)
        kv = jnp.einsum("bsr,rhk->bhsk", c_kv, p["wkv_b"],
                        preferred_element_type=pref).astype(x.dtype)
        k_nope, v = kv[..., :qk_nope_dim], kv[..., qk_nope_dim:]
        q_rope = apply_rope(q[..., qk_nope_dim:], positions[:, None, :],
                            rope_theta)
        q = jnp.concatenate([q[..., :qk_nope_dim], q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (qk_rope_dim,))],
            axis=-1)
        q = shard(q, "batch_attn", "heads", "attn_seq", "head_dim")
        k = shard(k, "batch_attn", "heads", "attn_seq", "head_dim")
        v = shard(v, "batch_attn", "heads", "attn_seq", "head_dim")
        o = self_attention(q, k, v, causal=True, chunk=chunk)
        y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"],
                       preferred_element_type=pref)
    return y.astype(x.dtype)
