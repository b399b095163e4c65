"""Decoder-only language model assembly (all non-enc-dec archs) + losses."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn import params as prm
from repro.nn.blocks import def_stack, init_stack_state, stack_apply
from repro.nn.layers import def_norm, embed_lookup, norm, unembed
from repro.parallel import shard


def def_lm(cfg: ModelConfig):
    d = {
        "embed": prm.embedding(cfg.vocab_size, cfg.d_model),
        "blocks": def_stack(cfg),
        "final_norm": def_norm(cfg.d_model, cfg.rms_norm),
    }
    if not cfg.tie_embeddings:
        d["unembed"] = prm.ParamDef((cfg.vocab_size, cfg.d_model),
                                    ("vocab", "embed"), init="normal", scale=0.02)
    return d


def lm_apply(p, tokens, cfg: ModelConfig, *, mode="train", states=None,
             cache_len=None, positions=None, buffers=None):
    """tokens: (B, S) int32 → (logits (B, S, V) fp32, new_states, aux).

    ``buffers`` holds state that gradients do not train (the routers'
    correction biases); aux is ``nn.blocks.stack_apply``'s."""
    if positions is None:
        if mode == "decode":
            positions = jnp.broadcast_to(cache_len, tokens.shape).astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = embed_lookup(p["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x = shard(x, "batch", "seq", "embed")
    x, new_states, aux = stack_apply(p["blocks"], x, cfg, positions=positions,
                                     mode=mode, states=states,
                                     cache_len=cache_len, buffers=buffers)
    x = norm(p["final_norm"], x, cfg.rms_norm, cfg.rms_eps)
    table = p["embed"] if cfg.tie_embeddings else p["unembed"]
    logits = unembed(table, x)
    logits = shard(logits, "batch", "seq", "vocab")
    return logits, new_states, aux


def init_lm_state(cfg: ModelConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    return init_stack_state(cfg, batch, s_max, dtype)


# --------------------------------------------------------------------------
# losses
# --------------------------------------------------------------------------

def cross_entropy(logits, labels, z_loss: float = 1e-4):
    """Mean token cross-entropy in fp32 with optional z-loss regularizer.

    logits: (B, S, V) fp32; labels: (B, S) int32 (-1 = masked out).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll * mask) / denom
