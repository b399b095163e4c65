"""Basic layers: linear application, norms, rotary embeddings, activations.

All layers are functional: ``def_*`` builds ParamDef trees, ``apply``-style
functions consume (params, inputs). Matmuls accumulate in fp32 via
``preferred_element_type`` — bf16 params, fp32 accumulation is the TPU MXU
native mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn import params as prm
from repro.nn.policy import interior_pref


# --------------------------------------------------------------------------
# Linear / embedding
# --------------------------------------------------------------------------

def def_linear(d_in, d_out, ax_in, ax_out, use_bias=False, scale=None):
    d = {"w": prm.matrix(d_in, d_out, ax_in, ax_out, scale=scale)}
    if use_bias:
        d["b"] = prm.bias(d_out, ax_out)
    return d


def linear(p, x):
    y = jnp.einsum("...i,io->...o", x, p["w"],
                   preferred_element_type=interior_pref())
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y.astype(x.dtype)


def embed_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


def unembed(table, x):
    """Tied unembedding: x @ table.T → logits in fp32."""
    return jnp.einsum("...d,vd->...v", x, table,
                      preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def def_rmsnorm(d):
    return {"scale": prm.norm_scale(d)}


def rmsnorm(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


def def_layernorm(d):
    return {"scale": prm.norm_scale(d), "bias": prm.ParamDef((d,), ("embed",), init="zeros", dtype="float32")}


def layernorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def def_norm(d, rms=True):
    return def_rmsnorm(d) if rms else def_layernorm(d)


def norm(p, x, rms=True, eps=1e-6):
    """RMSNorm with ``eps`` (the configuration's), or LayerNorm."""
    return rmsnorm(p, x, eps) if rms else layernorm(p, x)


# Per-head norm used by qk-norm archs (qwen3, chameleon): normalizes head_dim.
def def_headnorm(head_dim):
    return {"scale": prm.ParamDef((head_dim,), ("head_dim",), init="ones", dtype="float32")}


def headnorm(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim, theta):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta=10000.0):
    """x: (..., seq, head_dim); positions: (..., seq) int32."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq, d_model, offset=0):
    """Classic transformer sinusoidal table (whisper-style abs positions)."""
    pos = jnp.arange(offset, offset + seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    emb = jnp.zeros((seq, d_model), jnp.float32)
    emb = emb.at[:, 0::2].set(jnp.sin(angle))
    emb = emb.at[:, 1::2].set(jnp.cos(angle))
    return emb


# --------------------------------------------------------------------------
# Activations
# --------------------------------------------------------------------------

def activation(name):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
    }[name]
