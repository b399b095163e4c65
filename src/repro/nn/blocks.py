"""Per-layer blocks and the layer-stack machinery.

Block kinds (``cfg.block_pattern`` entries):
  * ``attn``  — GQA or latent attention (+ dense MLP or MoE FFN)
  * ``rglru`` — Griffin recurrent block (+ dense MLP)
  * ``mlstm`` — xLSTM matrix-LSTM block (self-contained up/down projections)
  * ``slstm`` — xLSTM scalar-LSTM block (self-contained gated FFN)

``stack_*`` drives a homogeneous stack through ``lax.scan`` over stacked
params (compile-time O(1) in depth — essential for the 94-layer MoE) or an
unrolled loop for heterogeneous patterns; both honor the remat policy.
Leading dense layers of an MoE model (``cfg.n_dense_layers``) run unrolled
in front of the scanned MoE layers.

Blocks return ``(x, new_state, aux)``; aux is ``{}`` or, for an MoE
layer, ``{"balance": (), "load": (n_experts,)}``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn import params as prm
from repro.nn.attention import KVCache, def_gqa, gqa_attention
from repro.nn.layers import def_norm, norm
from repro.nn.mla import def_mla, mla_attention
from repro.nn.mlp import def_mlp, mlp
from repro.nn.moe import def_moe, moe_ffn
from repro.nn.policy import interior_pref
from repro.nn.recurrent import (
    MLSTMState,
    SLSTMState,
    blockdiag,
    causal_conv,
    causal_conv_step,
    conv_state_init,
    def_blockdiag,
    def_causal_conv,
    def_rglru,
    def_slstm_core,
    mlstm_chunkwise,
    mlstm_state_init,
    mlstm_step,
    rglru,
    rglru_step,
    slstm_scan,
    slstm_state_init,
    slstm_step,
)
from repro.parallel import shard


# --------------------------------------------------------------------------
# defs
# --------------------------------------------------------------------------

def def_attn_block(cfg: ModelConfig, moe: Optional[bool] = None):
    if cfg.is_mla:
        attn = def_mla(cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                       cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim)
    else:
        attn = def_gqa(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                       cfg.qkv_bias, cfg.qk_norm)
    d = {
        "norm1": def_norm(cfg.d_model, cfg.rms_norm),
        "attn": attn,
        "norm2": def_norm(cfg.d_model, cfg.rms_norm),
    }
    if cfg.is_moe if moe is None else moe:
        d["moe"] = def_moe(cfg.d_model, cfg.n_experts, cfg.moe_d_ff,
                           e_local=cfg.experts_held,
                           shared_d_ff=cfg.shared_d_ff)
    else:
        d["mlp"] = def_mlp(cfg.d_model, cfg.d_ff, cfg.act)
    return d


def def_rglru_block(cfg: ModelConfig):
    w = cfg.lru_width or cfg.d_model
    return {
        "norm1": def_norm(cfg.d_model, cfg.rms_norm),
        "w_gate": prm.matrix(cfg.d_model, w, "embed", "lru"),
        "w_x": prm.matrix(cfg.d_model, w, "embed", "lru"),
        "conv": def_causal_conv(cfg.conv_width, w),
        "lru": def_rglru(w, cfg.n_heads),
        "w_out": prm.matrix(w, cfg.d_model, "lru", "embed"),
        "norm2": def_norm(cfg.d_model, cfg.rms_norm),
        "mlp": def_mlp(cfg.d_model, cfg.d_ff, cfg.act),
    }


def def_mlstm_block(cfg: ModelConfig):
    d, nh = cfg.d_model, cfg.n_heads
    di = 2 * d
    return {
        "norm": def_norm(d, cfg.rms_norm),
        "wu": prm.matrix(d, di, "embed", "lru"),
        "wg": prm.matrix(d, di, "embed", "lru"),
        "conv": def_causal_conv(cfg.conv_width, di),
        "wq": prm.matrix(di, di, "lru", None),
        "wk": prm.matrix(di, di, "lru", None),
        "wv": prm.matrix(di, di, "lru", None),
        "wi": prm.matrix(di, nh, "lru", "heads"),
        "bi": prm.bias(nh, "heads"),
        "wf": prm.matrix(di, nh, "lru", "heads"),
        "bf": prm.bias(nh, "heads"),
        "out_norm": prm.ParamDef((di,), ("lru",), init="ones", dtype="float32"),
        "wo": prm.matrix(di, d, "lru", "embed"),
    }


def def_slstm_block(cfg: ModelConfig):
    d, nh = cfg.d_model, cfg.n_heads
    dh = d // nh
    ffw = max(1, round(cfg.d_model * 4 / 3))
    return {
        "norm": def_norm(d, cfg.rms_norm),
        "conv": def_causal_conv(cfg.conv_width, d),
        "wi": prm.matrix(d, d, "embed", "lru"),
        "wf": prm.matrix(d, d, "embed", "lru"),
        "wz": prm.matrix(d, d, "embed", "lru"),
        "wo_g": prm.matrix(d, d, "embed", "lru"),
        "r": def_slstm_core(nh, dh),
        "out_norm": prm.ParamDef((d,), ("lru",), init="ones", dtype="float32"),
        "ffn": def_mlp(cfg.d_model, ffw, "silu"),
    }


_DEFS = {
    "attn": def_attn_block,
    "rglru": def_rglru_block,
    "mlstm": def_mlstm_block,
    "slstm": def_slstm_block,
}


def def_block(cfg: ModelConfig, kind: str):
    return _DEFS[kind](cfg)


# --------------------------------------------------------------------------
# per-head group norm used by xLSTM outputs
# --------------------------------------------------------------------------

def _group_rms(scale, x, n_heads, eps=1e-6):
    """x: (B, S, D) normalized per head-group of D/n_heads channels."""
    b, s, dd = x.shape
    xh = x.reshape(b, s, n_heads, dd // n_heads).astype(jnp.float32)
    var = jnp.mean(xh * xh, axis=-1, keepdims=True)
    y = (xh * jax.lax.rsqrt(var + eps)).reshape(b, s, dd) * scale
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# state init (decode)
# --------------------------------------------------------------------------

def init_block_state(cfg: ModelConfig, kind: str, batch: int, s_max: int,
                     dtype=jnp.bfloat16, compact: bool = False):
    if kind == "attn":
        if cfg.is_mla:
            raise NotImplementedError(
                f"{cfg.name}: latent attention has no decode cache yet")
        window = cfg.local_window
        # compact=True bounds a local-attention cache at the window (used by
        # the dry-run to size long_500k honestly); executed serving keeps
        # the full allocation so linear cache_len indexing stays valid.
        s_alloc = min(s_max, window + 1) if (window and compact) else s_max
        return KVCache(
            k=jnp.zeros((batch, cfg.n_kv_heads, s_alloc, cfg.hd), dtype),
            v=jnp.zeros((batch, cfg.n_kv_heads, s_alloc, cfg.hd), dtype),
        )
    if kind == "rglru":
        w = cfg.lru_width or cfg.d_model
        return {
            "conv": conv_state_init(batch, cfg.conv_width, w, dtype),
            "h": jnp.zeros((batch, w), jnp.float32),
        }
    if kind == "mlstm":
        d, nh = cfg.d_model, cfg.n_heads
        di = 2 * d
        return {
            "conv": conv_state_init(batch, cfg.conv_width, di, dtype),
            "state": mlstm_state_init(batch, nh, di // nh, di // nh),
        }
    if kind == "slstm":
        d, nh = cfg.d_model, cfg.n_heads
        return {
            "conv": conv_state_init(batch, cfg.conv_width, d, dtype),
            "state": slstm_state_init(batch, nh, d // nh),
        }
    raise ValueError(kind)


# --------------------------------------------------------------------------
# block apply — mode in {train, prefill, decode}
# --------------------------------------------------------------------------

def apply_attn_block(p, x, cfg: ModelConfig, *, positions, mode="train",
                     state=None, cache_len=None, bias=None):
    """``bias``: this layer's router correction bias (MoE layers)."""
    h = norm(p["norm1"], x, cfg.rms_norm, cfg.rms_eps)
    if cfg.is_mla:
        attn_out, new_cache = mla_attention(
            p["attn"], h, qk_nope_dim=cfg.qk_nope_dim,
            qk_rope_dim=cfg.qk_rope_dim, positions=positions,
            rope_theta=cfg.rope_theta, eps=cfg.rms_eps,
            chunk=cfg.attn_chunk), None
    else:
        attn_out, new_cache = gqa_attention(
            p["attn"], h,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
            positions=positions, rope_theta=cfg.rope_theta,
            use_rope=cfg.use_rope, causal=True, window=cfg.local_window,
            chunk=cfg.attn_chunk, cache=state, cache_len=cache_len, mode=mode,
        )
    x = x + attn_out
    x = shard(x, "batch", "seq", "embed")
    h = norm(p["norm2"], x, cfg.rms_norm, cfg.rms_eps)
    if "moe" in p:
        ffn_out, aux = moe_ffn(p["moe"], h, top_k=cfg.top_k, act=cfg.act,
                               score=cfg.router_score, bias=bias,
                               routed_scale=cfg.routed_scale,
                               balance=cfg.balance_loss)
    else:
        ffn_out, aux = mlp(p["mlp"], h, cfg.act), {}
    x = x + ffn_out
    x = shard(x, "batch", "seq", "embed")
    return x, new_cache, aux


def apply_rglru_block(p, x, cfg: ModelConfig, *, mode="train", state=None):
    w = cfg.lru_width or cfg.d_model
    del w
    h = norm(p["norm1"], x, cfg.rms_norm, cfg.rms_eps)
    gate = jax.nn.gelu(jnp.einsum(
        "bsd,dw->bsw", h, p["w_gate"],
        preferred_element_type=interior_pref()).astype(jnp.float32)
    ).astype(x.dtype)
    u = jnp.einsum("bsd,dw->bsw", h, p["w_x"],
                   preferred_element_type=interior_pref()).astype(x.dtype)
    new_state = None
    if mode == "decode":
        u1, conv_state = causal_conv_step(p["conv"], u[:, 0], state["conv"])
        r, h_new = rglru_step(p["lru"], u1, state["h"], cfg.n_heads)
        r = r[:, None]
        new_state = {"conv": conv_state, "h": h_new}
    else:
        u_raw = u
        u = causal_conv(p["conv"], u)
        u = shard(u, "batch", "seq", "lru")
        r, h_last = rglru(p["lru"], u, cfg.n_heads,
                          h0=state["h"] if state is not None else None)
        if mode == "prefill":
            width = p["conv"]["w"].shape[0]
            conv_state = jax.lax.dynamic_slice_in_dim(
                u_raw, u_raw.shape[1] - (width - 1), width - 1, axis=1)
            new_state = {"conv": conv_state, "h": h_last}
    y = jnp.einsum("bsw,wd->bsd", (r * gate).astype(x.dtype), p["w_out"],
                   preferred_element_type=interior_pref()).astype(x.dtype)
    x = x + y
    x = shard(x, "batch", "seq", "embed")
    x = x + mlp(p["mlp"], norm(p["norm2"], x, cfg.rms_norm, cfg.rms_eps), cfg.act)
    x = shard(x, "batch", "seq", "embed")
    return x, new_state, {}


def apply_mlstm_block(p, x, cfg: ModelConfig, *, mode="train", state=None):
    d, nh = cfg.d_model, cfg.n_heads
    di = 2 * d
    dh = di // nh
    h = norm(p["norm"], x, cfg.rms_norm, cfg.rms_eps)
    u = jnp.einsum("bsd,de->bse", h, p["wu"],
                   preferred_element_type=interior_pref()).astype(x.dtype)
    g = jnp.einsum("bsd,de->bse", h, p["wg"],
                   preferred_element_type=interior_pref()).astype(x.dtype)
    if mode == "decode":
        c, conv_state = causal_conv_step(p["conv"], u[:, 0], state["conv"])
        c = jax.nn.silu(c.astype(jnp.float32)).astype(x.dtype)
        q = (c @ p["wq"]).reshape(-1, nh, dh)
        k = (c @ p["wk"]).reshape(-1, nh, dh)
        v = (u[:, 0] @ p["wv"]).reshape(-1, nh, dh)
        ig = (c @ p["wi"] + p["bi"]).astype(jnp.float32)
        fg = (c @ p["wf"] + p["bf"] + 3.0).astype(jnp.float32)
        hout, mstate = mlstm_step(q, k, v, ig, fg, state["state"])
        hout = hout.reshape(-1, 1, di)
        new_state = {"conv": conv_state, "state": mstate}
    else:
        c = causal_conv(p["conv"], u)
        c = jax.nn.silu(c.astype(jnp.float32)).astype(x.dtype)
        b, s, _ = c.shape
        q = (c @ p["wq"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
        k = (c @ p["wk"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
        v = (u @ p["wv"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
        ig = (c @ p["wi"] + p["bi"]).astype(jnp.float32).transpose(0, 2, 1)
        fg = (c @ p["wf"] + p["bf"] + 3.0).astype(jnp.float32).transpose(0, 2, 1)
        hout, mstate = mlstm_chunkwise(q, k, v, ig, fg,
                                       state["state"] if state else None,
                                       chunk=min(cfg.attn_chunk, s))
        hout = hout.transpose(0, 2, 1, 3).reshape(b, s, di)
        new_state = None
        if mode == "prefill":
            width = p["conv"]["w"].shape[0]
            conv_state = jax.lax.dynamic_slice_in_dim(u, s - (width - 1), width - 1, 1)
            new_state = {"conv": conv_state, "state": mstate}
    hout = _group_rms(p["out_norm"], hout, nh)
    y = ((hout * jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)) @ p["wo"])
    x = x + y.astype(x.dtype)
    x = shard(x, "batch", "seq", "embed")
    return x, new_state, {}


def apply_slstm_block(p, x, cfg: ModelConfig, *, mode="train", state=None):
    d, nh = cfg.d_model, cfg.n_heads
    dh = d // nh
    h = norm(p["norm"], x, cfg.rms_norm, cfg.rms_eps)
    if mode == "decode":
        c, conv_state = causal_conv_step(p["conv"], h[:, 0], state["conv"])
        c = jax.nn.silu(c.astype(jnp.float32)).astype(x.dtype)
        gates = {
            "i": (c @ p["wi"]).reshape(-1, nh, dh),
            "f": (c @ p["wf"]).reshape(-1, nh, dh),
            "z": (h[:, 0] @ p["wz"]).reshape(-1, nh, dh),
            "o": (h[:, 0] @ p["wo_g"]).reshape(-1, nh, dh),
        }
        hout, sstate = slstm_step(p["r"], gates, state["state"])
        hout = hout.reshape(-1, 1, d).astype(x.dtype)
        new_state = {"conv": conv_state, "state": sstate}
    else:
        b, s, _ = h.shape
        c = causal_conv(p["conv"], h)
        c = jax.nn.silu(c.astype(jnp.float32)).astype(x.dtype)
        gates = {
            "i": (c @ p["wi"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3),
            "f": (c @ p["wf"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3),
            "z": (h @ p["wz"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3),
            "o": (h @ p["wo_g"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3),
        }
        hout, sstate = slstm_scan(p["r"], gates,
                                  state["state"] if state else None)
        hout = hout.transpose(0, 2, 1, 3).reshape(b, s, d).astype(x.dtype)
        new_state = None
        if mode == "prefill":
            width = p["conv"]["w"].shape[0]
            conv_state = jax.lax.dynamic_slice_in_dim(h, s - (width - 1), width - 1, 1)
            new_state = {"conv": conv_state, "state": sstate}
    hout = _group_rms(p["out_norm"], hout, nh)
    x = x + hout
    x = x + mlp(p["ffn"], x, "silu")
    x = shard(x, "batch", "seq", "embed")
    return x, new_state, {}


def apply_block(p, x, cfg: ModelConfig, kind: str, *, positions=None,
                mode="train", state=None, cache_len=None, bias=None):
    if kind == "attn":
        return apply_attn_block(p, x, cfg, positions=positions, mode=mode,
                                state=state, cache_len=cache_len, bias=bias)
    if kind == "rglru":
        return apply_rglru_block(p, x, cfg, mode=mode, state=state)
    if kind == "mlstm":
        return apply_mlstm_block(p, x, cfg, mode=mode, state=state)
    if kind == "slstm":
        return apply_slstm_block(p, x, cfg, mode=mode, state=state)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# layer stack
# --------------------------------------------------------------------------

def _stackable(cfg: ModelConfig) -> bool:
    return cfg.scan_layers and len(set(cfg.block_pattern)) == 1 \
        and cfg.block_pattern[0] == "attn"


def def_stack(cfg: ModelConfig):
    """Def-tree for the full stack of decoder blocks: ``{"scan": stacked}``
    (with ``"lead"``, a list of the leading dense layers, in front), or
    ``{"layers": [...]}`` unrolled."""
    n_dense = cfg.n_dense_layers
    if _stackable(cfg):
        one = def_block(cfg, "attn")

        def add_layer_axis(d: prm.ParamDef) -> prm.ParamDef:
            return prm.ParamDef((cfg.n_layers - n_dense,) + tuple(d.shape),
                                ("layers",) + tuple(d.axes),
                                init=d.init, scale=d.scale, dtype=d.dtype)

        out = {"scan": jax.tree.map(add_layer_axis, one, is_leaf=prm.is_def)}
        if n_dense:
            out["lead"] = [def_attn_block(cfg, moe=False)
                           for _ in range(n_dense)]
        return out
    pattern = cfg.pattern_for_layers()
    return {"layers": [
        def_attn_block(cfg, moe=cfg.is_moe and i >= n_dense) if k == "attn"
        else def_block(cfg, k) for i, k in enumerate(pattern)]}


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)


def stack_apply(p, x, cfg: ModelConfig, *, positions=None, mode="train",
                states=None, cache_len=None, buffers=None):
    """Run all decoder blocks. Returns (x, new_states, aux), aux =
    {"balance": summed balance loss (), and for MoE models "load": rows
    routed to each expert, (MoE layers, n_experts)}.

    ``states`` is a list (unrolled) or stacked pytree (scan) of block states,
    or None for train mode. ``buffers["router_bias"]`` (MoE layers,
    n_experts) holds the routers' correction biases, where the model has
    them.
    """
    if mode != "train" and (cfg.is_mla or cfg.n_dense_layers):
        raise NotImplementedError(
            f"{cfg.name}: {mode} is built for uniform GQA stacks only; "
            f"latent attention runs in training")
    biases = (buffers or {}).get("router_bias")
    balance = jnp.zeros((), jnp.float32)
    if _stackable(cfg):
        for lead_p in p.get("lead", []):  # dense: no balance loss
            def lead(h, lp):
                return apply_attn_block(lp, h, cfg, positions=positions)[0]

            x = _remat_wrap(lead, cfg)(x, lead_p)

        def body(carry, xs):
            h, bal = carry
            layer_p, layer_state, bias = xs
            h, new_state, a = apply_attn_block(
                layer_p, h, cfg, positions=positions, mode=mode,
                state=layer_state, cache_len=cache_len, bias=bias)
            return (h, bal + a.get("balance", 0.0)), (new_state, a.get("load"))

        body = _remat_wrap(body, cfg) if mode == "train" else body
        xs = (p["scan"], states if mode == "decode" else None, biases)
        (x, balance), (new_states, load) = jax.lax.scan(
            body, (x, balance), xs)
        if mode == "train":
            new_states = None
        aux = {"balance": balance}
        if load is not None:
            aux["load"] = load
        return x, new_states, aux

    pattern = cfg.pattern_for_layers()
    new_states, loads = [], []
    for i, kind in enumerate(pattern):
        st = states[i] if states is not None else None
        bias = None
        if biases is not None and i >= cfg.n_dense_layers:
            bias = biases[i - cfg.n_dense_layers]

        def one(h, layer_p, st=st, kind=kind, bias=bias):
            return apply_block(layer_p, h, cfg, kind, positions=positions,
                               mode=mode, state=st, cache_len=cache_len,
                               bias=bias)

        if mode == "train":
            one = _remat_wrap(one, cfg)
        x, ns, a = one(x, p["layers"][i])
        new_states.append(ns)
        balance = balance + a.get("balance", 0.0)
        if "load" in a:
            loads.append(a["load"])
    aux = {"balance": balance}
    if loads:
        aux["load"] = jnp.stack(loads)
    return x, new_states if states is not None or mode == "prefill" else None, aux


def init_stack_state(cfg: ModelConfig, batch: int, s_max: int,
                     dtype=jnp.bfloat16, compact: bool = False):
    """Decode-time state for the whole stack (stacked for scan models)."""
    if _stackable(cfg):
        one = init_block_state(cfg, "attn", batch, s_max, dtype, compact)
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (cfg.n_layers,) + l.shape), one)
    return [init_block_state(cfg, k, batch, s_max, dtype, compact)
            for k in cfg.pattern_for_layers()]


# --------------------------------------------------------------------------
# logical axes of decode state (for dry-run sharding of KV caches etc.)
# --------------------------------------------------------------------------

def block_state_axes(cfg: ModelConfig, kind: str):
    if kind == "attn":
        kv = ("batch", "kv_heads", "kv_seq", "head_dim")
        return KVCache(k=kv, v=kv)
    if kind == "rglru":
        return {"conv": ("batch", None, "lru"), "h": ("batch", "lru")}
    if kind == "mlstm":
        return {
            "conv": ("batch", None, "lru"),
            "state": MLSTMState(c=("batch", "heads", None, None),
                                n=("batch", "heads", None),
                                m=("batch", "heads")),
        }
    if kind == "slstm":
        return {
            "conv": ("batch", None, "lru"),
            "state": SLSTMState(c=("batch", "heads", None),
                                n=("batch", "heads", None),
                                m=("batch", "heads", None),
                                h=("batch", "heads", None)),
        }
    raise ValueError(kind)


def stack_state_axes(cfg: ModelConfig):
    if _stackable(cfg):
        one = block_state_axes(cfg, "attn")
        return jax.tree.map(lambda a: ("layers",) + a, one,
                            is_leaf=lambda l: isinstance(l, tuple) and
                            all(isinstance(x, (str, type(None))) for x in l))
    return [block_state_axes(cfg, k) for k in cfg.pattern_for_layers()]
