"""Seeded weights for the benchmark's MoE models with latent attention
(DeepSeek-V3 blocks), in the layout the program trains them in, made on
the device in one jitted call.

The initialisation follows ``bench/weights.py``: every matrix is scaled
by its true fan-in, the projections into the residual stream (``wo``,
every ``down``) by 1/sqrt(2 * layers) besides; the embedding's std is 0.2
and the final norm's scale is centred on 0 with a spread that puts the
logits' std near 0.6; norm scales (``kv_norm`` among them) are random
around 1. The routers' correction biases, state the program keeps beside
the weights, are not drawn: ``bench/train_moe.py`` balances them on the
seed's weights (``bench/reference_moe.py`` ``balanced_biases``).

Layout: ``blocks/lead`` is a list of the leading dense layers,
``blocks/scan`` the MoE layers stacked on a first axis. Each leaf draws
from its own key, and each layer of a stacked leaf from a key of its
own. The reference takes the same tree cast to float32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.weights import (EMBED_STD, LOGIT_STD, NORM_STD, _draw, _nest,
                           base_key, frozen)


def _attn_spec(m: dict, deep: float) -> dict:
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    return {
        "norm1/scale": ((d,), "float32", NORM_STD, 1.0),
        "attn/wq": ((d, h, nope + rope), "bfloat16", d ** -0.5, 0.0),
        "attn/wkv_a": ((d, r + rope), "bfloat16", d ** -0.5, 0.0),
        "attn/kv_norm/scale": ((r,), "float32", NORM_STD, 1.0),
        "attn/wkv_b": ((r, h, nope + dv), "bfloat16", r ** -0.5, 0.0),
        "attn/wo": ((h, dv, d), "bfloat16", deep * (h * dv) ** -0.5, 0.0),
        "norm2/scale": ((d,), "float32", NORM_STD, 1.0),
    }


def _mlp_spec(prefix: str, d: int, f: int, deep: float) -> dict:
    return {f"{prefix}/gate": ((d, f), "bfloat16", d ** -0.5, 0.0),
            f"{prefix}/up": ((d, f), "bfloat16", d ** -0.5, 0.0),
            f"{prefix}/down": ((f, d), "bfloat16", deep * f ** -0.5, 0.0)}


def lead_spec(m: dict) -> dict:
    """path -> (shape, dtype, std, centre) of a leading dense layer."""
    deep = 1 / math.sqrt(2 * m["n_layers"])
    return {**_attn_spec(m, deep),
            **_mlp_spec("mlp", m["d_model"], m["d_ff"], deep)}


def moe_spec(m: dict) -> dict:
    """path -> (shape of one layer, dtype, std, centre) of an MoE layer."""
    d, e, f = m["d_model"], m["e_local"], m["moe_d_ff"]
    deep = 1 / math.sqrt(2 * m["n_layers"])
    return {
        **_attn_spec(m, deep),
        "moe/router": ((d, m["n_experts"]), "float32", d ** -0.5, 0.0),
        "moe/gate": ((e, d, f), "bfloat16", d ** -0.5, 0.0),
        "moe/up": ((e, d, f), "bfloat16", d ** -0.5, 0.0),
        "moe/down": ((e, f, d), "bfloat16", deep * f ** -0.5, 0.0),
        **_mlp_spec("moe/shared", d, m["shared_d_ff"], deep),
    }


def global_spec(m: dict) -> dict:
    d, v = m["d_model"], m["vocab_size"]
    return {"embed": ((v, d), "bfloat16", EMBED_STD, 0.0),
            "unembed": ((v, d), "bfloat16", EMBED_STD, 0.0),
            "final_norm/scale": ((d,), "float32",
                                 LOGIT_STD / (EMBED_STD * d ** 0.5), 0.0)}


def n_moe(m: dict) -> int:
    return m["n_layers"] - m["n_dense_layers"]


@partial(jax.jit, static_argnums=(0, 2))
def _make(mt, base, cast):
    m = dict(mt)

    def cast_all(flat):
        return flat if cast is None else {
            p: v.astype(cast) for p, v in flat.items()}

    out = _nest(cast_all({p: _draw(base, p, *s)
                          for p, s in global_spec(m).items()}))
    lead = [_nest(cast_all({p: _draw(base, f"blocks/lead/{i}/{p}", *s)
                            for p, s in lead_spec(m).items()}))
            for i in range(m["n_dense_layers"])]
    stacked = jax.vmap(lambda l: {
        p: _draw(base, "blocks/scan/" + p, *s, layer=l)
        for p, s in moe_spec(m).items()})(jnp.arange(n_moe(m)))
    out["blocks"] = {"lead": lead, "scan": _nest(cast_all(stacked))}
    return out


def make(m: dict, seed: int, cast=None):
    """The whole parameter tree in the layout and dtypes the program
    stores it in, or with every leaf cast to ``cast`` ("float32")."""
    return _make(frozen(m), base_key(seed), cast)

