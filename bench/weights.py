"""Seeded weights for the benchmark's decoder models, in the layout the
program serves and trains them in, made on the device in one jitted call.

The initialisation is the benchmark's own. Every matrix is scaled by its
true fan-in, and the projections into the residual stream (``wo``,
``down``) by 1/sqrt(2 * layers) besides, so that at full depth a rounding
difference does not grow into an O(1) change of the logits: the program
and the plain reference can then be told apart from a fault. The
embedding's std is 0.2, and the final norm's scale is centred on 0 with
a spread that puts the logits' std near 0.6 at every width. With tied
embeddings a centred scale keeps a token's own embedding from voting for
that token again, which makes greedy decoding repeat one token. Norm
scales and biases are random too, so that a path that drops them is seen.

Each leaf draws from its own key, and each layer of a stacked leaf from a
key of its own. The reference takes the same tree cast to float32
(``make(m, seed, "float32")``), and nothing from the program.
"""

from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp

import bench.generator as gen
from bench.flops import head_dim

EMBED_STD = 0.2
BIAS_STD = 0.1
NORM_STD = 0.1
LOGIT_STD = 0.6


def layer_spec(m: dict) -> dict:
    """path -> (shape of one layer, dtype, std, centre) of a layer's leaves."""
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       head_dim(m), m["d_ff"])
    deep = 1 / math.sqrt(2 * m["n_layers"])
    qk = d ** -0.5
    spec = {
        "norm1/scale": ((d,), "float32", NORM_STD, 1.0),
        "attn/wq": ((d, h, hd), "bfloat16", qk, 0.0),
        "attn/wk": ((d, kv, hd), "bfloat16", qk, 0.0),
        "attn/wv": ((d, kv, hd), "bfloat16", d ** -0.5, 0.0),
        "attn/wo": ((h, hd, d), "bfloat16", deep * (h * hd) ** -0.5, 0.0),
        "norm2/scale": ((d,), "float32", NORM_STD, 1.0),
        "mlp/gate": ((d, f), "bfloat16", d ** -0.5, 0.0),
        "mlp/up": ((d, f), "bfloat16", d ** -0.5, 0.0),
        "mlp/down": ((f, d), "bfloat16", deep * f ** -0.5, 0.0),
    }
    if m.get("qkv_bias"):
        spec["attn/bq"] = ((h, hd), "bfloat16", BIAS_STD, 0.0)
        spec["attn/bk"] = ((kv, hd), "bfloat16", BIAS_STD, 0.0)
        spec["attn/bv"] = ((kv, hd), "bfloat16", BIAS_STD, 0.0)
    return spec


def global_spec(m: dict) -> dict:
    spec = {"embed": ((m["vocab_size"], m["d_model"]), "bfloat16",
                      EMBED_STD, 0.0),
            "final_norm/scale": ((m["d_model"],), "float32", LOGIT_STD
                                 / (EMBED_STD * m["d_model"] ** 0.5), 0.0)}
    if not m.get("tie_embeddings"):
        spec["unembed"] = spec["embed"]
    return spec


def base_key(seed: int):
    """The key of a seed's weights (the seed may exceed 32 bits)."""
    return jax.random.key(gen.sub_seed(seed, "weights"))


def frozen(m: dict) -> tuple:
    """The hashable form of a model block, a static argument of jit."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def _draw(base, path, shape, dtype, std, centre, layer=None):
    key = jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    x = centre + std * jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _nest(flat: dict) -> dict:
    out = {}
    for path, leaf in flat.items():
        *head, last = path.split("/")
        node = out
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    return out


def _layer_flat(m, base, layer):
    return {path: _draw(base, "blocks/" + path, *spec, layer=layer)
            for path, spec in layer_spec(m).items()}


def _globals_flat(m, base):
    return {path: _draw(base, path, *spec)
            for path, spec in global_spec(m).items()}


@partial(jax.jit, static_argnums=(0, 2))
def _make(mt, base, cast):
    m = dict(mt)
    layers = jax.vmap(lambda l: _layer_flat(m, base, l))(
        jnp.arange(m["n_layers"]))
    flat = _globals_flat(m, base)
    if cast is not None:
        flat = {p: v.astype(cast) for p, v in flat.items()}
        layers = {p: v.astype(cast) for p, v in layers.items()}
    out = _nest(flat)
    if m.get("scan_layers", True):
        out["blocks"] = {"scan": _nest(layers)}
    else:
        out["blocks"] = {"layers": [
            _nest({p: v[l] for p, v in layers.items()})
            for l in range(m["n_layers"])]}
    return out


def make(m: dict, seed: int, cast=None):
    """The whole parameter tree in the layout and dtypes the program
    stores it in, or with every leaf cast to ``cast`` ("float32")."""
    return _make(frozen(m), base_key(seed), cast)
