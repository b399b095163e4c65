"""Operations the model needs, counted from its shapes.

Counts are of the algorithm, not of the program: recomputed (remat)
operations are not counted. So a utilization computed from them cannot
pass 100% unless the time is too short.

``m`` is the ``model`` block of a configuration file (the program's
``ModelConfig`` fields as run).
"""

from __future__ import annotations

def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def layer_params(m: dict) -> dict:
    """Parameters of one decoder layer, split by how a step uses them."""
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       head_dim(m), m["d_ff"])
    matmul = d * hd * (h + 2 * kv) + h * hd * d + 3 * d * f
    bias = hd * (h + 2 * kv) if m.get("qkv_bias") else 0
    return {"matmul": matmul, "bias": bias, "norm": 2 * d}


def param_counts(m: dict) -> dict:
    """Stored parameters: the layers, the embedding table(s), the norms."""
    one = layer_params(m)
    n = m["n_layers"]
    table = m["vocab_size"] * m["d_model"]
    tables = table if m.get("tie_embeddings") else 2 * table
    out = {"layers_matmul": n * one["matmul"], "bias": n * one["bias"],
           "norm": n * one["norm"] + m["d_model"], "tables": tables,
           "unembed": table}
    out["total"] = (out["layers_matmul"] + out["bias"] + out["norm"]
                    + tables)
    return out


def _matmul_per_token(m: dict) -> int:
    """Multiply-adds a token needs outside attention: every layer's
    matrices and the unembedding (the embedding lookup is free)."""
    p = param_counts(m)
    return p["layers_matmul"] + p["unembed"]


def _attn_per_key(m: dict) -> int:
    """FLOPs of one query against one key over all layers: Q.K and P.V."""
    return 4 * m["n_layers"] * m["n_heads"] * head_dim(m)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward (3x the forward), causal attention, no remat."""
    return 3 * (2 * _matmul_per_token(m) + _attn_per_key(m) * (seq + 1) / 2)
