"""Operations and bytes of the MoE cells' model (DeepSeek-V3 blocks on one
chip's share of the experts), counted from its shapes.

Counts are of the algorithm, as in ``bench/flops.py``: the model FLOPs of
a step leave recomputation (remat) out, so a utilization over them
cannot pass 100% unless the time is too short. The held experts' work
follows the rows actually routed to them, which the program counts
(``moe.rows_held``). The grouped-matmul kernels' own counts
(``gmm_work``) are of what those kernels run, the recomputed forward
included, because their time in the trace includes it.

``m`` is the ``model`` block of a configuration file.
"""

from __future__ import annotations


def mla_matmul(m: dict) -> int:
    """Multiply-adds of one token through one layer's latent attention
    projections."""
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv)
            + h * dv * d)


def attn_per_key(m: dict) -> int:
    """FLOPs of one query against one key in one layer: Q.K and P.V."""
    return 2 * m["n_heads"] * (m["qk_nope_dim"] + m["qk_rope_dim"]
                               + m["v_head_dim"])


def moe_layers(m: dict) -> int:
    return m["n_layers"] - m["n_dense_layers"]


def dense_per_token(m: dict) -> int:
    """Multiply-adds of one token outside attention scores and the held
    experts: every layer's attention projections, the dense layers' MLP,
    each MoE layer's shared experts and router, and the head over the
    vocabulary slice."""
    d = m["d_model"]
    n_moe = moe_layers(m)
    return (m["n_layers"] * mla_matmul(m)
            + m["n_dense_layers"] * 3 * d * m["d_ff"]
            + n_moe * (3 * d * m["shared_d_ff"] + d * m["n_experts"])
            + d * m["vocab_size"])


def expert_row(m: dict) -> int:
    """Multiply-adds of one routed row through one expert (SwiGLU)."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def train_flops(m: dict, tokens: int, seq: int, rows_held: float) -> float:
    """Model FLOPs of training steps over ``tokens`` tokens in sequences
    of ``seq``, whose MoE layers routed ``rows_held`` rows in all to the
    held experts: forward and backward (3x the forward), causal
    attention, no recomputation."""
    attn = m["n_layers"] * attn_per_key(m) * (seq + 1) / 2
    forward = tokens * (2 * dense_per_token(m) + attn) \
        + rows_held * 2 * expert_row(m)
    return 3 * forward


def gmm_work(m: dict, rows_held: float, layer_steps: int) -> tuple:
    """(FLOPs, bytes) of the grouped-matmul kernels over ``layer_steps``
    MoE-layer steps whose held experts took ``rows_held`` rows in all.

    Each of a layer's three products (gate and up: rows x d by d x f;
    down: rows x f by f x d) runs four times a step: forward twice (the
    step and its remat recompute), and backward as a ``gmm`` for the
    rows' gradient and a ``tgmm`` for the experts'. Each is 2 * rows * d
    * f FLOPs. Bytes are each call's operands read once and its result
    written once, bf16: a call moves its rows in and out (d + f wide in
    all) and one matrix of each held expert, 2 * (rows * (d + f) + e * d
    * f) bytes, and a step makes 12 calls a layer."""
    d, f, e = m["d_model"], m["moe_d_ff"], m["e_local"]
    flops = 12 * 2 * rows_held * d * f
    bytes_ = 12 * 2 * (rows_held * (d + f) + layer_steps * e * d * f)
    return flops, bytes_
