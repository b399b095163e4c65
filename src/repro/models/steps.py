"""Step factories: the jit-able train / prefill / decode functions that the
executor, dry-run, benchmarks and examples all share.

``abstract_*`` helpers produce ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, zero allocation) so the multi-pod dry-run can lower 235B-param
models on a CPU container.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, lm
from repro.nn import params as prm
from repro.nn.blocks import init_stack_state
from repro.nn.moe import dispatch_capacity
from repro.optim import adamw


class TrainState(NamedTuple):
    step: jax.Array  # () int32
    params: dict
    opt: adamw.OptState
    # state that gradients do not train: {"router_bias": (MoE layers,
    # n_experts) f32} for routers with a correction bias, else empty
    buffers: dict = {}


def model_defs(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        return encdec.def_encdec(cfg)
    return lm.def_lm(cfg)


def init_params(cfg: ModelConfig, key):
    return prm.materialize(key, model_defs(cfg), jnp.dtype(cfg.dtype))


def abstract_params(cfg: ModelConfig):
    return prm.abstract(model_defs(cfg), jnp.dtype(cfg.dtype))


def param_axes(cfg: ModelConfig):
    return prm.axes_of(model_defs(cfg))


def init_buffers(cfg: ModelConfig) -> dict:
    """The routers' correction biases, zero, where the model has them."""
    if not (cfg.is_moe and cfg.router_bias_rate):
        return {}
    return {"router_bias": jnp.zeros((cfg.n_moe_layers, cfg.n_experts),
                                     jnp.float32)}


def init_train_state(cfg: ModelConfig, key) -> TrainState:
    params = init_params(cfg, key)
    return TrainState(jnp.zeros((), jnp.int32), params, adamw.init(params),
                      init_buffers(cfg))


def abstract_train_state(cfg: ModelConfig) -> TrainState:
    params = abstract_params(cfg)
    return TrainState(jax.ShapeDtypeStruct((), jnp.int32), params,
                      adamw.abstract_state(params),
                      jax.eval_shape(lambda: init_buffers(cfg)))


# --------------------------------------------------------------------------
# Train step
# --------------------------------------------------------------------------

def loss_fn(params, batch, cfg: ModelConfig, buffers=None):
    """Cross-entropy plus ``cfg.balance_weight`` x the MoE balance loss;
    the parts also carry each MoE layer's routed rows per expert."""
    load = None
    if cfg.is_encoder_decoder:
        memory = encdec.encode(params, batch["frames"], cfg)
        logits = encdec.decode_train(params, batch["tokens"], memory, cfg)
        aux = jnp.zeros((), jnp.float32)
    else:
        logits, _, stack_aux = lm.lm_apply(params, batch["tokens"], cfg,
                                           mode="train", buffers=buffers)
        aux, load = stack_aux["balance"], stack_aux.get("load")
    ce = lm.cross_entropy(logits, batch["labels"])
    parts = {"ce": ce, "aux": aux}
    if load is not None:
        parts["load"] = load
    return ce + cfg.balance_weight * aux, parts


def update_router_bias(bias, load, rate: float):
    """bias_i += rate * sign(mean load - load_i), per layer: experts
    routed less than the mean become likelier to be chosen."""
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + rate * jnp.sign(mean - load)


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig):
    """The step. Its metrics hold, for MoE models, ``moe_rows_held``: rows
    routed to the experts this program holds, summed over layers,
    ``moe_rows_busiest``: the busiest held expert's rows, summed over
    layers, and ``moe_full_buffer``: the layers whose held rows overflowed
    the sized dispatch buffer (``nn.moe.dispatch_capacity``, at the
    step's whole batch) and took the worst-case path."""

    def train_step(state: TrainState, batch):
        (loss, parts), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch, cfg, state.buffers)
        new_params, new_opt, om = adamw.update(
            opt_cfg, grads, state.opt, state.step, jnp.dtype(cfg.dtype))
        load = parts.pop("load", None)
        buffers = state.buffers
        if load is not None:
            held = load[:, :cfg.experts_held]
            parts["moe_rows_held"] = jnp.sum(held)
            parts["moe_rows_busiest"] = jnp.sum(jnp.max(held, axis=-1))
            cap = dispatch_capacity(batch["tokens"].size, cfg.top_k,
                                    cfg.experts_held, cfg.n_experts)
            parts["moe_full_buffer"] = jnp.sum(
                jnp.sum(held, axis=-1) > cap).astype(jnp.int32)
            if "router_bias" in buffers:
                buffers = dict(buffers, router_bias=update_router_bias(
                    buffers["router_bias"], load, cfg.router_bias_rate))
        metrics = {"loss": loss, **parts, **om, "step": state.step}
        return TrainState(state.step + 1, new_params, new_opt,
                          buffers), metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch, buffers=None):
        loss, parts = loss_fn(params, batch, cfg, buffers)
        parts.pop("load", None)
        return {"loss": loss, **parts}

    return eval_step


# --------------------------------------------------------------------------
# Serve steps
# --------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig):
    """Returns fn(params, batch) → (next_token (B,1), states, last_logits)."""

    if cfg.is_encoder_decoder:
        def prefill(params, batch):
            memory = encdec.encode(params, batch["frames"], cfg)
            logits = encdec.decode_train(params, batch["tokens"], memory, cfg)
            # Serving would keep decoding against `memory`; return it as state.
            nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            return nxt, memory, logits[:, -1]
        return prefill

    def prefill(params, batch):
        logits, states, _ = lm.lm_apply(params, batch["tokens"], cfg,
                                        mode="prefill")
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return nxt, states, logits[:, -1]

    return prefill


def make_decode_step(cfg: ModelConfig):
    """Returns fn(params, token (B,1), states, cache_len ()) →
    (next_token (B,1), new_states)."""

    if cfg.is_encoder_decoder:
        def decode(params, token, states, cache_len):
            logits, new_states = encdec.decode_step(params, token, states,
                                                    cache_len, cfg)
            nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            return nxt, new_states
        return decode

    def decode(params, token, states, cache_len):
        logits, new_states, _ = lm.lm_apply(params, token, cfg, mode="decode",
                                            states=states, cache_len=cache_len)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return nxt, new_states

    return decode


def decode_state(cfg: ModelConfig, batch: int, s_max: int):
    """Concrete decode-time state (tests / examples)."""
    dtype = jnp.dtype(cfg.dtype)
    if cfg.is_encoder_decoder:
        raise ValueError("enc-dec decode state needs params+memory; "
                         "use encdec.init_decode_state")
    return init_stack_state(cfg, batch, s_max, dtype)  # full alloc


def abstract_decode_state(cfg: ModelConfig, batch: int, s_max: int):
    """ShapeDtypeStruct decode state (dry-run)."""
    dtype = jnp.dtype(cfg.dtype)
    if cfg.is_encoder_decoder:
        return encdec.abstract_decode_state(cfg, batch, s_max, dtype)
    # compact: local-attention caches sized at the window (dry-run honesty
    # for long_500k — a full 500k cache would misstate the arch's memory)
    return jax.eval_shape(
        lambda: init_stack_state(cfg, batch, s_max, dtype, compact=True))


# --------------------------------------------------------------------------
# Input specs per shape cell (dry-run and smoke tests)
# --------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStructs for every model input of this (arch, shape) cell.

    train/prefill: token batch (+ frames for enc-dec).
    decode: single-token batch + full KV/recurrent state + cache_len.
    """
    b, s = shape.global_batch, shape.seq_len
    tok = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32)
    if shape.kind == "train":
        batch = {"tokens": tok(b, s), "labels": tok(b, s)}
        if cfg.is_encoder_decoder:
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
        return {"batch": batch}
    if shape.kind == "prefill":
        batch = {"tokens": tok(b, s)}
        if cfg.is_encoder_decoder:
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
        return {"batch": batch}
    # decode: one new token against a cache of size seq_len
    return {
        "token": tok(b, 1),
        "states": abstract_decode_state(cfg, b, s),
        "cache_len": jax.ShapeDtypeStruct((), jnp.int32),
    }
