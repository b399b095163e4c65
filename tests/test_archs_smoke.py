"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, output shapes + no NaNs; plus one decode step
and prefill/decode consistency for decoder-only archs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_tiny_config
from repro.models import encdec, steps
from repro.optim import adamw

B, S = 2, 64


def make_batch(cfg, key):
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch, rng_key):
    cfg = get_tiny_config(arch)
    state = steps.init_train_state(cfg, rng_key)
    batch = make_batch(cfg, rng_key)
    ts = jax.jit(steps.make_train_step(cfg, adamw.AdamWConfig(
        total_steps=100, warmup_steps=0)))
    state2, metrics = ts(state, batch)
    assert np.isfinite(float(metrics["loss"])), arch
    assert np.isfinite(float(metrics["grad_norm"])), arch
    assert int(state2.step) == 1
    # params actually changed on the second step (lr>0 after step 0)
    state3, m3 = ts(state2, batch)
    assert np.isfinite(float(m3["loss"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_smoke(arch, rng_key):
    cfg = get_tiny_config(arch)
    params = steps.init_params(cfg, rng_key)
    s_max = 32
    if cfg.is_mla:  # no latent cache yet: decoding says so
        with pytest.raises(NotImplementedError, match="latent attention"):
            steps.decode_state(cfg, B, s_max)
        return
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(rng_key, (B, cfg.enc_seq, cfg.d_model),
                                   jnp.bfloat16)
        memory = jax.jit(lambda p, f: encdec.encode(p, f, cfg))(params, frames)
        states = encdec.init_decode_state(params, memory, cfg, B, s_max)
    else:
        states = steps.decode_state(cfg, B, s_max)
    dec = jax.jit(steps.make_decode_step(cfg))
    tok = jnp.zeros((B, 1), jnp.int32)
    for i in range(3):
        tok, states = dec(params, tok, states, jnp.int32(i))
        assert tok.shape == (B, 1)
        assert int(tok.max()) < cfg.vocab_size


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen2.5-3b", "xlstm-125m",
                                  "recurrentgemma-2b"])
def test_prefill_then_decode_matches_full_forward(arch, rng_key):
    """Greedy decode after prefill == argmax of teacher-forced logits at the
    same position (KV-cache / recurrent-state correctness)."""
    cfg = get_tiny_config(arch)
    params = steps.init_params(cfg, rng_key)
    toks = jax.random.randint(rng_key, (B, 16), 0, cfg.vocab_size)

    from repro.models import lm
    logits_full, _, _ = jax.jit(
        lambda p, t: lm.lm_apply(p, t, cfg, mode="train"))(params, toks)

    prefill = jax.jit(steps.make_prefill_step(cfg))
    # prefill on the first 15 tokens, then decode the 16th
    nxt, states, last_logits = prefill(params, {"tokens": toks[:, :-1]})
    want = jnp.argmax(logits_full[:, -2], axis=-1)
    got = nxt[:, 0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_param_counts_match_reported_class():
    """Full configs should land in the right parameter-count ballpark."""
    expect = {
        "llama3-8b": (7e9, 9.5e9),
        "smollm-360m": (3.0e8, 4.4e8),
        "qwen3-moe-235b-a22b": (2.0e11, 2.7e11),
        "deepseek-coder-33b": (3.0e10, 3.7e10),
        "qwen2.5-3b": (2.6e9, 3.9e9),
        "chameleon-34b": (3.0e10, 3.9e10),
        "recurrentgemma-2b": (2.2e9, 3.4e9),
        "xlstm-125m": (1.0e8, 1.8e8),
        "whisper-tiny": (2.5e7, 5e7),
        "granite-moe-3b-a800m": (2.6e9, 3.9e9),
        "moonlight-16b-a3b": (1.5e10, 1.7e10),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3g} not in [{lo:.3g}, {hi:.3g}]"
    # MoE active params land near the advertised "a22b"/"a800m"
    a = get_config("qwen3-moe-235b-a22b").active_param_count()
    assert 1.5e10 <= a <= 3.0e10, a
    a = get_config("granite-moe-3b-a800m").active_param_count()
    assert 5e8 <= a <= 1.2e9, a


def test_moe_local_flops_scale_with_topk_not_experts(rng_key):
    """Dropless dispatch computes ~active rows, not experts x tokens."""
    from repro.nn import params as prm
    from repro.nn.moe import def_moe, moe_ffn_local

    d, ff = 32, 64
    for n_experts in [4, 16]:
        p = prm.materialize(rng_key, def_moe(d, n_experts, ff), jnp.float32)
        x = jax.random.normal(rng_key, (1, 128, d))
        y, aux = jax.jit(
            lambda p, x: moe_ffn_local(p, x, top_k=2))(p, x)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        assert float(aux["balance"]) > 0
        assert float(aux["load"].sum()) == 128 * 2
