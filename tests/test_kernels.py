"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), with shape/
dtype sweeps as required — plus the chunked-jnp fallback paths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, splash
from repro.kernels.ops import flash_attention, rglru_scan, self_attention
from repro.nn.attention import flash_attention as chunked_attn
from repro.nn.attention import naive_attention


KEY = jax.random.key(42)


def _qkv(b, h, kv, s, d, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, h, s, d), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (b, kv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (b, kv, s, d), jnp.float32).astype(dtype)
    return q, k, v


FLASH_CASES = [
    # (B, H, KV, S, D, causal, window)
    (2, 4, 2, 256, 64, True, 0),     # GQA causal
    (1, 8, 8, 128, 128, True, 0),    # MHA, mxu-wide head
    (2, 4, 1, 256, 64, True, 64),    # MQA + local window
    (1, 2, 2, 128, 64, False, 0),    # bidirectional (encoder)
    (1, 15, 5, 128, 64, True, 0),    # smollm-style 15H/5KV grouping
    (2, 2, 2, 512, 32, True, 128),   # long window
]


@pytest.mark.parametrize("b,h,kv,s,d,causal,window", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_matches_ref(b, h, kv, s, d, causal, window, dtype):
    q, k, v = _qkv(b, h, kv, s, d, dtype)
    out_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          force="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(out_ref, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("b,h,kv,s,d,causal,window", FLASH_CASES[:4])
def test_chunked_jnp_matches_naive(b, h, kv, s, d, causal, window):
    """The dry-run's chunked attention == O(S^2) oracle."""
    q, k, v = _qkv(b, h, kv, s, d, jnp.float32)
    out_naive = naive_attention(q, k, v, causal=causal, window=window)
    out_chunk = chunked_attn(q, k, v, causal=causal, window=window, chunk=64)
    np.testing.assert_allclose(np.asarray(out_chunk), np.asarray(out_naive),
                               atol=2e-5, rtol=2e-5)


def test_flash_q_offset_decode_suffix():
    """q as a suffix of the kv sequence (speculative/chunked prefill)."""
    b, h, s, d = 1, 4, 256, 64
    q, k, v = _qkv(b, h, h, s, d, jnp.float32)
    q_tail = q[:, :, -64:]
    out_full = ref.flash_attention_ref(q, k, v, causal=True)[:, :, -64:]
    out_off = flash_attention(q_tail, k, v, causal=True, q_offset=s - 64,
                              force="interpret")
    np.testing.assert_allclose(np.asarray(out_off), np.asarray(out_full),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_splash_kernel_matches_naive_forward_and_backward(dtype):
    """The train step's TPU attention: causal GQA output and dq, dk, dv."""
    q, k, v = _qkv(1, 4, 2, 256, 64, dtype)
    ct = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)

    def kernel(q, k, v):
        scaled = (q * q.shape[-1] ** -0.5).astype(q.dtype)
        return splash.causal_attention(scaled, k, v, block=128,
                                       interpret=True)

    def oracle(q, k, v):
        return naive_attention(*(t.astype(jnp.float32) for t in (q, k, v)))

    def loss(attn, q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * ct)

    out = kernel(q, k, v)
    assert out.dtype == dtype and out.shape == q.shape
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    outs = [(out, oracle(q, k, v))]
    outs += zip(jax.grad(functools.partial(loss, kernel), (0, 1, 2))(q, k, v),
                jax.grad(functools.partial(loss, oracle), (0, 1, 2))(q, k, v))
    for got, want in outs:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        # within tol of the largest element: bf16 rounds each operand
        assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


ROUTES = [
    # (causal, window, S, sharded, routed to the kernel on a TPU)
    (True, 0, 256, False, True),
    (False, 0, 256, False, False),   # bidirectional (encoder)
    (True, 64, 256, False, False),   # local window
    (True, 0, 192, False, False),    # not a multiple of a kernel block
    (True, 0, 256, True, False),     # under a mesh: no auto-partitioning
]


@pytest.mark.parametrize("causal,window,s,sharded,kernel", ROUTES)
def test_self_attention_routes_to_the_kernel_or_the_scan(causal, window, s,
                                                         sharded, kernel):
    from repro.obs.spans import ROOT, recent_ticks, span
    from repro.parallel import MeshEnv, null_env, use_env

    q, k, v = _qkv(1, 4, 2, s, 64, jnp.float32)
    attn = functools.partial(self_attention, causal=causal, window=window,
                             chunk=64)
    env = (MeshEnv(jax.make_mesh((1,), ("data",)), {"batch": "data"})
           if sharded else null_env())
    with use_env(env):
        with span(ROOT):
            jaxpr = str(jax.make_jaxpr(attn)(q, k, v))
        out = jax.jit(attn)(q, k, v)
    # the kernel is staged only where it may run; the CPU lowers the scan
    # and counts it so
    assert ("pallas_call" in jaxpr) == kernel
    counters = recent_ticks(1)[0].counters
    assert counters["attention.chunked"] == 1
    assert "attention.kernel" not in counters
    want = jax.jit(functools.partial(chunked_attn, causal=causal,
                                     window=window, chunk=64))(q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


RGLRU_CASES = [
    (8, 256, 128),
    (2, 512, 256),
    (1, 128, 512),
    (16, 64, 128),
]


@pytest.mark.parametrize("b,s,w", RGLRU_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_h0", [False, True])
def test_rglru_pallas_matches_ref(b, s, w, dtype, with_h0):
    k1, k2, k3 = jax.random.split(KEY, 3)
    a = (jax.nn.sigmoid(jax.random.normal(k1, (b, s, w))) * 0.2 + 0.79
         ).astype(dtype)
    bb = (jax.random.normal(k2, (b, s, w)) * 0.1).astype(dtype)
    h0 = jax.random.normal(k3, (b, w)) if with_h0 else None
    h_ref, hl_ref = ref.rglru_scan_ref(a, bb, h0)
    h, hl = rglru_scan(a, bb, h0, force="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hl_ref), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("b,s,w", RGLRU_CASES[:2])
def test_rglru_associative_scan_matches_ref(b, s, w):
    """The dry-run's associative-scan path == sequential oracle."""
    k1, k2 = jax.random.split(KEY)
    a = jax.nn.sigmoid(jax.random.normal(k1, (b, s, w))) * 0.2 + 0.79
    bb = jax.random.normal(k2, (b, s, w)) * 0.1
    h_ref, hl_ref = ref.rglru_scan_ref(a, bb)
    h, hl = rglru_scan(a, bb, force="jnp")
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=2e-5,
                               rtol=2e-5)


def test_mlstm_chunkwise_matches_stepwise():
    """Chunkwise-parallel mLSTM == step-by-step recurrence."""
    from repro.nn.recurrent import mlstm_chunkwise, mlstm_ref
    b, h, s, d = 2, 3, 128, 32
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, h, s, d))
    v = jax.random.normal(ks[2], (b, h, s, d))
    ig = jax.random.normal(ks[3], (b, h, s)) * 0.5
    fg = jax.random.normal(ks[4], (b, h, s)) * 0.5 + 2.0
    out_c, st_c = mlstm_chunkwise(q, k, v, ig, fg, chunk=32)
    out_r, st_r = mlstm_ref(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                               atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st_c.c), np.asarray(st_r.c),
                               atol=2e-4, rtol=2e-3)


def test_rglru_block_decode_matches_prefill():
    """One-step decode == last position of a prefill (state handoff)."""
    from repro.nn.recurrent import rglru, rglru_step, def_rglru
    from repro.nn import params as prm
    w, nh, b, s = 64, 2, 2, 16
    p = prm.materialize(jax.random.key(1), def_rglru(w, nh), jnp.float32)
    x = jax.random.normal(KEY, (b, s, w))
    full, h_last = rglru(p, x, nh)
    # replay: prefill first s-1 then decode the final token
    part, h_prev = rglru(p, x[:, :-1], nh)
    y_dec, h_dec = rglru_step(p, x[:, -1], h_prev, nh)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(full[:, -1]),
                               atol=1e-5, rtol=1e-5)
