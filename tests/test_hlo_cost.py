"""Loop-aware HLO analyzer vs XLA cost_analysis (exact on loop-free dots;
correct trip multiplication on scans — the dry-run's roofline source)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze


def test_loopfree_dot_flops_match_xla():
    def f(x, w1, w2):
        return jnp.tanh(x @ w1) @ w2

    x = jax.ShapeDtypeStruct((512, 256), jnp.float32)
    w1 = jax.ShapeDtypeStruct((256, 1024), jnp.float32)
    w2 = jax.ShapeDtypeStruct((1024, 128), jnp.float32)
    c = jax.jit(f).lower(x, w1, w2).compile()
    mine = analyze(c.as_text())
    expected = 2 * 512 * 256 * 1024 + 2 * 512 * 1024 * 128
    assert abs(mine["flops"] - expected) / expected < 0.01


@pytest.mark.parametrize("L", [2, 8, 32])
def test_scan_flops_multiply_by_trip_count(L):
    def body(x, w):
        return jnp.tanh(x @ w), None

    def g(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, 256, 256), jnp.float32)
    c = jax.jit(g).lower(x, ws).compile()
    mine = analyze(c.as_text())
    expected = L * 2 * 256 ** 3
    assert abs(mine["flops"] - expected) / expected < 0.02
    # XLA's own count is trip-count-blind (the reason this module exists)
    ca = c.cost_analysis()
    assert ca["flops"] < mine["flops"] or L == 1


def test_scanned_equals_unrolled_model():
    """A scanned layer stack must cost the same as its unrolled twin."""
    from repro.configs import get_tiny_config
    from repro.models import steps
    from repro.optim import adamw

    cfg0 = get_tiny_config("smollm-360m").replace(n_layers=4, attn_chunk=64)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    opt = adamw.AdamWConfig(total_steps=10)
    costs = {}
    for tag, cfg in [("unrolled", cfg0.replace(scan_layers=False)),
                     ("scanned", cfg0.replace(scan_layers=True))]:
        astate = steps.abstract_train_state(cfg)
        c = jax.jit(steps.make_train_step(cfg, opt)).lower(
            astate, batch).compile()
        costs[tag] = analyze(c.as_text())["flops"]
    ratio = costs["scanned"] / costs["unrolled"]
    assert 0.95 < ratio < 1.05, costs


def test_collectives_counted_with_loop_multiplier():
    # hand-written HLO exercise of the parser instead: collective inside while
    hlo = """
HloModule test

%cond (arg: (s32[], f32[16,16])) -> pred[] {
  %arg = (s32[], f32[16,16]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %limit = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %limit), direction=LT
}

%body (arg: (s32[], f32[16,16])) -> (s32[], f32[16,16]) {
  %arg = (s32[], f32[16,16]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[16,16] get-tuple-element(%arg), index=1
  %ar = f32[16,16] all-reduce(%x), replica_groups={}, to_apply=%sum
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[16,16]) tuple(%i2, %ar)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p: (s32[], f32[16,16])) -> (s32[], f32[16,16]) {
  %p = (s32[], f32[16,16]) parameter(0)
  ROOT %w = (s32[], f32[16,16]) while(%p), condition=%cond, body=%body
}
"""
    res = analyze(hlo)
    # one 16x16 f32 all-reduce, 10 iterations
    assert res["collective_bytes"] == 10 * 16 * 16 * 4
    assert res["collective_counts"]["all-reduce"] == 1
