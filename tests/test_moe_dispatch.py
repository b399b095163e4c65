"""The MoE layer's dispatch buffer sized to the held share of the
experts (``nn.moe.dispatch_capacity``), on the CPU, at the reduced
moonlight config (8 experts, top 3): over 2 x 512 tokens a share of 2
experts gets a buffer of 1,536 of the 3,072 rows, a share of 1 expert
1,024. The sized path equals the worst-case one in values and
gradients, whether the held rows fit (the sized branch) or overflow
(the worst-case branch); the step counts the overflowing layers; a layer
that holds every expert builds no condition; and the condition lies
outside the scopes that ``moe_dispatch_ms`` reads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _hlo
from repro.configs import get_tiny_config
from repro.kernels.ops import expert_ffn
from repro.models import steps
from repro.nn import moe
from repro.nn import params as prm
from repro.nn.mlp import mlp

BATCH, SEQ = 2, 512
SCOPES = ("moe.route", "moe.dispatch", "moe.combine")


def _cfg(**kw):
    return get_tiny_config("moonlight-16b-a3b").replace(dtype="float32",
                                                        **kw)


def _route_kw(cfg, bias, share):
    return dict(top_k=cfg.top_k, score="sigmoid", bias=bias,
                routed_scale=cfg.routed_scale, balance="seq", share=share)


def _worst_case(t, top_k, e_local, n_experts):
    return t * top_k


@jax.custom_vjp
def _undefined_out(rows, pad):
    """rows, NaN where ``pad``: what a grouped matmul may leave there."""
    return jnp.where(pad[:, None], jnp.nan, rows)


_undefined_out.defvjp(lambda rows, pad: (_undefined_out(rows, pad), None),
                      lambda _, g: (g, None))


@jax.custom_vjp
def _undefined_grad(rows, pad):
    """rows, whose gradient is NaN where ``pad``."""
    return rows


_undefined_grad.defvjp(lambda rows, pad: (rows, pad),
                       lambda pad, g: (jnp.where(pad[:, None], jnp.nan, g),
                                       None))


def _expert_ffn_undefined_past_sizes(rows, up, gate, down, sizes, act):
    """``expert_ffn`` as its contract allows it on a TPU: the rows past
    ``sum(sizes)`` get an undefined output and an undefined gradient."""
    pad = jnp.arange(rows.shape[0]) >= jnp.sum(sizes)
    out = expert_ffn(_undefined_grad(rows, pad), up, gate, down, sizes, act)
    return _undefined_out(out, pad)


def _close(a, b):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=1e-5 * max(np.abs(b).max(), 1e-30))


@pytest.mark.parametrize("held,e_start,overflow,share", [
    (2, 0, False, True),
    (2, 0, False, False),
    (1, 5, False, True),
    (2, 0, True, True),
    (2, 6, True, False),
], ids=["fits", "fits-router-grad", "one-expert-from-5", "overflow",
        "overflow-router-grad"])
def test_sized_buffer_equals_the_worst_case(monkeypatch, held, e_start,
                                            overflow, share):
    """y, the balance loss, the loads and the gradients to every
    parameter and to x: the sized path where the held rows fit its
    buffer, the worst-case branch where a bias sends every token to the
    held experts, each against the worst-case buffer forced. The grouped
    matmul leaves its padding rows undefined (NaN), value and gradient,
    as its contract allows: neither path may let them through."""
    cfg = _cfg()
    p = prm.materialize(jax.random.key(0), moe.def_moe(
        cfg.d_model, cfg.n_experts, cfg.moe_d_ff, e_local=held,
        shared_d_ff=cfg.shared_d_ff), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (BATCH, SEQ, cfg.d_model))
    c = jax.random.normal(jax.random.key(2), x.shape)
    bias = jnp.zeros(cfg.n_experts)
    if overflow:
        bias = bias.at[e_start:e_start + held].set(100.0)
    kw = _route_kw(cfg, bias, share)
    monkeypatch.setattr(moe, "expert_ffn", _expert_ffn_undefined_past_sizes)

    def loss(p, x):
        y, aux = moe.moe_ffn_local(p, x, e_start=e_start, e_local=held,
                                   **kw)
        return jnp.sum(y * c) + aux["balance"], (y, aux)

    def run():
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(p, x)

    cap = moe.dispatch_capacity(BATCH * SEQ, cfg.top_k, held, cfg.n_experts)
    assert cap < BATCH * SEQ * cfg.top_k
    (_, (y, aux)), grads = run()
    rows = float(aux["load"][e_start:e_start + held].sum())
    assert (rows > cap) == overflow, (rows, cap)
    with monkeypatch.context() as mp:
        mp.setattr(moe, "dispatch_capacity", _worst_case)
        (_, (want_y, want_aux)), want_grads = run()
    _close(y, want_y)
    _close(aux["load"], want_aux["load"])
    _close(aux["balance"], want_aux["balance"])
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        _close(got, want)
    router = grads[0]["router"]
    assert np.abs(np.asarray(router)).max() > 0


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["fits", "overflow"])
def test_step_counts_the_layers_that_overflow(overflow):
    """``moe_full_buffer``: the MoE layers whose held rows exceeded the
    sized buffer; a router bias that sends every token to the two held
    experts makes every layer overflow, and no token is dropped: each
    layer's loads still sum to every row."""
    cfg = _cfg(e_local=2)
    state = steps.init_train_state(cfg, jax.random.key(0))
    if overflow:
        bias = state.buffers["router_bias"].at[:, :2].set(100.0)
        state = state._replace(buffers={"router_bias": bias})
    toks = jax.random.randint(jax.random.key(3), (BATCH, SEQ + 1), 0,
                              cfg.vocab_size)
    step = jax.jit(steps.make_train_step(cfg, steps.adamw.AdamWConfig(
        total_steps=10)))
    _, metrics = step(state, {"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    cap = moe.dispatch_capacity(BATCH * SEQ, cfg.top_k, 2, cfg.n_experts)
    per_layer = float(metrics["moe_rows_held"]) / cfg.n_moe_layers
    assert int(metrics["moe_full_buffer"]) == (
        cfg.n_moe_layers if overflow else 0)
    assert (per_layer > cap) == overflow
    if overflow:
        assert per_layer == 2 * BATCH * SEQ


def _dense(p, x, kw):
    """Every expert on every token, the chosen ones weighted: the layer
    with no dispatch at all."""
    b, s, d = x.shape
    w, idx, _, _ = moe.route(p["router"], x, **kw)
    xs = x.reshape(-1, d)
    h = (jax.nn.silu(jnp.einsum("td,edf->tef", xs, p["gate"]))
         * jnp.einsum("td,edf->tef", xs, p["up"]))
    every = jnp.einsum("tef,efd->ted", h, p["down"])
    y = jnp.sum(jnp.take_along_axis(every, idx[..., None], axis=1)
                * w[..., None], axis=1)
    if "shared" in p:
        y = y + mlp(p["shared"], x, "silu").reshape(-1, d)
    return y.reshape(b, s, d)


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b",
                                  "granite-moe-3b-a800m"])
def test_a_whole_layer_builds_no_condition(arch):
    """Holding every expert the buffer is the worst case: no condition
    is lowered, forward or backward, and the layer equals every expert
    computed on every token."""
    cfg = get_tiny_config(arch).replace(dtype="float32", e_local=0)
    p = prm.materialize(jax.random.key(0), moe.def_moe(
        cfg.d_model, cfg.n_experts, cfg.moe_d_ff,
        shared_d_ff=cfg.shared_d_ff), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (BATCH, SEQ, cfg.d_model))
    kw = dict(top_k=cfg.top_k, score=cfg.router_score,
              routed_scale=cfg.routed_scale, balance=cfg.balance_loss)
    assert moe.dispatch_capacity(BATCH * SEQ, cfg.top_k, cfg.n_experts,
                                 cfg.n_experts) == BATCH * SEQ * cfg.top_k

    def loss(p, x):
        return jnp.sum(moe.moe_ffn_local(p, x, **kw)[0] ** 2)

    text = jax.jit(jax.grad(loss)).lower(p, x).compile().as_text()
    assert not _hlo.conditionals(text)
    y, _ = jax.jit(lambda p, x: moe.moe_ffn_local(p, x, **kw))(p, x)
    with jax.default_matmul_precision("highest"):
        want = _dense(p, x, kw)
    _close(y, want)


def test_condition_lies_outside_the_metric_scopes():
    """A moonlight job holding 2 of 8 experts, through submit and tick:
    its compiled step holds the condition, forward and backward, under
    none of the scopes that ``moe_dispatch_ms`` reads, so the condition's
    own device time is not counted on top of its branches'; the row
    gathers inside the branches carry ``moe.dispatch`` and
    ``moe.combine``; the sized branch has no worst-case buffer; and each
    stepping tick counts ``moe.full_buffer``."""
    from repro.api import ApiClient
    from repro.core import FfDLPlatform, JobManifest
    from repro.obs.spans import recent_ticks

    p = FfDLPlatform(n_hosts=1, chips_per_host=1, shard_id="t-moe-buffer")
    c = ApiClient.for_platform(p, tenant="moe")
    job = c.submit(JobManifest(
        name="moe", tenant="moe", arch="moonlight-16b-a3b", n_learners=1,
        chips_per_learner=1, train={"steps": 40, "batch": BATCH, "seq": SEQ,
                                    "overrides": {"e_local": 2}}))
    learner = None
    for _ in range(100):
        p.tick()
        g = p.guardians.get(job)
        learner = g.runtimes.get(0) if g is not None else None
        if learner is not None and learner.phase == "PROCESSING":
            break
    p.tick()
    p.tick()
    rec = recent_ticks(1)[0]
    assert rec.counters["learner.steps"] == learner.steps_per_tick
    assert rec.counters["moe.full_buffer"] == 0
    assert 0 < rec.counters["moe.rows_held"]

    rows = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    text = learner._train_step.lower(
        learner._state, {"tokens": rows, "labels": rows}).compile().as_text()
    conds = _hlo.conditionals(text)
    assert len(conds) == 2  # the scanned layers' forward and backward
    worst = f"[{BATCH * SEQ * learner.cfg.top_k},"
    for line, (full, sized) in conds:
        assert not any(s in _hlo.op_name(line) for s in SCOPES), line
        gathers = [_hlo.op_name(x) for x in sized if " gather(" in x]
        for scope in ("moe.dispatch", "moe.combine"):
            assert any(scope in name for name in gathers), (scope, gathers)
        assert not any(worst in x for x in sized)
        assert any(worst in x for x in full)
