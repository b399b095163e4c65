"""The DeepSeek-V3 block (moonlight-16b-a3b) against the plain reference
(``bench/reference_moe.py``) at a tiny size on the CPU: 1 dense + 2 MoE
layers, d_model 64, 8 experts of which 4 are held, 2 shared, vocab 256.

Also: latent attention's q/k width unlike v's through the chunked scan
and the splash kernel (interpret mode); the experts' grouped matmul as
the megablox kernel (interpret mode) against ``ragged_dot``; the shares
of a layer adding up to the uncut layer; a share's router trained by
the balance loss alone; dropless routing; the router bias's sign rule; the benchmark's balanced start of the biases; and a
job through the platform with its step donated.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import bench.reference_moe as ref  # noqa: E402
import bench.weights_moe as W  # noqa: E402
from repro.configs import get_tiny_config  # noqa: E402
from repro.kernels import splash  # noqa: E402
from repro.kernels.ops import expert_ffn  # noqa: E402
from repro.models import steps  # noqa: E402
from repro.nn.attention import flash_attention, naive_attention  # noqa: E402
from repro.nn.moe import moe_ffn_local  # noqa: E402

SEED = 2**33 + 21


def _cfg(**kw):
    return get_tiny_config("moonlight-16b-a3b").replace(dtype="float32", **kw)


def _model(cfg) -> dict:
    """The configuration as the reference and the weights take it."""
    return {"n_layers": cfg.n_layers, "n_dense_layers": cfg.n_dense_layers,
            "d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_dim": cfg.qk_nope_dim,
            "qk_rope_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
            "d_ff": cfg.d_ff, "moe_d_ff": cfg.moe_d_ff,
            "shared_d_ff": cfg.shared_d_ff, "n_experts": cfg.n_experts,
            "e_local": cfg.experts_held, "top_k": cfg.top_k,
            "routed_scale": cfg.routed_scale,
            "router_bias_rate": cfg.router_bias_rate,
            "balance_weight": cfg.balance_weight,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_eps}


def _batch(cfg, seq=64):
    toks = jax.random.randint(jax.random.key(3), (2, seq + 1), 0,
                              cfg.vocab_size)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _biases(m, params, seq=64):
    """The routers' biases balanced on a batch of their own."""
    toks = jax.random.randint(jax.random.key(4), (2, seq), 0, m["vocab_size"])
    return ref.balanced_biases(params, toks, m)


def _close(a, b, rtol, atol=0.0):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol)


def test_config_is_the_published_one():
    from repro.configs import get_config

    cfg = get_config("moonlight-16b-a3b")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (27, 2048, 163840)
    assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff, cfg.shared_d_ff) == (
        64, 6, 1408, 2816)
    assert (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert cfg.n_dense_layers == 1 and cfg.d_ff == 11264
    cut = cfg.replace(n_layers=5, e_local=8, vocab_size=20480)
    n = sum(x.size for x in jax.tree.leaves(steps.abstract_params(cut)))
    assert n == 568_484_352


def test_whole_model_loss_and_gradients_match_the_reference():
    cfg = _cfg()
    m = _model(cfg)
    params = W.make(m, SEED, "float32")
    buffers = {"router_bias": _biases(m, params)}
    batch = _batch(cfg)
    opt = {"z_loss": 1e-4}

    def program(p):
        loss, parts = steps.loss_fn(p, batch, cfg, buffers)
        return loss, parts["load"]

    (got, load), g_got = jax.jit(jax.value_and_grad(program, has_aux=True))(
        params)
    with jax.default_matmul_precision("highest"):
        (want, want_load), g_want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, buffers, batch, m, opt),
            has_aux=True))(params)
    _close(got, want, rtol=1e-5)
    _close(load, want_load, rtol=0)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        scale = float(jnp.max(jnp.abs(b)))
        _close(a, b, rtol=0, atol=2e-4 * scale + 1e-12)


@pytest.mark.parametrize("path", ["chunked", "splash"])
def test_latent_attention_widths_match_naive(path):
    """q/k of 192, v of 128: forward and q, k, v gradients."""
    b, h, s, dqk, dv = 1, 2, 256, 192, 128
    ks = jax.random.split(jax.random.key(7), 4)
    q, k = (jax.random.normal(kk, (b, h, s, dqk)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (b, h, s, dv))
    ct = jax.random.normal(ks[3], (b, h, s, dv))

    def run(fn):
        o, vjp = jax.vjp(fn, q, k, v)
        return (o,) + vjp(ct)

    if path == "chunked":
        got = run(lambda q, k, v: flash_attention(q, k, v, chunk=64))
    else:
        got = run(lambda q, k, v: splash.causal_attention(
            q * dqk ** -0.5, k, v, block=128, interpret=True))
    with jax.default_matmul_precision("highest"):
        want = run(lambda q, k, v: naive_attention(q, k, v))
    assert got[0].shape == (b, h, s, dv)
    for a, w in zip(got, want):
        _close(a, w, rtol=0, atol=2e-4 * float(jnp.max(jnp.abs(w))))


def test_grouped_matmul_kernel_matches_ragged_dot():
    """The megablox path (interpret mode) against ``ragged_dot``, forward
    and backward, with rows past the routed ones left out."""
    e, d, f, m = 4, 128, 128, 256
    ks = jax.random.split(jax.random.key(9), 5)
    rows = jax.random.normal(ks[0], (m, d), jnp.float32)
    up, gate = (jax.random.normal(kk, (e, d, f)) * d ** -0.5 for kk in ks[1:3])
    down = jax.random.normal(ks[3], (e, f, d)) * f ** -0.5
    sizes = jnp.array([40, 0, 100, 60], jnp.int32)  # 200 of 256 rows
    valid = (jnp.arange(m) < 200)[:, None]
    ct = jax.random.normal(ks[4], (m, d))

    def run(force):
        def fn(rows, up, gate, down):
            out = expert_ffn(rows, up, gate, down, sizes, force=force)
            return jnp.where(valid, out, 0)
        o, vjp = jax.vjp(fn, rows, up, gate, down)
        return (o,) + vjp(ct)

    got = run("interpret")
    want = run(None)
    for a, w in zip(got, want):
        _close(jnp.where(valid, a, 0) if a.shape == rows.shape else a,
               jnp.where(valid, w, 0) if w.shape == rows.shape else w,
               rtol=0, atol=1e-4 * float(jnp.max(jnp.abs(w))))


def _moe_params(cfg):
    m = _model(cfg)
    params = W.make(m, SEED, "float32")
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["scan"]["moe"])
    return m, layer, _biases(m, params)[0]


def _route_kw(cfg, bias):
    return dict(top_k=cfg.top_k, score="sigmoid", bias=bias,
                routed_scale=cfg.routed_scale, balance="seq")


def test_shares_add_up_to_the_uncut_layer():
    """Every 4-expert share of an 8-expert layer, with the shared experts
    counted once, adds up to the uncut reference layer."""
    cfg = _cfg(e_local=0)
    m, p, bias = _moe_params(cfg)
    x = jax.random.normal(jax.random.key(5), (2, 32, cfg.d_model))
    held = 4
    total = 0.0
    for e_start in range(0, cfg.n_experts, held):
        part = dict(p, **{k: p[k][e_start:e_start + held]
                          for k in ("up", "gate", "down")})
        y, _ = moe_ffn_local(part, x, e_start=e_start, e_local=held,
                             **_route_kw(cfg, bias))
        total = total + y
    shared = ref.mlp(p["shared"], x)
    total = total - (cfg.n_experts // held - 1) * shared
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.moe(p, bias, x, dict(m, e_local=cfg.n_experts))
    _close(total, want, rtol=0, atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_dropless_every_token_to_one_expert():
    """A bias that sends every token to held expert 1 (and top_k - 1
    others) loses no row: the load counts them all, and the layer equals
    the reference, which computes every expert on every token."""
    cfg = _cfg()
    m, p, bias = _moe_params(cfg)
    bias = bias.at[1].set(100.0)
    t = 2 * 32
    x = jax.random.normal(jax.random.key(6), (2, 32, cfg.d_model))
    y, aux = moe_ffn_local(p, x, **_route_kw(cfg, bias))
    assert float(aux["load"][1]) == t
    assert float(aux["load"].sum()) == t * cfg.top_k
    with jax.default_matmul_precision("highest"):
        want, _, load = ref.moe(p, bias, x, m)
    _close(aux["load"], load, rtol=0)
    _close(y, want, rtol=0, atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_a_share_trains_its_router_by_the_balance_loss_alone():
    """Holding 4 of 8 experts, the routing weights keep the whole
    layer's values but pass no gradient to the router, which the balance
    loss still trains; holding all of them, the weights' gradient reaches
    the router."""
    from repro.nn.moe import route

    e, k, t, d = 8, 3, 64, 16
    x = jax.random.normal(jax.random.key(7), (1, t, d))
    router = jax.random.normal(jax.random.key(8), (d, e)) * d ** -0.5
    c = jax.random.normal(jax.random.key(9), (t, k))
    kw = dict(top_k=k, score="sigmoid", routed_scale=2.446, balance="seq")

    def grads(share):
        def weighted(r):
            return jnp.sum(c * route(r, x, share=share, **kw)[0])

        def balance(r):
            return route(r, x, share=share, **kw)[2]
        return (np.asarray(jax.grad(weighted)(router)),
                np.asarray(jax.grad(balance)(router)))

    _close(route(router, x, share=True, **kw)[0],
           route(router, x, share=False, **kw)[0], rtol=0)
    through_w, through_balance = grads(True)
    assert np.all(through_w == 0)
    assert np.abs(through_balance).max() > 0
    whole_w, whole_balance = grads(False)
    assert np.abs(whole_w).max() > 0
    _close(whole_balance, through_balance, rtol=0)


def test_router_bias_sign_rule():
    bias = jnp.array([[0.5, 0.0, -0.5, 0.1]])
    load = jnp.array([[10.0, 30.0, 20.0, 20.0]])  # mean 20
    new = steps.update_router_bias(bias, load, 1e-3)
    _close(new - bias, jnp.array([[1e-3, -1e-3, 0.0, 0.0]]), rtol=0,
           atol=1e-7)


def test_balanced_biases_give_every_expert_its_share():
    """On the batch they were balanced on, each MoE layer's router sends
    every expert the same rows, in the reference and in the program; with
    the biases dropped the rows are far from even, so a path that drops
    them is seen."""
    cfg = _cfg()
    m = _model(cfg)
    params = W.make(m, SEED, "float32")
    toks = jax.random.randint(jax.random.key(4), (2, 64), 0, cfg.vocab_size)
    bias = ref.balanced_biases(params, toks, m)
    assert bias.shape == (cfg.n_moe_layers, cfg.n_experts)
    _close(jnp.mean(bias, -1), jnp.zeros(cfg.n_moe_layers), rtol=0,
           atol=1e-6)
    share = 2 * 64 * cfg.top_k / cfg.n_experts
    batch = {"tokens": toks, "labels": toks}
    with jax.default_matmul_precision("highest"):
        _, load = ref.loss(params, {"router_bias": bias}, batch, m,
                           {"z_loss": 0.0})
        _, raw = ref.loss(params, {"router_bias": jnp.zeros_like(bias)},
                          batch, m, {"z_loss": 0.0})
    assert np.abs(np.asarray(load) - share).max() <= 1
    assert np.abs(np.asarray(raw) - share).max() >= 0.25 * share
    _, parts = jax.jit(lambda p: steps.loss_fn(
        p, batch, cfg, {"router_bias": bias}))(params)
    _close(parts["load"], load, rtol=0)


def test_solve_bias_balances_offset_scores():
    """Scores whose experts differ by a wide offset: the solved bias
    gives each expert the mean rows, where no bias gives some 3x that."""
    k1, k2 = jax.random.split(jax.random.key(11))
    t, e, k = 4096, 16, 2
    scores = jax.nn.sigmoid(jax.random.normal(k1, (t, e))
                            + 0.5 * jax.random.normal(k2, (e,)))

    def loads(b):
        _, idx = jax.lax.top_k(scores + b, k)
        return np.bincount(np.asarray(idx).ravel(), minlength=e)

    share = t * k / e
    assert loads(jnp.zeros(e)).max() >= 2 * share
    assert np.abs(loads(ref.solve_bias(scores, k)) - share).max() <= 1


def test_train_step_updates_the_bias_from_the_loads():
    cfg = get_tiny_config("moonlight-16b-a3b")
    state = steps.init_train_state(cfg, jax.random.key(0))
    assert state.buffers["router_bias"].shape == (2, cfg.n_experts)
    step = jax.jit(steps.make_train_step(cfg, steps.adamw.AdamWConfig(
        total_steps=10)))
    new, metrics = step(state, _batch(cfg))
    moved = np.asarray(new.buffers["router_bias"])
    assert np.allclose(np.abs(moved[moved != 0]), 1e-3)
    assert np.count_nonzero(moved) > 0
    assert 0 < float(metrics["moe_rows_busiest"]) <= float(
        metrics["moe_rows_held"]) <= 2 * 64 * cfg.top_k * 2


def test_prefill_and_decode_say_latent_attention_trains_only():
    cfg = get_tiny_config("moonlight-16b-a3b")
    params = steps.init_params(cfg, jax.random.key(0))
    with pytest.raises(NotImplementedError, match="latent attention"):
        steps.make_prefill_step(cfg)(params, {"tokens": jnp.zeros(
            (1, 8), jnp.int32)})


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_job_through_the_platform_donates_and_counts(monkeypatch, backend):
    """A moonlight job through submit → tick → RealLearner: the first
    stepping tick counts the jnp expert path (the step runs on the CPU),
    stepping ticks count the routed rows, and a learner built for an
    accelerator donates the state it is given to its step; on the CPU
    backend the given state stays valid."""
    from repro.api import ApiClient
    from repro.core import FfDLPlatform, JobManifest
    from repro.obs.spans import recent_ticks

    p = FfDLPlatform(n_hosts=1, chips_per_host=1, shard_id=f"t-moe-{backend}")
    c = ApiClient.for_platform(p, tenant="moe")
    job = c.submit(JobManifest(
        name="moe", tenant="moe", arch="moonlight-16b-a3b", n_learners=1,
        chips_per_learner=1, train={"steps": 40, "batch": 2, "seq": 64}))
    learner = None
    with monkeypatch.context() as mp:  # the backend the learner builds for
        mp.setattr(jax, "default_backend", lambda: backend)
        for _ in range(100):
            p.tick()
            g = p.guardians.get(job)
            learner = g.runtimes.get(0) if g is not None else None
            if learner is not None and learner.phase == "PROCESSING":
                break
    old = learner._state
    p.tick()
    built = recent_ticks(1)[0]
    assert built.counters["moe.dense"] >= 1 and "moe.gmm" not in built.counters
    assert built.counters["attention.chunked"] >= 1
    assert all(x.is_deleted() == (backend == "tpu")
               for x in jax.tree.leaves(old.params))
    p.tick()
    rec = recent_ticks(1)[0]
    assert rec.counters["learner.steps"] == learner.steps_per_tick
    assert rec.counters["moe.rows_held"] > 0
    assert 0 < rec.counters["moe.rows_busiest"] <= rec.counters[
        "moe.rows_held"]
    assert len(learner.loss_history) == 2 * learner.steps_per_tick
