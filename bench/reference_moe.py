"""The plain reference of the MoE cells: a DeepSeek-V3 block stack (latent
attention; a leading dense layer, then layers of routed and shared
experts under sigmoid routing with a correction bias) on one chip's
share of the experts. Its forward pass, loss, gradients, AdamW and the
bias update, in straightforward ``jax.numpy``, float32 at the highest
matmul precision.

No kernel and no sort-based dispatch: every held expert runs on every
token and is masked by its routing weight, expert by expert. Attention
runs in blocks of query rows, each against all keys under the causal
mask, and the loss over blocks of tokens: at the cells' sizes the whole
score or logit tensors do not fit on the chip.

It imports nothing of the program and takes nothing the program made: it
builds its weights from the seed with ``bench.weights_moe``, and takes
AdamW and the learning rate from ``bench.reference``. The routers'
biases it starts from are given; ``balanced_biases`` computes them, with
this forward pass, as a deployed model's bias update leaves them: each
expert takes the same rows of a calibration batch.

``lowp=True`` computes every matrix product from operands rounded to
float8 (e4m3, one scale per tensor): the control.

Departures from the published model, each as the program runs it and as
the configuration's ``assumed`` records: the held experts are this chip's
share (the router spans all experts; the absent experts' part is left
out, and the routing weights pass no gradient back, as ``route``
says), and the vocabulary is this chip's slice; the rope dimensions rotate
as two halves, not interleaved; the loss adds a z-loss of 1e-4 *
logsumexp**2 per token; weight decay applies to every leaf of rank 2 or
more as the program stores it, which includes its layer-stacked norm
scales and routers.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

import bench.weights_moe as W
from bench.reference import adamw, einsum, rmsnorm, rope

Q_BLOCK = 512  # query rows per attention block
LOSS_ROWS = 2048  # tokens per block of the loss


def mla(p, x, m, lowp=False):
    """Latent attention on x: (B, T, d), positions 0..T-1, causal."""
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    nope, r = m["qk_nope_dim"], m["kv_lora_rank"]
    b, t, _ = x.shape
    h = m["n_heads"]
    pos = jnp.arange(t)
    q = einsum("btd,dhk->bhtk", x, p["wq"], lowp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    kv_a = einsum("btd,dr->btr", x, p["wkv_a"], lowp)
    c = rmsnorm(kv_a[..., :r], p["kv_norm"]["scale"], eps)
    k_rope = rope(kv_a[..., r:], pos, theta)  # (B, T, rope), one for all heads
    kv = einsum("btr,rhk->bhtk", c, p["wkv_b"], lowp)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (b, h, t, k_rope.shape[-1]))],
        -1)
    scale = 1 / math.sqrt(q.shape[-1])
    qb = min(Q_BLOCK, t)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=2) * scale
        s = einsum("bhtk,bhsk->bhts", qi, k, lowp)
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(rows[:, None] >= pos[None, :], s, -jnp.inf)
        return einsum("bhts,bhsk->bhtk", jax.nn.softmax(s, -1), v, lowp)

    o = jax.lax.map(block, jnp.arange(t // qb))  # (blocks, B, H, qb, dv)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, t, -1)
    return einsum("bhtk,hkd->btd", o, p["wo"], lowp)


def mlp(p, x, lowp=False):
    g = jax.nn.silu(einsum("btd,df->btf", x, p["gate"], lowp))
    return einsum("btf,fd->btd", g * einsum("btd,df->btf", x, p["up"], lowp),
                  p["down"], lowp)


def rows_blocked(fn, x, rows=LOSS_ROWS):
    """fn over x: (B, T, d) a block of ``rows`` tokens at a time."""
    b, t, d = x.shape
    n = min(rows, b * t)
    y = jax.lax.map(jax.checkpoint(fn), x.reshape(-1, n, d))
    return y.reshape(b, t, -1)


def route(router, bias, x, m, lowp=False):
    """Sigmoid scores; top-k on score + bias; weights the chosen scores,
    normalised and scaled; where fewer experts are held than routed, the
    weights pass no gradient back. Returns the weight of each held expert per
    token (B, T, held), the balance loss and the rows routed to each of
    all the experts."""
    e_all, k, held = m["n_experts"], m["top_k"], m["e_local"]
    scores = jax.nn.sigmoid(einsum("btd,de->bte", x, router, lowp))
    _, idx = jax.lax.top_k(scores + bias, k)  # (B, T, k)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * m["routed_scale"]
    if held < e_all:
        w = jax.lax.stop_gradient(w)
    chosen = jax.nn.one_hot(idx, e_all)  # (B, T, k, E)
    per_expert = jnp.sum(w[..., None] * chosen, axis=2)
    counts = jnp.sum(chosen, axis=(1, 2))  # (B, E), rows of each sequence
    f = counts * e_all / (k * x.shape[1])
    share = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    balance = jnp.mean(jnp.sum(f * share, -1))
    return per_expert[..., :held], balance, jnp.sum(counts, 0)


def moe(p, bias, x, m, lowp=False):
    """The held experts' part for every token, plus the shared experts."""
    gates, balance, load = route(p["router"], bias, x, m, lowp)

    def expert(y, e):
        pe = jax.tree.map(lambda a: a[e], {k: p[k] for k in
                                           ("gate", "up", "down")})
        return y + gates[..., e, None] * jax.checkpoint(mlp, static_argnums=2)(
            pe, x, lowp), None

    y, _ = jax.lax.scan(expert, mlp(p["shared"], x, lowp),
                        jnp.arange(m["e_local"]))
    return y, balance, load


def attend(p, x, m, lowp=False):
    """A layer's attention: x after it, and the normed input of the
    layer's MLP or experts."""
    eps = m["rms_norm_eps"]
    x = x + mla(p["attn"], rmsnorm(x, p["norm1"]["scale"], eps), m, lowp)
    return x, rmsnorm(x, p["norm2"]["scale"], eps)


def layer(p, x, m, bias=None, lowp=False):
    """One layer; an MoE layer (``bias`` given) also returns its balance
    loss and routed rows per expert."""
    x, h = attend(p, x, m, lowp)
    if bias is None:  # the dense layer's MLP is wide: a block at a time
        return x + rows_blocked(lambda r: mlp(p["mlp"], r[None], lowp)[0], h)
    y, balance, load = moe(p["moe"], bias, h, m, lowp)
    return x + y, balance, load


def loss(params, buffers, batch, m, opt, lowp=False):
    """Mean token cross-entropy plus the z-loss, over all rows, plus the
    balance weight times the MoE layers' summed balance loss. Returns
    (loss, rows routed to each expert per MoE layer)."""
    x = params["embed"][batch["tokens"]]
    for lp in params["blocks"]["lead"]:
        x = jax.checkpoint(lambda x, lp: layer(lp, x, m, lowp=lowp))(x, lp)

    def body(x, pb):
        lp, bias = pb
        x, balance, load = layer(lp, x, m, bias, lowp)
        return x, (balance, load)

    x, (balance, load) = jax.lax.scan(
        jax.checkpoint(body), x,
        (params["blocks"]["scan"], buffers["router_bias"]))
    x = rmsnorm(x, params["final_norm"]["scale"], m["rms_norm_eps"])
    table = params["unembed"]

    @jax.checkpoint
    def row(xl):
        xr, lr = xl
        z = einsum("td,vd->tv", xr, table, lowp)
        lse = jax.nn.logsumexp(z, -1)
        gold = jnp.take_along_axis(z, lr[:, None], -1)[:, 0]
        return jnp.sum(lse - gold + opt["z_loss"] * lse * lse)

    n = batch["labels"].size
    rows = min(LOSS_ROWS, n)
    ce = jnp.sum(jax.lax.map(row, (x.reshape(n // rows, rows, -1),
                                   batch["labels"].reshape(-1, rows)))) / n
    return ce + m["balance_weight"] * jnp.sum(balance), load


def update_bias(bias, load, rate):
    """bias_i += rate * sign(mean load - load_i), per layer."""
    return bias + rate * jnp.sign(jnp.mean(load, -1, keepdims=True) - load)


BIAS_ITERS = 300  # steps of the balancing rule
BIAS_STEP = 0.1  # its first step, on the relative shortfall
BIAS_DECAY = 0.97  # the step's decay per iteration


def solve_bias(scores, k):
    """A bias, mean zero, under which top-k of ``scores`` (T, E) + bias
    sends every expert the same rows, to within a row where ties allow:
    each expert's bias steps by its shortfall from the mean over the mean,
    the step decaying from ``BIAS_STEP`` by ``BIAS_DECAY`` each of
    ``BIAS_ITERS`` iterations."""
    n = scores.shape[-1]
    target = scores.shape[0] * k / n

    def body(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros(n).at[idx.reshape(-1)].add(1.0)
        b = b + BIAS_STEP * BIAS_DECAY ** i * (target - load) / target
        return b - jnp.mean(b)

    return jax.lax.fori_loop(0, BIAS_ITERS, body, jnp.zeros(n))


@partial(jax.jit, static_argnums=0)
def _lead_forward(mt, params, tokens):
    m = dict(mt)
    x = params["embed"][tokens]
    for lp in params["blocks"]["lead"]:
        x = layer(lp, x, m)
    return x


@partial(jax.jit, static_argnums=0)
def _balanced_layer(mt, lp, x):
    m = dict(mt)
    x, h = attend(lp, x, m)
    scores = jax.nn.sigmoid(einsum("btd,de->bte", h, lp["moe"]["router"]))
    bias = solve_bias(scores.reshape(-1, m["n_experts"]), m["top_k"])
    y, _, _ = moe(lp["moe"], bias, h, m)
    return x + y, bias


def balanced_biases(params, tokens, m: dict):
    """The routers' biases (MoE layers, experts) of a model whose bias
    update has balanced its experts: layer by layer, the bias under which
    that layer's router sends each expert the same rows of ``tokens``
    (B, T), the layers before it routing under their balanced biases."""
    mt = W.frozen(m)
    x = _lead_forward(mt, params, jnp.asarray(tokens))
    out = []
    for i in range(W.n_moe(m)):
        lp = jax.tree.map(lambda a: a[i], params["blocks"]["scan"])
        x, bias = _balanced_layer(mt, lp, x)
        out.append(bias)
    return jnp.stack(out)


@partial(jax.jit, static_argnums=(0, 1, 5))
def _value_grad(mt, ot, p, buffers, batch, lowp):
    return jax.value_and_grad(
        lambda p: loss(p, buffers, batch, dict(mt), dict(ot), lowp),
        has_aux=True)(p)


@partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1, 2))
def _update(ot, grads, state, step):
    return adamw(dict(ot), grads, state, step)


def train(m: dict, opt: dict, seed: int, bias, batches: list, lowp=False,
          reduce=lambda tree: tree):
    """The first ``len(batches)`` steps from the seed's weights and the
    routers' biases ``bias`` (MoE layers, experts). Returns the losses, the first step's gradient as the
    optimizer takes it (clipped), the change of the float32 weights after
    the last, and the routers' biases after it; the two trees passed
    through ``reduce`` as soon as they exist."""
    mt, ot = W.frozen(m), W.frozen(opt)
    p0 = W.make(m, seed, "float32")
    buffers = {"router_bias": jnp.asarray(bias, jnp.float32)}
    state = (p0, jax.tree.map(jnp.zeros_like, p0),
             jax.tree.map(jnp.zeros_like, p0))
    del p0
    losses, first = [], None
    for i, batch in enumerate(batches):
        batch = jax.tree.map(jnp.asarray, batch)
        (value, load), grads = _value_grad(mt, ot, state[0], buffers, batch,
                                           lowp)
        master, mm, vv, clipped = _update(ot, grads, state, i)
        state = (master, mm, vv)
        buffers = {"router_bias": update_bias(
            buffers["router_bias"], load, m["router_bias_rate"])}
        losses.append(float(value))
        if first is None:
            first = reduce(clipped)
        del grads, clipped
    master = state[0]
    del state, mm, vv
    change = jax.tree.map(jnp.subtract, master, W.make(m, seed, "float32"))
    del master
    return losses, first, reduce(change), buffers["router_bias"]
