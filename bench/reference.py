"""The plain reference: the decoder's forward pass, its loss, gradients
and AdamW in straightforward ``jax.numpy``, float32 at the highest
matmul precision, with no cache, no chunking and no batching tricks.

It imports nothing of the program and takes nothing the program made: it
builds its weights from the seed with ``bench.weights``.

``lowp=True`` computes every matrix product from operands rounded to
float8 (e4m3, one scale per tensor): the control, one precision step
below the bfloat16 that the configurations state.

Departures from the published architectures, each as the program runs
them, as the configuration's ``model`` block states and its ``assumed``
records: RMSNorm's epsilon is the model block's (1e-6 where the published
one is 1e-5); the loss adds a z-loss of 1e-4 * logsumexp**2 per token;
weight decay applies to every leaf of rank 2 or more as the program
stores it, which includes its layer-stacked norm scales.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

import bench.weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round8(x):
    """x rounded to float8 at one scale for the tensor. The gradient
    passes straight through; the backward products then take the
    rounded operands of the forward."""
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * s).astype(F8).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def einsum(eq, a, b, lowp=False):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if lowp:
        a, b = _round8(a), _round8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, theta):
    """x: (..., T, hd); the two halves of a head rotate together."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def layer(p, x, m, lowp=False):
    """One decoder layer on x: (B, T, d), positions 0..T-1, causal."""
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    kv, hd = m["n_kv_heads"], W.head_dim(m)
    b, t, _ = x.shape
    pos = jnp.arange(t)
    a = p["attn"]
    h = rmsnorm(x, p["norm1"]["scale"], eps)
    q = einsum("btd,dhk->bhtk", h, a["wq"], lowp)
    k = einsum("btd,dhk->bhtk", h, a["wk"], lowp)
    v = einsum("btd,dhk->bhtk", h, a["wv"], lowp)
    if "bq" in a:
        q = q + a["bq"][None, :, None, :]
        k = k + a["bk"][None, :, None, :]
        v = v + a["bv"][None, :, None, :]
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    q = q.reshape(b, kv, -1, t, hd) / math.sqrt(hd)
    s = einsum("bkgtd,bksd->bkgts", q, k, lowp)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = einsum("bkgts,bksd->bkgtd", jax.nn.softmax(s, -1), v, lowp)
    x = x + einsum("bhtk,hkd->btd", o.reshape(b, -1, t, hd), a["wo"], lowp)
    h = rmsnorm(x, p["norm2"]["scale"], eps)
    mp = p["mlp"]
    g = jax.nn.silu(einsum("btd,df->btf", h, mp["gate"], lowp))
    u = einsum("btd,df->btf", h, mp["up"], lowp)
    return x + einsum("btf,fd->btd", g * u, mp["down"], lowp)


def _table(g):
    return g["unembed"] if "unembed" in g else g["embed"]


# -- training: loss, gradients and AdamW over whole float32 trees ------------

def _stack_apply(layers, x, m, lowp):
    body = jax.checkpoint(lambda x, p: (layer(p, x, m, lowp), None))
    return jax.lax.scan(body, x, layers)[0]


def loss(params, batch, m, opt, lowp=False):
    """Mean token cross-entropy plus the z-loss, over all rows."""
    x = params["embed"][batch["tokens"]]
    layers = params["blocks"]["scan"]
    x = _stack_apply(layers, x, m, lowp)
    x = rmsnorm(x, params["final_norm"]["scale"], m["rms_norm_eps"])
    table = _table(params)

    @jax.checkpoint
    def row(xl):
        xr, lr = xl
        z = einsum("td,vd->tv", xr, table, lowp)
        lse = jax.nn.logsumexp(z, -1)
        gold = jnp.take_along_axis(z, lr[:, None], -1)[:, 0]
        return jnp.sum(lse - gold + opt["z_loss"] * lse * lse)

    per_row = jax.lax.map(row, (x, batch["labels"]))
    return jnp.sum(per_row) / batch["labels"].size


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up from 0, then cosine decay to ``min_lr_frac``."""
    warm = min(step / max(opt["warmup"], 1), 1.0)
    t = min(max((step - opt["warmup"])
                / max(opt["total_steps"] - opt["warmup"], 1), 0.0), 1.0)
    cos = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * t))
    return opt["lr"] * warm * cos


def adamw(opt, grads, state, step):
    """One AdamW step on float32 ``state = (master, m, v)`` after
    clipping the gradient to a global norm of ``clip_norm``."""
    master, mm, vv = state
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["beta1"], opt["beta2"]
    lr = lr_at(opt, step)
    bc1, bc2 = 1 - b1 ** (step + 1), 1 - b2 ** (step + 1)
    g = jax.tree.map(lambda g: g * scale, grads)
    mm = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, mm, g)
    vv = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, vv, g)

    def upd(w, m_, v_):
        u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + opt["eps"])
        if w.ndim >= 2:
            u = u + opt["weight_decay"] * w
        return w - lr * u

    return jax.tree.map(upd, master, mm, vv), mm, vv, g


@partial(jax.jit, static_argnums=(0, 1, 4))
def _value_grad(mt, ot, p, batch, lowp):
    return jax.value_and_grad(
        lambda p: loss(p, batch, dict(mt), dict(ot), lowp))(p)


@partial(jax.jit, static_argnums=(0, 3), donate_argnums=(1, 2))
def _update(ot, grads, state, step):
    return adamw(dict(ot), grads, state, step)


def train(m: dict, opt: dict, seed: int, batches: list, lowp=False,
          reduce=lambda tree: tree):
    """The first ``len(batches)`` steps from the seed's weights. Returns
    the losses, the first step's gradient as the optimizer takes it
    (clipped), and the change of the float32 weights after the last;
    the two trees passed through ``reduce`` as soon as they exist."""
    mt, ot = W.frozen(m), W.frozen(opt)
    p0 = W.make(m, seed, "float32")
    # the update takes the state's and the gradient's buffers for its own
    # (a whole float32 state of smollm-360m is 4.3 GB); the first weights
    # are made again from the seed for the change
    state = (p0, jax.tree.map(jnp.zeros_like, p0),
             jax.tree.map(jnp.zeros_like, p0))
    del p0
    losses, first = [], None
    for i, batch in enumerate(batches):
        batch = jax.tree.map(jnp.asarray, batch)
        value, grads = _value_grad(mt, ot, state[0], batch, lowp)
        master, mm, vv, clipped = _update(ot, grads, state, i)
        state = (master, mm, vv)
        losses.append(float(value))
        if first is None:
            first = reduce(clipped)
        del grads, clipped
    master = state[0]
    del state, mm, vv
    change = jax.tree.map(jnp.subtract, master, W.make(m, seed, "float32"))
    del master
    return losses, first, reduce(change)
