"""Mixture-of-experts FFN: top-k routing over all experts, dropless, for
the experts this program holds.

* ``moe_ffn_local`` — one device's part of the layer. It holds
  ``e_local`` experts from ``e_start`` and routes every token over all
  ``n_experts``; it returns the held experts' part of the result for the
  tokens routed to them, plus the shared experts, which every holder
  computes alike. On one chip of an expert-parallel deployment that is
  the chip's share; holding every expert, it is the whole layer.
* ``moe_ffn`` — under a mesh: the local path in ``jax.shard_map``, with
  the held experts sharded over the ``model`` axis (their parts summed by
  one ``psum``) or, where they do not divide it, token-parallel.

No token is dropped. Dispatch sorts the (token, choice) rows by held
expert, and the grouped matmul (``kernels.ops.expert_ffn``) computes
only the rows routed: on a TPU its grid follows the tiles actually
routed. The buffer it reads is sized to the share of experts the
program holds (``dispatch_capacity``): twice their even share of the
``tokens * top_k`` rows, in whole row tiles. Where that is less than
every row, the layer gathers only the held rows straight from the
tokens and sums each token's rows, weighted, back into a float32
output; a layer step whose held rows overflow the buffer takes the
worst-case path instead (``lax.cond``), one buffer of ``tokens *
top_k`` rows in which rows for experts held elsewhere sort last and are
neither computed nor combined. Both compute the same function. Holding
every expert, the buffer is the worst case and no condition is built.

Routing is softmax top-k (weights renormalised over the k) or, for
DeepSeek-V3-style layers, sigmoid scores whose top-k is chosen on score
plus a correction bias that gradients do not train (``router_bias``,
updated by ``models.steps``); the weights are the chosen raw scores,
normalised and scaled by ``routed_scale``.

A program that holds a share of the experts sees the outputs of its own
experts only, so its loss's gradient through the routing weights would
turn the router towards or away from the held experts as a whole,
against those held elsewhere, which the whole layer's gradient does not:
at random weights it starves the held experts within tens of steps. In a
share the routing weights therefore pass no gradient back, to the router
or through it to the layer's input (``route``'s ``share``); the balance
loss and the correction bias still train and steer the router. The
forward values do not change.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import GMM_ROW_TILE, expert_ffn
from repro.nn import params as prm
from repro.nn.mlp import def_mlp, mlp
from repro.parallel import current_env


def def_moe(d_model, n_experts, moe_d_ff, *, e_local: Optional[int] = None,
            shared_d_ff: int = 0):
    e = e_local or n_experts
    d = {
        "router": prm.matrix(d_model, n_experts, "embed", "experts",
                             dtype="float32"),
        "up": prm.ParamDef((e, d_model, moe_d_ff),
                           ("experts", "embed", "expert_mlp"), init="scaled_fan_in"),
        "gate": prm.ParamDef((e, d_model, moe_d_ff),
                             ("experts", "embed", "expert_mlp"), init="scaled_fan_in"),
        "down": prm.ParamDef((e, moe_d_ff, d_model),
                             ("experts", "expert_mlp", "embed"), init="scaled_fan_in"),
    }
    if shared_d_ff:
        d["shared"] = def_mlp(d_model, shared_d_ff)
    return d


def route(p_router, x, *, top_k: int, score: str = "softmax", bias=None,
          routed_scale: float = 1.0, balance: str = "switch",
          share: bool = False):
    """x: (B, S, d) → weights (B*S, k) fp32, experts (B*S, k) int32, the
    balance loss () and each expert's routed rows (n_experts,) fp32.

    ``share``: the program holds a share of the experts; the weights then
    pass no gradient back.

    ``balance="switch"``: n_experts * mean(top-1 share * mean score).
    ``balance="seq"``: DeepSeek-V3's per-sequence loss, the mean over
    sequences of sum_i f_i * P_i, with f_i = n_experts / (k * S) times the
    rows routed to expert i and P_i the mean of its normalised score.
    """
    b, s, d = x.shape
    n_experts = p_router.shape[-1]
    logits = jnp.einsum("td,de->te", x.reshape(-1, d).astype(jnp.float32),
                        p_router)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores if bias is None else scores + bias
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * routed_scale
    if share:
        w = jax.lax.stop_gradient(w)
    load = jnp.bincount(idx.reshape(-1), length=n_experts).astype(jnp.float32)
    if balance == "seq":
        chosen = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32),
                         axis=1).reshape(b, s, n_experts)
        f = jnp.sum(chosen, axis=1) * (n_experts / (top_k * s))
        norm = scores / jnp.sum(scores, axis=-1, keepdims=True)
        pm = jnp.mean(norm.reshape(b, s, n_experts), axis=1)
        aux = jnp.mean(jnp.sum(f * pm, axis=-1))
    else:
        hard = jax.nn.one_hot(idx[:, 0], n_experts, dtype=jnp.float32)
        aux = n_experts * jnp.mean(jnp.mean(hard, axis=0)
                                   * jnp.mean(scores, axis=0))
    return w, idx, aux, load


@jax.custom_vjp
def _permute(x, order, inv):
    """x[order] for a permutation ``order`` whose inverse is ``inv``; the
    gradient is a gather by ``inv`` and not a scatter-add."""
    return x[order]


def _permute_fwd(x, order, inv):
    return x[order], (order, inv)


def _permute_bwd(res, g):
    order, inv = res
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def dispatch_capacity(tokens: int, top_k: int, e_local: int,
                      n_experts: int) -> int:
    """Rows of the dispatch buffer: twice the held experts' even share of
    the ``tokens * top_k`` rows, rounded up to whole grouped-matmul row
    tiles, and at most all of them."""
    rows = tokens * top_k
    tiles = -(-2 * rows * e_local // (n_experts * GMM_ROW_TILE))
    return min(rows, tiles * GMM_ROW_TILE)


def _take(src, tok):
    """src's rows at ``tok``, clipped to the last: a row whose ``tok``
    lies past them reads a row it must not count (zero weight, or left
    out of ``_sum``)."""
    return jnp.take(src, tok, axis=0, mode="clip")


def _sum(t: int, rows, tok, wts=None):
    """(t, d) float32: each token's rows, weighted by ``wts`` where given,
    summed; rows whose ``tok`` lies past ``t`` are left out."""
    rows = rows.astype(jnp.float32)
    if wts is not None:
        rows = wts[:, None] * rows
    return jnp.zeros((t, rows.shape[1]), jnp.float32).at[tok].add(
        rows, mode="drop")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(t, src, tok):
    """Take rows by token, from ``src`` of ``t`` rows: ``_take``. Its
    transpose sums rows per token."""
    return _take(src, tok)


def _take_rows_fwd(t, src, tok):
    return _take(src, tok), tok


def _take_rows_bwd(t, tok, g):
    return _sum(t, g, tok).astype(g.dtype), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sum_rows(t, rows, tok, wts):
    """Sum rows per token, weighted: ``_sum``. Its transpose takes rows by
    token."""
    return _sum(t, rows, tok, wts)


def _sum_rows_fwd(t, rows, tok, wts):
    return _sum(t, rows, tok, wts), (rows, tok, wts)


def _sum_rows_bwd(t, res, g):
    rows, tok, wts = res
    g_rows = _take(g, tok)
    return ((wts[:, None] * g_rows).astype(rows.dtype), None,
            jnp.sum(g_rows * rows.astype(jnp.float32), axis=-1))


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


def _worst_case(act, x2d, w, held, order, sizes, up, gate, down):
    """The held experts' part of the layer, (T, d) float32, through one
    buffer of every (token, choice) row: ``order`` sorts them by held
    expert, the rest last."""
    t, d = x2d.shape
    n = order.shape[0]
    top_k = n // t
    with jax.named_scope("moe.dispatch"):
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        valid = (jnp.arange(n) < jnp.sum(sizes))[:, None]
        rows = jnp.repeat(x2d, top_k, axis=0)
        rows = jnp.where(valid, _permute(rows, order, inv), 0)
    with jax.named_scope("moe.experts"):
        out = expert_ffn(rows, up, gate, down, sizes, act)
    with jax.named_scope("moe.combine"):
        out = _permute(jnp.where(valid, out, 0), inv, order)
        wk = jnp.where(held, w.reshape(-1), 0.0).reshape(t, top_k, 1)
        return jnp.sum(out.reshape(t, top_k, d).astype(jnp.float32) * wk,
                       axis=1)


def _sized(cap, act, x2d, w, held, order, sizes, up, gate, down):
    """``_worst_case`` through a buffer of ``cap`` rows, for held rows
    that fit it: each sorted row's token is its (token, choice) row // k;
    padding rows take the token past the last, so that their gradient
    and their undefined outputs are summed into no token."""
    del held  # the first sum(sizes) sorted rows are the held ones
    t = x2d.shape[0]
    top_k = order.shape[0] // t
    with jax.named_scope("moe.dispatch"):
        sel = order[:cap]
        tok = jnp.where(jnp.arange(cap) < jnp.sum(sizes), sel // top_k, t)
        rows = _take_rows(t, x2d, tok)
    with jax.named_scope("moe.experts"):
        out = expert_ffn(rows, up, gate, down, sizes, act)
    with jax.named_scope("moe.combine"):
        wk = jnp.where(tok < t, w[sel // top_k, sel % top_k], 0.0)
        return _sum_rows(t, out, tok, wk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _routed(cap, act, share, *ops):
    """``_sized`` where the held rows fit ``cap``, else ``_worst_case``.
    The backward chooses again and runs the chosen path's own backward,
    so neither branch holds the other's residuals."""
    return _routed_fwd(cap, act, share, *ops)[0]


def _paths(cap, act):
    return (functools.partial(_sized, cap, act),
            functools.partial(_worst_case, act))


def _routed_fwd(cap, act, share, *ops):
    fits = jnp.sum(ops[4]) <= cap  # ops[4]: the held experts' sizes
    return jax.lax.cond(fits, *_paths(cap, act), *ops), ops


def _routed_bwd(cap, act, share, ops, g):
    def backward(path):
        def run(g, x2d, w, held, order, sizes, *experts):
            def f(x2d, w, *experts):
                w = jax.lax.stop_gradient(w) if share else w
                return path(x2d, w, held, order, sizes, *experts)
            return jax.vjp(f, x2d, w, *experts)[1](g)
        return run

    fits = jnp.sum(ops[4]) <= cap
    dx, dw, *d_experts = jax.lax.cond(
        fits, *map(backward, _paths(cap, act)), g, *ops)
    return (dx, dw, None, None, None, *d_experts)


_routed.defvjp(_routed_fwd, _routed_bwd)


def moe_ffn_local(p, x, *, top_k: int, act: str = "silu", e_start=0,
                  e_local: Optional[int] = None, score: str = "softmax",
                  bias=None, routed_scale: float = 1.0,
                  balance: str = "switch", share: bool = False):
    """The held experts' part of the layer for x: (B, S, d), plus the
    shared experts. Returns (y (B, S, d), aux) with aux = {"balance": (),
    "load": (n_experts,) rows routed to each expert}. ``e_local`` defaults
    to the experts ``p`` holds; the router spans all of them. ``share`` as
    ``route``'s: the whole program holds a share of the experts (under a
    mesh, each shard holds part of the program's)."""
    b, s, d = x.shape
    t = b * s
    e_local = p["up"].shape[0] if e_local is None else e_local
    with jax.named_scope("moe.route"):
        w, idx, aux, load = route(p["router"], x, top_k=top_k, score=score,
                                  bias=bias, routed_scale=routed_scale,
                                  balance=balance, share=share)
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(-1) - e_start  # (T*k,)
        held = (local >= 0) & (local < e_local)
        key = jnp.where(held, local, e_local)  # the rest sort last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.bincount(key, length=e_local + 1)[:e_local].astype(
            jnp.int32)
    ops = (x.reshape(t, d), w, held, order, sizes,
           p["up"], p["gate"], p["down"])
    cap = dispatch_capacity(t, top_k, e_local, p["router"].shape[-1])
    if cap < t * top_k:
        # outside the scopes: the op that holds the branches is not
        # counted on top of their ops
        y = _routed(cap, act, share, *ops)
    else:
        y = _worst_case(act, *ops)
    with jax.named_scope("moe.combine"):
        y = y.reshape(b, s, d)
        if "shared" in p:
            y = y + mlp(p["shared"], x, act).astype(jnp.float32)
    return y.astype(x.dtype), {"balance": aux, "load": load}


def moe_ffn(p, x, *, top_k: int, act: str = "silu", **routing):
    """The MoE layer on x: (B, S, d) → (B, S, d), aux as ``moe_ffn_local``.

    Without an active mesh env this is the local path: this program's
    experts (all of them, or a chip's share). ``routing`` passes
    ``score``, ``bias``, ``routed_scale`` and ``balance`` on.
    """
    env = current_env()
    b, s, d = x.shape
    held = p["up"].shape[0]
    routing = dict(routing, share=held < p["router"].shape[-1])

    if not env.active:
        return moe_ffn_local(p, x, top_k=top_k, act=act, **routing)

    mesh = env.mesh
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    model_ax = "model"
    n_model = mesh.shape[model_ax]
    # The held experts (all of them, or this program's share from expert
    # 0) shard over model when divisible (expert parallelism). When not
    # (granite: 40 experts on a 16-way axis), fall back to TOKEN-parallel
    # MoE: sequence sharded over the model axis, experts replicated — every
    # shard routes/computes only its own tokens, no collectives inside the
    # block at all.
    ep = n_model if held % n_model == 0 else 1
    token_parallel = ep == 1 and s % n_model == 0
    e_local = held // ep
    # the shared experts are every shard's alike: run once, outside
    p = dict(p)
    shared = p.pop("shared", None)
    rep = jax.tree.map(lambda _: P(), p)
    aux_spec = {"balance": P(), "load": P()}

    def with_shared(y, aux):
        if shared is not None:
            y = y + mlp(shared, x, act)
        return y, aux

    if token_parallel:
        in_specs = (rep, P(dp_axes, model_ax, None))
        out_specs = (P(dp_axes, model_ax, None), aux_spec)

        def tp_fn(p_loc, x_loc):
            y, aux = moe_ffn_local(p_loc, x_loc, top_k=top_k, act=act,
                                   **routing)
            aux = {"balance": jax.lax.pmean(aux["balance"],
                                            dp_axes + (model_ax,)),
                   "load": jax.lax.psum(aux["load"], dp_axes + (model_ax,))}
            return y, aux

        return with_shared(*jax.shard_map(
            tp_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(p, x))

    expert_spec = P(model_ax) if ep > 1 else P()
    in_specs = (
        dict(rep, up=expert_spec, gate=expert_spec, down=expert_spec),
        P(dp_axes, None, None),  # x: batch over DP, replicated over model
    )
    out_specs = (P(dp_axes, None, None), aux_spec)

    def shard_fn(p_loc, x_loc):
        m_idx = jax.lax.axis_index(model_ax)
        e_start = (m_idx * e_local) if ep > 1 else 0
        y, aux = moe_ffn_local(p_loc, x_loc, top_k=top_k, act=act,
                               e_start=e_start, e_local=e_local, **routing)
        balance = aux["balance"]
        if ep > 1:
            y = jax.lax.psum(y, model_ax)
            balance = jax.lax.pmean(balance, model_ax)
        return y, {"balance": jax.lax.pmean(balance, dp_axes),
                   "load": jax.lax.psum(aux["load"], dp_axes)}

    return with_shared(*jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(p, x))
