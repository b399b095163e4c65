"""Per-leaf norms of a parameter tree, one leaf per layer of a stacked
leaf, under names that do not depend on how the program stacks layers."""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp


def _path(kp) -> list:
    out = []
    for k in kp:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return out


@jax.jit
def _norms(tree):
    def one(x):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x))

    def stacked(x):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [stacked(x) if _path(kp)[:2] == ["blocks", "scan"] else one(x)
            for kp, x in flat]


def leaf_norms(tree, scale: float = 1.0) -> dict:
    """``{"blocks/attn/wq@3": norm, "embed": norm, ...}`` of ``tree``,
    each times ``scale``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    values = jax.device_get(_norms(tree))
    out = {}
    for (kp, _), v in zip(flat, values):
        p = _path(kp)
        if p[:2] == ["blocks", "scan"]:
            for l, x in enumerate(v):
                out[f"blocks/{'/'.join(p[2:])}@{l}"] = float(x) * scale
        elif p[:2] == ["blocks", "layers"]:
            out[f"blocks/{'/'.join(p[3:])}@{p[2]}"] = float(v) * scale
        else:
            out["/".join(p)] = float(v) * scale
    return out


def gaps(got: dict, ref: dict, keep=None) -> dict:
    """Each leaf's gap between two norms, |got - ref|, over the larger of
    the reference's norm of that leaf and of the median leaf. ``keep``
    limits the leaves compared."""
    names = sorted(ref if keep is None else keep)
    if set(names) - set(got):
        missing = sorted(set(names) - set(got))[:3]
        raise KeyError(f"leaves missing from the program's tree: {missing}")
    vals = sorted(ref[n] for n in names)
    median = vals[len(vals) // 2]
    return {n: abs(got[n] - ref[n]) / max(ref[n], median, 1e-30)
            for n in names}


def worst_gap(got: dict, ref: dict, keep=None) -> tuple:
    """The worst leaf's gap (``gaps``) and that leaf: (gap, leaf)."""
    g = gaps(got, ref, keep)
    leaf = max(g, key=g.get)
    return g[leaf], leaf


def median_gap(got: dict, ref: dict, keep=None) -> float:
    """The median over leaves of ``gaps``: steady from seed to seed where
    the worst leaf is not."""
    return statistics.median(gaps(got, ref, keep).values())
