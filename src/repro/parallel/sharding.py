"""Logical-axis sharding: the single place where parallelism policy lives.

Every parameter and activation in the model code is annotated with *logical*
axis names ("batch", "embed", "heads", "mlp", "experts", ...). A
:class:`MeshEnv` maps logical names onto physical mesh axes via a rules table.
Model code never mentions physical axes, so the same model runs:

  * unsharded on one CPU device (tests / smoke),
  * on a single-pod (data, model) mesh,
  * on the multi-pod (pod, data, model) production mesh,

purely by swapping rules. This mirrors t5x/maxtext logical-axis design and is
what lets the dry-run sweep meshes without touching model code.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# A logical rule maps a logical axis name to one mesh axis, a tuple of mesh
# axes (sharded over their product), or None (replicated).
MeshAxes = Union[None, str, tuple]

# Baseline rules for a (data, model) single-pod mesh.
SINGLE_POD_RULES: dict[str, MeshAxes] = {
    "batch": ("data",),
    "batch_attn": ("data",),  # attention-block batch (batch-TP override
                              # reshards attention over data x model when
                              # heads %% TP != 0 would replicate compute)
    "seq": None,            # residual-stream sequence axis (SP shards this)
    "attn_seq": None,       # attention-internal q seq (never SP-sharded:
                            # the blocked kv walk needs whole sequences)
    "kv_seq": None,         # kv-cache sequence axis
    "embed": None,
    "residual": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "qkv": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_mlp": None,
    "lru": "model",
    "conv": None,
    "layers": None,
    "enc_seq": None,
    "zero": None,           # extra axis ZeRO-1 adds to optimizer state
}

# Production multi-pod rules: the pod axis joins the data axis for DP.
MULTI_POD_RULES: dict[str, MeshAxes] = dict(
    SINGLE_POD_RULES,
    batch=("pod", "data"),
    batch_attn=("pod", "data"),
)


def zero1_rules(rules: dict[str, MeshAxes]) -> dict[str, MeshAxes]:
    """Rules with the ZeRO-1 axis bound to the DP axes (optimizer sharding)."""
    return dict(rules, zero=rules["batch"])


@dataclass(frozen=True)
class MeshEnv:
    """A mesh plus the logical→physical rules to use inside it."""

    mesh: Optional[Mesh]
    rules: dict[str, MeshAxes] = field(default_factory=dict)

    @property
    def active(self) -> bool:
        return self.mesh is not None

    def axis_size(self, name: str) -> int:
        assert self.mesh is not None
        return self.mesh.shape[name]


def null_env() -> MeshEnv:
    """Environment with no mesh: all sharding helpers become no-ops."""
    return MeshEnv(mesh=None, rules={})


class _EnvStack(threading.local):
    def __init__(self):
        self.stack: list[MeshEnv] = []


_ENVS = _EnvStack()


def current_env() -> MeshEnv:
    if _ENVS.stack:
        return _ENVS.stack[-1]
    return null_env()


@contextlib.contextmanager
def use_env(env: MeshEnv):
    """Install a MeshEnv (and enter its mesh) for the dynamic extent."""
    _ENVS.stack.append(env)
    try:
        if env.mesh is not None:
            with jax.set_mesh(env.mesh):
                yield env
        else:
            yield env
    finally:
        _ENVS.stack.pop()


def _mesh_axes_tuple(mesh_axes: MeshAxes) -> tuple:
    if mesh_axes is None:
        return ()
    if isinstance(mesh_axes, str):
        return (mesh_axes,)
    return tuple(mesh_axes)


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    env: Optional[MeshEnv] = None,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """Resolve logical axis names to a PartitionSpec under env's rules.

    A mesh axis may appear at most once in a PartitionSpec; later (lower
    priority) occurrences are dropped. If ``shape`` is given, mesh axes whose
    size does not divide the corresponding dim are dropped too (e.g. kv_heads=4
    cannot shard over model=16 — it stays replicated rather than erroring).
    """
    env = env or current_env()
    if not env.active:
        return P()
    used: set[str] = set()
    out = []
    for i, name in enumerate(logical_axes):
        mesh_axes = _mesh_axes_tuple(env.rules.get(name)) if name else ()
        picked = []
        size = 1
        for ax in mesh_axes:
            if ax in used or ax not in env.mesh.shape:
                continue
            picked.append(ax)
            size *= env.axis_size(ax)
        if shape is not None and picked and shape[i] % size != 0:
            # Try progressively shorter prefixes of the axis tuple.
            while picked:
                picked.pop()
                size = 1
                for ax in picked:
                    size *= env.axis_size(ax)
                if size == 1 or shape[i] % size == 0:
                    break
        used.update(picked)
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x, *logical_axes: Optional[str]):
    """with_sharding_constraint under the current env (no-op when unset)."""
    env = current_env()
    if not env.active:
        return x
    spec = logical_to_spec(logical_axes, env, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(env.mesh, spec))


def resolve_spec(axes_leaf, shape, env: Optional[MeshEnv] = None) -> P:
    """PartitionSpec for one parameter given its logical axes and shape."""
    return logical_to_spec(axes_leaf, env=env, shape=shape)


def param_shardings(axes_tree, shapes_tree, env: Optional[MeshEnv] = None):
    """NamedShardings for a parameter tree.

    ``axes_tree`` has the same structure as the params with tuples of logical
    names at the leaves; ``shapes_tree`` carries arrays/ShapeDtypeStructs.
    """
    env = env or current_env()
    if not env.active:
        return jax.tree.map(
            lambda _: None, shapes_tree, is_leaf=lambda l: hasattr(l, "shape")
        )

    def one(axes, arr):
        spec = resolve_spec(tuple(axes), arr.shape, env)
        return NamedSharding(env.mesh, spec)

    return jax.tree.map(
        one, axes_tree, shapes_tree, is_leaf=lambda l: isinstance(l, tuple)
    )
