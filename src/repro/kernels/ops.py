"""Dispatching wrappers: Pallas on TPU, chunked-jnp equivalent elsewhere.

The model code calls these; on a TPU runtime the Pallas kernels execute, on
CPU (tests, dry-run) the structurally-equivalent jnp paths run (same math,
same memory behavior class), with ``force`` overrides for kernel tests in
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import ref, splash
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.rglru import rglru_scan_tpu
from repro.obs.spans import count
from repro.parallel import current_env


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def self_attention(q, k, v, *, causal=True, window=0, chunk=512):
    """Train and prefill attention: q/k (B, H|KV, S, D), v (B, KV, S, Dv).

    Causal attention over whole sequences of a multiple of a kernel block,
    in an unsharded program, runs the splash kernel (forward and backward)
    where the program is lowered for a TPU, and the chunked jnp scan
    (``nn/attention.flash_attention``) on every other platform: the choice
    is made per lowering platform, so one trace serves both. Local
    windows, bidirectional attention, other lengths and sharded programs
    (a Mosaic kernel is not partitioned automatically) take the scan.

    Counts ``attention.kernel`` or ``attention.chunked`` into the open
    span's root at trace time, by the default backend, which is where the
    platform's learner lowers its step.
    """
    from repro.nn.attention import flash_attention as chunked

    def scan(q, k, v):
        return chunked(q, k, v, causal=causal, window=window, chunk=chunk)

    s = q.shape[2]
    block = splash.block_for(s)
    if (not causal or window or block is None or k.shape[2] != s
            or current_env().active):
        count("attention.chunked")
        return scan(q, k, v)
    count("attention.kernel" if _on_tpu() else "attention.chunked")

    def kernel(q, k, v):
        scaled = (q * q.shape[-1] ** -0.5).astype(q.dtype)
        return splash.causal_attention(scaled, k, v, block=block)

    return jax.lax.platform_dependent(q, k, v, tpu=kernel, default=scan)


# rows of one megablox tile
GMM_ROW_TILE = 512


def gmm_tiling(m: int, k: int, n: int) -> tuple:
    """The megablox kernel's (m, k, n) tiles: ``GMM_ROW_TILE`` rows by
    1024-wide slices, or the whole dimension where it is smaller; partial
    tiles of k and n are masked by the kernel."""
    return min(GMM_ROW_TILE, m), min(1024, k), min(1024, n)


def expert_ffn(rows, up, gate, down, sizes, act="silu", *,
               force: str | None = None):
    """SwiGLU experts over rows grouped by expert.

    rows (M, d) hold expert g's rows at [sum(sizes[:g]), sum(sizes[:g+1]));
    up/gate (G, d, f), down (G, f, d), sizes (G,) int32. Rows past
    ``sum(sizes)`` are not computed, and their output is undefined: the
    caller masks them. bf16 operands, f32 accumulation, output in rows'
    dtype.

    Where the program is lowered for a TPU, outside a mesh, the three
    products run as JAX's megablox grouped matmul (``gmm``; its backward
    is ``gmm`` and ``tgmm``), whose grid covers only the tiles routed;
    elsewhere as ``jax.lax.ragged_dot``. Counts ``moe.gmm`` or
    ``moe.dense`` at trace time, by the default backend. ``force="interpret"``
    runs the kernel in the Pallas interpreter (tests).
    """
    from repro.nn.layers import activation

    fn = activation(act)

    def ffn(matmul):
        def run(rows, up, gate, down, sizes):
            h = (fn(matmul(rows, gate, sizes).astype(jnp.float32))
                 * matmul(rows, up, sizes).astype(jnp.float32))
            return matmul(h.astype(rows.dtype), down, sizes)
        return run

    def gmm(lhs, rhs, sizes, interpret=False):
        tiling = gmm_tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2])
        return megablox.gmm(lhs, rhs, sizes, lhs.dtype, tiling,
                            interpret=interpret)

    def ragged(lhs, rhs, sizes):
        return jax.lax.ragged_dot(lhs, rhs, sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(lhs.dtype)

    args = (rows, up, gate, down, sizes)
    if force == "interpret":
        return ffn(functools.partial(gmm, interpret=True))(*args)
    if current_env().active:
        count("moe.dense")
        return ffn(ragged)(*args)
    count("moe.gmm" if _on_tpu() else "moe.dense")
    return jax.lax.platform_dependent(*args, tpu=ffn(gmm),
                                      default=ffn(ragged))


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    force: str | None = None):
    """GQA flash attention. force in {None, 'pallas', 'interpret', 'ref'}."""
    mode = force or ("pallas" if _on_tpu() else "jnp")
    if mode == "pallas":
        return flash_attention_tpu(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset)
    if mode == "interpret":
        return flash_attention_tpu(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, interpret=True)
    if mode == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       q_offset=q_offset)
    # memory-efficient jnp path (the dry-run lowers this)
    from repro.nn.attention import flash_attention as chunked
    return chunked(q, k, v, causal=causal, window=window, q_offset=q_offset)


def rglru_scan(a, b, h0=None, *, force: str | None = None):
    """Linear recurrence. force in {None, 'pallas', 'interpret', 'ref'}."""
    mode = force or ("pallas" if _on_tpu() else "jnp")
    if mode == "pallas":
        return rglru_scan_tpu(a, b, h0)
    if mode == "interpret":
        return rglru_scan_tpu(a, b, h0, interpret=True)
    if mode == "ref":
        return ref.rglru_scan_ref(a, b, h0)
    # associative-scan jnp path (log-depth, what the dry-run lowers)
    import jax.numpy as jnp

    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    if h0 is not None:
        bf = bf.at[:, 0].add(af[:, 0] * h0.astype(jnp.float32))

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (af, bf), axis=1)
    return h.astype(b.dtype), h[:, -1]
