"""Causal GQA flash attention on TPU, forward and backward, from JAX's
splash-attention kernels (``jax.experimental.pallas.ops.tpu``).

The multi-query form runs one kv head against its group of query heads:
vmapped over batch and kv heads, each kv head serves its ``H // KV``
query heads with no k/v copy. Score blocks live in VMEM only; blocks
above the diagonal are skipped. The backward pass is the kernel's own
(a dq and a dkv kernel, from the forward's logsumexp), so neither the
scores nor the probabilities reach HBM in either direction.

Numerics match ``nn/attention.flash_attention``: the caller pre-scales q
in its own dtype; bf16 operands feed the MXU with f32 accumulation and
f32 softmax statistics, and the probabilities are cast to v's dtype
before ``p @ v``.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as sa

# Square q/kv blocks, largest first: a sequence takes the first that
# divides it, or the caller's fallback where none does. Each block walks
# its kv block in slices of at most COMPUTE. On a v5e at smollm-360m's
# widths (batch 4 x 2048, 15/5 heads of 64) a whole train step took 0.322
# s with 1024-blocks in 512-slices, 0.329 s with 512-blocks, 0.510 s with
# the chunked scan; 2048-blocks overflow VMEM.
BLOCKS = (1024, 512, 256, 128)
COMPUTE = 512


def block_for(seq: int) -> Optional[int]:
    """The kernel's block for a sequence of ``seq`` tokens, or None."""
    return next((b for b in BLOCKS if seq % b == 0), None)


def causal_attention(q, k, v, *, block: int, interpret: bool = False):
    """Causal self-attention: q (B, H, S, D), pre-scaled; k/v (B, KV, S, D).

    Returns (B, H, S, D) in q's dtype. ``S`` must be a multiple of
    ``block``; ``interpret`` runs the kernels in the Pallas interpreter
    (CPU tests).
    """
    b, h, s, d = q.shape
    n_kv = k.shape[1]
    groups = h // n_kv
    mask = sa.MultiHeadMask([sa.CausalMask((s, s))] * groups)
    step = min(block, COMPUTE)
    sizes = sa.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=step,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=step,
        block_q_dq=block, block_kv_dq=block)
    kernel = sa.make_splash_mqa_single_device(mask, block_sizes=sizes,
                                              interpret=interpret)
    qg = q.reshape(b, n_kv, groups, s, d)
    o = jax.vmap(jax.vmap(kernel))(qg, k, v)  # over batch, then kv heads
    return o.reshape(b, h, s, d)
