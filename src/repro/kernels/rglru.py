"""Pallas TPU kernel for the RG-LRU linear recurrence (RecurrentGemma).

The GPU reference implementation is a fused CUDA scan. On TPU we restructure
(DESIGN.md §2): the recurrence h_t = a_t * h_{t-1} + b_t is elementwise over
the width dim, so the natural TPU decomposition is

  grid = (batch_blocks, width_blocks, time_blocks)

with the time dimension walked sequentially by the LAST grid axis (Pallas
TPU executes the grid in row-major order, so for a fixed (i, j) the t blocks
run in order) carrying h in a VMEM scratch accumulator. Each program
processes a (block_b, block_t, block_w) tile with an in-register scan over
the tile's time steps — pure VPU work, no MXU — and writes the tile's
outputs. HBM traffic is exactly one read of (a, b) and one write of h:
bandwidth-optimal for a memory-bound op.

Time is the sublane (second-minor) axis of a tile, and the TPU compiler
loads or stores at a loop-dependent time offset only in whole sublane tiles
(8 rows of 32-bit, 16 of bf16). So the scan walks ``rows`` time steps per
load and store, and steps through them with static slices.

Width/batch tiles are (8, 128)-lane aligned. Validated against ``ref.py``
in interpret mode (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, hlast_ref, carry_ref, *,
                  block_t, rows):
    """Refs: a/b/o: (block_b, block_t, block_w); h0/hlast: (block_b, block_w);
    carry_ref: VMEM scratch (block_b, block_w) fp32 persisting across the
    sequential time-block walk."""
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        carry_ref[...] = h0_ref[...].astype(jnp.float32)

    def chunk(c, h):
        ts = pl.ds(pl.multiple_of(c * rows, rows), rows)
        a = a_ref[:, ts, :].astype(jnp.float32)
        b = b_ref[:, ts, :].astype(jnp.float32)
        out = []
        for t in range(rows):
            h = a[:, t, :] * h + b[:, t, :]
            out.append(h)
        o_ref[:, ts, :] = jnp.stack(out, axis=1).astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t // rows, chunk, carry_ref[...])
    carry_ref[...] = h

    num_t = pl.num_programs(2)

    @pl.when(t_idx == num_t - 1)
    def _finish():
        hlast_ref[...] = h.astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_t", "block_w",
                                             "interpret"))
def rglru_scan_tpu(a, b, h0=None, *, block_b=8, block_t=256, block_w=128,
                   interpret=False):
    """Linear recurrence h_t = a_t*h_{t-1} + b_t over axis 1.

    a, b: (B, S, W); h0: (B, W) fp32 or None. Returns (h (B,S,W) in b.dtype,
    h_last (B, W) fp32).
    """
    bsz, s, w = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, w), jnp.float32)
    block_b = min(block_b, bsz)
    block_t = min(block_t, s)
    block_w = min(block_w, w)
    rows = min(block_t, 32 // a.dtype.itemsize)  # one sublane tile of a
    if bsz % block_b or s % block_t or w % block_w or block_t % rows:
        raise ValueError(f"dims must divide blocks: {(bsz, s, w)} vs "
                         f"{(block_b, block_t, block_w)}, {rows} rows")
    grid = (bsz // block_b, w // block_w, s // block_t)
    kernel = functools.partial(_rglru_kernel, block_t=block_t, rows=rows)
    h, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_t, block_w),
                         lambda i, j, t: (i, t, j)),
            pl.BlockSpec((block_b, block_t, block_w),
                         lambda i, j, t: (i, t, j)),
            pl.BlockSpec((block_b, block_w), lambda i, j, t: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, block_t, block_w),
                         lambda i, j, t: (i, t, j)),
            pl.BlockSpec((block_b, block_w), lambda i, j, t: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, w), b.dtype),
            jax.ShapeDtypeStruct((bsz, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_b, block_w), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
    return h, hlast
