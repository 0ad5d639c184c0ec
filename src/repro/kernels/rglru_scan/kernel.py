"""Blocked linear-recurrence Pallas TPU kernel for the RG-LRU.

h_t = a_t * h_{t-1} + b_t, elementwise over the rnn width. Grid:
(batch, width_blocks, seq_blocks) with the sequence axis innermost and
sequential; the carry h lives in VMEM scratch and flows across seq blocks.
Within a block the recurrence is stepped with a fori_loop over the time
rows of the VMEM tile — the channel dimension (lanes) stays fully vectorized.

The XLA path (models/rglru.py) uses an associative scan, which is O(S log S)
data movement; this kernel is the O(S) streaming version — the win is on the
memory roofline term, which dominates recurrent layers at train/prefill.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, carry_ref, *, block_s: int,
                  rows: int):
    is_ = pl.program_id(2)

    @pl.when(is_ == 0)
    def _init():
        carry_ref[...] = h0_ref[...].astype(jnp.float32)

    # Mosaic loads and stores whole sublane tiles at dynamic offsets, so the
    # loop steps over tiles of `rows` time rows and unrolls the recurrence
    # inside each tile; each h_t is merged into the output tile by a select.
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def tile(i, h):
        r0 = pl.multiple_of(i * rows, rows)
        a_t = a_ref[0, pl.ds(r0, rows), :].astype(jnp.float32)    # [rows, bw]
        b_t = b_ref[0, pl.ds(r0, rows), :].astype(jnp.float32)
        out = jnp.zeros_like(a_t)
        for r in range(rows):
            h = a_t[r:r + 1] * h + b_t[r:r + 1]                    # [1, bw]
            out = jnp.where(row_id == r, h, out)
        o_ref[0, pl.ds(r0, rows), :] = out.astype(o_ref.dtype)
        return h

    carry_ref[...] = jax.lax.fori_loop(0, block_s // rows, tile,
                                       carry_ref[...])


def rglru_scan_fwd(a, b, h0, *, block_s: int = 256, block_w: int = 512,
                   interpret: bool = False):
    """a, b: [B, S, W]; h0: [B, W]. Returns h: [B, S, W] (same dtype as b)."""
    B, S, W = a.shape
    block_s = min(block_s, S)
    block_w = min(block_w, W)
    assert S % block_s == 0 and W % block_w == 0, (S, W, block_s, block_w)
    grid = (B, W // block_w, S // block_s)

    # one native sublane tile: 8 rows of 32-bit, 16 of 16-bit values
    rows = math.gcd(block_s, 8 * 4 // min(a.dtype.itemsize, b.dtype.itemsize,
                                          4))
    kernel = functools.partial(_rglru_kernel, block_s=block_s, rows=rows)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda ib, iw, is_: (ib, is_, iw)),
            pl.BlockSpec((1, block_s, block_w), lambda ib, iw, is_: (ib, is_, iw)),
            pl.BlockSpec((1, block_w), lambda ib, iw, is_: (ib, iw)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w),
                               lambda ib, iw, is_: (ib, is_, iw)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
