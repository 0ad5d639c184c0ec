"""Few-row expert Pallas TPU kernel: each hit expert's gated MLP over the
handful of rows routed to it, its weights streamed in large blocks.

A decode step routes N tokens (N <= a few dozen) to top-k distinct experts
each, so an expert gets at most N rows and most get one or two. Its time is
the expert weights' bytes. XLA's grouped matmul (``jax.lax.ragged_dot``)
walks each expert in windows of 128 KiB, whose fixed cost a grid step
outweighs their DMA time; here a grid step moves a [tk, F] gate block and
its [tk, F] up block (1.4 MiB each at tk = 512, F = 1408), or a [F, tn]
down block (5.8 MiB at tn = 2048). tn = 2048 was the fastest width and
tk = 512 the fastest height, or within 1% of it, in sweeps of tk in
256-2048 on a TPU v5e at DeepSeek-V2-Lite's widths
(``benchmarks/moe_decode_tiles.py``); ragged_dot read the same experts at
290-351 GB/s.

Grid ``(slots, steps)``: slot s is the s-th expert that got a row (the
scalar-prefetched ``ids``), in expert order. Its first ``nk`` steps run
gate and up over the model width, its last ``nn`` steps the down
projection. Each weight block is fetched once: gate and up hold their
last block through the down steps, and the down weight holds the previous
slot's last block through the gate/up steps. So the next step's fetch,
issued as a step starts, is never empty within a live slot: with the
down block fetched at the slot's start instead, the last gate/up step had
nothing in flight, and the sweep read 11% slower at 16 tokens. Index
maps point at ``(layer, expert)`` of the whole
stacked weights [R, E, ...], so a scanned layer's experts are read in
place. Slots past the ``nhit`` experts hit repeat the last block index, so
they issue no DMA, and ``pl.when`` skips their compute.

The rows [M, D], sorted by expert, stay resident in VMEM, as does the
output. Expert e's rows are ``[off_e, off_e + cnt_e)``; each visit
multiplies the ``window``-row slice that starts at ``off_e`` rounded down
to ``ALIGN``, keeps ``act(g) * u`` in VMEM, and writes back only the
expert's own rows.

A visit multiplies its weight block ``LANES`` columns at a time in a loop,
and one pallas_call does both phases, because every decode program of a
serving cell holds the kernel: written as one unrolled dot a block, and
as two calls, it took 123 KB of each compiled program (zstd, as JAX's
compile cache stores it) against 83 KB for ragged_dot, which pushed the
serving cell's programs past a compile cache capped at 192 MiB
(``JAX_COMPILATION_CACHE_MAX_SIZE``); and each call's lowering adds to
every program's set-up. Now it takes 44 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# row alignment of a window's start: a bfloat16 tile is 16 rows
ALIGN = 16
# a weight block is multiplied LANES columns at a time, in a loop, so that
# the kernel's code does not grow with the block
LANES = 128


def window_rows(max_rows: int) -> int:
    """Rows a visit multiplies: an expert's at most ``max_rows`` rows from
    a start rounded down to ``ALIGN``."""
    return -(-(max_rows + ALIGN - 1) // ALIGN) * ALIGN


def _expert_window(off_ref, cnt_ref, s, window):
    """(first row of slot s's window, mask [window, 1] of its own rows)."""
    off = off_ref[s]
    start = pl.multiple_of(lax.bitwise_and(off, np.int32(-ALIGN)), ALIGN)
    r = start + lax.broadcasted_iota(jnp.int32, (window, 1), 0)
    return start, (r >= off) & (r < off + cnt_ref[s])


def _dot(a, b):
    return lax.dot_general(a, b.astype(a.dtype), (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _own(own, new, old):
    """``new`` in the expert's own rows, ``old`` elsewhere."""
    return lax.select(lax.broadcast_in_dim(own, new.shape, (0, 1)), new, old)


def _chunks(width: int) -> int:
    """Lanes a loop iteration takes of a weight block ``width`` wide."""
    return LANES if width % LANES == 0 else width


def _expert_kernel(layer_ref, ids_ref, off_ref, cnt_ref, nhit_ref,
                   x_ref, wg_ref, wu_ref, wo_ref, y_ref, g_acc, u_acc, h_ref,
                   *, act, window: int, tk: int, tn: int, nk: int):
    s, j = pl.program_id(0), pl.program_id(1)
    F = h_ref.shape[1]
    fc, dc = _chunks(F), _chunks(tn)

    @pl.when((s == 0) & (j == 0))
    def _clear():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(s < nhit_ref[0])
    def _visit():
        start, own = _expert_window(off_ref, cnt_ref, s, window)
        rows = pl.ds(start, window)

        @pl.when(j == 0)
        def _init():
            g_acc[...] = jnp.zeros_like(g_acc)
            u_acc[...] = jnp.zeros_like(u_acc)

        @pl.when(j < nk)
        def _gate_up():
            x_cols = pl.ds(pl.multiple_of(j * tk, tk), tk)

            def chunk(i, carry):
                c = pl.ds(pl.multiple_of(i * fc, fc), fc)
                xs = x_ref[rows, x_cols]
                g_acc[:, c] += _dot(xs, wg_ref[:, c])
                u_acc[:, c] += _dot(xs, wu_ref[:, c])
                return carry

            lax.fori_loop(0, F // fc, chunk, 0)

        @pl.when(j == nk - 1)
        def _act():
            def chunk(i, carry):
                c = pl.ds(pl.multiple_of(i * fc, fc), fc)
                h_ref[:, c] = (act(g_acc[:, c]) * u_acc[:, c]).astype(
                    h_ref.dtype)
                return carry

            lax.fori_loop(0, F // fc, chunk, 0)

        @pl.when(j >= nk)
        def _down():
            def chunk(i, carry):
                y = _dot(h_ref[...],
                         wo_ref[:, pl.ds(pl.multiple_of(i * dc, dc), dc)])
                c = pl.ds(pl.multiple_of((j - nk) * tn + i * dc, dc), dc)
                y_ref[rows, c] = _own(own, y, y_ref[rows, c])
                return carry

            lax.fori_loop(0, tn // dc, chunk, 0)


def _weight_map(live, last: int, col: bool):
    """Index map of a weight block: (layer, expert of slot, block), with
    ``live(s, j)`` the (slot, block) that step j of slot s holds. Slots
    past the hit count stay on the last live slot's ``last`` block, so
    they fetch nothing."""
    def index(s, j, layer_ref, ids_ref, off_ref, cnt_ref, nhit_ref):
        n = nhit_ref[0]
        slot, b = live(s, j)
        dead = s >= n
        e = ids_ref[lax.select(dead, n - np.int32(1), slot)]
        b = lax.select(dead, np.int32(last), b)
        return (layer_ref[0], e, 0, b) if col else (layer_ref[0], e, b, 0)
    return index


def _resident(shape):
    return pl.BlockSpec(shape, lambda s, b, *_: (0, 0))


def _vmem_limit(*buffers) -> int:
    """Double-buffered blocks and scratch, with room for the compiler's
    own temporaries, within v5e's 128 MiB of VMEM."""
    need = sum(2 * n for n in buffers)
    return int(min(max(need + need // 2 + (8 << 20), 32 << 20), 100 << 20))


def expert_mlp_fwd(x, ids, off, cnt, nhit, layer, wg, wu, wo, *, act,
                   window: int, tk: int = 512, tn: int = 2048,
                   interpret: bool = False):
    """x: [Mp, D] rows sorted by expert, ``window`` rows of padding after
    the last; ids/off/cnt: [S] int32, the hit experts in order, their first
    row and row count; nhit, layer: [1] int32; wg, wu: [R, E, D, F]; wo:
    [R, E, F, D]. Returns y [Mp, D] float32, each row its expert's
    ``(act(x wg) * (x wu)) wo``, with ``act(g) * u`` rounded once to x's
    dtype; rows no expert owns are 0."""
    Mp, D = x.shape
    F = wg.shape[-1]
    S = ids.shape[0]
    tk, tn = min(tk, D), min(tn, D)
    assert D % tk == 0 and D % tn == 0, (D, tk, tn)
    nk, nn = D // tk, D // tn
    scalars = (layer, ids, off, cnt, nhit)
    wbytes = wg.dtype.itemsize
    xbytes = x.dtype.itemsize
    # steps 0..nk-1 of a slot take gate and up blocks, steps nk.. down
    # blocks. Gate and up hold their last block through the down steps;
    # the down weight holds the previous slot's last block through the
    # gate/up steps, so that its first block is fetched during the last
    # of them: a fetch is in flight through every step of a live slot.
    gate_up = pl.BlockSpec((None, None, tk, F), _weight_map(
        lambda s, j: (s, lax.min(j, np.int32(nk - 1))), nk - 1, False))
    up_phase = lambda j: j < np.int32(nk)                    # noqa: E731
    down = pl.BlockSpec((None, None, F, tn), _weight_map(
        lambda s, j: (lax.select(up_phase(j), lax.max(s - 1, 0), s),
                      lax.select(up_phase(j), np.int32(nn - 1),
                                 j - np.int32(nk))), nn - 1, True))
    return pl.pallas_call(
        functools.partial(_expert_kernel, act=act, window=window, tk=tk,
                          tn=tn, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(S, nk + nn),
            in_specs=[_resident((Mp, D)), gate_up, gate_up, down],
            out_specs=_resident((Mp, D)),
            scratch_shapes=[pltpu.VMEM((window, F), jnp.float32),
                            pltpu.VMEM((window, F), jnp.float32),
                            pltpu.VMEM((window, F), x.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(Mp * D * xbytes,
                                         2 * tk * F * wbytes,
                                         F * tn * wbytes, Mp * D * 4,
                                         window * F * 5)),
        name="moe_decode",
        interpret=interpret,
    )(*scalars, x, wg, wu, wo)
