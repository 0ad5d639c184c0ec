"""Public wrapper of the few-row expert kernel: ragged_dot's inputs in
(rows sorted by expert, each expert's row count), the kernel's scalar
tables built here.

``impl`` picks the implementation when the program is traced, as the
repo's other kernels do: ``"auto"`` is the Pallas kernel where the default
backend is a TPU and the jnp reference elsewhere, ``"interpret"`` the
kernel in Pallas's interpreter. The gradient is the reference's, since
Pallas differentiates no kernel with scalar-prefetched tables; a training
step of few tokens routes through here too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_decode.kernel import expert_mlp_fwd, window_rows
from repro.kernels.moe_decode.ref import expert_mlp_reference

# What ``impl="auto"`` takes where it is set. A program lowered for a TPU
# from a host without one (tests/test_tpu_compile.py) sets "pallas" here,
# so that it holds the kernel the chip would run.
AUTO_IMPL: str | None = None


def _tables(sizes, slots: int):
    """(ids, off, cnt, nhit): the experts with rows, in order, as many as
    ``slots``; their first row among the sorted rows; their row count.
    Built from [slots, E] masks, whose compiled code is a third of
    ``jnp.nonzero``'s."""
    hit = sizes > 0
    rank = jnp.cumsum(hit) - 1
    first = jnp.cumsum(sizes) - sizes
    pick = hit & (rank == jnp.arange(slots)[:, None])        # [slots, E]

    def at(v):
        return jnp.sum(jnp.where(pick, v, 0), axis=1).astype(jnp.int32)

    return (at(jnp.arange(sizes.shape[0])), at(first), at(sizes),
            jnp.sum(hit).astype(jnp.int32).reshape(1))


# jitted, so that the decode programs of a serving cell that share a
# batch size (one for each length class) trace the kernel once
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _forward(rows, sizes, wg, wu, wo, layer, act, max_rows, interpret, tk,
             tn):
    M, D = rows.shape
    window = window_rows(max_rows)
    x = jnp.pad(rows, ((0, window), (0, 0)))
    ids, off, cnt, nhit = _tables(sizes, min(M, sizes.shape[0]))
    y = expert_mlp_fwd(x, ids, off, cnt, nhit,
                       jnp.asarray(layer, jnp.int32).reshape(1), wg, wu, wo,
                       act=act, window=window, tk=tk, tn=tn,
                       interpret=interpret)
    return y[:M]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _expert_mlp(rows, sizes, wg, wu, wo, layer, act, max_rows, interpret,
                tk, tn):
    return _forward(rows, sizes, wg, wu, wo, layer, act, max_rows,
                    interpret, tk, tn)


def _fwd(rows, sizes, wg, wu, wo, layer, act, max_rows, interpret, tk, tn):
    y = _expert_mlp(rows, sizes, wg, wu, wo, layer, act, max_rows,
                    interpret, tk, tn)
    return y, (rows, sizes, wg, wu, wo, layer)


def _bwd(act, max_rows, interpret, tk, tn, res, dy):
    rows, sizes, wg, wu, wo, layer = res
    _, vjp = jax.vjp(lambda r, g, u, o: expert_mlp_reference(
        r, sizes, g, u, o, layer, act=act), rows, wg, wu, wo)
    dr, dg, du, do = vjp(dy)
    return dr, None, dg, du, do, None


_expert_mlp.defvjp(_fwd, _bwd)


def expert_mlp(rows, sizes, wg, wu, wo, layer, *, act, max_rows: int,
               impl: str = "auto", tk: int = 512, tn: int = 2048):
    """rows: [M, D] sorted by expert; sizes: [E] int, rows of each expert
    of layer ``layer`` (summing to M), none above ``max_rows``; wg, wu:
    [R, E, D, F]; wo: [R, E, F, D]. Returns y [M, D] float32, row by row
    ``(act(x wg) * (x wu)) wo`` with its expert's weights.

    impl: "pallas", "interpret", "ref", or "auto" (see the module)."""
    if impl == "auto":
        impl = AUTO_IMPL or ("pallas" if jax.default_backend() == "tpu"
                             else "ref")
    if impl == "ref":
        return expert_mlp_reference(rows, sizes, wg, wu, wo, layer, act=act)
    return _expert_mlp(rows, sizes, wg, wu, wo, layer, act, max_rows,
                       impl == "interpret", tk, tn)
