"""Pure-jnp oracle for the few-row expert kernel: every row multiplied by
its own expert's weights, gathered per row."""
from __future__ import annotations

import jax.numpy as jnp


def expert_mlp_reference(rows, sizes, wg, wu, wo, layer, *, act):
    """rows: [M, D] sorted by expert; sizes: [E] rows of each expert (they
    sum to M); wg, wu: [R, E, D, F]; wo: [R, E, F, D]; layer: int32 scalar.
    Returns y [M, D] float32: ``(act(x wg) * (x wu)) wo`` with each row's
    expert, ``act(g) * u`` rounded once to the rows' dtype."""
    M = rows.shape[0]
    expert = jnp.repeat(jnp.arange(sizes.shape[0]), sizes,
                        total_repeat_length=M)
    g, u, o = (w[layer][expert].astype(rows.dtype) for w in (wg, wu, wo))
    f32 = jnp.float32
    h = act(jnp.einsum("md,mdf->mf", rows, g, preferred_element_type=f32)) \
        * jnp.einsum("md,mdf->mf", rows, u, preferred_element_type=f32)
    return jnp.einsum("mf,mfd->md", h.astype(rows.dtype), o,
                      preferred_element_type=f32)
