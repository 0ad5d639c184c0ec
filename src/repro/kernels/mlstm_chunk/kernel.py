"""Chunkwise-parallel mLSTM Pallas TPU kernel (xLSTM matrix memory).

Grid: (batch*heads, num_chunks) — chunks iterate sequentially; the matrix
memory C [dqk, dv], normalizer n [dqk] and max-stabilizer m live in VMEM
scratch and carry across chunks. Per chunk the kernel computes the
intra-chunk attention-like term (q k^T decayed by the gate matrix D) on the
MXU plus the inter-chunk contribution through C, then updates the state —
the same stabilized math as models/xlstm.mlstm_chunkwise (the oracle).

VMEM budget per step: q,k [c,dqk] + v,h [c,dv] + D,scores [c,c] + C [dqk,dv]
(f32). With c=128, dqk=256, dv=512: ~1.3 MB — well within v5e VMEM; chunk
sizes are multiples of 8 (sublanes), dqk/dv multiples of 128 (lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, o_ref,
                  c_ref, n_ref, m_ref, *, chunk: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    q = q_ref[0].astype(jnp.float32)                    # [c, dqk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                    # [c, dv]
    ig = i_ref[0].astype(jnp.float32)                   # [1, c] row vector
    fg = f_ref[0].astype(jnp.float32)

    # Mosaic has no cumsum and no 1-D or scalar vectors: every quantity is
    # a 2-D tile, prefix sums are a matmul with the causal mask, and a row
    # vector becomes a column through a transposed broadcast.
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    def col(row):                                       # [1, c] -> [c, 1]
        return jnp.broadcast_to(row, (chunk, chunk)).T[:, :1]

    b = jax.lax.dot_general(fg, causal.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)   # [1, c]
    btot = jnp.sum(fg, axis=1, keepdims=True)           # [1, 1]
    m_prev = m_ref[...]                                 # [1, 1]
    C = c_ref[...]
    n = n_ref[...]                                      # [1, dqk]

    # intra-chunk decay matrix: D[j,l] = b_j - b_l + i_l  (l <= j)
    bl = jnp.broadcast_to(b, (chunk, chunk))
    logD = bl.T - bl + jnp.broadcast_to(ig, (chunk, chunk))
    logD = jnp.where(causal, logD, NEG_INF)
    m_intra = jnp.max(logD, axis=1, keepdims=True)      # [c, 1]
    m_inter = col(b) + m_prev                           # [c, 1]
    m_j = jnp.maximum(m_intra, m_inter)
    D = jnp.exp(logD - m_j)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * D
    h_intra = jax.lax.dot_general(w, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    n_intra = jax.lax.dot_general(w, k, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    dec_q = jnp.exp(m_inter - m_j)                      # [c, 1]
    h_inter = jax.lax.dot_general(q, C, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32) * dec_q
    n_inter = jax.lax.dot_general(q, n, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) * dec_q
    num = h_intra + h_inter
    den = jnp.abs(jnp.sum(q * n_intra, axis=1, keepdims=True) + n_inter)
    h = num / jnp.maximum(den, jnp.exp(-m_j))
    o_ref[0] = h.astype(o_ref.dtype)

    # ---- state update ----
    g = btot - b + ig                                   # [1, c]
    m_state = jnp.maximum(btot + m_prev, jnp.max(g, axis=1, keepdims=True))
    kd = k * jnp.exp(col(g) - m_state)                  # [c, dqk]
    # a [1, 1] tile broadcasts along one axis at a time: widen the exponent
    # to a [1, dv] row before exp, so C's update broadcasts over sublanes only
    decay = jnp.exp(btot + m_prev - m_state)            # [1, 1]
    decay_row = jnp.exp(jnp.zeros((1, C.shape[1]), jnp.float32)
                        + (btot + m_prev - m_state))    # [1, dv]
    c_ref[...] = C * decay_row + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_ref[...] = n * decay + jnp.sum(kd, axis=0, keepdims=True)
    m_ref[...] = m_state


def mlstm_chunk_fwd(q, k, v, i_log, f_log, *, chunk: int = 128,
                    interpret: bool = False):
    """q,k: [BH, S, dqk]; v: [BH, S, dv]; i_log/f_log: [BH, S].

    Returns h: [BH, S, dv].
    """
    BH, S, dqk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    grid = (BH, S // chunk)
    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dqk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, dqk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, t: (b, 0, t)),
            pl.BlockSpec((1, 1, chunk), lambda b, t: (b, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((dqk, dv), jnp.float32),   # C
            pltpu.VMEM((1, dqk), jnp.float32),    # n
            pltpu.VMEM((1, 1), jnp.float32),      # m
        ],
        interpret=interpret,
    )(q, k, v, i_log.reshape(BH, 1, S), f_log.reshape(BH, 1, S))
