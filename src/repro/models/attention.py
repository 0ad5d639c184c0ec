"""Causal self-attention: global + sliding-window, GQA, KV caches.

Training / prefill use a *chunked* streaming-softmax implementation (flash
attention expressed in pure JAX): an outer python loop over query chunks and an
inner ``lax.scan`` over the key/value chunks visible to that query chunk. This
keeps peak activation memory at O(S·c) instead of O(S²) — a 32k-token prefill
would otherwise materialize a 128 GB logit tensor per device — while keeping
HLO FLOPs *exactly* causal (we never visit kv chunks above the diagonal).

The models run this XLA path on every backend, the TPU included: nothing in
``repro.models`` calls the Pallas kernels in ``repro.kernels``, which are
tested against their own references and not yet wired in.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_norm, apply_rope, dense, init_dense, init_norm

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": init_dense(k1, d, cfg.q_dim, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_dense(k2, d, cfg.kv_dim, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_dense(k3, d, cfg.kv_dim, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_dense(k4, cfg.q_dim, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm("rmsnorm", cfg.head_dim)
        p["k_norm"] = init_norm("rmsnorm", cfg.head_dim)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions, theta: float):
    """x: [B, S, D] -> q [B,S,H,hd], k/v [B,S,K,hd] (rope applied)."""
    B, S, _ = x.shape
    q = dense(p["wq"], x).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = dense(p["wk"], x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = dense(p["wv"], x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q)
        k = apply_norm(p["k_norm"], k)
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


def _chunk_attend(q, k, v, q_pos, k_pos, scale):
    """One (q-chunk, kv-chunk) streaming-softmax step.

    q: [B, qc, K, G, hd]   (kv head-grouped query)
    k/v: [B, kc, K, hd]
    returns unnormalized (acc, m, l) update terms.
    """
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
    mask = (k_pos[None, :] <= q_pos[:, None])  # [qc, kc] causal
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    m_new = jnp.max(logits, axis=-1)                     # [B,K,G,qc]
    p = jnp.exp(logits - m_new[..., None])
    l_new = jnp.sum(p, axis=-1)                          # [B,K,G,qc]
    acc_new = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v.dtype), v)
    return acc_new, m_new, l_new


def _merge(acc, m, l, acc2, m2, l2):
    m12 = jnp.maximum(m, m2)
    a1 = jnp.exp(m - m12)
    a2 = jnp.exp(m2 - m12)
    acc12 = acc * a1[..., None].astype(acc.dtype) + acc2 * a2[..., None].astype(acc.dtype)
    l12 = l * a1 + l2 * a2
    return acc12, m12, l12


def chunked_causal_attention(q, k, v, positions, *, window: int = 0,
                             q_chunk: int = 0) -> jnp.ndarray:
    """Flash-style causal attention in pure JAX.

    q: [B,S,H,hd], k/v: [B,S,K,hd] (GQA: H = K*G), positions: [S].
    window > 0: sliding-window (each query sees the last `window` keys).
    Returns [B,S,H,hd].
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qc = q_chunk or (2048 if S >= 8192 else min(S, 1024))
    qc = min(qc, S)
    assert S % qc == 0, (S, qc)
    nq = S // qc
    qg = q.reshape(B, S, K, G, hd)

    outs = []
    if window:
        # pad keys in front with `wpad` so every q chunk slices [wpad + qc].
        wpad = ((window + qc - 1) // qc) * qc
        kp = jnp.pad(k, ((0, 0), (wpad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (wpad, 0), (0, 0), (0, 0)))
        kpos = jnp.pad(positions, (wpad, 0), constant_values=-10**9)
        for i in range(nq):
            qi = jax.lax.dynamic_slice_in_dim(qg, i * qc, qc, axis=1)
            qp = jax.lax.dynamic_slice_in_dim(positions, i * qc, qc, axis=0)
            ki = jax.lax.dynamic_slice_in_dim(kp, i * qc, wpad + qc, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(vp, i * qc, wpad + qc, axis=1)
            kposi = jax.lax.dynamic_slice_in_dim(kpos, i * qc, wpad + qc, axis=0)
            logits = jnp.einsum("bqkgh,bskh->bkgqs", qi, ki).astype(jnp.float32) * scale
            mask = (kposi[None, :] <= qp[:, None]) & \
                   (kposi[None, :] > qp[:, None] - window)
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            w = jax.nn.softmax(logits, axis=-1)
            oi = jnp.einsum("bkgqs,bskh->bkgqh", w.astype(vi.dtype), vi)
            outs.append(oi)
    else:
        kc = qc
        for i in range(nq):
            qi = jax.lax.dynamic_slice_in_dim(qg, i * qc, qc, axis=1)
            qp = jax.lax.dynamic_slice_in_dim(positions, i * qc, qc, axis=0)

            def kv_step(carry, idx):
                acc, m, l = carry
                kj = jax.lax.dynamic_slice_in_dim(k, idx * kc, kc, axis=1)
                vj = jax.lax.dynamic_slice_in_dim(v, idx * kc, kc, axis=1)
                kposj = jax.lax.dynamic_slice_in_dim(positions, idx * kc, kc, axis=0)
                acc2, m2, l2 = _chunk_attend(qi, kj, vj, qp, kposj, scale)
                return _merge(acc, m, l, acc2, m2, l2), None

            acc0 = jnp.zeros((B, K, G, qc, hd), v.dtype)
            m0 = jnp.full((B, K, G, qc), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, K, G, qc), jnp.float32)
            (acc, m, l), _ = jax.lax.scan(
                kv_step, (acc0, m0, l0), jnp.arange(i + 1))
            oi = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
            outs.append(oi)

    out = jnp.concatenate(outs, axis=3)  # [B,K,G,S,hd] concat on q dim
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
    return out


def attention_forward(p, x, cfg: ModelConfig, positions, *, window: int = 0,
                      theta: float = 10_000.0) -> jnp.ndarray:
    """Full-sequence attention block ([B,S,D] -> [B,S,D])."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions[None, :].repeat(B, 0)
                           if positions.ndim == 1 else positions, theta)
    out = chunked_causal_attention(q, k, v, positions if positions.ndim == 1
                                   else positions[0], window=window)
    return dense(p["wo"], out.reshape(B, S, cfg.q_dim))


# ------------------------------------------------------------------ caches


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *, window: int = 0,
                  dtype=jnp.bfloat16, abstract: bool = False):
    """KV cache for one attention layer.

    Layout: k/v [B, L, K, hd]; pos [L] slot→global-position (-1 empty).
    Sliding-window layers use a ring buffer of size `window`.
    """
    L = min(window, max_len) if window else max_len
    shape_kv = (batch, L, cfg.num_kv_heads, cfg.head_dim)
    if abstract:
        return {
            "k": jax.ShapeDtypeStruct(shape_kv, dtype),
            "v": jax.ShapeDtypeStruct(shape_kv, dtype),
            "pos": jax.ShapeDtypeStruct((L,), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape_kv, dtype),
        "v": jnp.zeros(shape_kv, dtype),
        "pos": jnp.full((L,), -1, jnp.int32),
    }


def attention_prefill(p, x, cfg: ModelConfig, positions, *, window: int = 0,
                      theta: float = 10_000.0, max_len: int = 0):
    """Prefill: full-seq attention AND the populated cache.

    The cache is allocated at ``max_len`` (>= S) slots so subsequent decode
    steps can append; sliding-window layers use a ring buffer whose slot for
    position p is ``p % L`` — consistent with ``attention_decode``.
    """
    B, S, _ = x.shape
    pos1d = positions if positions.ndim == 1 else positions[0]
    q, k, v = _project_qkv(p, x, cfg, pos1d[None, :].repeat(B, 0), theta)
    out = chunked_causal_attention(q, k, v, pos1d, window=window)
    y = dense(p["wo"], out.reshape(B, S, cfg.q_dim))
    max_len = max(max_len or S, S)
    L = min(window, max_len) if window else max_len
    keep = min(L, S)
    # the kept positions S-keep..S-1 are contiguous, so their ring slots
    # (pos % L) are one contiguous segment of 0..L-1, wrapping at most once:
    # pad the segment to L slots (empty slots hold pos -1), then rotate it to
    # start at slot (S-keep) % L. Pad + roll, not a scatter: GSPMD partitions
    # rolls cleanly but replicates scattered caches, and the TPU compiler
    # aborts on the scatter once it fuses into the prefill program.
    pad = L - keep
    shift = (S - keep) % L
    kv_pos = pos1d[S - keep:].astype(jnp.int32)
    widths = ((0, 0), (0, pad), (0, 0), (0, 0))
    ck = jnp.roll(jnp.pad(k[:, S - keep:], widths), shift, axis=1)
    cv = jnp.roll(jnp.pad(v[:, S - keep:], widths), shift, axis=1)
    cpos = jnp.roll(jnp.pad(kv_pos, (0, pad), constant_values=-1), shift)
    return y, {"k": ck, "v": cv, "pos": cpos}


def attention_decode(p, x, cache, cfg: ModelConfig, cur_pos, *, window: int = 0,
                     theta: float = 10_000.0):
    """One-token decode. x: [B, 1, D]; cur_pos: scalar int (current position).

    Returns ([B,1,D], new_cache). Ring-buffer update for window layers.
    """
    B = x.shape[0]
    pos_b = jnp.full((B, 1), cur_pos, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, pos_b, theta)   # q [B,1,H,hd]
    L = cache["k"].shape[1]
    # ring slot; for global caches cur_pos < L always, so this is identity.
    slot = (jnp.asarray(cur_pos) % L).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((1,), cur_pos, jnp.int32), slot, axis=0)

    K, G = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    hd = cfg.head_dim
    qg = q.reshape(B, 1, K, G, hd)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, ck).astype(jnp.float32) * scale
    valid = cpos >= 0
    if window:
        valid = valid & (cpos > cur_pos - window)
    logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", w.astype(cv.dtype), cv)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, 1, cfg.q_dim)
    y = dense(p["wo"], o)
    return y, {"k": ck, "v": cv, "pos": cpos}
