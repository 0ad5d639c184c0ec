"""Multi-head latent attention (MLA), DeepSeek-V2 (arXiv:2405.04434), without
query compression.

Each token's keys and values come from one latent: ``kv_a`` projects the
hidden state to a latent ``c`` of ``kv_lora_rank`` (with its own RMSNorm)
and one rotary key ``k_pe`` of ``qk_rope_head_dim`` shared by every head;
``kv_b`` expands ``c`` to each head's non-rotary key and its value. The
cache holds ``[c, k_pe]`` alone, ``latent_dim`` values a token a layer.

Two paths compute the same attention:

- prefill (and training) expands keys and values per head and runs
  ``chunked_causal_attention`` (qk width nope + rope, v width v_head_dim);
- decode uses the absorbed form: the key half of ``kv_b`` (W_uk) is folded
  into the query, which then attends over the cached latents directly, and
  the value half (W_uv) is applied to the attended latent. The cache is
  never expanded.

Rotary embeddings rotate interleaved pairs (2i, 2i + 1) with YaRN-scaled
frequencies, as the published code does, and write the result as the
rotated first members then the rotated second members (its layout).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig, YarnScaling
from repro.models.attention import NEG_INF, chunked_causal_attention
from repro.models.layers import apply_norm, dense, init_dense, init_norm


# ------------------------------------------------------------------- YaRN


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) \
        / (2 * math.log(base))


def rope_inv_freq(m: MLAConfig, theta: float) -> jnp.ndarray:
    """Inverse frequencies [rope_dim / 2] of the rotary key, YaRN-scaled
    where ``m.rope_scaling`` is set: dimensions below the ``beta_fast``
    correction keep theta's frequency, those above ``beta_slow``'s are
    divided by ``factor``, with a linear ramp between."""
    dim = m.qk_rope_head_dim
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    y = m.rope_scaling
    if y is None:
        return extra
    low = max(math.floor(_correction_dim(
        y.beta_fast, dim, theta, y.original_max_position_embeddings)), 0)
    high = min(math.ceil(_correction_dim(
        y.beta_slow, dim, theta, y.original_max_position_embeddings)),
        dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / y.factor * (1.0 - keep) + extra * keep


def rope_mscale(y: YarnScaling | None) -> float:
    """The factor on cos and sin (1 where mscale equals mscale_all_dim)."""
    if y is None:
        return 1.0
    return yarn_get_mscale(y.factor, y.mscale) \
        / yarn_get_mscale(y.factor, y.mscale_all_dim)


def softmax_scale(m: MLAConfig) -> float:
    """1 / sqrt(qk head dim), times mscale(mscale_all_dim)^2 under YaRN."""
    s = 1.0 / math.sqrt(m.qk_head_dim)
    y = m.rope_scaling
    if y is not None and y.mscale_all_dim:
        s *= yarn_get_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def apply_rope_interleaved(x, positions, m: MLAConfig, theta: float):
    """x: [B, S, H, d]; positions: [B, S]. Rotates the pairs (2i, 2i + 1)
    by position * inv_freq[i]; returns [rotated 2i..., rotated 2i+1...]."""
    inv = rope_inv_freq(m, theta)
    ang = positions[..., None].astype(jnp.float32) * inv      # [B, S, d/2]
    ms = rope_mscale(m.rope_scaling)
    cos = (jnp.cos(ang) * ms)[:, :, None]
    sin = (jnp.sin(ang) * ms)[:, :, None]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- params


def init_mla(key, cfg: ModelConfig, dtype):
    m, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": init_dense(k1, d, h * m.qk_head_dim, dtype=dtype),
        "wkv_a": init_dense(k2, d, m.latent_dim, dtype=dtype),
        "kv_norm": init_norm("rmsnorm", m.kv_lora_rank),
        "wkv_b": init_dense(k3, m.kv_lora_rank,
                            h * (m.qk_nope_head_dim + m.v_head_dim),
                            dtype=dtype),
        "wo": init_dense(k4, h * m.v_head_dim, d, dtype=dtype),
    }


def _project(p, x, cfg: ModelConfig, positions):
    """x: [B, S, D], positions [B, S] -> q_nope [B,S,H,nope], q_pe
    [B,S,H,rope] (rotated), latent rows [B, S, latent_dim] ([c, k_pe],
    normed and rotated)."""
    m = cfg.mla
    B, S, _ = x.shape
    q = dense(p["wq"], x).reshape(B, S, cfg.num_heads, m.qk_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_pe = apply_rope_interleaved(q[..., m.qk_nope_head_dim:], positions, m,
                                  cfg.rope_theta)
    kv = dense(p["wkv_a"], x)
    c = apply_norm(p["kv_norm"], kv[..., :m.kv_lora_rank])
    k_pe = apply_rope_interleaved(kv[..., None, m.kv_lora_rank:], positions,
                                  m, cfg.rope_theta)[:, :, 0]
    return q_nope, q_pe, jnp.concatenate([c, k_pe], axis=-1)


def _kv_b(p, cfg: ModelConfig):
    """kv_b's kernel as [rank, H, nope + v]: W_uk then W_uv per head."""
    m = cfg.mla
    return p["wkv_b"]["kernel"].reshape(
        m.kv_lora_rank, cfg.num_heads, m.qk_nope_head_dim + m.v_head_dim)


def _expanded(p, x, cfg: ModelConfig, positions):
    """Full-sequence attention with per-head keys and values; returns
    (y [B, S, D], latent rows [B, S, latent_dim])."""
    m = cfg.mla
    B, S, _ = x.shape
    q_nope, q_pe, lat = _project(p, x, cfg, positions[None, :].repeat(B, 0))
    w = _kv_b(p, cfg).astype(x.dtype)
    kv = jnp.einsum("bsc,chd->bshd", lat[..., :m.kv_lora_rank], w)
    k_nope, v = kv[..., :m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    k_pe = jnp.broadcast_to(lat[:, :, None, m.kv_lora_rank:],
                            (B, S, cfg.num_heads, m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    out = chunked_causal_attention(q, k, v, positions,
                                   scale=softmax_scale(m))
    y = dense(p["wo"], out.reshape(B, S, cfg.num_heads * m.v_head_dim))
    return y, lat


def mla_forward(p, x, cfg: ModelConfig, positions):
    with jax.named_scope("mla"):
        return _expanded(p, x, cfg, positions)[0]


# ------------------------------------------------------------------ cache


def init_latent_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                      dtype=jnp.bfloat16, abstract: bool = False):
    """One layer's cache: lat [B, L, latent_dim] and pos [L] (slot ->
    position, -1 empty)."""
    shape = (batch, max_len, cfg.mla.latent_dim)
    if abstract:
        return {"lat": jax.ShapeDtypeStruct(shape, dtype),
                "pos": jax.ShapeDtypeStruct((max_len,), jnp.int32)}
    return {"lat": jnp.zeros(shape, dtype),
            "pos": jnp.full((max_len,), -1, jnp.int32)}


def mla_prefill(p, x, cfg: ModelConfig, positions, *, max_len: int = 0):
    """Prefill: expanded attention and the cache of ``max_len`` (>= S)
    slots, the prompt's latents in slots 0..S-1."""
    with jax.named_scope("mla"):
        S = x.shape[1]
        y, lat = _expanded(p, x, cfg, positions)
        pad = max(max_len or S, S) - S
        return y, {"lat": jnp.pad(lat, ((0, 0), (0, pad), (0, 0))),
                   "pos": jnp.pad(positions.astype(jnp.int32), (0, pad),
                                  constant_values=-1)}


def mla_decode(p, x, cache, cfg: ModelConfig, cur_pos):
    """One-token decode in the absorbed form, reading ``cache`` and not
    writing it.

    x: [B, 1, D]. The query's non-rotary part times W_uk gives a query in
    latent space; with the rotary part beside it, it scores every cached
    latent row [c, k_pe] at once. The token attends to the cache's slots
    other than its own (which it is about to fill) and to its own row,
    under one softmax; the attended latent times W_uv gives each head's
    value. Returns ([B, 1, D], rows), rows = {"lat": [B, 1, latent_dim],
    "pos": [1]} for ``write_latent_rows``.
    """
    with jax.named_scope("mla"):
        m = cfg.mla
        B = x.shape[0]
        R, H = m.kv_lora_rank, cfg.num_heads
        pos_b = jnp.full((B, 1), cur_pos, jnp.int32)
        q_nope, q_pe, row = _project(p, x, cfg, pos_b)
        w = _kv_b(p, cfg).astype(x.dtype)
        w_uk, w_uv = w[..., :m.qk_nope_head_dim], w[..., m.qk_nope_head_dim:]
        q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk)
        q_cat = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1)   # [B, H, R+r]
        lat = cache["lat"]
        L = lat.shape[1]
        slot = jnp.asarray(cur_pos).astype(jnp.int32)
        scale = softmax_scale(m)
        old = jnp.einsum("bhc,blc->bhl", q_cat, lat,
                         preferred_element_type=jnp.float32) * scale
        valid = (cache["pos"] >= 0) & (jnp.arange(L) != slot)
        old = jnp.where(valid[None, None, :], old, NEG_INF)
        own = jnp.einsum("bhc,bc->bh", q_cat, row[:, 0],
                         preferred_element_type=jnp.float32) * scale
        a = jax.nn.softmax(jnp.concatenate([old, own[..., None]], axis=-1),
                           axis=-1)
        o = jnp.einsum("bhl,blc->bhc", a[..., :L].astype(lat.dtype), lat,
                       preferred_element_type=jnp.float32)[..., :R]
        o = o + a[..., L:] * row[:, :, :R].astype(jnp.float32)
        o = jnp.einsum("bhc,chd->bhd", o.astype(x.dtype), w_uv)
        y = dense(p["wo"], o.reshape(B, 1, H * m.v_head_dim))
        return y, {"lat": row, "pos": jnp.full((1,), cur_pos, jnp.int32)}


def write_latent_rows(cache, rows, cur_pos, *, lead: int = 0):
    """Write one step's ``rows`` into ``cache`` at slot cur_pos; ``lead``
    stacked axes precede each leaf's own (1 for the scanned layers)."""
    slot = jnp.asarray(cur_pos).astype(jnp.int32)
    put = jax.lax.dynamic_update_slice_in_dim
    return {"lat": put(cache["lat"], rows["lat"], slot, axis=lead + 1),
            "pos": put(cache["pos"], rows["pos"], slot, axis=lead)}
