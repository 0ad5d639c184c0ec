"""Plain reference of DeepSeek-V2 (arXiv:2405.04434; DeepSeek-V2-Lite's
published config.json): pre-norm RMSNorm decoder layers of multi-head
latent attention (no query compression) and, after ``first_k_dense_replace``
dense SiLU-MLP layers, a mixture of experts: a float32 softmax router, the
greedy top ``num_experts_per_tok`` experts with their raw probabilities as
gates (``norm_topk_prob`` false, ``routed_scaling_factor`` 1), plus the
shared experts as one SiLU MLP; untied output head.

It imports nothing of the program. Attention is computed in the expanded
form: every head's key is [latent x W_uk, rotary key] and its value latent
x W_uv, from the normed latent ``c`` and the one rotary key ``k_pe`` that
``kv_a`` makes; queries attend in blocks of rows. Rotary embeddings rotate
the interleaved pairs (2i, 2i + 1) by YaRN-scaled frequencies (the
published ``DeepseekV2YarnRotaryEmbedding``), and the softmax scale is
mscale(mscale_all_dim)^2 / sqrt(qk_nope + qk_rope). Each token's routed
output is the gate-weighted sum over all experts, computed densely in
blocks of rows, with zero gates outside its top k: no cache, no sorting,
no absorption.

``init_weights`` makes the weights from a key, in the type they are served
in (the router in float32), each layer group's leaves stacked on a leading
axis; the benchmark hands the program the same weights in its own layout.
``logits`` runs the full forward pass of whole sequences in float32 at the
highest matmul precision. With ``quant`` set, every matrix multiplication
rounds both inputs to that type first (per row of the activations and per
output column of the weights), the control one precision below the served
bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NORM_SCALE_STD = 0.1        # norms start at 1 + N(0, 0.1), so they matter
EMBED_STD = 0.02
Q_BLOCK = 512               # query rows a block of attention holds
ROW_BLOCK = 512             # token rows a block of the all-experts sum holds


def _dims(s):
    nope, rope = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
    return dict(d=s["hidden_size"], h=s["num_attention_heads"], nope=nope,
                rope=rope, qk=nope + rope, v=s["v_head_dim"],
                r=s["kv_lora_rank"], ff=s["intermediate_size"],
                e=s["n_routed_experts"], f=s["moe_intermediate_size"],
                sf=s["n_shared_experts"] * s["moe_intermediate_size"],
                k=s["num_experts_per_tok"], V=s["vocab_size"],
                dense=s["first_k_dense_replace"],
                moe=s["num_hidden_layers"] - s["first_k_dense_replace"])


def _attn_shapes(n, m):
    return {"wq": (n, m["d"], m["h"] * m["qk"]),
            "wkv_a": (n, m["d"], m["r"] + m["rope"]),
            "wkv_b": (n, m["r"], m["h"] * (m["nope"] + m["v"])),
            "wo": (n, m["h"] * m["v"], m["d"])}


def init_weights(key, s, dtype=jnp.bfloat16):
    """Weights from ``key``: dense kernels (each expert's too) N(0, 1/fan_in),
    embedding N(0, 0.02^2), norm gains 1 + N(0, 0.1^2) kept as the float32
    offset, the router N(0, 1/hidden) in float32."""
    m = _dims(s)
    nd, nm = m["dense"], m["moe"]
    dense = dict(_attn_shapes(nd, m), w_gate=(nd, m["d"], m["ff"]),
                 w_up=(nd, m["d"], m["ff"]), w_down=(nd, m["ff"], m["d"]))
    moe = dict(_attn_shapes(nm, m),
               e_gate=(nm, m["e"], m["d"], m["f"]),
               e_up=(nm, m["e"], m["d"], m["f"]),
               e_down=(nm, m["e"], m["f"], m["d"]),
               s_gate=(nm, m["d"], m["sf"]), s_up=(nm, m["d"], m["sf"]),
               s_down=(nm, m["sf"], m["d"]))
    keys = iter(jax.random.split(key, len(dense) + len(moe) + 12))

    def kernel(shp, dt=dtype):
        return (jax.random.normal(next(keys), shp, jnp.float32)
                / math.sqrt(shp[-2])).astype(dt)

    def gains(*shp):
        return jax.random.normal(next(keys), shp) * NORM_SCALE_STD

    w = {"dense": {n: kernel(shp) for n, shp in dense.items()},
         "moe": {n: kernel(shp) for n, shp in moe.items()}}
    w["moe"]["router"] = kernel((nm, m["d"], m["e"]), jnp.float32)
    for group, n in (("dense", nd), ("moe", nm)):
        w[group]["attn_norm"] = gains(n, m["d"])
        w[group]["kv_norm"] = gains(n, m["r"])
        w[group]["mlp_norm"] = gains(n, m["d"])
    w["embed"] = (jax.random.normal(next(keys), (m["V"], m["d"]), jnp.float32)
                  * EMBED_STD).astype(dtype)
    w["head"] = kernel((m["d"], m["V"]))
    w["final_norm"] = gains(m["d"])
    return w


# ------------------------------------------------------------ arithmetic


def _round_to(x, quant, axis):
    """x rounded to ``quant`` with one scale per slice along ``axis``."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(quant)


def _mm(x, w, quant):
    """[..., k] @ [k, n] in float32."""
    return jnp.matmul(_round_to(x, quant, -1), _round_to(w, quant, 0),
                      precision=HIGHEST)


def _rmsnorm(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + offset)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(s):
    """The rotary key's inverse frequencies, YaRN-scaled as published."""
    dim, base = s["qk_rope_head_dim"], s["rope_theta"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    y = s.get("rope_scaling")
    if not y:
        return extra
    inter = extra / y["factor"]
    orig = y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def softmax_scale(s):
    scale = 1.0 / math.sqrt(s["qk_nope_head_dim"] + s["qk_rope_head_dim"])
    y = s.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        scale *= _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope(x, s):
    """x: [B, S, H, d]; rotates the interleaved pairs (2i, 2i + 1) by
    pos * inv_freq_i and returns them as the published code lays them out:
    the rotated first members, then the rotated second members."""
    S = x.shape[1]
    y = s.get("rope_scaling") or {}
    ms = (_yarn_mscale(y["factor"], y.get("mscale", 1))
          / _yarn_mscale(y["factor"], y.get("mscale_all_dim", 0))) if y else 1.0
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq(s)
    cos, sin = (jnp.cos(ang) * ms)[:, None], (jnp.sin(ang) * ms)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(x, lw, s, quant):
    m = _dims(s)
    eps = s["rms_norm_eps"]
    B, S, _ = x.shape
    h, nope, r = m["h"], m["nope"], m["r"]
    a = _rmsnorm(x, lw["attn_norm"], eps)
    q = _mm(a, lw["wq"], quant).reshape(B, S, h, m["qk"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], s)], -1)
    kv = _mm(a, lw["wkv_a"], quant)
    c = _rmsnorm(kv[..., :r], lw["kv_norm"], eps)
    k_pe = _rope(kv[..., None, r:], s)                      # [B, S, 1, rope]
    kvb = _mm(c, lw["wkv_b"], quant).reshape(B, S, h, nope + m["v"])
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, h, m["rope"]))], -1)
    v = kvb[..., nope:]
    kr, vr = _round_to(k, quant, -1), _round_to(v, quant, 1)
    nb = -(-S // Q_BLOCK)
    qr = jnp.pad(_round_to(q, quant, -1),
                 ((0, 0), (0, nb * Q_BLOCK - S), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qr, i * Q_BLOCK, Q_BLOCK, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kr,
                        precision=HIGHEST) * softmax_scale(s)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        causal = jnp.arange(S)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _round_to(p, quant, -1), vr,
                          precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(nb))          # [nb, B, Q_BLOCK, h, v]
    o = jnp.moveaxis(o, 0, 1).reshape(B, nb * Q_BLOCK, h * m["v"])[:, :S]
    return x + _mm(o, lw["wo"], quant)


def _mlp(x, g, u, d, quant):
    return _mm(jax.nn.silu(_mm(x, g, quant)) * _mm(x, u, quant), d, quant)


def gates(x, router, s, quant=None):
    """[..., E] gates: the softmax probabilities of the top k experts, zero
    elsewhere (not renormalized)."""
    probs = jax.nn.softmax(_mm(x, router, quant), axis=-1)
    top, idx = jax.lax.top_k(probs, s["num_experts_per_tok"])
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
    return jnp.einsum("...ke,...k->...e", onehot, top)


def _experts(x, g, lw, quant):
    """sum_e g_e * expert_e(x) over every expert, x [N, D], g [N, E]."""
    rx = _round_to(x, quant, -1)
    wg, wu, wd = (_round_to(lw["e_gate"], quant, 1),
                  _round_to(lw["e_up"], quant, 1),
                  _round_to(lw["e_down"], quant, 1))
    hid = jax.nn.silu(jnp.einsum("nd,edf->nef", rx, wg, precision=HIGHEST)) \
        * jnp.einsum("nd,edf->nef", rx, wu, precision=HIGHEST)
    return jnp.einsum("nef,efd->nd", _round_to(hid, quant, -1) * g[..., None],
                      wd, precision=HIGHEST)


def _moe(x, lw, s, quant):
    B, S, D = x.shape
    n = B * S
    nb = -(-n // ROW_BLOCK)
    rows = jnp.pad(x.reshape(n, D), ((0, nb * ROW_BLOCK - n), (0, 0)))
    g = gates(rows, lw["router"], s, quant)

    def block(args):
        xb, gb = args
        return _experts(xb, gb, lw, quant)

    y = jax.lax.map(block, (rows.reshape(nb, ROW_BLOCK, D),
                            g.reshape(nb, ROW_BLOCK, -1)))
    y = y.reshape(nb * ROW_BLOCK, D)[:n] + _mlp(
        rows[:n], lw["s_gate"], lw["s_up"], lw["s_down"], quant)
    return y.reshape(B, S, D)


def _dense_layer(x, lw, s, quant):
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    x = _attention(x, lw, s, quant)
    m = _rmsnorm(x, lw["mlp_norm"], s["rms_norm_eps"])
    return x + _mlp(m, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def _moe_layer(x, lw, s, quant):
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    x = _attention(x, lw, s, quant)
    return x + _moe(_rmsnorm(x, lw["mlp_norm"], s["rms_norm_eps"]), lw, s,
                    quant)


def logits(w, tokens, s, first: int, quant=None):
    """Float32 logits [B, S - first, V] at positions first..S-1 of
    ``tokens`` [B, S]. Layers run one at a time (a scan over each group),
    so only one layer's weights are ever held in float32."""
    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda x, lw: (_dense_layer(x, lw, s, quant), None),
                        x, w["dense"])
    x, _ = jax.lax.scan(lambda x, lw: (_moe_layer(x, lw, s, quant), None),
                        x, w["moe"])
    x = _rmsnorm(x[:, first:], w["final_norm"], s["rms_norm_eps"])
    return _mm(x, w["head"].astype(jnp.float32), quant)
