"""Plain reference of a LLaMA-style dense decoder (DeepSeek LLM 7B's
architecture, arXiv:2401.02954): pre-norm RMSNorm, rotary embeddings on
the two halves of each head, causal softmax attention, SiLU-gated MLP,
untied output head.

It imports nothing of the program. ``init_weights`` makes the weights from
a key, in the type they are served in, with each layer's leaves stacked on
a leading axis; the benchmark hands the program the same weights in the
program's own layout. ``logits`` runs the full forward pass of whole
sequences in float32 at the highest matmul precision, one layer at a time.
With ``quant`` set, every matrix multiplication rounds both of its inputs
to that type first (per row of the activations and per output column of
the weights, the usual scaling), which is the control: the same
reference computed one precision below the served bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NORM_SCALE_STD = 0.1        # norms start at 1 + N(0, 0.1), so they matter
EMBED_STD = 0.02


def _dims(s):
    d = s["hidden_size"]
    h = s["num_attention_heads"]
    hd = s.get("head_dim") or d // h
    kv = s.get("num_key_value_heads") or h
    return d, h, hd, kv, s["intermediate_size"], s["vocab_size"], \
        s["num_hidden_layers"]


def init_weights(key, s, dtype=jnp.bfloat16):
    """Weights from ``key``: dense kernels N(0, 1/fan_in), embedding
    N(0, 0.02^2), norm gains 1 + N(0, 0.1^2) kept as the float32 offset."""
    d, h, hd, kv, ff, V, L = _dims(s)
    shapes = {
        "wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
        "wo": (L, h * hd, d), "w_gate": (L, d, ff), "w_up": (L, d, ff),
        "w_down": (L, ff, d),
    }
    keys = jax.random.split(key, len(shapes) + 5)
    w = {name: (jax.random.normal(k, shp, jnp.float32)
                / math.sqrt(shp[1])).astype(dtype)
         for k, (name, shp) in zip(keys, shapes.items())}
    k = keys[len(shapes):]
    w["embed"] = (jax.random.normal(k[0], (V, d), jnp.float32)
                  * EMBED_STD).astype(dtype)
    w["head"] = (jax.random.normal(k[1], (d, V), jnp.float32)
                 / math.sqrt(d)).astype(dtype)
    w["attn_norm"] = jax.random.normal(k[2], (L, d)) * NORM_SCALE_STD
    w["mlp_norm"] = jax.random.normal(k[3], (L, d)) * NORM_SCALE_STD
    w["final_norm"] = jax.random.normal(k[4], (d,)) * NORM_SCALE_STD
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


def _rope(x, theta):
    """x: [B, S, H, hd]; rotates the pairs (i, i + hd/2) by pos * freq_i."""
    S, hd = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq      # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw, s, quant):
    d, h, hd, kv, ff, V, L = _dims(s)
    eps, theta = s["rms_norm_eps"], s["rope_theta"]
    B, S, _ = x.shape
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    a = _rmsnorm(x, lw["attn_norm"], eps)
    q = _rope(_mm(a, lw["wq"], quant).reshape(B, S, h, hd), theta)
    k = _rope(_mm(a, lw["wk"], quant).reshape(B, S, kv, hd), theta)
    v = _mm(a, lw["wv"], quant).reshape(B, S, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round_to(q, quant, -1),
                        _round_to(k, quant, -1), precision=HIGHEST)
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round_to(p, quant, -1),
                   _round_to(v, quant, 1), precision=HIGHEST)
    x = x + _mm(o.reshape(B, S, h * hd), lw["wo"], quant)
    m = _rmsnorm(x, lw["mlp_norm"], eps)
    g = jax.nn.silu(_mm(m, lw["w_gate"], quant)) * _mm(m, lw["w_up"], quant)
    return x + _mm(g, lw["w_down"], quant)


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
              "w_up", "w_down")


def logits(w, tokens, s, first: int, quant=None):
    """Float32 logits [B, S - first, V] at positions first..S-1 of
    ``tokens`` [B, S]. Layers run one at a time (a scan), so only one
    layer's weights are ever held in float32."""
    x = w["embed"][tokens].astype(jnp.float32)

    def body(x, lw):
        return _layer(x, lw, s, quant), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in LAYER_KEYS})
    x = _rmsnorm(x[:, first:], w["final_norm"], s["rms_norm_eps"])
    return _mm(x, w["head"].astype(jnp.float32), quant)
