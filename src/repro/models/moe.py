"""Sort-based top-k Mixture-of-Experts: capacity-bounded (dropping), or
dropless where the configuration sets no capacity.

Dispatch is *sort-based*, not one-hot-einsum based: GShard-style dispatch
einsums cost O(tokens x experts x capacity x d_model) HLO FLOPs — at
qwen3-moe's 128 experts that is ~20x the useful expert FLOPs, which would
poison the roofline's MODEL_FLOPS/HLO_FLOPS ratio. Here dispatch/combine are
pure data movement (argsort + scatter/gather), so HLO FLOPs stay ~= active
expert FLOPs.

Sharding: tokens are grouped into `num_groups` groups laid out on the data
axis (dispatch is group-local => no cross-shard communication); expert weights
are sharded over the `model` axis on the ffn dimension ("expert-TP"), so the
expert matmuls behave exactly like a dense TP FFN (reduce over `model`).
An expert-parallel all-to-all variant is explored in the perf hillclimb.

The dropless path (``capacity_factor`` None) sorts every (token, expert)
pair by expert and runs each expert's rows as one group of a grouped
matmul, so no token is dropped and no token's result depends on its
batch-mates. A call of few rows, as a decode step is, runs them through
the few-row expert kernel (``kernels/moe_decode``), which reads each hit
expert's weights once in large blocks; a call of many (prefill, training)
through ``jax.lax.ragged_dot``. Shared experts, where configured, are one
gated MLP every token passes through, added to the routed output.

Every call also counts, per layer, the experts that the router chose for
at least one token (``moe_hit``), the rows of the busiest one
(``moe_max_rows``) and, on the dropless path, whether the few-row kernel
ran its experts (``moe_kernel_layers``); the decode step sums them into
its cache.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.moe_decode import expert_mlp
from repro.kernels.moe_decode.kernel import window_rows
from repro.models.layers import activation, dense, init_dense, init_mlp, mlp


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    kr, kg, ku, ko, ks = jax.random.split(key, 5)
    d, e, f = cfg.d_model, m.num_experts, m.d_ff_expert
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": init_dense(kr, d, e, dtype=jnp.float32),
        "wi_gate": (jax.random.normal(kg, (e, d, f), jnp.float32) * scale).astype(dtype),
        "wi_up": (jax.random.normal(ku, (e, d, f), jnp.float32) * scale).astype(dtype),
        "wo": (jax.random.normal(ko, (e, f, d), jnp.float32)
               / math.sqrt(f)).astype(dtype),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks, cfg.with_(d_ff=m.num_shared_experts * f),
                               dtype)
    return p


def _capacity(tokens_per_group: int, m) -> int:
    cap = int(math.ceil(tokens_per_group * m.top_k * m.capacity_factor
                        / m.num_experts))
    return max(8, ((cap + 7) // 8) * 8)  # MXU-friendly multiple of 8


def _dispatch_group(xg, probs, eidx, num_experts: int, cap: int):
    """Group-local sort-based dispatch.

    xg: [n, d]; probs/eidx: [n, k]. Returns (buf [E, cap, d],
    scatter coords for combine: token [n*k], expert [n*k], pos [n*k],
    keep [n*k], flat probs [n*k]).
    """
    n, k = eidx.shape
    flat_e = eidx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts))
    pos_sorted = jnp.arange(n * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_sorted < cap
    token_sorted = (order // k).astype(jnp.int32)
    pos_safe = jnp.where(keep, pos_sorted, cap)  # cap == OOB -> dropped
    src = jnp.take(xg, token_sorted, axis=0)
    buf = jnp.zeros((num_experts, cap, xg.shape[-1]), xg.dtype)
    buf = buf.at[sorted_e, pos_safe].set(src, mode="drop")
    probs_sorted = probs.reshape(-1)[order]
    return buf, (token_sorted, sorted_e, pos_safe, keep, probs_sorted)


def _combine_group(yb, coords, n: int):
    token_sorted, sorted_e, pos_safe, keep, probs_sorted = coords
    gathered = yb.at[sorted_e, pos_safe].get(mode="fill", fill_value=0.0)
    gathered = gathered * (keep[:, None] * probs_sorted[:, None]).astype(yb.dtype)
    out = jnp.zeros((n, yb.shape[-1]), yb.dtype)
    return out.at[token_sorted].add(gathered)


def _aux(router_logits, router_probs, top_i, m):
    """Load-balance loss (Switch), router z-loss, and the routing counts:
    the experts chosen for at least one token, the busiest one's rows."""
    me = jnp.mean(router_probs.reshape(-1, m.num_experts), axis=0)     # [E]
    ce = jnp.mean(jax.nn.one_hot(top_i, m.num_experts).sum(axis=-2)
                  .reshape(-1, m.num_experts), axis=0)
    lb = m.num_experts * jnp.sum(me * ce) / m.top_k
    zl = jnp.mean(jnp.square(jax.nn.logsumexp(router_logits, axis=-1)))
    rows = jnp.bincount(top_i.reshape(-1), length=m.num_experts)
    return {"moe_lb": lb, "moe_z": zl,
            "moe_hit": jnp.sum(rows > 0).astype(jnp.int32),
            "moe_max_rows": jnp.max(rows).astype(jnp.int32)}


def _route(p, x, m, precision=None):
    """Router in float32: (logits, probs, top-k gates, top-k experts). The
    dropless path asks for the HIGHEST matmul precision, so that its expert
    choices follow the float32 reference's; the capacity path keeps the
    default."""
    router_logits = jnp.matmul(x.astype(jnp.float32), p["router"]["kernel"],
                               precision=precision)
    router_probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_i = jax.lax.top_k(router_probs, m.top_k)
    if m.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return router_logits, router_probs, top_p, top_i


EXPERT_WEIGHTS = ("wi_gate", "wi_up", "wo")

# The few-row kernel multiplies each hit expert's weights (2 bytes a
# weight in bfloat16) by a window of ``window_rows(N)`` rows (2 flops a
# weight a row): that many flops a byte. v5e's ridge point is 197 TFLOP/s
# over 819 GB/s, 240 flops a byte; a window of at most one 128-row MXU
# pass keeps it bound by the weights' bytes with room to spare. Calls
# with more rows give each expert enough of them to fill ragged_dot's
# tiles, and keep it. The bound is derived, not measured: the chip sweep
# (benchmarks/moe_decode_tiles.py) covers calls of 1-16 tokens, and no
# serving cell makes a call of 17-113.
FEW_ROWS_WINDOW = 128


def few_rows(n_tokens: int) -> bool:
    """Whether a dropless call over ``n_tokens`` tokens runs its experts
    through the few-row kernel. Top-k picks distinct experts, so an
    expert gets at most ``n_tokens`` rows."""
    return window_rows(n_tokens) <= FEW_ROWS_WINDOW


def dropless_routed(p, x, top_p, top_i, act, num_experts: int):
    """Every token's gate-weighted sum over its top-k experts, none dropped.

    x: [N, D]; top_p, top_i: [N, k]. The N*k (token, expert) rows are
    sorted by expert and each expert's rows run as one group of a grouped
    matmul; the rows are put back in token order (the inverse of the sort)
    and summed over k in float32. Where ``few_rows(N)``, the few-row
    kernel runs the groups, each hit expert's weights read once; else
    ``jax.lax.ragged_dot``.

    Where ``p`` holds ``layer``, its expert weights are the whole stack of
    the scanned layers [R, E, ...]: the experts of layer ``layer`` are
    read there in place, instead of from a slice of the stack copied for
    it. For ragged_dot they are groups R*E of which only that layer's have
    rows; the TPU compiler refuses that form unless the rows fill whole
    tiles of 8, so pad rows, computed by the layer's last expert, make
    them up and are dropped after."""
    N, D = x.shape
    k = top_i.shape[-1]
    flat_e = top_i.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    rows = jnp.take(x, order // k, axis=0)                      # [N*k, D]
    sizes = jnp.bincount(flat_e, length=num_experts).astype(jnp.int32)
    if few_rows(N):
        ws = [p[n] for n in EXPERT_WEIGHTS]
        if "layer" not in p:
            ws = [w[None] for w in ws]
        y = expert_mlp(rows, sizes, *ws, p.get("layer", 0), act=act,
                       max_rows=N)
    else:
        y = _ragged_routed(p, rows, sizes, flat_e, act, num_experts)
    y = jnp.take(y[:N * k], jnp.argsort(order), axis=0).reshape(N, k, D)
    return jnp.einsum("nkd,nk->nd", y, top_p.astype(jnp.float32))


def _ragged_routed(p, rows, sizes, flat_e, act, num_experts: int):
    """The sorted rows' expert outputs [>= N*k, D] by ragged_dot."""
    wg, wu, wo = (p[n].astype(rows.dtype) for n in EXPERT_WEIGHTS)
    if "layer" in p:
        groups = wg.shape[0] * num_experts
        wg, wu, wo = (w.reshape((groups,) + w.shape[2:]) for w in (wg, wu, wo))
        pad = -rows.shape[0] % 8
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        sizes = jnp.bincount(flat_e + p["layer"] * num_experts,
                             length=groups).astype(jnp.int32)
        sizes = sizes.at[(p["layer"] + 1) * num_experts - 1].add(pad)
    h = act(jax.lax.ragged_dot(rows, wg, sizes)) \
        * jax.lax.ragged_dot(rows, wu, sizes)
    return jax.lax.ragged_dot(h, wo, sizes, preferred_element_type=jnp.float32)


def moe_forward(p, x, cfg: ModelConfig, *, num_groups: int = 0,
                constrain=lambda x, kind: x):
    """x: [B, S, D] -> (y [B, S, D], aux dict: losses and routing counts)."""
    m = cfg.moe
    if m.capacity_factor is None:
        with jax.named_scope("moe"):
            return _moe_dropless(p, x, cfg)
    B, S, D = x.shape
    N = B * S
    G = num_groups or m.num_groups or 1
    G = max(1, min(G, N))
    while N % G:
        G -= 1
    n = N // G
    cap = _capacity(n, m)

    xf = constrain(x.reshape(G, n, D), "moe_local")
    router_logits, router_probs, top_p, top_i = _route(p, xf, m)  # [G, n, k]
    top_p = constrain(top_p, "moe_local")
    top_i = constrain(top_i, "moe_local")

    buf, coords = jax.vmap(
        lambda xg, pg, ig: _dispatch_group(xg, pg, ig, m.num_experts, cap)
    )(xf, top_p, top_i)                                           # buf [G,E,cap,D]
    ep = m.expert_parallel
    buf = constrain(buf, "moe_ep_buf" if ep else "moe_local")
    coords = tuple(constrain(c, "moe_local") for c in coords)

    act = activation(cfg.act)
    wg, wu, wo = (p["wi_gate"].astype(x.dtype), p["wi_up"].astype(x.dtype),
                  p["wo"].astype(x.dtype))
    h = act(jnp.einsum("gecd,edf->gecf", buf, wg)) \
        * jnp.einsum("gecd,edf->gecf", buf, wu)
    h = constrain(h, "moe_ep_ff" if ep else "moe_ff")
    yb = constrain(jnp.einsum("gecf,efd->gecd", h, wo), "moe_local")

    y = jax.vmap(lambda b, c: _combine_group(b, c, n))(yb, coords)
    y = constrain(y, "moe_local")
    y = y.reshape(B, S, D)
    return y, _aux(router_logits, router_probs, top_i, m)


def _moe_dropless(p, x, cfg: ModelConfig):
    m = cfg.moe
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    router_logits, router_probs, top_p, top_i = _route(
        p, xf, m, jax.lax.Precision.HIGHEST)
    y = dropless_routed(p, xf, top_p, top_i, activation(cfg.act),
                        m.num_experts)
    if "shared" in p:
        y = y + mlp(p["shared"], xf, cfg.act).astype(jnp.float32)
    aux = _aux(router_logits, router_probs, top_i, m)
    aux["moe_kernel_layers"] = jnp.int32(few_rows(B * S))
    return y.astype(x.dtype).reshape(B, S, D), aux
