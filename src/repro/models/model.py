"""Unified decoder-only CausalLM covering all 10 assigned architectures.

Layer stacking uses ``jax.lax.scan`` over *pattern repeats*: the per-layer
block kinds are ``cfg.block_pattern`` tiled over depth, parameters for each
pattern position are stacked along a leading ``repeat`` axis, and one scan
body applies a whole pattern instance. This keeps HLO size O(pattern) instead
of O(depth) — a hard requirement for 512-way SPMD compiles of 88-layer models
on this host. A non-divisible depth remainder (e.g. recurrentgemma's 26 = 3x8
+ 2) is applied as unstacked "tail" layers after the scan. A MoE model's
``first_k_dense`` leading layers, with a dense MLP, run unstacked as "lead"
layers before it (``params["lead"]`` and ``cache["lead"]`` exist only then).

Modes:
  train   — full-seq forward, logits (+ MoE aux losses)
  prefill — full-seq forward + populated caches
  decode  — single-token step against caches
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mla
from repro.models import rglru as rg
from repro.models import xlstm as xl
from repro.models.layers import (activation, apply_norm, dense, embed,
                                 init_dense, init_embedding, init_norm, mlp,
                                 init_mlp, softcap, unembed)

Constrain = Callable[[jnp.ndarray, str], jnp.ndarray]
_ident: Constrain = lambda x, kind: x


def _pattern_split(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    p = cfg.block_pattern
    lead = cfg.first_k_dense
    reps = (cfg.num_layers - lead) // len(p)
    tail = cfg.layer_kinds()[lead + reps * len(p):]
    return reps, tail


def _lead(cfg: ModelConfig) -> Tuple[str, ...]:
    return cfg.layer_kinds()[:cfg.first_k_dense]


def _split_experts(cfg: ModelConfig, stacked):
    """(the layer scan's xs, expert weights kept whole). A dropless MoE's
    expert weights stay out of the scanned xs, with each layer's index in
    their place: its grouped matmul reads them from the whole stack, where
    a scanned slice would be copied for it a layer at a time."""
    if cfg.moe is None or cfg.moe.capacity_factor is not None:
        return stacked, {}
    from repro.models.moe import EXPERT_WEIGHTS
    xs, whole = {}, {}
    for j, b in stacked.items():
        xs[j] = b
        if "moe" in b:
            moe = {k: v for k, v in b["moe"].items() if k not in EXPERT_WEIGHTS}
            reps = b["moe"]["router"]["kernel"].shape[0]
            xs[j] = dict(b, moe=dict(moe, layer=jnp.arange(reps, dtype=jnp.int32)))
            whole[j] = {k: b["moe"][k] for k in EXPERT_WEIGHTS}
    return xs, whole


def _join_experts(rep_params, whole):
    return {j: dict(b, moe=dict(b["moe"], **whole[j])) if j in whole else b
            for j, b in rep_params.items()}


# the decode step's routing counts, summed into cache["moe_stats"]
MOE_STATS = ("moe_hit", "moe_max_rows", "moe_kernel_layers")
# prefill runs a MLA layer's MLP or MoE over blocks of this many rows, so
# that a long batch's expert and MLP activations fit beside the weights
PREFILL_ROW_BLOCK = 8192


# ------------------------------------------------------------------ blocks


def init_block(kind: str, key, cfg: ModelConfig, dtype, *,
               dense_mlp: bool = False):
    k1, k2, k3 = jax.random.split(key, 3)
    p: Dict[str, Any] = {"pre_norm": init_norm(cfg.norm, cfg.d_model)}
    if kind in ("attn", "local", "mla"):
        p["mixer"] = (mla.init_mla(k1, cfg, dtype) if kind == "mla"
                      else attn.init_attention(k1, cfg, dtype))
        p["mlp_norm"] = init_norm(cfg.norm, cfg.d_model)
        if cfg.moe is not None and not dense_mlp:
            p["moe"] = init_moe_lazy(k2, cfg, dtype)
        else:
            p["mlp"] = init_mlp(k2, cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = rg.init_rglru_block(k1, cfg, dtype)
        p["mlp_norm"] = init_norm(cfg.norm, cfg.d_model)
        p["mlp"] = init_mlp(k2, cfg, dtype)
    elif kind == "mlstm":
        p["mixer"] = xl.init_mlstm_block(k1, cfg, dtype)
    elif kind == "slstm":
        p["mixer"] = xl.init_slstm_block(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    return p


def init_moe_lazy(key, cfg, dtype):
    from repro.models.moe import init_moe
    return init_moe(key, cfg, dtype)


def _theta(cfg: ModelConfig, kind: str) -> float:
    if kind == "local" and cfg.rope_theta_local:
        return cfg.rope_theta_local
    return cfg.rope_theta


def _by_row_blocks(fn, x, block: int):
    """``fn`` over blocks of ``block`` rows of x [B, S, D] (the rows padded
    to a whole number of blocks and the pad dropped after): the same
    result as ``fn(x)`` for a row-wise ``fn``, with a block's activations
    live at a time."""
    B, S, D = x.shape
    n = B * S
    if n <= block:
        return fn(x)
    nb = -(-n // block)
    xs = jnp.pad(x.reshape(n, D), ((0, nb * block - n), (0, 0)))
    ys = jax.lax.map(lambda r: fn(r[None])[0], xs.reshape(nb, block, D))
    return ys.reshape(nb * block, -1)[:n].reshape(B, S, -1)


def apply_block(kind: str, p, x, cfg: ModelConfig, *, mode: str,
                positions=None, cache=None, cur_pos=None,
                constrain: Constrain = _ident, moe_groups: int = 1,
                max_len: int = 0):
    """Returns (x, aux, new_cache).

    In decode mode an attention block leaves its cache as it is and returns,
    in place of a new cache, the token's rows for ``attn.write_kv_rows``
    (``mla.write_latent_rows``); recurrent blocks return their whole
    (small) new state. A MoE block's aux holds its losses outside decode,
    its routing counts (``MOE_STATS``) in decode.
    """
    act = activation(cfg.act)
    aux: Dict[str, jnp.ndarray] = {}
    new_cache = None
    h = apply_norm(p["pre_norm"], x)
    window = cfg.window_size if kind == "local" else 0

    if kind == "mla":
        if mode == "train":
            y = mla.mla_forward(p["mixer"], h, cfg, positions)
        elif mode == "prefill":
            y, new_cache = mla.mla_prefill(p["mixer"], h, cfg, positions,
                                           max_len=max_len)
        else:
            y, new_cache = mla.mla_decode(p["mixer"], h, cache, cfg, cur_pos)
        x = constrain(x + y, "residual")
        h2 = apply_norm(p["mlp_norm"], x)
        if "moe" in p:
            from repro.models.moe import moe_forward
            ffn = lambda t: moe_forward(p["moe"], t, cfg)  # noqa: E731
        else:
            ffn = lambda t: (mlp(p["mlp"], t, cfg.act), {})  # noqa: E731
        if mode == "prefill":
            y2 = _by_row_blocks(lambda t: ffn(t)[0], h2, PREFILL_ROW_BLOCK)
        else:
            y2, aux = ffn(h2)
        x = x + y2
    elif kind in ("attn", "local"):
        theta = _theta(cfg, kind)
        if mode == "train":
            y = attn.attention_forward(p["mixer"], h, cfg, positions,
                                       window=window, theta=theta)
        elif mode == "prefill":
            y, new_cache = attn.attention_prefill(p["mixer"], h, cfg, positions,
                                                  window=window, theta=theta,
                                                  max_len=max_len)
        else:
            y, new_cache = attn.attention_decode(p["mixer"], h, cache, cfg,
                                                 cur_pos, window=window,
                                                 theta=theta)
        x = x + y
        x = constrain(x, "residual")
        h2 = apply_norm(p["mlp_norm"], x)
        if cfg.moe is not None:
            from repro.models.moe import moe_forward
            y2, aux = moe_forward(p["moe"], h2, cfg, num_groups=moe_groups,
                                  constrain=constrain)
        else:
            y2 = mlp(p["mlp"], h2, cfg.act)
        x = x + y2
    elif kind == "rglru":
        if mode == "train":
            y = rg.rglru_block_forward(p["mixer"], h, cfg, act)
        elif mode == "prefill":
            y, new_cache = rg.rglru_block_prefill(p["mixer"], h, cfg, act)
        else:
            y, new_cache = rg.rglru_block_decode(p["mixer"], h, cache, cfg, act)
        x = x + y
        x = constrain(x, "residual")
        h2 = apply_norm(p["mlp_norm"], x)
        x = x + mlp(p["mlp"], h2, cfg.act)
    elif kind == "mlstm":
        if mode == "train":
            y = xl.mlstm_block_forward(p["mixer"], h, cfg)
        elif mode == "prefill":
            y, new_cache = xl.mlstm_block_prefill(p["mixer"], h, cfg)
        else:
            y, new_cache = xl.mlstm_block_decode(p["mixer"], h, cache, cfg)
        x = x + y
    elif kind == "slstm":
        if mode == "train":
            y = xl.slstm_block_forward(p["mixer"], h, cfg, act)
        elif mode == "prefill":
            y, st = xl.slstm_block_forward(p["mixer"], h, cfg, act,
                                           return_state=True)
            new_cache = st
        else:
            y, new_cache = xl.slstm_block_decode(p["mixer"], h, cache, cfg, act)
        x = x + y
    else:
        raise ValueError(kind)
    x = constrain(x, "residual")
    aux = {k: v for k, v in aux.items() if (k in MOE_STATS) == (mode == "decode")}
    return x, aux, new_cache


def init_block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16, abstract: bool = False):
    if kind == "attn":
        return attn.init_kv_cache(cfg, batch, max_len, window=0, dtype=dtype,
                                  abstract=abstract)
    if kind == "mla":
        return mla.init_latent_cache(cfg, batch, max_len, dtype=dtype,
                                     abstract=abstract)
    if kind == "local":
        return attn.init_kv_cache(cfg, batch, max_len, window=cfg.window_size,
                                  dtype=dtype, abstract=abstract)
    if kind == "rglru":
        return rg.init_rglru_cache(cfg, batch, dtype=dtype, abstract=abstract)
    if kind == "mlstm":
        return xl.init_mlstm_cache(cfg, batch, dtype=dtype, abstract=abstract)
    if kind == "slstm":
        return xl.init_slstm_cache(cfg, batch, abstract=abstract)
    raise ValueError(kind)


# ------------------------------------------------------------------ model


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    reps, tail = _pattern_split(cfg)
    keys = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_norm(cfg.norm, cfg.d_model),
    }
    if cfg.num_codebooks > 0:
        params["head"] = init_dense(keys[1], cfg.d_model,
                                    cfg.num_codebooks * cfg.vocab_size,
                                    dtype=dtype)
    elif not cfg.tie_embeddings:
        params["head"] = init_dense(keys[1], cfg.d_model, cfg.vocab_size,
                                    dtype=dtype)

    bkeys = jax.random.split(keys[2], max(reps, 1) * len(cfg.block_pattern))
    repeats: Dict[str, Any] = {}
    for j, kind in enumerate(cfg.block_pattern):
        per_rep = [init_block(kind, bkeys[r * len(cfg.block_pattern) + j],
                              cfg, dtype) for r in range(reps)]
        repeats[f"b{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep) \
            if reps > 1 else jax.tree.map(lambda v: v[None], per_rep[0])
    params["repeats"] = repeats
    tkeys = jax.random.split(keys[3], max(len(tail), 1))
    params["tail"] = {f"t{j}": init_block(kind, tkeys[j], cfg, dtype)
                      for j, kind in enumerate(tail)}
    if cfg.first_k_dense:
        lkeys = jax.random.split(jax.random.fold_in(key, 1), cfg.first_k_dense)
        params["lead"] = {f"l{j}": init_block(kind, lkeys[j], cfg, dtype,
                                              dense_mlp=True)
                          for j, kind in enumerate(_lead(cfg))}
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, abstract: bool = False):
    reps, tail = _pattern_split(cfg)
    cache: Dict[str, Any] = {"repeats": {}, "tail": {}}
    for j, kind in enumerate(cfg.block_pattern):
        one = init_block_cache(kind, cfg, batch, max_len, dtype, abstract)
        if abstract:
            cache["repeats"][f"b{j}"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((reps,) + s.shape, s.dtype), one)
        else:
            cache["repeats"][f"b{j}"] = jax.tree.map(
                lambda v: jnp.broadcast_to(v[None], (reps,) + v.shape).copy(), one)
    for j, kind in enumerate(tail):
        cache["tail"][f"t{j}"] = init_block_cache(kind, cfg, batch, max_len,
                                                  dtype, abstract)
    if cfg.first_k_dense:
        cache["lead"] = {f"l{j}": init_block_cache(kind, cfg, batch, max_len,
                                                   dtype, abstract)
                         for j, kind in enumerate(_lead(cfg))}
    if cfg.moe is not None:
        cache["moe_stats"] = (jax.ShapeDtypeStruct((len(MOE_STATS),), jnp.int32)
                              if abstract else _no_stats())
    return cache


def _no_stats():
    return jnp.zeros((len(MOE_STATS),), jnp.int32)


def _embed_in(params, batch_in, cfg: ModelConfig, compute_dtype):
    if cfg.input_mode == "embeddings":
        x = batch_in.astype(compute_dtype)
    else:
        x = embed(params["embed"], batch_in, compute_dtype)
    if cfg.tie_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    return x


def _head_out(params, x, cfg: ModelConfig):
    B, S, _ = x.shape
    if cfg.num_codebooks > 0:
        logits = dense(params["head"], x).reshape(
            B, S, cfg.num_codebooks, cfg.vocab_size)
    elif cfg.tie_embeddings:
        logits = unembed(params["embed"], x)
    else:
        logits = dense(params["head"], x)
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return logits


def _sum_aux(a: Dict, b: Dict) -> Dict:
    out = dict(a)
    for k, v in b.items():
        out[k] = out.get(k, 0.0) + v
    return out


def forward(params, batch_in, cfg: ModelConfig, *, constrain: Constrain = _ident,
            remat: str = "none", moe_groups: int = 1):
    """Train-mode forward: logits [B,S,V] (or [B,S,C,V]), aux losses."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = _embed_in(params, batch_in, cfg, compute_dtype)
    B, S = x.shape[:2]
    positions = jnp.arange(S, dtype=jnp.int32)
    reps, tail = _pattern_split(cfg)
    pattern = cfg.block_pattern
    lead_aux: Dict[str, jnp.ndarray] = {}
    for j, kind in enumerate(_lead(cfg)):
        x, a, _ = apply_block(kind, params["lead"][f"l{j}"], x, cfg,
                              mode="train", positions=positions,
                              constrain=constrain, moe_groups=moe_groups)
        lead_aux = _sum_aux(lead_aux, a)

    xs, whole = _split_experts(cfg, params["repeats"])

    def rep_body(xc, rep_params):
        rep_params = _join_experts(rep_params, whole)
        aux = {}
        for j, kind in enumerate(pattern):
            xc, a, _ = apply_block(kind, rep_params[f"b{j}"], xc, cfg,
                                   mode="train", positions=positions,
                                   constrain=constrain, moe_groups=moe_groups)
            aux = _sum_aux(aux, a)
        # fixed key-set for scan ys
        return xc, {k: aux.get(k, jnp.float32(0.0))
                    for k in ("moe_lb", "moe_z")}

    body = rep_body
    if remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat == "dots" else None)
        body = jax.checkpoint(rep_body, policy=policy, prevent_cse=False)

    x, auxs = jax.lax.scan(body, x, xs)
    aux = _sum_aux(lead_aux, {k: jnp.sum(v) for k, v in auxs.items()})
    for j, kind in enumerate(tail):
        x, a, _ = apply_block(kind, params["tail"][f"t{j}"], x, cfg,
                              mode="train", positions=positions,
                              constrain=constrain, moe_groups=moe_groups)
        aux = _sum_aux(aux, a)
    x = apply_norm(params["final_norm"], x)
    return _head_out(params, x, cfg), aux


def prefill(params, batch_in, cfg: ModelConfig, *, constrain: Constrain = _ident,
            moe_groups: int = 1, max_len: int = 0):
    """Prefill: returns (logits of last position [B,V...], cache)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = _embed_in(params, batch_in, cfg, compute_dtype)
    B, S = x.shape[:2]
    positions = jnp.arange(S, dtype=jnp.int32)
    reps, tail = _pattern_split(cfg)
    pattern = cfg.block_pattern
    lead_caches = {}
    for j, kind in enumerate(_lead(cfg)):
        x, _, lead_caches[f"l{j}"] = apply_block(
            kind, params["lead"][f"l{j}"], x, cfg, mode="prefill",
            positions=positions, constrain=constrain, moe_groups=moe_groups,
            max_len=max_len)

    xs, whole = _split_experts(cfg, params["repeats"])

    def rep_body(xc, rep_params):
        rep_params = _join_experts(rep_params, whole)
        caches = {}
        for j, kind in enumerate(pattern):
            xc, _, c = apply_block(kind, rep_params[f"b{j}"], xc, cfg,
                                   mode="prefill", positions=positions,
                                   constrain=constrain, moe_groups=moe_groups,
                                   max_len=max_len)
            caches[f"b{j}"] = c
        return xc, caches

    x, rep_caches = jax.lax.scan(rep_body, x, xs)
    cache = {"repeats": rep_caches, "tail": {}}
    for j, kind in enumerate(tail):
        x, _, c = apply_block(kind, params["tail"][f"t{j}"], x, cfg,
                              mode="prefill", positions=positions,
                              constrain=constrain, moe_groups=moe_groups,
                              max_len=max_len)
        cache["tail"][f"t{j}"] = c
    if cfg.first_k_dense:
        cache["lead"] = lead_caches
    if cfg.moe is not None:
        cache["moe_stats"] = _no_stats()
    x = apply_norm(params["final_norm"], x)
    logits = _head_out(params, x[:, -1:], cfg)
    return logits[:, 0], cache


def _write_decode(kind: str, cache, update, cur_pos, *, lead: int = 0):
    """A block's cache after one decode step: an attention block's rows
    written into its cache at the step's slot, a recurrent block's state
    as returned."""
    if kind in ("attn", "local"):
        return attn.write_kv_rows(cache, update, cur_pos, lead=lead)
    if kind == "mla":
        return mla.write_latent_rows(cache, update, cur_pos, lead=lead)
    return update


def _stats(aux: Dict) -> jnp.ndarray:
    return jnp.stack([jnp.asarray(aux.get(k, 0), jnp.int32)
                      for k in MOE_STATS])


def decode_step(params, cache, tokens, cur_pos, cfg: ModelConfig, *,
                constrain: Constrain = _ident, moe_groups: int = 1):
    """One decode step.

    tokens: [B, 1] token ids (or [B, 1, D] embeddings for embedding-input
    archs); cur_pos: scalar int32 (current position, uniform across batch).
    Returns (logits [B, V...], new_cache).

    The layer scan only reads the KV caches and emits each attention
    layer's new rows; one write per leaf after the scan puts them in
    place. Under a jit that donates ``cache`` the step thus reads the cache
    once and writes one position, with no copy of it. A MoE model's
    ``cache["moe_stats"]`` gains the step's routing counts, summed over
    its MoE layers.
    """
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = _embed_in(params, tokens, cfg, compute_dtype)
    reps, tail = _pattern_split(cfg)
    pattern = cfg.block_pattern
    new_cache = {"repeats": {}, "tail": {}}
    moe = cfg.moe is not None
    stats = [cache["moe_stats"]] if moe else []

    def unstacked(group, prefix, kinds, x):
        for j, kind in enumerate(kinds):
            c = cache[group][f"{prefix}{j}"]
            x, a, u = apply_block(kind, params[group][f"{prefix}{j}"], x,
                                  cfg, mode="decode", cache=c,
                                  cur_pos=cur_pos, constrain=constrain,
                                  moe_groups=moe_groups)
            new_cache[group][f"{prefix}{j}"] = _write_decode(kind, c, u,
                                                            cur_pos)
            if moe:
                stats.append(_stats(a))
        return x

    if cfg.first_k_dense:
        new_cache["lead"] = {}
        x = unstacked("lead", "l", _lead(cfg), x)

    xs, whole = _split_experts(cfg, params["repeats"])

    def rep_body(xc, inp):
        rep_params, rep_cache = inp
        rep_params = _join_experts(rep_params, whole)
        updates, aux = {}, {}
        for j, kind in enumerate(pattern):
            xc, a, u = apply_block(kind, rep_params[f"b{j}"], xc, cfg,
                                   mode="decode", cache=rep_cache[f"b{j}"],
                                   cur_pos=cur_pos, constrain=constrain,
                                   moe_groups=moe_groups)
            updates[f"b{j}"] = u
            aux = _sum_aux(aux, a)
        return xc, (updates, _stats(aux) if moe else None)

    x, (rep_updates, rep_stats) = jax.lax.scan(
        rep_body, x, (xs, cache["repeats"]))
    if moe:
        stats.append(jnp.sum(rep_stats, axis=0))
    new_cache["repeats"] = {
        f"b{j}": _write_decode(kind, cache["repeats"][f"b{j}"],
                               rep_updates[f"b{j}"], cur_pos, lead=1)
        for j, kind in enumerate(pattern)}
    x = unstacked("tail", "t", tail, x)
    if moe:
        new_cache["moe_stats"] = sum(stats)
    x = apply_norm(params["final_norm"], x)
    logits = _head_out(params, x, cfg)
    return logits[:, 0], new_cache
