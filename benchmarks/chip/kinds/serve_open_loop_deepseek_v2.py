"""Traffic kind ``serve_open_loop_deepseek_v2``: ``serve_open_loop`` for a
DeepSeek-V2 configuration (latent attention, a leading dense layer, then
dropless routed and shared experts), on one chip.

The window, the traffic, the warm-up, the sample that is checked and the
summary are ``serve_open_loop``'s own functions; this module gives them the
program's ``ModelConfig`` for a DeepSeek-V2 configuration file, its weights
from ``references/deepseek_v2.py`` in the program's layout, and the
comparison with that reference. A program without latent attention fails
at import (``repro.models.mla``), before it touches the chip.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import flops, flops_deepseek_v2, trace_reduce
from benchmarks.chip.kinds.serve_open_loop import (WINDOW, ServeRun,
                                                   attribute_rounds,
                                                   check_sample, device_ops,
                                                   make_requests,
                                                   serve_window, summarize,
                                                   warm, weight_key, widest)
from benchmarks.chip.references import deepseek_v2 as ref
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnScaling
from repro.models import mla  # noqa: F401  (the program has latent attention)
from repro.models import model as M
from repro.runtime.serving_pool import ServingPool
from repro.serving.batching import ContinuousBatcher

# published settings the program computes as stated and does not take as
# options: a different value is another model
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
         "q_lora_rank": None, "scoring_func": "softmax",
         "topk_method": "greedy", "routed_scaling_factor": 1,
         "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
         "attention_bias": False}


# ------------------------------------------------------------ the program


def program_config(sizes: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a DeepSeek-V2 configuration file."""
    for k, v in FIXED.items():
        if sizes.get(k, v) != v:
            raise ValueError(f"{k}={sizes[k]!r}: this kind runs {k}={v!r}")
    y = sizes.get("rope_scaling")
    if y and y.get("type") != "yarn":
        raise ValueError(f"rope_scaling {y!r}: this kind runs YaRN only")
    h = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return ModelConfig(
        name=sizes["name"], family="moe",
        num_layers=sizes["num_hidden_layers"], d_model=sizes["hidden_size"],
        num_heads=h, num_kv_heads=h, head_dim=qk,
        d_ff=sizes["intermediate_size"], vocab_size=sizes["vocab_size"],
        block_pattern=("mla",), act="silu", rope_theta=sizes["rope_theta"],
        first_k_dense=sizes["first_k_dense_replace"],
        mla=MLAConfig(
            kv_lora_rank=sizes["kv_lora_rank"],
            qk_nope_head_dim=sizes["qk_nope_head_dim"],
            qk_rope_head_dim=sizes["qk_rope_head_dim"],
            v_head_dim=sizes["v_head_dim"],
            rope_scaling=YarnScaling(
                factor=y["factor"],
                original_max_position_embeddings=y[
                    "original_max_position_embeddings"],
                beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
                mscale=y["mscale"], mscale_all_dim=y["mscale_all_dim"])
            if y else None),
        moe=MoEConfig(num_experts=sizes["n_routed_experts"],
                      top_k=sizes["num_experts_per_tok"],
                      d_ff_expert=sizes["moe_intermediate_size"],
                      capacity_factor=None,
                      num_shared_experts=sizes["n_shared_experts"],
                      norm_topk_prob=sizes["norm_topk_prob"]),
        param_dtype=sizes["torch_dtype"], compute_dtype=sizes["torch_dtype"])


def _block(lw: dict, moe: bool) -> dict:
    k = lambda a: {"kernel": a}  # noqa: E731
    b = {"pre_norm": {"scale": lw["attn_norm"]},
         "mixer": {"wq": k(lw["wq"]), "wkv_a": k(lw["wkv_a"]),
                   "kv_norm": {"scale": lw["kv_norm"]},
                   "wkv_b": k(lw["wkv_b"]), "wo": k(lw["wo"])},
         "mlp_norm": {"scale": lw["mlp_norm"]}}
    if moe:
        b["moe"] = {"router": k(lw["router"]), "wi_gate": lw["e_gate"],
                    "wi_up": lw["e_up"], "wo": lw["e_down"],
                    "shared": {"wi_gate": k(lw["s_gate"]),
                               "wi_up": k(lw["s_up"]),
                               "wo": k(lw["s_down"])}}
    else:
        b["mlp"] = {"wi_gate": k(lw["w_gate"]), "wi_up": k(lw["w_up"]),
                    "wo": k(lw["w_down"])}
    return b


def program_params(w: dict) -> dict:
    """The reference's weights in the layout of ``repro.models.model``: the
    dense layers unstacked as lead layers, the MoE layers one scanned block
    kind with its leaves stacked over the layers."""
    params = {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "head": {"kernel": w["head"]},
        "repeats": {"b0": _block(w["moe"], moe=True)},
        "tail": {},
    }
    n_dense = w["dense"]["wq"].shape[0]
    if n_dense:
        params["lead"] = {
            f"l{j}": _block(jax.tree.map(lambda a: a[j], w["dense"]), False)
            for j in range(n_dense)}
    return params


def make_weights(sizes: dict, seed: int, device, layout):
    """All weights in one jitted call on ``device``, in the served type."""
    dtype = jnp.dtype(sizes["torch_dtype"])
    fn = jax.jit(lambda key: layout(ref.init_weights(key, sizes, dtype)))
    with jax.default_device(device):
        return jax.block_until_ready(fn(weight_key(seed)))


def build(ctx, seed: int):
    """Weights from ``seed`` on the chip, checked against the program's
    layout, a one-replica pool holding them, and every program the traffic
    can make it run, compiled."""
    device = ctx.devices[0]
    cfg = program_config(ctx.sizes)
    params = make_weights(ctx.sizes, seed, device, program_params)
    want = jax.eval_shape(lambda k: M.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError("weights do not match the program's layout")
    pool = ServingPool(cfg, params, capacity_tokens_per_replica=float("inf"))
    pool.scale_to([device])
    warm(pool.replicas[0], ctx.traffic["classes"],
         ctx.traffic["max_batch"], ctx.log)
    return pool


# ----------------------------------------------------------------- check


def logit_gaps(w, sizes, sample, quant=None, rows: int = 4):
    """``serve_open_loop.logit_gaps`` with the DeepSeek-V2 reference: for
    each sampled request, the gap by which each served token's logit lies
    below the reference's best at that position, and, with ``quant``, the
    gap of the token that the reference at that precision puts first. An
    answer of the wrong length or with a token outside the vocabulary
    reads an infinite gap."""
    groups: Dict[tuple, list] = {}
    served_gaps, quant_gaps = [], []
    for r in sample:
        done = None if r.done is None else np.asarray(r.done, np.int64)
        if done is None or done.shape != (r.max_new,) or done.min() < 0 \
                or done.max() >= sizes["vocab_size"]:
            served_gaps.append(np.full((1, r.max_new), np.inf))
        else:
            groups.setdefault((len(r.prompt), r.max_new), []).append(
                (r.prompt, done))
    for (S, new), rs in sorted(groups.items()):
        full = jax.jit(lambda w, t: ref.logits(w, t, sizes, S - 1))
        low = (jax.jit(lambda w, t: ref.logits(w, t, sizes, S - 1, quant))
               if quant else None)
        for j in range(0, len(rs), rows):
            blk = rs[j:j + rows]
            k = len(blk)
            blk = blk + blk[-1:] * (rows - k)
            served = np.stack([d for _, d in blk])
            seq = np.concatenate([np.stack([p for p, _ in blk]),
                                  served[:, :-1]], 1).astype(np.int32)
            lg = full(w, seq)
            top = jnp.max(lg, -1)
            mine = jnp.take_along_axis(lg, jnp.asarray(served)[..., None],
                                       -1)[..., 0]
            served_gaps.append(np.asarray(top - mine)[:k])
            if low is not None:
                pick = jnp.argmax(low(w, seq), -1)
                theirs = jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
                quant_gaps.append(np.asarray(top - theirs)[:k])
    return served_gaps, quant_gaps


def check(ctx, seed: int, completed, recs, quant=None):
    """Sample the served requests and run the reference over them, with
    the program's state already freed. Returns (sample, served gaps,
    gaps of the ``quant`` control's first choices)."""
    sample = check_sample(completed, recs, ctx.traffic["check_requests"],
                          seed)
    t = time.perf_counter()
    w = make_weights(ctx.sizes, seed, ctx.devices[0], lambda w: w)
    gaps, qgaps = logit_gaps(w, ctx.sizes, sample, quant)
    del w
    ctx.log(f"[serve] reference over {len(sample)} sampled requests, "
            f"{sum(g.size for g in gaps)} served tokens: "
            f"{time.perf_counter() - t:.3f} s")
    return sample, gaps, qgaps


# ------------------------------------------------------------------- run


def bound_count(run: ServeRun) -> str:
    """How many of the window's decode steps the roofline puts on memory,
    counting each step's experts as all of them (an upper bound on bytes:
    a step bound by memory with every expert read may still be bound by
    compute with fewer)."""
    s = run.sizes
    every = (s["num_hidden_layers"] - s["first_k_dense_replace"]) \
        * s["n_routed_experts"]
    bounds = [flops.least_seconds(
        flops_deepseek_v2.decode_flops(s, b, pos),
        flops_deepseek_v2.decode_bytes(s, b, pos, every), run.peaks)[1]
        for b, pos in run.decode_steps()]
    return f"{bounds.count('memory')} of {len(bounds)}"


def run(ctx) -> dict:
    """One run of a ``serve_open_loop_deepseek_v2`` cell; see
    ``benchmarks/chip/run.py`` for ``ctx`` and for what the returned dict
    holds."""
    log, traffic = ctx.log, ctx.traffic
    device = ctx.devices[0]
    pool = build(ctx, ctx.seed)
    batcher = ContinuousBatcher(max_batch=traffic["max_batch"])
    reqs, recs = make_requests(traffic, ctx.seconds, ctx.seed,
                               ctx.sizes["vocab_size"])
    setup_s = time.perf_counter() - ctx.t_start
    log(f"[serve] set-up {setup_s:.3f} s; {len(reqs)} requests due in "
        f"{ctx.seconds} s at {traffic['rate_per_s']}/s")

    compiles0 = ctx.compiles.count
    tracer = ctx.tracer() if ctx.trace else None
    if tracer:
        tracer.start()
    with jax.profiler.TraceAnnotation(WINDOW):
        rounds, span_s = serve_window(pool, batcher, reqs, recs, log)
    trace = tracer.stop() if tracer else None
    compiles = ctx.compiles.count - compiles0
    memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    e2e, failed, _ = summarize(traffic, recs, span_s, log)
    e2e["setup_s"] = setup_s
    log(f"[serve] {len(rounds)} rounds; {compiles} compilations in the "
        f"window; memory peak {memory_peak} bytes")

    out = ServeRun(ctx.sizes, ctx.peaks, traffic["max_batch"], rounds,
                   compiles, chips=(device.id,))
    extra = {}
    if trace is not None:
        win = trace_reduce.spans(trace, WINDOW)
        if win:
            lo, hi = win[0].start, win[0].end
            out.trace, out.window_ns = trace, (lo, hi)
            out.phases = attribute_rounds(trace, rounds, device.id)
            busy = trace_reduce.mean_busy_s(trace, out.chips, lo, hi)
            extra["device"] = {"busy_s": busy, "window_s": (hi - lo) / 1e9}
            extra["breakdown"] = {
                "device_ops": device_ops(out.phases or []),
                "idle_gaps": trace_reduce.gaps_by_host(trace, out.chips,
                                                       lo, hi)}
            log(f"[serve] traced window {(hi - lo) / 1e9:.3f} s, device "
                f"busy {busy:.3f} s; rounds attributed: "
                f"{out.phases is not None}; decode steps bound by memory "
                f"with every expert read: {bound_count(out)}")

    # the check runs with the program's state freed: the pool holds the
    # weights and the batcher's requests hold only host arrays
    completed = batcher.completed
    del pool, batcher
    gc.collect()
    sample, gaps, _ = check(ctx, ctx.seed, completed, recs)
    gap = widest(gaps) if sample else float("inf")
    limit = ctx.limits["max_logit_gap"]
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = bool(gap <= limit and failed == 0)
    return {"correct": correct, "attempted": len(recs), "failed": failed,
            "end_to_end": e2e, "layers": out, "memory_peak_bytes": memory_peak,
            "checks": checks, **extra}
