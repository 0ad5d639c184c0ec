"""Traffic kind ``serve_open_loop``: the Web department's requests, offered
on an open loop to the program's ``ContinuousBatcher`` and ``ServingPool``
on one chip.

Set-up makes the weights on the chip from the seed, builds the pool, and
compiles every (batch size, class) program the traffic can produce: each
class's prefill and decode step at batch sizes 1..max_batch, in threads,
plus the small per-token programs ``Replica.generate`` runs between them.
The window then does what ``launch/serve.serve_queue`` does, round by
round, except that requests are admitted when they are due: at each round
boundary every request due by then joins the queue; with nothing queued
the loop sleeps until the next one is due. After the last request due in
the window has its tokens on the host, the window closes.

Latency is timed from when a request was due to when its round returned
its tokens. ``correct`` compares a seeded sample of the served answers
with the plain reference (see ``check``).
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import gc
import time
import traceback
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import arrivals, trace_reduce
from benchmarks.chip.references import llama
from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.runtime.serving_pool import ServingPool
from repro.serving.batching import ContinuousBatcher, Request

ROUND, ADMIT, WAIT, WINDOW = ("bench.round", "bench.admit",
                              "bench.wait_arrival", "bench.window")


# ------------------------------------------------------------ the program


def program_config(sizes: dict):
    """The program's ``ModelConfig`` for a LLaMA-style configuration file."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    if sizes.get("hidden_act", "silu") != "silu" or sizes.get(
            "tie_word_embeddings"):
        raise ValueError("serve_open_loop runs untied SiLU decoders only")
    return ModelConfig(
        name=sizes["name"], family="dense",
        num_layers=sizes["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=sizes.get("num_key_value_heads") or h,
        head_dim=sizes.get("head_dim") or d // h,
        d_ff=sizes["intermediate_size"], vocab_size=sizes["vocab_size"],
        block_pattern=("attn",), act="silu", rope_theta=sizes["rope_theta"],
        param_dtype=sizes["torch_dtype"], compute_dtype=sizes["torch_dtype"])


def program_params(w: dict) -> dict:
    """The reference's weights in the layout of ``repro.models.model``: one
    scanned block kind, its leaves stacked over the layers."""
    k = lambda a: {"kernel": a}  # noqa: E731
    return {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "head": k(w["head"]),
        "repeats": {"b0": {
            "pre_norm": {"scale": w["attn_norm"]},
            "mixer": {"wq": k(w["wq"]), "wk": k(w["wk"]), "wv": k(w["wv"]),
                      "wo": k(w["wo"])},
            "mlp_norm": {"scale": w["mlp_norm"]},
            "mlp": {"wi_gate": k(w["w_gate"]), "wi_up": k(w["w_up"]),
                    "wo": k(w["w_down"])},
        }},
        "tail": {},
    }


def weight_key(seed: int):
    """A JAX key from any whole number, wider than 32 bits included."""
    state = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


def make_weights(sizes: dict, seed: int, device, layout):
    """All weights in one jitted call on ``device``, in the served type."""
    dtype = jnp.dtype(sizes["torch_dtype"])
    fn = jax.jit(lambda key: layout(llama.init_weights(key, sizes, dtype)))
    with jax.default_device(device):
        return jax.block_until_ready(fn(weight_key(seed)))


# ------------------------------------------------------------- traffic


@dataclasses.dataclass
class Served:
    req_id: int
    cls: int
    due: float
    finish: float = float("nan")
    failed: bool = False


def make_requests(traffic: dict, seconds: float, seed: int, vocab: int):
    """(requests for the batcher, their records), due times from the seed."""
    rng = np.random.default_rng(seed)
    classes = traffic["classes"]
    sched = arrivals.poisson_schedule(
        traffic["rate_per_s"], seconds, [c["share"] for c in classes], rng)
    reqs, recs = [], []
    for i, (due, c) in enumerate(sched):
        prompt = rng.integers(0, vocab, classes[c]["prompt_tokens"],
                              dtype=np.int32)
        reqs.append(Request(i, prompt, classes[c]["answer_tokens"],
                            arrival=due))
        recs.append(Served(i, c, due))
    return reqs, recs


# --------------------------------------------------------------- warm-up


def warm(replica, classes, max_batch: int, log, threads: int = 8):
    """Compile every program the traffic can make ``replica`` run: for each
    class and batch size, the prefill and the decode step ahead of time in
    ``threads`` threads (the calls in the window find them), then the small
    per-token programs of ``Replica.generate`` by running them once."""
    from jax.sharding import SingleDeviceSharding
    dev = replica.device
    one = SingleDeviceSharding(dev)

    def compile_pair(b, c):
        S, new = c["prompt_tokens"], c["answer_tokens"]
        prompt = jax.ShapeDtypeStruct((b, S), jnp.int32, sharding=one)
        low = replica._prefill.lower(replica.params, prompt, S + new)
        low.compile()
        cache = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            low.out_info[1])
        replica._decode.lower(
            replica.params, cache,
            jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((), jnp.int32)).compile()

    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        futs = [pool.submit(compile_pair, b, c)
                for c in classes for b in range(1, max_batch + 1)]
        for f in futs:
            f.result()
    V = replica.cfg.vocab_size
    for b in range(1, max_batch + 1):
        logits = jax.device_put(np.zeros((b, V), np.float32), dev)
        tok = jnp.argmax(logits, axis=-1)
        np.asarray(tok[:, None])
    log(f"[serve] warmed {len(futs)} (batch, class) prefill/decode pairs")


# ---------------------------------------------------------------- window


def serve_window(pool, batcher, reqs, recs, log):
    """Offer ``reqs`` when due; returns the rounds run, each with its batch,
    class shape and host seconds, and fills each record's finish time."""
    t0 = time.perf_counter()
    rounds, i, n, left = [], 0, len(reqs), len(reqs)
    by_id = {r.req_id: r for r in recs}
    while left:
        now = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation(ADMIT):
            while i < n and reqs[i].arrival <= now:
                batcher.submit(reqs[i])
                i += 1
        if not batcher.queue:
            with jax.profiler.TraceAnnotation(WAIT):
                time.sleep(max(0.0, reqs[i].arrival - now))
            continue
        rnd = batcher.next_round()
        start = time.perf_counter() - t0
        failed = False
        with jax.profiler.TraceAnnotation(ROUND):
            try:
                batcher.run_round(rnd, pool.submit, now=start)
            except Exception:           # a failed round fails its requests
                log(traceback.format_exc())
                failed = True
        end = time.perf_counter() - t0
        for r in rnd:
            by_id[r.req_id].finish = end
            by_id[r.req_id].failed = failed
        left -= len(rnd)
        rounds.append({"batch": len(rnd), "prompt": len(rnd[0].prompt),
                       "max_new": rnd[0].max_new, "start": start,
                       "end": end, "failed": failed})
    return rounds, time.perf_counter() - t0


# ----------------------------------------------------------------- check


def check_sample(completed, recs, n: int, seed: int) -> list:
    """A seeded sample of ``n`` served requests that holds the one with the
    longest sequence and the one with the most served tokens."""
    ok = [r for r in completed if not recs[r.req_id].failed]
    if not ok:
        return []
    longest = max(ok, key=lambda r: (len(r.prompt) + r.max_new, -r.req_id))
    most = max(ok, key=lambda r: (r.max_new, -r.req_id))
    first = {longest.req_id: longest, most.req_id: most}
    rest = [r for r in ok if r.req_id not in first]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(len(rest), max(0, n - len(first))),
                      replace=False)
    return list(first.values()) + [rest[j] for j in sorted(pick)]


def logit_gaps(w, sizes, sample, quant=None, rows: int = 4):
    """For each sampled request, the gap by which each served token's logit
    lies below the reference's best at that position, and, with ``quant``,
    the gap of the token that the reference at that precision puts first.
    Runs blocks of ``rows`` requests of one shape (the last block padded
    with repeats, so each shape compiles once). An answer of the wrong
    length or with a token outside the vocabulary reads an infinite gap."""
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
        ref = jax.jit(lambda w, t: llama.logits(w, t, sizes, S - 1))
        low = (jax.jit(lambda w, t: llama.logits(w, t, sizes, S - 1, quant))
               if quant else None)
        for j in range(0, len(rs), rows):
            blk = rs[j:j + rows]
            k = len(blk)
            blk = blk + blk[-1:] * (rows - k)
            served = np.stack([d for _, d in blk])
            seq = np.concatenate([np.stack([p for p, _ in blk]),
                                  served[:, :-1]], 1).astype(np.int32)
            lg = ref(w, seq)
            top = jnp.max(lg, -1)
            mine = jnp.take_along_axis(lg, jnp.asarray(served)[..., None],
                                       -1)[..., 0]
            served_gaps.append(np.asarray(top - mine)[:k])
            if low is not None:
                pick = jnp.argmax(low(w, seq), -1)
                theirs = jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
                quant_gaps.append(np.asarray(top - theirs)[:k])
    return served_gaps, quant_gaps


def widest(gaps) -> float:
    vals = [float(np.max(g)) for g in gaps if np.size(g)]
    return max(vals) if vals else float("inf")


# ------------------------------------------------------------------- run


@dataclasses.dataclass
class ServeRun:
    """What the per-layer readers read."""
    sizes: dict
    peaks: dict
    max_batch: int
    rounds: List[dict]
    compiles_in_window: int
    trace: Optional[trace_reduce.Trace] = None
    chips: tuple = (0,)
    window_ns: tuple = (0, 0)
    phases: Optional[List[dict]] = None     # per round, from the trace

    def decode_steps(self):
        """(batch, position of the new token) of every decode step run."""
        for rd in self.rounds:
            for i in range(rd["max_new"] - 1):
                yield rd["batch"], rd["prompt"] + i


def attribute_rounds(trace, rounds, chip):
    """Split each round's device time into its prefill (the round's first
    program execution: ``Replica.generate`` starts with it) and its decode
    phase (everything the device ran after it until the round returned).
    None when the trace's round spans do not match the rounds run."""
    spans = trace_reduce.spans(trace, ROUND)
    if len(spans) != len(rounds):
        return None
    busy = trace_reduce.busy_intervals(trace, chip)
    phases = []
    for sp, rd in zip(spans, rounds):
        mods = trace_reduce.modules_in(trace, chip, sp.start, sp.end)
        if not mods:
            return None
        pre = mods[0]
        dec = sum(e - s for s, e in trace_reduce.clip(busy, pre.end, sp.end))
        phases.append({"prefill_ns": pre.dur, "decode_ns": dec,
                       "modules": mods, "steps": rd["max_new"] - 1})
    return phases


def device_ops(phases, top: int = 10) -> list:
    """Device seconds by (phase, program name), the largest first."""
    tot: Dict[str, float] = {}
    for ph in phases:
        for j, m in enumerate(ph["modules"]):
            key = ("prefill:" if j == 0 else "decode:") + \
                trace_reduce.program_name(m.name)
            tot[key] = tot.get(key, 0.0) + m.dur / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])][:top]


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


def summarize(traffic, recs, span_s: float, log):
    """End-to-end numbers of a window: the 95th percentile of every
    request's latency (a failed one counts as waiting to the window's end)
    and the output tokens completed over the time until the last was."""
    lat = np.array([(r.finish - r.due) if not r.failed
                    else span_s - r.due for r in recs])
    failed = sum(r.failed for r in recs)
    tokens = sum(traffic["classes"][r.cls]["answer_tokens"]
                 for r in recs if not r.failed)
    last = max(r.finish for r in recs)
    e2e = {"ws_latency_p95_s": float(np.percentile(lat, 95)),
           "ws_tokens_per_s": tokens / last}
    log(f"[serve] {len(recs)} requests ({failed} failed), last done at "
        f"{last:.3f} s; latency p50 {np.percentile(lat, 50):.3f} s p95 "
        f"{e2e['ws_latency_p95_s']:.3f} s max {lat.max():.3f} s; "
        f"{e2e['ws_tokens_per_s']:.1f} tokens/s")
    return e2e, failed, lat


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


def run(ctx) -> dict:
    """One run of a ``serve_open_loop`` cell; see ``benchmarks/chip/run.py``
    for ``ctx`` and for what the returned dict holds."""

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
                f"{out.phases is not None}; decode steps bound by memory: "
                f"{bound_count(out)}")

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


def bound_count(run: ServeRun) -> str:
    """How many of the window's decode steps the roofline puts on memory."""
    from benchmarks.chip import flops
    bounds = [flops.least_seconds(flops.decode_flops(run.sizes, b, pos),
                                  flops.decode_bytes(run.sizes, b, pos),
                                  run.peaks)[1]
              for b, pos in run.decode_steps()]
    return f"{bounds.count('memory')} of {len(bounds)}"
