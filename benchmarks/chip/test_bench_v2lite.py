"""The ``serve_open_loop_deepseek_v2`` kind end to end on the CPU at a
reduced size, its comparison with the plain DeepSeek-V2 reference passing
a sound program and failing planted faults and the fp8 control, the
operation and byte counts of ``flops_deepseek_v2.py`` against hand counts,
and the readers of the cell's per-layer metrics on a hand-made trace.

As in ``test_bench_serve.py``, the harness's look for a chip is skipped
(the kind's ``run`` is called directly); the configuration keeps the cell's
structure (a dense layer, then MoE layers with shared experts and raw
top-k gates, a latent cache) at a few widths, in float32.
"""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import flops_deepseek_v2 as F  # noqa: E402
from benchmarks.chip import run as R  # noqa: E402
from benchmarks.chip.kinds import serve_open_loop_deepseek_v2 as K  # noqa: E402
from repro.serving import spans  # noqa: E402

CELL = "serve-v2lite-docqa"
SEED = 2**33 + 12345            # wider than 32 bits, as benchmark seeds may be


def _ctx(width=64, layers=3, vocab=256, experts=8, answers=(6, 4)):
    bench = R.load_benchmark()
    cell, sizes, traffic, limits = R.cell_files(bench, CELL)
    sizes = dict(sizes, hidden_size=width, num_attention_heads=4,
                 intermediate_size=2 * width, kv_lora_rank=32,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 n_routed_experts=experts, num_experts_per_tok=2,
                 moe_intermediate_size=width // 2,
                 num_hidden_layers=layers, vocab_size=vocab,
                 torch_dtype="float32")
    # a burst, so that rounds hold several requests; the two classes fall
    # in different length buckets of the batcher (64 tokens), as the
    # cell's do, so no round pads a prompt
    traffic = dict(traffic, rate_per_s=300.0, max_batch=4, check_requests=12,
                   classes=[dict(traffic["classes"][0], prompt_tokens=16,
                                 answer_tokens=answers[0]),
                            dict(traffic["classes"][1], prompt_tokens=72,
                                 answer_tokens=answers[1])])
    return R.Ctx(cell, sizes, traffic, limits, SEED, 0.1, False,
                 jax.devices()[:1], R.peaks_for("TPU v5 lite"),
                 R.CompileLog(), time.perf_counter(), lambda m: None)


def test_sound_program_is_correct_and_compiles_nothing_in_the_window():
    spans.reset()
    out = K.run(_ctx())
    assert out["correct"], out["checks"]
    assert out["attempted"] == 30 and out["failed"] == 0
    assert out["checks"]["max_logit_gap"]["value"] < 1e-3
    run = out["layers"]
    assert run.compiles_in_window == 0
    assert max(r["batch"] for r in run.rounds) > 1
    c = spans.snapshot()
    assert c["serve.rounds"] == len(run.rounds)
    steps = c["serve.decode_steps"]
    # 2 MoE layers a step, 1 to 4 rows a step of top-2 over 8 experts
    assert 2 * steps <= c["serve.moe_experts_hit"] <= 2 * steps * 8
    assert 2 * steps <= c["serve.moe_max_expert_rows"] <= 2 * steps * 4
    # every per-layer metric of the cell: the counters' readers read the
    # run; with no trace on it, the device readers find nothing and say so
    bench = R.load_benchmark()
    cell = R.find(bench["workloads"], CELL, "workload")
    listed = {m["name"] for m in bench["per_layer"] if R.applies(m, cell, bench)}
    counted = {"batch_fill.serve", "compiles_in_window.serve",
               "queue_wait_s.batcher", "step_fill.replica"}
    traced = {"decode_step_ms.replica", "prefill_us_per_token.replica",
              "round_gap_ms.replica", "device_idle.serve",
              "decode_roofline.v2lite", "serve_mfu.v2lite"}
    assert listed == counted | traced
    assert 0 < R.reader("batch_fill.serve")(run) <= 100
    assert 0 < R.reader("step_fill.replica")(run) <= 100
    assert R.reader("compiles_in_window.serve")(run) == 0
    assert R.reader("queue_wait_s.batcher")(run) >= 0
    for name in traced:
        assert R.reader(name)(run) is None
    spans.reset()


def _drop_first_pick(orig):
    """Token 0's first routed expert is dropped, as a capacity would."""
    def routed(p, x, top_p, top_i, act, num_experts):
        return orig(p, x, top_p.at[0, 0].set(0.0), top_i, act, num_experts)
    return routed


def _leave_out_expert(orig):
    """Expert 0's output never reaches any token."""
    def routed(p, x, top_p, top_i, act, num_experts):
        return orig(p, x, jnp.where(top_i == 0, 0.0, top_p), top_i, act,
                    num_experts)
    return routed


def _stale_row(orig):
    """The step marks its position written but leaves the latent row as it
    was (the prefill's empty padding)."""
    def write(cache, rows, cur_pos, *, lead=0):
        new = orig(cache, rows, cur_pos, lead=lead)
        return dict(new, lat=cache["lat"])
    return write


@pytest.mark.parametrize("fault", ["routed_token_dropped", "expert_left_out",
                                   "stale_latent_row"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.models import mla, moe
    if fault == "routed_token_dropped":
        monkeypatch.setattr(moe, "dropless_routed",
                            _drop_first_pick(moe.dropless_routed))
    elif fault == "expert_left_out":
        monkeypatch.setattr(moe, "dropless_routed",
                            _leave_out_expert(moe.dropless_routed))
    else:
        monkeypatch.setattr(mla, "write_latent_rows",
                            _stale_row(mla.write_latent_rows))
    # 4 experts (each serves about half the tokens) and answers of 16 and
    # 12 tokens, so that a fault has steps in which to show
    out = K.run(_ctx(experts=4, answers=(16, 12)))
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"], gap


def test_the_control_is_not_correct():
    """The control, the reference computed in fp8 (one precision below the
    served bfloat16), reads a widest gap above the cell's limit on the
    program's served tokens, while the program (float32 here) reads within
    it."""
    ctx = _ctx(width=256, layers=3, vocab=2048)
    pool = K.build(ctx, SEED)
    from repro.serving.batching import ContinuousBatcher
    batcher = ContinuousBatcher(max_batch=ctx.traffic["max_batch"])
    reqs, recs = K.make_requests(ctx.traffic, ctx.seconds, SEED,
                                 ctx.sizes["vocab_size"])
    K.serve_window(pool, batcher, reqs, recs, ctx.log)
    del pool
    _, gaps, qgaps = K.check(ctx, SEED, batcher.completed, recs, "fp8")
    limit = ctx.limits["max_logit_gap"]
    assert K.widest(gaps) <= limit < K.widest(qgaps)


def test_reference_matches_the_program_forward_in_float32():
    """The reference's logits equal the program's cache-free forward on the
    same weights, at every position: the layouts and the equations agree."""
    from benchmarks.chip.references import deepseek_v2 as ref
    from repro.models import model as M
    ctx = _ctx()
    cfg = K.program_config(ctx.sizes)
    w = ref.init_weights(K.weight_key(7), ctx.sizes, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 256, (2, 12)).astype(np.int32)
    want = ref.logits(w, toks, ctx.sizes, 0)
    got = M.forward(K.program_params(w), toks, cfg)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------- counts


def _sizes():
    return R.cell_files(R.load_benchmark(), CELL)[1]


def test_weights_and_bytes_by_hand():
    s = _sizes()
    # MLA: q 2048x3072, kv_a 2048x576, kv_b 512x4096, o 2048x2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert F.attention_params(s) == attn == 13_762_560
    assert F.expert_params(s) == 3 * 2048 * 1408
    nonexpert = 2 * (7 * attn + 3 * 2048 * 10944 + 6 * 2 * 3 * 2048 * 1408
                     + 2048 * 102400) + 6 * 2048 * 64 * 4
    assert F.nonexpert_weight_bytes(s) == nonexpert
    assert 0.95e9 < nonexpert < 0.97e9
    assert F.latent_row_bytes(s) == 7 * 576 * 2 == 8064
    # 16 rows at position 99, 200 experts hit over the 6 MoE layers
    want = nonexpert + 200 * 3 * 2048 * 1408 * 2 + 16 * 100 * 7 * 1152
    assert F.decode_bytes(s, 16, 99, 200) == want
    # never all experts: fewer hit, fewer bytes
    assert F.decode_bytes(s, 16, 99, 200) < F.decode_bytes(s, 16, 99, 6 * 64)


def test_operations_by_hand():
    s = _sizes()
    ffn = 2 * 3 * 2048 * 10944 + 6 * (2 * 3 * 2048 * 1408 * 8 + 2 * 2048 * 64)
    head = 2 * 2048 * 102400
    proj = 2 * (2048 * 3072 + 2048 * 576 + 16 * 128 * 512 + 16 * 512 * 128
                + 2048 * 2048)
    assert F.decode_flops(s, 1, 0) == 7 * (proj + 2 * 16 * 1088) + ffn + head
    assert F.decode_flops(s, 4, 9) == 4 * (
        7 * (proj + 2 * 16 * 1088 * 10) + ffn + head)
    per_tok = 2 * 7 * 13_762_560 + ffn
    attn = 7 * 2 * 16 * 320 * 10 * 11 / 2
    assert F.prefill_flops(s, 2, 10) == 2 * (10 * per_tok + attn + head)
    # about 1.2-1.3 GFLOP a prompt token at 4096
    assert 1.2e9 < F.prefill_flops(s, 1, 4096) / 4096 < 1.35e9


# ------------------------------------------------------- trace readers


def test_readers_on_a_hand_made_trace():
    """The shared hand-made trace of ``test_bench_program.py`` (three
    rounds, 4 decode steps of 29 ns, 25 ns of prefill) with this cell's
    sizes and counters."""
    from benchmarks.chip.test_bench_program import ROUNDS, _trace
    from benchmarks.chip.kinds import serve_open_loop as S
    s = _sizes()
    trace = _trace()
    run = S.ServeRun(s, R.peaks_for("TPU v5 lite"), 4, ROUNDS, 0,
                     trace=trace, chips=(0,), window_ns=(0, 100),
                     phases=S.attribute_rounds(trace, ROUNDS, 0))
    spans.reset()
    for name, n in {"serve.rounds": 3, "serve.prefills": 3,
                    "serve.prompt_tokens": 24, "serve.decode_steps": 4,
                    "serve.decode_rows": 8}.items():
        spans.add(name, n)
    assert R.reader("decode_step_ms.replica")(run) == pytest.approx(29e-6 / 4)
    assert R.reader("prefill_us_per_token.replica")(run) == pytest.approx(
        25e-3 / 24)
    assert R.reader("step_fill.replica")(run) == pytest.approx(50.0)
    steps = list(run.decode_steps())
    ops = sum(F.decode_flops(s, b, p) for b, p in steps)
    assert R.reader("serve_mfu.v2lite")(run) == pytest.approx(100 * (
        sum(F.prefill_flops(s, r["batch"], r["prompt"]) for r in ROUNDS)
        + ops) / (100e-9 * 197e12))
    spans.add("serve.moe_experts_hit", 37)
    least = max(ops / 197e12, (sum(F.decode_bytes(s, b, p, 0)
                                   for b, p in steps)
                               + 37 * F.expert_params(s) * 2) / 819e9)
    assert R.reader("decode_roofline.v2lite")(run) == pytest.approx(
        100 * least / 29e-9)
    spans.add("serve.decode_steps")         # counts disagree: no reading
    for name in ("decode_step_ms.replica", "decode_roofline.v2lite"):
        assert R.reader(name)(run) is None
    spans.reset()
