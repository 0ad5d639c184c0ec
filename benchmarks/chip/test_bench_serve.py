"""The ``serve_open_loop`` kind end to end on the CPU at a reduced size:
it serves through the program's batcher and pool, compiles nothing in the
window, and its comparison with the plain reference passes a sound program
and fails a broken one.

The harness's look for a chip is skipped (the kind's ``run`` is called
directly); everything after it runs as on the chip, with the configuration
cut to a few widths and float32 so that a sound program matches the
reference to rounding.
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

from benchmarks.chip import run as R  # noqa: E402
from benchmarks.chip.kinds import serve_open_loop as K  # noqa: E402
from benchmarks.chip.references import llama  # noqa: E402

CELL = "serve-deepseek-chat"
SEED = 2**33 + 12345            # wider than 32 bits, as the driver's are


def _ctx(dtype="float32", check_requests=12, width=64, layers=2, vocab=256):
    bench = R.load_benchmark()
    cell, sizes, traffic, limits = R.cell_files(bench, CELL)
    sizes = dict(sizes, hidden_size=width, num_attention_heads=4,
                 num_key_value_heads=4, intermediate_size=2 * width,
                 num_hidden_layers=layers, vocab_size=vocab,
                 torch_dtype=dtype)
    # a burst, so that rounds hold several requests; the two classes fall
    # in different length buckets of the batcher (64 tokens), as the
    # cell's do, so no round pads a prompt
    traffic = dict(traffic, rate_per_s=300.0, max_batch=4,
                   check_requests=check_requests,
                   classes=[dict(traffic["classes"][0], prompt_tokens=16,
                                 answer_tokens=6),
                            dict(traffic["classes"][1], prompt_tokens=72,
                                 answer_tokens=4)])
    return R.Ctx(cell, sizes, traffic, limits, SEED, 0.1, False,
                 jax.devices()[:1], R.peaks_for("TPU v5 lite"),
                 R.CompileLog(), time.perf_counter(), lambda m: None)


def test_sound_program_is_correct_and_compiles_nothing_in_the_window():
    ctx = _ctx()
    out = K.run(ctx)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 30 and out["failed"] == 0
    assert out["checks"]["max_logit_gap"]["value"] < 1e-3
    run = out["layers"]
    assert run.compiles_in_window == 0
    assert max(r["batch"] for r in run.rounds) > 1
    assert sum(r["batch"] for r in run.rounds) == 30
    e2e = out["end_to_end"]
    assert e2e["ws_latency_p95_s"] > 0 and e2e["ws_tokens_per_s"] > 0
    assert e2e["setup_s"] > 0
    assert 0 < R.reader("batch_fill.serve")(run) <= 100
    # no trace on this run: the device readers find nothing and say so
    for name in ("decode_step_ms.serve", "decode_roofline", "serve_mfu",
                 "device_idle.serve"):
        assert R.reader(name)(run) is None


def _alter_last_token(orig):
    def generate(self, prompt, max_new):
        out = orig(self, prompt, max_new)
        out[:, -1] = (out[:, -1] + 1) % self.cfg.vocab_size
        return out
    return generate


def _half_batch(orig):
    def generate(self, prompt, max_new):
        half = orig(self, prompt[:max(1, len(prompt) // 2)], max_new)
        return np.concatenate(
            [half, np.repeat(half[:1], len(prompt) - len(half), 0)])
    return generate


@pytest.mark.parametrize("fault", ["token_altered", "half_batch",
                                   "state_unchanged", "round_fails"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.models import model as M
    from repro.runtime.serving_pool import Replica
    if fault == "token_altered":
        monkeypatch.setattr(Replica, "generate",
                            _alter_last_token(Replica.generate))
    elif fault == "half_batch":
        monkeypatch.setattr(Replica, "generate", _half_batch(Replica.generate))
    elif fault == "state_unchanged":
        step = M.decode_step

        def stale(params, cache, *a, **k):
            logits, _ = step(params, cache, *a, **k)
            return logits, cache
        monkeypatch.setattr(M, "decode_step", stale)
    else:
        def boom(self, prompt, max_new):
            raise RuntimeError("replica lost")
        monkeypatch.setattr(Replica, "generate", boom)
    out = K.run(_ctx())
    assert not out["correct"]
    if fault == "round_fails":
        assert out["failed"] == out["attempted"]
        assert out["checks"]["failed_requests"]["value"] == out["failed"]
    else:
        gap = out["checks"]["max_logit_gap"]
        assert gap["value"] > gap["limit"]


def test_the_control_is_not_correct():
    """The control, the reference computed in fp8 (one precision below the
    served bfloat16) and put in the program's place, reads a widest gap
    above the cell's limit on the program's own served tokens, while the
    program (float32 here) reads within it."""
    from benchmarks.chip import calibrate as C
    ctx = _ctx(width=256, layers=4, vocab=2048)
    (row,) = C.control(ctx, [SEED], 0.1, ["fp8"])
    limit = ctx.limits["max_logit_gap"]
    assert row["program"] <= limit < row["fp8"], row


def test_reference_matches_the_program_forward_in_float32():
    """The reference's logits equal the program's cache-free forward on the
    same weights, at every position: the layouts and the equations agree."""
    from repro.models import model as M
    ctx = _ctx()
    cfg = K.program_config(ctx.sizes)
    w = llama.init_weights(K.weight_key(7), ctx.sizes, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 256, (2, 12)).astype(np.int32)
    ref = llama.logits(w, toks, ctx.sizes, 0)
    prog = M.forward(K.program_params(w), toks, cfg)[0]
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
