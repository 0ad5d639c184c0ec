"""The trace reduction, the FLOP and byte counts and the arrival schedule,
on small hand-made inputs."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import arrivals, flops, run as R  # noqa: E402
from benchmarks.chip import trace_reduce as tr  # noqa: E402
from benchmarks.chip.kinds import serve_open_loop as K  # noqa: E402

E = tr.Event


def _trace():
    """One chip, a 100 ns window: two rounds. Round 1 [10, 50): a prefill
    [12, 20) then two decode steps [22, 30) and [32, 40) with an argmax
    [40, 41). Round 2 [60, 90): prefill [61, 70), one step [75, 85). Ops
    nest inside the modules; one op overlaps another."""
    modules = {0: [E("jit__lambda(1)", 12, 20), E("jit__lambda(2)", 22, 30),
                   E("jit__lambda(2)", 32, 40), E("jit__argmax(3)", 40, 41),
                   E("jit__lambda(4)", 61, 70), E("jit__lambda(5)", 75, 85)]}
    ops = {0: [E("a", 12, 16), E("b", 15, 20), E("c", 22, 30),
               E("d", 32, 40), E("e", 40, 41), E("f", 61, 70),
               E("g", 75, 85)]}
    host = [E("bench.window", 0, 100), E("bench.round", 10, 50),
            E("bench.round", 60, 90), E("bench.wait_arrival", 50, 60)]
    return tr.Trace(modules, ops, host)


def test_merge_and_clip():
    assert tr.merge([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert tr.clip([(1, 4), (5, 7)], 3, 6) == [(3, 4), (5, 6)]


def test_busy_is_the_union_of_operations():
    t = _trace()
    # 12-20, 22-30, 32-41, 61-70, 75-85 = 8 + 8 + 9 + 9 + 10
    assert tr.busy_ns(t, 0, 0, 100) == 44
    assert tr.mean_busy_s(t, [0], 0, 100) == pytest.approx(44e-9)
    assert tr.busy_ns(t, 0, 15, 25) == 8


def test_busy_falls_back_to_modules_without_operations():
    t = _trace()
    t.ops = {}
    assert tr.busy_ns(t, 0, 0, 100) == 44


def test_idle_gaps_are_named_by_the_innermost_host_span():
    t = _trace()
    gaps = tr.idle_gaps(t, 0, 0, 100)
    assert gaps == [(0, 12), (20, 22), (30, 32), (41, 61), (70, 75),
                    (85, 100)]
    named = dict(tr.gaps_by_host(t, [0], 0, 100))
    # gaps at 6 (window), 21, 31 (round 1), 51 (waiting), 72 (round 2),
    # 92 (window)
    assert named == pytest.approx({"bench.window": 27e-9,
                                   "bench.round": 9e-9,
                                   "bench.wait_arrival": 20e-9})


def test_program_name_drops_the_program_id():
    assert tr.program_name("jit__lambda(13608012324902408474)") == \
        "jit__lambda"
    assert tr.program_name("jit_decode") == "jit_decode"


def test_rounds_split_into_prefill_and_decode_phase():
    t = _trace()
    rounds = [{"batch": 2, "prompt": 4, "max_new": 3},
              {"batch": 1, "prompt": 4, "max_new": 2}]
    ph = K.attribute_rounds(t, rounds, 0)
    assert [p["prefill_ns"] for p in ph] == [8, 9]
    assert [p["decode_ns"] for p in ph] == [17, 10]
    assert [p["steps"] for p in ph] == [2, 1]
    ops = dict(K.device_ops(ph))
    assert ops["prefill:jit__lambda"] == pytest.approx(17e-9)
    assert ops["decode:jit__lambda"] == pytest.approx(26e-9)
    assert ops["decode:jit__argmax"] == pytest.approx(1e-9)
    # a trace whose round spans do not match the rounds run attributes none
    assert K.attribute_rounds(t, rounds[:1], 0) is None


def test_load_reads_the_benchmarks_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.round"):
            jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    names = [sp.name for sp in t.host]
    assert names.count("bench.window") == 1 and names.count("bench.round") == 1
    (w,), (r,) = tr.spans(t, "bench.window"), tr.spans(t, "bench.round")
    assert w.start <= r.start and r.end <= w.end


SIZES = {"hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16,
         "num_hidden_layers": 3, "vocab_size": 32}


def test_flop_and_byte_counts_by_hand():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 64+64+64+384
    assert flops.layer_matmul_params(SIZES) == 576
    assert flops.matmul_params(SIZES) == 3 * 576 + 8 * 32
    # decode of 2 sequences at position 5: 2 * (2 * 1984 + 3 * 4*2*4*6)
    assert flops.decode_flops(SIZES, 2, 5) == 2 * (2 * 1984 + 3 * 192)
    # weights 2 B each, 2 embedding rows, 6 positions of k and v (1 head of
    # 4, 2 B) in 3 layers for 2 sequences, float32 logits
    assert flops.decode_bytes(SIZES, 2, 5) == \
        1984 * 2 + 2 * 8 * 2 + 3 * 2 * 6 * 16 + 2 * 32 * 4
    # prefill of 2 prompts of 4: matmuls per token, causal attention
    # (1+2+3+4 keys), the head once per prompt
    assert flops.prefill_flops(SIZES, 2, 4) == \
        2 * (4 * 2 * 3 * 576 + 3 * 4 * 2 * 4 * 10 + 2 * 8 * 32)
    peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    assert flops.least_seconds(10, 3, peaks) == (10, "compute")
    assert flops.least_seconds(3, 10, peaks) == (10, "memory")


def test_every_seed_gets_the_same_work_in_another_order():
    a = arrivals.poisson_schedule(4.0, 30.0, [0.8, 0.2],
                                  np.random.default_rng(3))
    b = arrivals.poisson_schedule(4.0, 30.0, [0.8, 0.2],
                                  np.random.default_rng(2**33 + 1))
    assert len(a) == len(b) == 120
    ta, tb = np.array([t for t, _ in a]), np.array([t for t, _ in b])
    assert ta[0] == tb[0] == 0.0 and np.all(np.diff(ta) > 0)
    assert ta[-1] < 30.0 and tb[-1] < 30.0
    # the same gaps (the last one runs to the window's end), in another order
    ga = np.append(np.diff(ta), 30.0 - ta[-1])
    gb = np.append(np.diff(tb), 30.0 - tb[-1])
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert [c for _, c in a].count(1) == [c for _, c in b].count(1) == 24
    assert [c for _, c in a] != [c for _, c in b]


def test_device_readers_on_a_hand_made_run():
    """The per-layer readers on the hand-made trace: decode 27 ns over 3
    steps, the window 44 ns busy of 100."""
    rounds = [{"batch": 2, "prompt": 4, "max_new": 3},
              {"batch": 1, "prompt": 4, "max_new": 2}]
    t = _trace()
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    run = K.ServeRun(SIZES, peaks, 4, rounds, 0, trace=t, chips=(0,),
                     window_ns=(0, 100),
                     phases=K.attribute_rounds(t, rounds, 0))
    assert list(run.decode_steps()) == [(2, 4), (2, 5), (1, 4)]
    assert R.reader("decode_step_ms.serve")(run) == pytest.approx(27e-6 / 3)
    assert R.reader("device_idle.serve")(run) == pytest.approx(56.0)
    assert R.reader("batch_fill.serve")(run) == pytest.approx(37.5)
    assert R.reader("compiles_in_window.serve")(run) == 0
    least = sum(max(flops.decode_flops(SIZES, b, p),
                    flops.decode_bytes(SIZES, b, p)) / 1e12
                for b, p in [(2, 4), (2, 5), (1, 4)])
    assert R.reader("decode_roofline")(run) == pytest.approx(
        100 * least / 27e-9)
    total = (flops.prefill_flops(SIZES, 2, 4) + flops.prefill_flops(SIZES, 1, 4)
             + sum(flops.decode_flops(SIZES, b, p)
                   for b, p in [(2, 4), (2, 5), (1, 4)]))
    assert R.reader("serve_mfu")(run) == pytest.approx(
        100 * total / (100e-9 * 1e12))
