"""The readers of the serving program's own counters and named programs
(``serve_program.py`` and the ``*.replica`` and ``*.batcher`` metrics), on a
hand-made trace and counter snapshot, and on the CPU end to end."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import run as R  # noqa: E402
from benchmarks.chip import serve_program as P  # noqa: E402
from benchmarks.chip import trace_reduce as tr  # noqa: E402
from benchmarks.chip.kinds import serve_open_loop as K  # noqa: E402
from repro.serving import spans  # noqa: E402

E = tr.Event
NEW = ("decode_step_ms.replica", "prefill_us_per_token.replica",
       "step_fill.replica", "queue_wait_s.batcher", "round_gap_ms.replica")
ROUNDS = [{"batch": 2, "prompt": 4, "max_new": 3},
          {"batch": 1, "prompt": 4, "max_new": 2},
          {"batch": 3, "prompt": 4, "max_new": 2}]
SIZES = {"hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16,
         "num_hidden_layers": 3, "vocab_size": 32}


def _trace(prefill="jit_serve_prefill", decode="jit_serve_decode",
           wait=(45, 55)):
    """One chip, a 100 ns window, three rounds. Round 1: prefill [10, 20),
    decode steps [22, 30) and [32, 40), an argmax [40, 41). Round 2: prefill
    [60, 70), one step [75, 85). Round 3: prefill [90, 95), one step
    [96, 99). The host waited for an arrival over ``wait``."""
    modules = {0: [E(f"{prefill}(1)", 10, 20), E(f"{decode}(2)", 22, 30),
                   E(f"{decode}(2)", 32, 40), E("jit__argmax(3)", 40, 41),
                   E(f"{prefill}(4)", 60, 70), E(f"{decode}(5)", 75, 85),
                   E(f"{prefill}(6)", 90, 95), E(f"{decode}(7)", 96, 99)]}
    host = [E("bench.window", 0, 100), E("bench.round", 9, 42),
            E("bench.wait_arrival", *wait), E("bench.round", 58, 86),
            E("bench.round", 88, 100)]
    return tr.Trace(modules, {}, host)


def _run(trace=None, rounds=ROUNDS):
    trace = trace or _trace()
    return K.ServeRun(SIZES, {}, 4, rounds, 0, trace=trace, chips=(0,),
                      window_ns=(0, 100),
                      phases=K.attribute_rounds(trace, rounds, 0))


@pytest.fixture
def counted():
    """The counters as the three rounds leave them."""
    spans.reset()
    for name, n in {"serve.requests_queued": 6, "serve.rounds": 3,
                    "serve.requests_batched": 6, "serve.queue_wait_s": 1.5,
                    "serve.prefills": 3, "serve.prompt_tokens": 24,
                    "serve.decode_steps": 4, "serve.decode_rows": 8}.items():
        spans.add(name, n)
    yield
    spans.reset()


def test_readers_of_the_named_programs_and_counters(counted, capsys):
    run = _run()
    # decode: 8 + 8 + 10 + 3 ns over the 4 steps counted
    assert R.reader("decode_step_ms.replica")(run) == pytest.approx(29e-6 / 4)
    # prefill: 10 + 10 + 5 ns over 24 prompt tokens
    assert R.reader("prefill_us_per_token.replica")(run) == pytest.approx(
        25e-3 / 24)
    # rows 2*2 + 1 + 3 over 4 steps of 4 slots
    fill = R.reader("step_fill.replica")(run)
    assert fill == pytest.approx(50.0)
    assert fill == pytest.approx(
        100 * sum(r["batch"] * (r["max_new"] - 1) for r in ROUNDS)
        / sum(4 * (r["max_new"] - 1) for r in ROUNDS))
    assert R.reader("queue_wait_s.batcher")(run) == pytest.approx(0.25)
    # boundaries: 41 -> 60 less the wait [45, 55) = 9; 85 -> 90 = 5
    assert R.reader("round_gap_ms.replica")(run) == pytest.approx(7e-6)
    assert "over 2 boundaries" in capsys.readouterr().err


def test_round_idle_splits_between_and_inside_rounds():
    # inside: 20-22 and 30-32 in round 1, 70-75 in round 2, 95-96 in round 3
    assert P.round_idle(_run()) == ([9, 5], [4, 5, 1])


def test_the_named_decode_step_agrees_with_the_round_split(counted):
    """The old reader splits rounds at their first program and counts the
    argmax too: 30 ns against 29 over the same 4 steps."""
    run = _run()
    old = R.reader("decode_step_ms.serve")(run)
    assert old == pytest.approx(30e-6 / 4)
    assert R.reader("decode_step_ms.replica")(run) == pytest.approx(
        old * 29 / 30)


def test_no_reading_where_the_rounds_do_not_match(counted):
    spans.add("serve.rounds")           # a round served outside the window
    run = _run()
    for name in NEW:
        assert R.reader(name)(run) is None, name


def test_no_device_reading_without_named_programs(counted):
    run = _run(_trace(prefill="jit__lambda", decode="jit__lambda"))
    for name in ("decode_step_ms.replica", "prefill_us_per_token.replica",
                 "round_gap_ms.replica"):
        assert R.reader(name)(run) is None, name
    # the counters alone still read
    assert R.reader("step_fill.replica")(run) == pytest.approx(50.0)
    assert R.reader("queue_wait_s.batcher")(run) == pytest.approx(0.25)


def test_no_device_reading_where_the_counts_disagree(counted):
    spans.add("serve.decode_steps")
    spans.add("serve.prefills")
    run = _run()
    for name in ("decode_step_ms.replica", "prefill_us_per_token.replica",
                 "round_gap_ms.replica"):
        assert R.reader(name)(run) is None, name


def test_a_gap_wholly_under_the_wait_for_arrivals_is_left_out(counted):
    """Two rounds whose one boundary lies under ``bench.wait_arrival``: no
    boundary is left to average."""
    trace = _trace(wait=(41, 60))
    trace.modules[0] = trace.modules[0][:6]
    trace.host = trace.host[:4]
    spans.add("serve.rounds", -1)
    spans.add("serve.prefills", -1)
    run = _run(trace, ROUNDS[:2])
    assert P.round_idle(run)[0] == []
    assert R.reader("round_gap_ms.replica")(run) is None


def test_no_reading_from_a_program_without_counters(counted, monkeypatch):
    """An older program has no ``repro.serving.spans``: every reader says
    nothing and none raises."""
    import repro.serving
    monkeypatch.setitem(sys.modules, "repro.serving.spans", None)
    monkeypatch.delattr(repro.serving, "spans")
    run = _run()
    for name in NEW:
        assert R.reader(name)(run) is None, name


def test_no_reading_without_a_trace(counted):
    run = K.ServeRun(SIZES, {}, 4, ROUNDS, 0)
    for name in ("decode_step_ms.replica", "prefill_us_per_token.replica",
                 "round_gap_ms.replica"):
        assert R.reader(name)(run) is None, name


def test_the_counters_cover_exactly_the_window_on_the_cpu():
    """The cell's set-up compiles and checks without serving, so after a
    run the counters hold the window's rounds alone; the step-weighted fill
    equals the one computed from the rounds run."""
    from benchmarks.chip.test_bench_serve import _ctx
    spans.reset()
    out = K.run(_ctx())
    run = out["layers"]
    c = spans.snapshot()
    assert c["serve.rounds"] == len(run.rounds)
    assert c["serve.requests_queued"] == c["serve.requests_batched"] == 30
    fill = 100 * sum(r["batch"] * (r["max_new"] - 1) for r in run.rounds) \
        / sum(run.max_batch * (r["max_new"] - 1) for r in run.rounds)
    assert R.reader("step_fill.replica")(run) == pytest.approx(fill, rel=1e-9)
    assert R.reader("queue_wait_s.batcher")(run) >= 0
    spans.reset()
