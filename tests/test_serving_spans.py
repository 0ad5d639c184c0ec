"""The serving path's spans, counters and program names
(``repro.serving.spans``) on a reduced config served on the CPU through
``launch/serve.serve_queue``."""
import glob
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced_config
from repro.launch.serve import serve_queue
from repro.runtime.serving_pool import ServingPool, init_host_params
from repro.serving import spans
from repro.serving.batching import ContinuousBatcher, Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPANS = ("serve.round", "serve.pack", "serve.prefill", "serve.decode",
         "serve.fetch")
# five requests of prompt 6 and answer 4, then three of prompt 70 and
# answer 3 (another length bucket), at most 4 a round: rounds of 4 and 1 of
# the first class, then one of 3 of the second
CLASSES = [(6, 4)] * 5 + [(70, 3)] * 3


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One drain of the queue with the profiler on; the batcher, the log,
    the counters after it and the trace's directory."""
    cfg = reduced_config(ARCHS["deepseek-7b"])
    pool = ServingPool(cfg, init_host_params(cfg, seed=0),
                       capacity_tokens_per_replica=1e9)
    batcher = ContinuousBatcher(max_batch=4)
    rng = np.random.default_rng(0)
    spans.reset()
    for i, (S, new) in enumerate(CLASSES):
        batcher.submit(Request(i, rng.integers(0, cfg.vocab_size, S,
                                               dtype=np.int32), new))
    logs = []
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(trace_dir):
        serve_queue(pool, batcher, jax.devices()[:1], log=logs.append)
    counts = spans.snapshot()
    spans.reset()
    return batcher, logs, counts, trace_dir, pool


def test_counters_equal_hand_counts(served):
    batcher, _, c, _, _ = served
    assert len(batcher.completed) == 8
    assert c["serve.requests_queued"] == c["serve.requests_batched"] == 8
    assert c["serve.rounds"] == c["serve.prefills"] == 3
    assert c["serve.prompt_tokens"] == 4 * 6 + 1 * 6 + 3 * 70
    assert c["serve.decode_steps"] == 3 + 3 + 2
    assert c["serve.decode_rows"] == 4 * 3 + 1 * 3 + 3 * 2


def test_queue_wait_is_the_sum_of_batched_minus_queued(served):
    batcher, logs, c, _, _ = served
    waits = [r.batched_at - r.queued_at for r in batcher.completed]
    assert all(w >= 0 for w in waits)
    assert c["serve.queue_wait_s"] == pytest.approx(sum(waits), rel=1e-12)
    # the launcher's last line gives the mean over every request batched
    assert logs[-1].endswith(f"mean queue wait {sum(waits) / 8:.3f} s")


def test_reset_zeroes_the_counters():
    spans.add("serve.rounds")
    spans.add("serve.queue_wait_s", 0.5)
    assert spans.snapshot()["serve.rounds"] >= 1
    spans.reset()
    assert spans.snapshot() == dict.fromkeys(spans.COUNTERS, 0)


def test_counters_lose_no_update_across_threads():
    spans.reset()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [spans.add("serve.decode_rows", 1)
                            for _ in range(5000)]) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert spans.snapshot()["serve.decode_rows"] == 16 * 5000
    spans.reset()


def test_the_replica_programs_have_stable_names(served):
    replica = served[4].replicas[0]
    prompt = jax.ShapeDtypeStruct((2, 6), np.int32)
    low = replica._prefill.lower(replica.params, prompt, 10)
    assert "jit_serve_prefill" in low.as_text()
    cache = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                         low.out_info[1])
    dec = replica._decode.lower(replica.params, cache,
                                jax.ShapeDtypeStruct((2, 1), np.int32),
                                jax.ShapeDtypeStruct((), np.int32))
    assert "jit_serve_decode" in dec.as_text()


def test_the_trace_holds_the_spans_nested_under_each_round(served):
    from jax.profiler import ProfileData
    _, _, _, trace_dir, _ = served
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    found = {name: [] for name in SPANS}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in found:
                    found[e.name].append(e)
    assert all(len(evs) == 3 for evs in found.values()), {
        k: len(v) for k, v in found.items()}
    rounds = sorted(found["serve.round"], key=lambda e: e.start_ns)
    for name in SPANS[1:]:
        for e in found[name]:
            assert any(r.start_ns <= e.start_ns and e.end_ns <= r.end_ns
                       for r in rounds), name
    args = [dict(r.stats) for r in rounds]
    assert [a["round"] for a in args] == [1, 2, 3]
    assert [a["batch"] for a in args] == [4, 1, 3]
    assert [str(a["requests"]) for a in args] == ["0 1 2 3", "4", "5 6 7"]


def test_the_batcher_does_not_load_jax():
    """The simulator imports the batcher for its service-time model and
    runs without JAX; the spans are then no-ops."""
    code = ("import sys, numpy as np\n"
            "from repro.serving.batching import ContinuousBatcher, Request\n"
            "b = ContinuousBatcher(max_batch=2)\n"
            "b.submit(Request(0, np.ones(3, np.int32), 2))\n"
            "b.run_round(b.next_round(), lambda p, n: np.zeros((len(p), n)))\n"
            "assert 'jax' not in sys.modules and len(b.completed) == 1\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)
