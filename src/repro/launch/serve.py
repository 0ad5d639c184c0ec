"""Production serving launcher: WS-CMS pool + continuous batcher driven by a
synthetic (or World-Cup-like) request trace, with the paper's autoscaler.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --requests 64 --devices 4

Without ``--reduced`` it serves the registered config at its published
widths, e.g. ``--arch recurrentgemma-2b --prompt-len 128 --max-new 32``
on one 16 GB chip. ``--profile DIR`` records the run in a profiler trace
under ``DIR``: the serving path's ``serve.*`` spans (``repro.serving.spans``)
on the device trace's clock, for TensorBoard's profile plugin or for
Perfetto (``perfetto_trace.json.gz``).
"""
import os
import sys


def _early_args(argv):
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={argv[i + 1]}")


_early_args(sys.argv)

import argparse  # noqa: E402
import contextlib  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.serving import spans  # noqa: E402


def serve_queue(pool, batcher, devices, log=print):
    """Drain the batcher's queue through the pool, one generation round at
    a time, rescaling replicas over ``devices`` before each round by the
    paper's utilization rule. Returns (rounds, wall seconds); each round's
    tokens are on the host when its generate call returns. Each round's log
    line gives the process's mean queue wait so far, from the counters."""
    t0 = time.perf_counter()
    rounds = 0
    while batcher.queue:
        reqs = batcher.next_round()
        offered = float(sum(len(r.prompt) + r.max_new
                            for r in list(batcher.queue) + reqs))
        pool.scale_to(devices[:max(
            1, min(pool.desired_replicas(offered), len(devices)))])
        batcher.run_round(reqs, pool.submit, now=time.perf_counter() - t0)
        rounds += 1
        c = spans.snapshot()
        wait = c["serve.queue_wait_s"] / c["serve.requests_batched"]
        log(f"round {rounds}: batch={len(reqs)} "
            f"replicas={len(pool.replicas)} queued={len(batcher.queue)} "
            f"mean queue wait {wait:.3f} s")
    return rounds, time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced same-family config (CPU-scale)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--capacity", type=float, default=400.0,
                    help="tokens/interval one replica absorbs at 100%% util")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--profile", metavar="DIR",
                    help="record the serving run in a profiler trace "
                         "under DIR")
    args = ap.parse_args(argv)

    from repro.configs import ARCHS, reduced_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.runtime.serving_pool import ServingPool, init_host_params
    from repro.serving.batching import ContinuousBatcher, Request

    use_compile_cache()
    cfg = reduced_config(ARCHS[args.arch]) if args.reduced else ARCHS[args.arch]
    pool = ServingPool(cfg, init_host_params(cfg, seed=0),
                       capacity_tokens_per_replica=args.capacity)
    batcher = ContinuousBatcher(max_batch=args.max_batch)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        batcher.submit(Request(
            i, rng.integers(0, cfg.vocab_size, args.prompt_len,
                            dtype=np.int32), args.max_new))
    with (jax.profiler.trace(args.profile, create_perfetto_trace=True)
          if args.profile else contextlib.nullcontext()):
        _, dt = serve_queue(pool, batcher, jax.devices(),
                            log=lambda m: print(m, flush=True))
    total_new = sum(r.max_new for r in batcher.completed)
    print(f"served {len(batcher.completed)} requests / {total_new} tokens "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
