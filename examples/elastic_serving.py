"""Serving a small model with batched requests through the WS CMS stack:
continuous batcher + least-outstanding balancer + utilization autoscaler.

    PYTHONPATH=src python examples/elastic_serving.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import ARCHS, reduced_config
from repro.runtime.serving_pool import ServingPool, init_host_params
from repro.serving.batching import ContinuousBatcher, Request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--requests", type=int, default=24)
    args = ap.parse_args(argv)

    cfg = reduced_config(ARCHS[args.arch])
    params = init_host_params(cfg, seed=0)
    pool = ServingPool(cfg, params, capacity_tokens_per_replica=400.0)
    pool.scale_to(jax.devices()[:1])
    batcher = ContinuousBatcher(max_batch=8, bucket=64)

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=(8,), dtype=np.int32)
        batcher.submit(Request(req_id=i, prompt=prompt, max_new=8,
                               arrival=i * 0.01))

    t0 = time.time()
    rounds = 0
    while batcher.queue:
        reqs = batcher.next_round()
        # autoscale against the queue's offered load
        offered = sum(len(r.prompt) + r.max_new for r in list(batcher.queue)
                      + reqs)
        want = pool.desired_replicas(float(offered))
        pool.scale_to(jax.devices()[:min(want, 4)])
        batcher.run_round(reqs, pool.submit, now=time.time() - t0)
        rounds += 1
        print(f"round {rounds}: batch={len(reqs)} replicas="
              f"{len(pool.replicas)} queued={len(batcher.queue)}")
    done = batcher.completed
    print(f"\nserved {len(done)} requests in {rounds} rounds, "
          f"{time.time()-t0:.2f}s wall")
    print("throughput:",
          f"{sum(r.max_new for r in done)/(time.time()-t0):.1f} tok/s")
    assert all(r.done is not None and len(r.done) == r.max_new for r in done)
    return 0


if __name__ == "__main__":
    sys.exit(main())
