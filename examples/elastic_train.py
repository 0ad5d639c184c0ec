"""Elastic training under the Phoenix policies — the runtime showcase.

Runs on 8 host devices: an ElasticTrainer (the "ST job") trains while a
synthetic WS load trace drives the §III-C autoscaler; the provision service
reclaims devices from / returns devices to the trainer live. Demonstrates
checkpoint-resize-resume with no lost work (vs the paper's kill policy).

    PYTHONPATH=src python examples/elastic_train.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import sys
import tempfile

import numpy as np

from repro.configs import ARCHS, reduced_config
from repro.configs.base import TrainConfig
from repro.runtime.elastic import ElasticTrainer
from repro.runtime.orchestrator import MultiTenantOrchestrator
from repro.runtime.serving_pool import ServingPool, init_host_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--intervals", type=int, default=6)
    args = ap.parse_args(argv)

    cfg = reduced_config(ARCHS[args.arch])
    ckpt_dir = tempfile.mkdtemp(prefix="phoenix_ckpt_")
    trainer = ElasticTrainer(cfg, TrainConfig(learning_rate=1e-3),
                             global_batch=8, seq_len=32,
                             ckpt_dir=ckpt_dir, model_size=1)
    params = init_host_params(cfg, seed=0)
    pool = ServingPool(cfg, params, capacity_tokens_per_replica=200.0)
    # the paper's two departments: ST (the trainer) and WS (the pool)
    orch = MultiTenantOrchestrator(policy="paper")
    orch.add_batch("st", trainer, priority=1, min_devices=2)
    orch.add_latency("ws", pool, priority=0)
    orch.start()

    # WS offered load (tokens/interval): trough -> spike -> trough
    loads = np.interp(np.arange(args.intervals),
                      [0, 2, 3, args.intervals - 1], [0, 0, 900, 0])
    for i, load in enumerate(loads):
        orch.latency_tick("ws", float(load))
        m = orch.train_steps("st", 2)
        print(f"interval {i}: ws_load={load:6.0f} "
              f"replicas={len(pool.replicas)} "
              f"train_devices={m['devices']} step={m['step']} "
              f"loss={m['loss']:.4f}")
        if pool.replicas:
            out = pool.submit(np.array([[5, 6, 7, 8]], dtype=np.int32), 4)
            print(f"            served 1 request -> tokens {out[0].tolist()}")
    print(f"resizes: {trainer.resizes}; ST events: "
          f"{[e for e in orch.events if e['kind'] == 'shrink']}")
    print("final step:", trainer.step, "(no work lost across resizes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
