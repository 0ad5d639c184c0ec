"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
        --steps 100 --batch 8 --seq 128 --reduced --ckpt-dir /tmp/ckpt

Wraps the elastic trainer: checkpoint/restart comes for free (re-running the
same command resumes from the latest step); --devices simulates a host
device count for local runs (on real TPU hosts leave it unset). The
paper-facing orchestration (provision policies + serving co-tenant) lives in
examples/elastic_train.py; this is the bare ST-CMS payload.
"""
import os
import sys


def _early_args(argv):
    # --devices must be applied before jax import
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={argv[i + 1]}")


_early_args(sys.argv)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-size", type=int, default=1,
                    help="TP width (devices per model replica)")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--devices", type=int, default=0)  # handled pre-import
    ap.add_argument("--log", default="")
    args = ap.parse_args(argv)

    from repro.configs import ARCHS, reduced_config
    from repro.configs.base import TrainConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch.compile_cache import use_compile_cache
    from repro.runtime.elastic import ElasticTrainer

    use_compile_cache()
    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced_config(cfg)
    tcfg = TrainConfig(learning_rate=args.lr, microbatch=args.microbatch)
    data = SyntheticLM(cfg, seed=0)
    trainer = ElasticTrainer(cfg, tcfg, global_batch=args.batch,
                             seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                             model_size=args.model_size,
                             data_fn=data.data_fn)
    os.makedirs(args.ckpt_dir, exist_ok=True)
    trainer.start(jax.devices())
    print(f"arch={cfg.name} devices={trainer.mesh.size} "
          f"start_step={trainer.step}")
    t0 = time.time()
    while trainer.step < args.steps:
        n = min(args.ckpt_every, args.steps - trainer.step)
        m = trainer.train_steps(n)
        trainer.checkpoint()
        print(f"step {m['step']}: loss={m['loss']:.4f} "
              f"({(time.time() - t0):.1f}s)", flush=True)
    if args.log:
        json.dump(trainer.metrics_log, open(args.log, "w"), indent=1)
    print("done; checkpoint at", args.ckpt_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
