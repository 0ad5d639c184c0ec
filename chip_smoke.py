#!/usr/bin/env python3
"""Bring-up smoke run of the main path on TPU chips, in one process.

    python chip_smoke.py             # one chip: control plane, then serving
    python chip_smoke.py --chips 4   # four chips: consolidation phase only

Phases run in order through the entry points a user calls, and the first
failure ends the run with its own exception. Earlier lines print the
devices, each phase's wall time, and the compilations (count, seconds,
persistent-cache hits): set-up facts, not benchmark numbers. The last line
of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Without a TPU the script exits non-zero before any phase: it has no CPU
mode. The phase functions take their sizes as arguments, so the tests run
them on the CPU at reduced sizes (tests/test_chip_smoke.py).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.configs.base import ModelConfig, TrainConfig  # noqa: E402
from repro.core.types import SLOConfig  # noqa: E402
from repro.data.pipeline import SyntheticLM  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import serve_queue  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.runtime.elastic import ElasticTrainer  # noqa: E402
from repro.runtime.orchestrator import MultiTenantOrchestrator  # noqa: E402
from repro.runtime.serving_pool import ServingPool, init_host_params  # noqa: E402
from repro.serving.batching import (ContinuousBatcher, Request,  # noqa: E402
                                    ServiceTimeModel)
from repro.workloads import queueing  # noqa: E402
from repro.workloads.autoscaler import SLOAutoscaler  # noqa: E402
from repro.workloads.campaign import MIXES, make_grid, run_campaign  # noqa: E402

# batched (float32, device) vs exact (float64, host) campaign reductions:
# the golden tolerance of the CI smoke, |x - y| <= RTOL * max(|x|, |y|) + ATOL
RTOL, ATOL = 3e-4, 2e-3
# a served token may differ from the cache-free reference's argmax only
# where the reference scores it within this many bf16 ulps (of the top
# logit) of the top: the two paths round differently in bf16, and a wrong
# token from a 256k vocabulary sits several whole logits below the top
BF16_MARGIN_ULPS = 16
# trainer losses across elastic resizes vs the same steps on a fixed mesh:
# the data-parallel width changes only the order of the gradient reduction
LOSS_RTOL = 1e-2


def _stamp(log, phase: str, t0: float, compiles: "CompileLog"):
    log(f"[{phase}] wall {time.perf_counter() - t0:.3f} s; "
        f"{compiles.summary()}")


class CompileLog:
    """Counts XLA compilations, their seconds and persistent-cache hits
    through ``jax.monitoring``."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def summary(self) -> str:
        return (f"compilations so far {self.count} "
                f"({self.seconds:.1f} s, {self.cache_hits} from the "
                f"persistent cache)")


# ------------------------------------------------------------ control plane


def compare_reductions(x, y, path=()):
    """Walk two campaign reduction trees; every number must agree within
    the golden tolerance. Returns (numbers compared, worst |x-y|/tol)."""
    if isinstance(x, dict):
        assert set(x) == set(y), (path, sorted(set(x) ^ set(y)))
        n, worst = 0, 0.0
        for k in x:
            kn, kw = compare_reductions(x[k], y[k], path + (k,))
            n, worst = n + kn, max(worst, kw)
        return n, worst
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        assert x == y, (path, x, y)
        return 0, 0.0
    if np.isinf(x) or np.isinf(y):
        assert x == y, (path, x, y)
        return 1, 0.0
    tol = RTOL * max(abs(x), abs(y)) + ATOL
    assert abs(x - y) <= tol, (path, x, y, tol)
    return 1, abs(x - y) / tol


def control_plane_phase(grid: str, platform: str, log=print) -> dict:
    """Run ``grid`` twice in this process: every WS queue flushed by the
    batched device cores, which must all run on ``platform``, then on the
    exact float64 host path; the reductions must agree."""
    served0 = dict(queueing.SERVED_ON)
    batched = run_campaign(make_grid(grid, queue_impl="batched"), workers=1,
                           grid_name=grid)
    ws_queues = 0
    for row in batched["cells"]:
        n_ws = MIXES[row["mix"]][1]
        impls = row["queue_sim"]["impls"]
        assert impls == {"jax_batched": n_ws}, (row["cell_id"], impls)
        ws_queues += n_ws
    served = {p: n - served0.get(p, 0)
              for p, n in queueing.SERVED_ON.items()
              if n != served0.get(p, 0)}
    assert served == {platform: ws_queues}, (served, platform, ws_queues)
    log(f"[control] {grid}: {batched['n_cells']} cells, {ws_queues} WS "
        f"queues served jax_batched on {served}")

    exact = run_campaign(make_grid(grid, queue_impl="exact"), workers=1,
                         grid_name=grid)
    for row in exact["cells"]:
        assert "jax_batched" not in row["queue_sim"]["impls"], row["cell_id"]
    n, worst = compare_reductions(batched["reductions"], exact["reductions"])
    log(f"[control] batched vs exact reductions: {n} numbers agree, worst "
        f"|x-y| at {worst:.3f} of the tolerance "
        f"({RTOL:g}*max(|x|,|y|) + {ATOL:g})")
    return {"cells": batched["n_cells"], "ws_queues": ws_queues,
            "served_on": served, "compared": n, "worst": worst}


# ------------------------------------------------------------------ serving


def _bf16_ulp(x: np.ndarray) -> np.ndarray:
    """bf16 spacing at |x|: 7 explicit mantissa bits."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


def serving_phase(cfg: ModelConfig, *, rounds: int, batch: int,
                  prompt_len: int, max_new: int, device, seed: int = 0,
                  log=print) -> dict:
    """Serve ``rounds`` full batches through ``ContinuousBatcher`` and a
    one-replica ``ServingPool`` on ``device``, as ``launch/serve.py`` does,
    then check every greedy token against a cache-free ``M.forward`` over
    the prompt plus the generated tokens."""
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_host_params(cfg, seed))
    pool = ServingPool(cfg, params, capacity_tokens_per_replica=float("inf"))
    log(f"[serve] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab_size}; host weights from seed "
        f"{seed} in {time.perf_counter() - t0:.3f} s")
    batcher = ContinuousBatcher(max_batch=batch)
    rng = np.random.default_rng(seed)
    for i in range(rounds * batch):
        batcher.submit(Request(i, rng.integers(0, cfg.vocab_size, prompt_len,
                                               dtype=np.int32), max_new))
    n_rounds, wall = serve_queue(pool, batcher, [device],
                                 log=lambda m: log(f"[serve] {m}"))
    assert n_rounds == rounds and len(batcher.completed) == rounds * batch
    replica = pool.replicas[0]
    assert {d for leaf in jax.tree.leaves(replica.params)
            for d in leaf.devices()} == {device}
    log(f"[serve] {rounds} rounds of batch {batch}, prompt {prompt_len}, "
        f"{max_new} new tokens: {wall:.3f} s including compilation")

    @jax.jit
    def reference(params, seq, served):
        # logits at the positions that produced the served tokens
        logits = M.forward(params, seq, cfg)[0][:, prompt_len - 1:-1]
        top = jnp.max(logits, axis=-1)
        mine = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
        return (jnp.argmax(logits, axis=-1), top, mine,
                jnp.all(jnp.isfinite(logits)))

    agree = total = within = 0
    worst_gap_ulps = 0.0
    done = batcher.completed
    for r in range(rounds):
        reqs = done[r * batch:(r + 1) * batch]
        served = np.stack([q.done for q in reqs]).astype(np.int32)
        assert served.shape == (batch, max_new)
        assert ((served >= 0) & (served < cfg.vocab_size)).all()
        seq = np.concatenate([np.stack([q.prompt for q in reqs]), served], 1)
        argmax, top, mine, finite = jax.device_get(reference(
            replica.params, jax.device_put(seq, device),
            jax.device_put(served, device)))
        assert finite, "reference logits are not finite"
        gap_ulps = (top - mine) / _bf16_ulp(top)
        same = argmax == served
        assert (same | (gap_ulps <= BF16_MARGIN_ULPS)).all(), \
            (r, np.argwhere(~same & (gap_ulps > BF16_MARGIN_ULPS))[:8],
             gap_ulps.max())
        agree += int(same.sum())
        within += int((~same).sum())
        total += same.size
        if (~same).any():
            worst_gap_ulps = max(worst_gap_ulps, float(gap_ulps[~same].max()))
    log(f"[serve] greedy tokens vs cache-free forward: {agree}/{total} equal "
        f"the reference argmax; the other {within} lie within "
        f"{BF16_MARGIN_ULPS} bf16 ulps of the reference top logit "
        f"(worst {worst_gap_ulps:.2f} ulps)")
    return {"agree": agree, "total": total, "within_margin": within,
            "worst_gap_ulps": worst_gap_ulps}


# ------------------------------------------------------------ consolidation


def _losses(trainer) -> list:
    return [m["loss"] for m in trainer.metrics_log]


def _replica_devices(pool, trainer) -> list:
    """The replicas' devices, after checking that each replica's weights
    sit on its own device only and that no replica shares a device with
    the trainer's mesh. (A function, so no loop variable keeps a dropped
    replica's weights alive on its chip.)"""
    devs = []
    for r in pool.replicas:
        on = {d for leaf in jax.tree.leaves(r.params) for d in leaf.devices()}
        assert on == {r.device}, (r.device, on)
        devs.append(r.device)
    assert len(set(devs)) == len(devs), devs
    assert not set(devs) & set(trainer.mesh.devices.flat)
    return devs


def consolidation_phase(serve_cfg: ModelConfig, train_cfg: ModelConfig,
                        devices, *, global_batch: int, seq_len: int,
                        steps: int, lr: float, ckpt_root: str,
                        log=print) -> dict:
    """``examples/multi_department_runtime.py`` on ``devices``: one
    serving department (one replica per device) and one elastic trainer
    (``min_devices=2``) under the ``slo_headroom`` engine, through a
    trough, a spike that reclaims a device from the trainer and a trough
    that returns it. The trainer's losses must match the same steps on a
    fixed mesh of ``min_devices``."""
    min_train = 2
    t0 = time.perf_counter()
    pool = ServingPool(serve_cfg, init_host_params(serve_cfg, seed=0),
                       capacity_tokens_per_replica=200.0)
    data = SyntheticLM(train_cfg, seed=0)

    def trainer(tag):
        return ElasticTrainer(train_cfg, TrainConfig(learning_rate=lr),
                              global_batch=global_batch, seq_len=seq_len,
                              ckpt_dir=tempfile.mkdtemp(prefix=tag,
                                                        dir=ckpt_root),
                              model_size=1, data_fn=data.data_fn)

    slo = SLOConfig(latency_target_s=2.0)
    scaler = SLOAutoscaler(ServiceTimeModel(), slo, n_min=1,
                           n_max=len(devices) - min_train)
    orch = MultiTenantOrchestrator(devices=devices, policy="slo_headroom")
    orch.add_latency("serve", pool, priority=0, slo_autoscaler=scaler,
                     floor=1)
    elastic = trainer("elastic_")
    orch.add_batch("train", elastic, priority=1, min_devices=min_train)
    mean_s, scv = 0.35, 1.0
    # the serving floor is claimed from the free pool before start(), so
    # the trainer starts on the rest and every later resize is a reclaim
    # or a return
    orch.latency_tick_slo("serve", 0.2, mean_s, scv)
    orch.start()
    log(f"[consolidate] start: serve={len(pool.replicas)} replica(s), "
        f"train={elastic.mesh.size} devices; "
        f"{time.perf_counter() - t0:.3f} s")
    placements = []
    for label, rate in (("trough", 0.2), ("spike", 30.0), ("trough", 0.2)):
        ti = time.perf_counter()
        orch.latency_tick_slo("serve", rate, mean_s, scv)
        m = orch.train_steps("train", steps)
        placements.append([d.id for d in _replica_devices(pool, elastic)])
        log(f"[consolidate] {label} {rate} req/s: serve on devices "
            f"{placements[-1]}, train on "
            f"{[d.id for d in elastic.mesh.devices.flat]} step {m['step']} "
            f"loss {m['loss']:.4f}; {time.perf_counter() - ti:.3f} s")
    orch.devs.check()
    orch.svc.check()
    shrinks = [e for e in orch.events
               if e["kind"] == "shrink" and e["dept"] == "train"]
    returns = [e for e in orch.events
               if e["kind"] == "grant" and e["dept"] == "train"][1:]
    assert shrinks and returns, orch.events
    got = _losses(elastic)
    log(f"[consolidate] reclaims {len(shrinks)}, returns {len(returns)}, "
        f"resizes {elastic.resizes}; devs.check() and svc.check() pass")
    # free the elastic trainer's state and the replicas (the provision
    # service's callbacks make reference cycles) before the reference run
    del orch, pool, elastic
    gc.collect()

    fixed = trainer("fixed_")
    fixed.start(devices[:min_train])
    for _ in range(len(got)):
        fixed.train_steps(steps)
    want = _losses(fixed)
    log(f"[consolidate] elastic losses {[round(x, 4) for x in got]}")
    log(f"[consolidate] fixed-mesh losses {[round(x, 4) for x in want]}")
    assert np.all(np.isfinite(got)) and np.all(np.isfinite(want))
    np.testing.assert_allclose(got, want, rtol=LOSS_RTOL)
    # the comparison can see a lost step only if the losses move by more
    # than the tolerance over the run
    assert want[0] - want[-1] > 2 * LOSS_RTOL * abs(want[0]), want
    return {"reclaims": len(shrinks), "returns": len(returns),
            "placements": placements, "losses": got, "reference": want}


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: control plane + serving on one chip; "
                         "4: the consolidation phase on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {devices}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:args.chips]
    log = lambda m: print(m, flush=True)  # noqa: E731
    cache = use_compile_cache()
    compiles = CompileLog()
    log(f"jax {jax.__version__}; devices {devices}; compile cache {cache}")
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    log(f"scratch {scratch}: {shutil.disk_usage(scratch).free / 2**30:.1f} "
        f"GiB free")
    cfg = ARCHS["recurrentgemma-2b"]
    try:
        if args.chips == 1:
            t0 = time.perf_counter()
            control_plane_phase("mix", "tpu", log)
            _stamp(log, "control", t0, compiles)
            t0 = time.perf_counter()
            serving_phase(cfg, rounds=3, batch=4, prompt_len=128, max_new=32,
                          device=devices[0], log=log)
            _stamp(log, "serve", t0, compiles)
        else:
            t0 = time.perf_counter()
            # the trainer keeps the published widths; depth is cut to one
            # whole (rglru, rglru, local) period. 12 is divisible by every
            # data width it can be given (2, 3, 4)
            consolidation_phase(
                cfg, cfg.with_(num_layers=len(cfg.block_pattern)), devices,
                global_batch=12, seq_len=128, steps=2, lr=1e-3,
                ckpt_root=scratch, log=log)
            _stamp(log, "consolidate", t0, compiles)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
