#!/usr/bin/env python3
"""Readings that set a serving cell's rate and its limit, on the chip, in
one process (set-up is paid once):

    python3 benchmarks/chip/calibrate.py sweep --workload <cell> \
        --rates 2,3,4 --seconds 30 --seed <n>
    python3 benchmarks/chip/calibrate.py control --workload <cell> \
        --seeds 1,2,3 --seconds 10 --quant int8,fp8

``sweep`` offers the cell's traffic at each rate (and, with ``--seeds``,
for each seed) through the same pool and prints latency and backlog per
window: the knee is the highest rate whose latency does not grow over the
window; windows of one rate and several seeds show the spread at a
length. ``control`` serves a short window at the cell's rate for each
seed, with weights from that seed, and prints the widest gap of the
program's served tokens (the lower reading) beside the widest gap of the
tokens that the reference computed at each ``--quant`` precision would put
first (the control, the upper reading). The benchmark's own runs run
neither.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from benchmarks.chip import run as R  # noqa: E402


def _window(ctx, pool, traffic, seconds, seed):
    from benchmarks.chip.kinds import serve_open_loop as K
    from repro.serving.batching import ContinuousBatcher
    batcher = ContinuousBatcher(max_batch=traffic["max_batch"])
    reqs, recs = K.make_requests(traffic, seconds, seed,
                                 ctx.sizes["vocab_size"])
    c0 = ctx.compiles.count
    rounds, span = K.serve_window(pool, batcher, reqs, recs, ctx.log)
    return batcher, recs, rounds, span, ctx.compiles.count - c0


def sweep(ctx, rates, seconds, seeds):
    import numpy as np
    from benchmarks.chip.kinds import serve_open_loop as K
    pool = K.build(ctx, ctx.seed)
    rows = []
    for rate, seed in [(r, s) for r in rates for s in seeds]:
        traffic = dict(ctx.traffic, rate_per_s=rate)
        _, recs, rounds, span, comp = _window(ctx, pool, traffic, seconds,
                                              seed)
        e2e, failed, lat = K.summarize(traffic, recs, span, ctx.log)
        due = np.array([r.due for r in recs])
        early, late = lat[due < seconds / 2], lat[due >= seconds / 2]
        row = {"rate": rate, "seed": seed, "requests": len(recs), "rounds": len(rounds),
               "mean_batch": float(np.mean([r["batch"] for r in rounds])),
               "p50_s": float(np.percentile(lat, 50)), **e2e,
               "p95_first_half_s": float(np.percentile(early, 95)),
               "p95_second_half_s": float(np.percentile(late, 95)),
               "drain_s": max(r.finish for r in recs) - seconds,
               "failed": failed, "compiles": comp}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def control(ctx, seeds, seconds, quants):
    import jax
    from benchmarks.chip.kinds import serve_open_loop as K
    pool = None
    rows = []
    for seed in seeds:
        if pool is None:
            pool = K.build(ctx, seed)
        else:           # same shapes: new weights, the same programs
            gc.collect()
            pool.params_host = pool.replicas[0].params = jax.device_put(
                K.make_weights(ctx.sizes, seed, ctx.devices[0],
                               K.program_params), ctx.devices[0])
        batcher, recs, rounds, span, comp = _window(ctx, pool, ctx.traffic,
                                                    seconds, seed)
        completed = batcher.completed
        pool.replicas[0].params = pool.params_host = None
        gc.collect()
        row = {"seed": seed, "requests": len(recs), "compiles": comp}
        for q in quants or [None]:
            sample, gaps, qgaps = K.check(ctx, seed, completed, recs, q)
            row["program"] = K.widest(gaps)
            row["tokens"] = int(sum(g.size for g in gaps))
            if q:
                row[q] = K.widest(qgaps)
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = max(r["program"] for r in rows)
    summary = {"lower": prog}
    for q in quants:
        summary[f"upper_{q}"] = min(r[q] for r in rows)
    print(json.dumps(summary), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--quant", default="int8,fp8")
    args = ap.parse_args(argv)
    bench = R.load_benchmark()
    cell, sizes, traffic, limits = R.cell_files(bench, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"needs a TPU; JAX found {devices}", file=sys.stderr)
        return 2
    R.use_cache()
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    ctx = R.Ctx(cell, sizes, traffic, limits, args.seed, args.seconds, False,
                devices[:1], R.peaks_for(devices[0].device_kind),
                R.CompileLog(), T_START, log)
    if args.what == "sweep":
        sweep(ctx, [float(r) for r in args.rates.split(",")], args.seconds,
              [int(s) for s in (args.seeds or str(args.seed)).split(",")])
    else:
        control(ctx, [int(s) for s in args.seeds.split(",")], args.seconds,
                [q for q in args.quant.split(",") if q])
    return 0


if __name__ == "__main__":
    sys.exit(main())
