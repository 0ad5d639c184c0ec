"""Time the routed experts of a MoE decode step on the chip: XLA's grouped
matmul (``jax.lax.ragged_dot``, as prefill runs it) against the few-row
expert kernel (``repro.kernels.moe_decode``) at a sweep of weight-block
sizes.

    python benchmarks/moe_decode_tiles.py [--out .bench_cache/moe_tiles.json]
        [--tk 256,512,1024,2048] [--tn 512,1024,2048]

At DeepSeek-V2-Lite's widths (6 stacked MoE layers of 64 experts, width
2048, expert width 1408, top-6) and batches of 1-16 tokens, each routed
uniformly at random to 6 distinct experts a layer. One timed call runs the
6 layers in turn, each reading its own experts from the whole stack, as
the decode step's layer scan does. Times are host-clock means over
``--iters`` calls after a warm-up, each ended by ``block_until_ready``;
``gbps`` is the hit experts' weight bytes over that time. On a host
without a TPU, ``--reduced`` runs tiny widths through Pallas's
interpreter, which checks the script and says nothing of speed.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.kernels.moe_decode import expert_mlp
from repro.models.moe import _ragged_routed


def _routing(key, tokens: int, experts: int, top_k: int):
    keys = jax.random.split(key, tokens)
    return jax.vmap(lambda k: jax.random.permutation(k, experts)[:top_k])(keys)


def _stack(key, layers, experts, d, f):
    ks = jax.random.split(key, 3)
    mk = jax.jit(lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                                   / d ** 0.5).astype(jnp.bfloat16),
                 static_argnums=1)
    return (mk(ks[0], (layers, experts, d, f)),
            mk(ks[1], (layers, experts, d, f)),
            mk(ks[2], (layers, experts, f, d)))


def _step(routed, layers: int, top_k: int, experts: int):
    """All layers' routed experts in turn, each layer's input the last's
    output, for routings [layers, N, k]."""
    def run(x, picks, wg, wu, wo):
        for layer in range(layers):
            flat = picks[layer].reshape(-1)
            order = jnp.argsort(flat, stable=True)
            rows = jnp.take(x, order // top_k, axis=0)
            sizes = jnp.bincount(flat, length=experts).astype(jnp.int32)
            y = routed(rows, sizes, flat, wg, wu, wo, jnp.int32(layer))
            y = jnp.take(y[:rows.shape[0]], jnp.argsort(order), axis=0)
            x = (x + y.reshape(x.shape[0], top_k, -1).sum(1)
                 .astype(x.dtype) * 1e-3)
        return x
    return jax.jit(run)


def _time(fn, args, iters: int) -> float:
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=".bench_cache/moe_tiles.json")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--seed", type=int, default=20261019)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tk", default="256,512,1024,2048",
                    help="gate/up block heights to sweep, comma-separated")
    ap.add_argument("--tn", default="512,1024,2048",
                    help="down block widths to sweep, comma-separated")
    a = ap.parse_args(argv)
    if a.reduced:
        L, E, D, F, K = 2, 8, 256, 256, 2
        batches, tiles = (1, 4), [(128, 128), (256, 256)]
    else:
        L, E, D, F, K = 6, 64, 2048, 1408, 6
        batches = (1, 4, 8, 16)
        tiles = [(int(tk), int(tn)) for tk in a.tk.split(",")
                 for tn in a.tn.split(",")]
    key = jax.random.PRNGKey(a.seed)
    wg, wu, wo = _stack(key, L, E, D, F)
    act = jax.nn.silu

    def ragged(rows, sizes, flat, g, u, o, layer):
        return _ragged_routed({"wi_gate": g, "wi_up": u, "wo": o,
                               "layer": layer}, rows, sizes, flat, act, E)

    def kernel(tk, tn, n):
        def run(rows, sizes, flat, g, u, o, layer):
            return expert_mlp(rows, sizes, g, u, o, layer, act=act,
                              max_rows=n, tk=tk, tn=tn,
                              impl="interpret" if a.reduced else "pallas")
        return run

    result = {"device": str(jax.devices()[0].device_kind),
              "shape": {"layers": L, "experts": E, "d": D, "f": F, "k": K},
              "rows": []}
    for n in batches:
        picks = jnp.stack([_routing(jax.random.fold_in(key, 100 * n + i),
                                    n, E, K) for i in range(L)])
        hit = int(sum(len(set(map(int, picks[i].reshape(-1))))
                      for i in range(L)))
        gbytes = hit * 3 * D * F * wg.dtype.itemsize / 1e9
        x = jax.random.normal(jax.random.fold_in(key, n), (n, D),
                              jnp.float32).astype(jnp.bfloat16)
        args = (x, picks, wg, wu, wo)
        base_fn = _step(ragged, L, K, E)
        base = base_fn(*args)
        cases = [("ragged_dot", None, None, base_fn)] + [
            ("kernel", tk, tn, _step(kernel(tk, tn, n), L, K, E))
            for tk, tn in tiles]
        for name, tk, tn, fn in cases:
            out = fn(*args)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                        - base.astype(jnp.float32))))
            s = _time(fn, args, a.iters)
            row = {"tokens": n, "experts_hit": hit, "impl": name, "tk": tk,
                   "tn": tn, "ms": s * 1e3, "gbps": gbytes / s,
                   "max_abs_diff_vs_ragged": err}
            result["rows"].append(row)
            print(json.dumps(row), flush=True)
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    Path(a.out).write_text(json.dumps(result, indent=1))
    return result


if __name__ == "__main__":
    main()
