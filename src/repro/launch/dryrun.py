import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init). --devices can override them for small smoke
# runs, which is why argument parsing also happens before `import jax`.
import argparse
import sys


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Multi-pod dry-run: lower + compile every "
                    "(arch x shape x mesh) cell; record memory/cost/roofline.")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--devices", type=int, default=512)
    ap.add_argument("--mesh-shape", default="",
                    help="override mesh, e.g. '2,4' or '2,2,4' (test-scale)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-hlo", action="store_true",
                    help="do not save gzipped HLO text")
    ap.add_argument("--sequence-parallel", default="",
                    help="force on/off (hillclimb experiments)")
    ap.add_argument("--fsdp", default="", help="force on/off")
    ap.add_argument("--remat", default="", help="override remat policy")
    ap.add_argument("--moe-ep", action="store_true",
                    help="expert-parallel MoE (all-to-all dispatch)")
    ap.add_argument("--microbatch", type=int, default=-1,
                    help="override gradient-accumulation count (-1 = plan)")
    ap.add_argument("--tp", type=int, default=-1,
                    help="-1=auto (train: pure-FSDP, serve: TP); "
                         "0=force model-axis TP; 1=force pure FSDP")
    ap.add_argument("--tag", default="", help="suffix for result files")
    return ap.parse_args(argv)


ARGS = _parse_args()
if ARGS.devices != 512:
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={ARGS.devices}"

import dataclasses
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES_BY_NAME, shapes_for
from repro.hlo.analysis import analyze_text
from repro.hlo.roofline import score as roofline_score
from repro.launch.mesh import HW, make_mesh, make_production_mesh
from repro.launch.specs import batch_shardings, cell_plan, input_specs
from repro.models import model as M
from repro.serving.engine import make_decode_fn, make_prefill_fn
from repro.sharding import partitioning as pt
from repro.training.optimizer import OptState
from repro.training.train_step import TrainState, init_state, make_train_step


def _mesh_for(tag: str):
    if ARGS.mesh_shape:
        dims = tuple(int(x) for x in ARGS.mesh_shape.split(","))
        if tag == "multi":
            assert len(dims) == 3, "multi mesh override needs 3 dims"
            return make_mesh(dims, ("pod", "data", "model"))
        return make_mesh(dims[-2:], ("data", "model"))
    return make_production_mesh(multi_pod=(tag == "multi"))


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _state_specs(state_shapes, cfg, mesh, plan):
    pspecs = pt.param_specs(state_shapes.params, cfg, mesh, fsdp=plan.fsdp,
                            tp=plan.tp)
    if plan.tcfg.zero1 and not plan.fsdp:
        opt_p = pt.zero1_specs(pspecs, state_shapes.params, mesh)
    else:
        opt_p = pspecs
    return TrainState(
        params=pspecs,
        opt=OptState(step=P(), m=opt_p, v=opt_p, master=opt_p))


def lower_cell(cfg, shape, mesh, plan):
    """Returns the lowered computation for one cell."""
    constrain = pt.make_constrain(
        mesh, sequence_parallel=plan.tcfg.sequence_parallel, tp=plan.tp)
    ins = input_specs(cfg, shape)
    bspecs = batch_shardings(cfg, shape, mesh, tp=plan.tp)

    if shape.kind == "train":
        state_shapes = jax.eval_shape(
            lambda k: init_state(k, cfg), jax.random.PRNGKey(0))
        sspecs = _state_specs(state_shapes, cfg, mesh, plan)
        step = make_train_step(cfg, plan.tcfg, constrain=constrain,
                               moe_groups=plan.moe_groups)
        metr_specs = {"loss": P(), "nll": P(), "grad_norm": P()}
        fn = jax.jit(step,
                     in_shardings=(_ns(mesh, sspecs), _ns(mesh, bspecs)),
                     out_shardings=(_ns(mesh, sspecs), _ns(mesh, metr_specs)),
                     donate_argnums=(0,))
        return fn.lower(state_shapes, ins)

    params_shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                   jax.random.PRNGKey(0))
    pspecs = pt.param_specs(params_shapes, cfg, mesh, fsdp=plan.fsdp,
                            tp=plan.tp)

    if shape.kind == "prefill":
        fn = make_prefill_fn(cfg, constrain=constrain,
                             moe_groups=plan.moe_groups, max_len=plan.max_len)
        out_shapes = jax.eval_shape(fn, params_shapes, ins["batch_in"])
        tok_spec = pt.data_spec(mesh, out_shapes[0].shape, tp=plan.tp)
        cspecs = pt.cache_specs(out_shapes[1], cfg, mesh, tp=plan.tp)
        jfn = jax.jit(fn,
                      in_shardings=(_ns(mesh, pspecs),
                                    _ns(mesh, bspecs["batch_in"])),
                      out_shardings=(_ns(mesh, tok_spec), _ns(mesh, cspecs)))
        return jfn.lower(params_shapes, ins["batch_in"])

    # decode
    cache_shapes = M.init_cache(cfg, shape.global_batch, plan.max_len,
                                dtype=jnp.dtype(cfg.compute_dtype),
                                abstract=True)
    cspecs = pt.cache_specs(cache_shapes, cfg, mesh, tp=plan.tp)
    fn = make_decode_fn(cfg, constrain=constrain, moe_groups=plan.moe_groups)
    out_shapes = jax.eval_shape(fn, params_shapes, cache_shapes,
                                ins["tokens"], ins["cur_pos"])
    tok_spec = pt.data_spec(mesh, out_shapes[0].shape, tp=plan.tp)
    jfn = jax.jit(fn,
                  in_shardings=(_ns(mesh, pspecs), _ns(mesh, cspecs),
                                _ns(mesh, bspecs["tokens"]),
                                _ns(mesh, P())),
                  out_shardings=(_ns(mesh, tok_spec), _ns(mesh, cspecs)),
                  donate_argnums=(1,))
    return jfn.lower(params_shapes, cache_shapes, ins["tokens"],
                     ins["cur_pos"])


def run_cell(arch: str, shape_name: str, mesh_tag: str, outdir: str) -> dict:
    cfg = ARCHS[arch]
    if ARGS.moe_ep and cfg.moe is not None:
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                expert_parallel=True))
    shape = SHAPES_BY_NAME[shape_name]
    mesh = _mesh_for(mesh_tag)
    plan = cell_plan(cfg, shape, mesh, tp=ARGS.tp)
    if ARGS.sequence_parallel:
        plan = dataclasses.replace(plan, tcfg=dataclasses.replace(
            plan.tcfg, sequence_parallel=ARGS.sequence_parallel == "on"))
    if ARGS.fsdp:
        plan = dataclasses.replace(plan, fsdp=ARGS.fsdp == "on")
    if ARGS.remat:
        plan = dataclasses.replace(plan, tcfg=dataclasses.replace(
            plan.tcfg, remat=ARGS.remat))
    if ARGS.microbatch >= 0:
        plan = dataclasses.replace(plan, tcfg=dataclasses.replace(
            plan.tcfg, microbatch=ARGS.microbatch))

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "mesh_shape": dict(mesh.shape), "devices": mesh.size,
        "plan": plan.as_dict(),
        "status": "ok",
    }
    t0 = time.time()
    try:
        lowered = lower_cell(cfg, shape, mesh, plan)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_est": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
        }
        rec["fits_hbm"] = rec["memory"]["peak_bytes_est"] <= HW["hbm_bytes"]
        ca = compiled.cost_analysis() or {}
        rec["xla_cost"] = {k: float(v) for k, v in ca.items()
                           if k in ("flops", "bytes accessed",
                                    "transcendentals")}
        text = compiled.as_text()
        totals = analyze_text(text)
        rec["hlo"] = {k: v for k, v in totals.items()
                      if k != "collective_detail"}
        rec["collective_detail"] = totals["collective_detail"]
        rec["roofline"] = roofline_score(cfg, shape, mesh.size,
                                         rec["plan"], totals)
        if not ARGS.no_hlo:
            hdir = os.path.join(outdir, "hlo")
            os.makedirs(hdir, exist_ok=True)
            with gzip.open(os.path.join(
                    hdir, f"{mesh_tag}__{arch}__{shape_name}{ARGS.tag}"
                          ".hlo.gz"), "wt") as f:
                f.write(text)
    except Exception as e:  # noqa: BLE001 — sweep must survive cell failures
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    archs = sorted(ARCHS) if ARGS.arch == "all" else ARGS.arch.split(",")
    mesh_tags = {"single": ["single"], "multi": ["multi"],
                 "both": ["single", "multi"]}[ARGS.mesh]
    failures = 0
    for mesh_tag in mesh_tags:
        os.makedirs(os.path.join(ARGS.out, mesh_tag), exist_ok=True)
        for arch in archs:
            cfg = ARCHS[arch]
            names = [s.name for s in shapes_for(cfg)] if ARGS.shape == "all" \
                else [s for s in ARGS.shape.split(",")
                      if s in {x.name for x in shapes_for(cfg)}]
            for shape_name in names:
                path = os.path.join(ARGS.out, mesh_tag,
                                    f"{arch}__{shape_name}{ARGS.tag}.json")
                if ARGS.skip_existing and os.path.exists(path):
                    print(f"[skip] {mesh_tag} {arch} {shape_name}", flush=True)
                    continue
                print(f"[cell] {mesh_tag} {arch} {shape_name} ...", flush=True)
                rec = run_cell(arch, shape_name, mesh_tag, ARGS.out)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"  ok lower={rec['lower_s']}s "
                          f"compile={rec['compile_s']}s "
                          f"peak={rec['memory']['peak_bytes_est']/1e9:.2f}GB "
                          f"dom={r['dominant']} "
                          f"frac={r['roofline_fraction']:.3f}", flush=True)
                else:
                    failures += 1
                    print(f"  ERROR {rec['error']}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
