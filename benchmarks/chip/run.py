#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, from the root of a checkout:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <window> --trace <0|1>

Everything is found by the names in ``BENCHMARK.json``: the cell's
configuration file, its traffic mix ``traffic/<traffic>.json`` (whose
``kind`` names the module under ``kinds/`` that runs it), its limits
``limits/<cell>.json``, and each per-layer metric's reader
``metrics/<metric>.py``. A cell, a configuration, a traffic mix or a metric
is added with new files and entries only.

The run needs as many TPU chips as the cell asks for and exits 2 with no
result otherwise. Set-up (``setup_s``) is counted from the start of this
process to the start of the measured window. With ``--trace 0`` the result
holds the cell's end-to-end metrics; with ``--trace 1`` the window is
traced and the result holds its per-layer metrics, the device's busy and
window seconds, and a breakdown. The numbers that decide ``correct`` are
printed with their limits as the last lines of standard error and under
``checks``, the last key of the result, which is the last line of
standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACES = ROOT / ".bench_cache" / "trace"


# ------------------------------------------------------------- registry


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell_files(bench: dict, workload: str, root: Path = ROOT,
               here: Path = HERE):
    """(cell, configuration sizes, traffic, limits) for ``workload``."""
    cell = find(bench["workloads"], workload, "workload")
    conf = find(bench["configs"], cell["config"], "configuration")
    sizes = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((here / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((here / "limits" / f"{cell['name']}.json")
                        .read_text())
    return cell, sizes, traffic, limits


def applies(metric: dict, cell: dict, bench: dict) -> bool:
    """A metric with ``workloads`` belongs to the cells it lists; one
    without belongs to every cell that reports the metric it moves (an
    end-to-end metric without the key belongs to every cell)."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    if "moves" in metric:
        moved = find(bench["end_to_end"], metric["moves"], "metric")
        return applies(moved, cell, bench)
    return True


def reader(name: str, here: Path = HERE) -> Callable:
    """``metrics/<name>.py``'s ``read``; loaded by path, since a metric's
    name may hold dots."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def kind_module(kind: str):
    return importlib.import_module(f"benchmarks.chip.kinds.{kind}")


def peaks_for(device_kind: str, here: Path = HERE) -> dict:
    table = json.loads((here / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


# ------------------------------------------------------------ run state


class CompileLog:
    """Counts XLA compilations, their seconds and persistent-cache hits
    through ``jax.monitoring`` (copied from ``chip_smoke.py``)."""

    def __init__(self):
        import jax
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


class Tracer:
    """The profiler around the window, writing under ``log_dir``; ``stop``
    returns the read trace and deletes the files."""

    def __init__(self, log_dir: Path):
        self.log_dir = log_dir

    def start(self):
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1          # the benchmark's own spans
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)

    def stop(self):
        import jax
        from benchmarks.chip import trace_reduce
        jax.profiler.stop_trace()
        try:
            return trace_reduce.load(str(self.log_dir))
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


@dataclasses.dataclass
class Ctx:
    """What a traffic kind's ``run`` gets."""
    cell: dict
    sizes: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    compiles: CompileLog
    t_start: float
    log: Callable[[str], None]
    tracer: Optional[Callable[[], Tracer]] = None


def use_cache() -> str:
    """The program's persistent compilation cache, for every program
    compiled here, however short; returns its directory
    (``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``.jax_cache/``
    in the checkout)."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return use_compile_cache()


def result_line(cell, bench, out, trace: bool, device) -> dict:
    """The result object; ``out`` is what the kind's ``run`` returned."""
    metrics = {}
    if trace:
        for m in bench["per_layer"]:
            if applies(m, cell, bench):
                v = reader(m["name"])(out["layers"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if applies(m, cell, bench):
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": cell["chips"],
           "memory_peak_bytes": out["memory_peak_bytes"]}
    if trace:
        dev.update(out.get("device", {}))
    res = {"correct": out["correct"], "attempted": out["attempted"],
           "failed": out["failed"], "metrics": metrics, "device": dev}
    if trace and "breakdown" in out:
        res["breakdown"] = out["breakdown"]
    # a check that found nothing to compare reads inf; JSON has no inf
    res["checks"] = {k: {f: (x if x == x and abs(x) != float("inf")
                             else None) for f, x in c.items()}
                     for k, c in out["checks"].items()}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    bench = load_benchmark()
    cell, sizes, traffic, limits = cell_files(bench, args.workload)
    kind = kind_module(traffic["kind"])
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"{cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
            f"{devices}")
        return 2
    devices = devices[:cell["chips"]]
    peaks = peaks_for(devices[0].device_kind)
    use_cache()
    compiles = CompileLog()
    trace_dir = TRACES / cell["name"]
    ctx = Ctx(cell, sizes, traffic, limits, args.seed, args.seconds,
              bool(args.trace), devices, peaks, compiles, T_START, log,
              tracer=lambda: Tracer(trace_dir))
    out = kind.run(ctx)
    log(f"[run] {compiles.summary()}")
    res = result_line(cell, bench, out, bool(args.trace), devices[0])
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
