"""``BENCHMARK.json`` and the files the harness finds by name: a new
configuration, traffic mix and metric are found from new files and entries
alone, and the committed benchmark names only files that exist."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import run as R  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_a_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    here = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (here / sub).mkdir(parents=True)
    (here / "configs" / "tiny-x.json").write_text(json.dumps(
        {"name": "tiny-x", "hidden_size": 8}))
    (here / "traffic" / "burst-x.json").write_text(json.dumps(
        {"kind": "serve_open_loop", "rate_per_s": 9.5}))
    (here / "limits" / "tiny-burst.json").write_text(json.dumps(
        {"max_logit_gap": 1.0}))
    (here / "metrics" / "made_up.layer.py").write_text(
        "def read(run):\n    return run['x'] * 2\n")
    bench = {
        "configs": [{"name": "tiny-x", "file": "bench/configs/tiny-x.json"}],
        "workloads": [{"name": "tiny-burst", "config": "tiny-x",
                       "traffic": "burst-x", "chips": 1}],
        "end_to_end": [{"name": "e2e_a"},
                       {"name": "e2e_b", "workloads": ["other"]}],
        "per_layer": [{"name": "made_up.layer", "moves": "e2e_a"},
                      {"name": "silent", "moves": "e2e_b"}],
    }
    cell, sizes, traffic, limits = R.cell_files(bench, "tiny-burst",
                                                root=tmp_path, here=here)
    assert sizes["hidden_size"] == 8 and traffic["rate_per_s"] == 9.5
    assert limits == {"max_logit_gap": 1.0}
    assert R.reader("made_up.layer", here=here)({"x": 21}) == 42
    assert [m["name"] for m in bench["per_layer"]
            if R.applies(m, cell, bench)] == ["made_up.layer"]
    with pytest.raises(KeyError):
        R.cell_files(bench, "no-such-cell", root=tmp_path, here=here)


def test_an_unknown_device_has_no_peaks():
    assert R.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        R.peaks_for("TPU v0 imaginary")


def test_the_committed_benchmark_names_files_that_exist():
    bench = R.load_benchmark()
    paths = bench["paths"]
    assert bench["command"][:2] == ["python3", "benchmarks/chip/run.py"]
    for entry in (bench["configs"] + bench["workloads"] + bench["end_to_end"]
                  + bench["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for conf in bench["configs"]:
        assert any(conf["file"].startswith(p + "/") for p in paths)
        sizes = json.loads((ROOT / conf["file"]).read_text())
        assert set(conf["reduced"]) <= set(sizes["reduced"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for cell in bench["workloads"]:
        c, sizes, traffic, limits = R.cell_files(bench, cell["name"])
        assert R.kind_module(traffic["kind"]).run
        reported = [m for m in bench["end_to_end"] if R.applies(m, c, bench)]
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        layers = [m for m in bench["per_layer"] if R.applies(m, c, bench)]
        assert layers
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert callable(R.reader(m["name"]))
        for w in m.get("workloads", []):
            cell = R.find(bench["workloads"], w, "workload")
            moved = R.find(bench["end_to_end"], m["moves"], "metric")
            assert R.applies(moved, cell, bench)


def test_run_refuses_without_a_tpu(capsys):
    """On the CPU the run exits 2 before set-up and prints no result."""
    assert R.main(["--workload", "serve-deepseek-chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_run_fails_with_only_the_benchmarks_own_files(tmp_path):
    """A checkout holding only BENCHMARK.json and benchmarks/chip has no
    program to run: the command exits non-zero and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    cmd = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    p = subprocess.run(cmd + ["--workload", "serve-deepseek-chat", "--seed",
                              "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert p.stdout.strip() == ""
