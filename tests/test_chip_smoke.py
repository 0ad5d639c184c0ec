"""chip_smoke.py's phases and comparisons, run on the CPU at reduced sizes.

The script itself needs a TPU; its phase functions take their sizes as
arguments, so these tests drive the same code at ``mix_tiny`` and the
reduced recurrentgemma config, and check that each comparison fails when it
should. The four-device consolidation phase runs in a subprocess with four
virtual CPU devices.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402


def _quiet(msg):
    pass


def test_control_plane_phase_on_mix_tiny():
    out = cs.control_plane_phase("mix_tiny", jax.default_backend(), _quiet)
    assert out["cells"] == 7
    assert out["ws_queues"] == 14               # two WS departments per cell
    assert out["served_on"] == {jax.default_backend(): 14}
    assert out["compared"] > 100 and out["worst"] <= 1.0


def test_control_plane_phase_rejects_another_platform():
    with pytest.raises(AssertionError):
        cs.control_plane_phase("mix_tiny", "tpu", _quiet)


@pytest.mark.parametrize("delta,ok", [(0.0, True), (1e-3, True),
                                      (0.1, False), (float("inf"), False)])
def test_compare_reductions_holds_the_golden_tolerance(delta, ok):
    x = {"overall": {"ws_p99_s": 20.0, "cells": 7, "slo_met": True},
         "by_policy": {"paper": {"ws_p99_s": 10.0}}}
    y = json.loads(json.dumps(x))
    y["by_policy"]["paper"]["ws_p99_s"] += delta
    if ok:
        n, worst = cs.compare_reductions(x, y)
        assert n == 3 and worst <= 1.0
    else:
        with pytest.raises(AssertionError):
            cs.compare_reductions(x, y)


def test_compare_reductions_needs_the_same_keys():
    with pytest.raises(AssertionError):
        cs.compare_reductions({"a": 1.0}, {"a": 1.0, "b": 2.0})


def test_bf16_ulp():
    assert cs._bf16_ulp(np.float32(1.0)) == 2.0 ** -7
    assert cs._bf16_ulp(np.float32(-4.5)) == 2.0 ** -5
    assert cs._bf16_ulp(np.float32(0.75)) == 2.0 ** -8


def test_serving_phase_at_reduced_size():
    from repro.configs import ARCHS, reduced_config
    cfg = reduced_config(ARCHS["recurrentgemma-2b"])
    out = cs.serving_phase(cfg, rounds=2, batch=2, prompt_len=8, max_new=4,
                           device=jax.devices()[0], log=_quiet)
    assert out["total"] == 2 * 2 * 4
    # float32 at the reduced size: the cached decode reproduces the
    # cache-free forward's argmax everywhere
    assert out["agree"] == out["total"]


def test_serving_phase_catches_a_wrong_cache(monkeypatch):
    """A decode step that reads a stale position must fail the reference
    comparison, not slip through the bf16 margin."""
    from repro.configs import ARCHS, reduced_config
    from repro.models import model as M
    cfg = reduced_config(ARCHS["deepseek-7b"])
    step = M.decode_step
    monkeypatch.setattr(M, "decode_step",
                        lambda p, c, t, pos, cfg, **kw: step(p, c, t, pos - 1,
                                                             cfg, **kw))
    with pytest.raises(AssertionError):
        cs.serving_phase(cfg, rounds=1, batch=2, prompt_len=8, max_new=6,
                         device=jax.devices()[0], log=_quiet)


def test_consolidation_phase_on_four_cpu_devices(tmp_path):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        import chip_smoke as cs
        from repro.configs import ARCHS, reduced_config
        cfg = reduced_config(ARCHS["recurrentgemma-2b"])
        out = cs.consolidation_phase(cfg, cfg, jax.devices(),
                                     global_batch=12, seq_len=32, steps=2,
                                     lr=1e-3, ckpt_root={str(tmp_path)!r},
                                     log=lambda m: None)
        assert out["reclaims"] >= 1 and out["returns"] >= 1, out
        # the spike's second replica sits on a device of its own
        assert len(out["placements"][1]) == 2, out
        print("OK", out["losses"], out["reference"])
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_main_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=REPO)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_script_alone_fails(tmp_path):
    """Copied without the rest of the repository, the script cannot run."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    res = subprocess.run([sys.executable, str(alone)], capture_output=True,
                         text=True, timeout=300, cwd=tmp_path,
                         env=dict(os.environ, PYTHONPATH=""))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


@pytest.mark.parametrize("outside", [True, False])
def test_compile_cache_placement(tmp_path, outside):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache is .jax_cache/ at the checkout root."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if outside:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr
    chosen, configured = res.stdout.split()
    want = str(tmp_path) if outside else os.path.join(REPO, ".jax_cache")
    assert chosen == configured == want
