"""End-to-end behaviour tests for the paper's system (public API surface)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DAY = 86400.0


def test_consolidation_end_to_end_small():
    """Shared-cluster run: policies + simulator + traces wired together."""
    from repro.core.experiment import run_dynamic, run_static
    from repro.core.traces import synthetic_sdsc_blue, worldcup_demand_events
    jobs = synthetic_sdsc_blue(seed=3, n_jobs=200, horizon=DAY)
    ws = worldcup_demand_events(seed=3, horizon=DAY)
    dc = run_dynamic(jobs, ws, 180, horizon=DAY)
    assert dc.completed > 0
    assert dc.ws_unmet_node_seconds == 0.0
    sc = run_static(jobs, horizon=DAY)
    assert sc.completed > 0


def test_train_and_serve_roundtrip():
    """Train a tiny model a few steps, then serve it with batched requests."""
    from repro.configs import ARCHS, reduced_config
    from repro.configs.base import TrainConfig
    from repro.data.pipeline import SyntheticLM
    from repro.runtime.serving_pool import ServingPool
    from repro.serving.batching import ContinuousBatcher, Request
    from repro.training.train_step import init_state, make_train_step

    cfg = reduced_config(ARCHS["qwen2-7b"])
    state = init_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, TrainConfig(learning_rate=1e-3)),
                   donate_argnums=(0,))
    data = SyntheticLM(cfg, seed=1)
    losses = []
    for i in range(4):
        state, m = step(state, data.batch(i, 4, 32))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]

    pool = ServingPool(cfg, state.params, capacity_tokens_per_replica=1e9)
    pool.scale_to(jax.devices()[:1])
    batcher = ContinuousBatcher(max_batch=4)
    for i in range(4):
        batcher.submit(Request(i, np.arange(6, dtype=np.int32) + 1, 4))
    reqs = batcher.next_round()
    batcher.run_round(reqs, pool.submit)
    assert len(batcher.completed) == 4
    assert all(r.done.shape == (4,) for r in batcher.completed)


def test_dryrun_small_mesh_subprocess():
    """The dry-run driver works end-to-end on a test-scale mesh."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen2-7b", "--shape", "decode_32k", "--mesh", "single",
         "--devices", "8", "--mesh-shape", "2,4",
         "--out", "/tmp/dryrun_pytest", "--no-hlo"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ok lower=" in res.stdout


def test_input_specs_cover_all_cells():
    """Every (arch x applicable shape) produces well-formed abstract inputs."""
    from repro.configs import ARCHS, shapes_for
    from repro.launch.specs import input_specs
    cells = 0
    for cfg in ARCHS.values():
        for shape in shapes_for(cfg):
            specs = input_specs(cfg, shape)
            assert all(hasattr(v, "shape") for v in specs.values())
            cells += 1
    # 11 archs x 3 shapes (train_4k, prefill_32k, decode_32k) + long_500k
    # for the 3 sub-quadratic ones
    assert cells == 36
