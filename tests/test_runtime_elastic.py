"""Elastic runtime integration tests.

These need multiple host devices, so each test body runs in a subprocess
with XLA_FLAGS set before jax imports (the main test process keeps 1 device
— see the dry-run note in the assignment).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, n: int = 8, timeout: int = 900):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import sys
        sys.path.insert(0, {REPO + "/src"!r})
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def test_elastic_resize_preserves_training(tmp_path):
    out = run_with_devices(f"""
        import jax, numpy as np
        from repro.configs import ARCHS, reduced_config
        from repro.configs.base import TrainConfig
        from repro.runtime.elastic import ElasticTrainer

        cfg = reduced_config(ARCHS["deepseek-7b"])
        t = ElasticTrainer(cfg, TrainConfig(zero1=True), global_batch=8,
                           seq_len=16, ckpt_dir={str(tmp_path)!r},
                           model_size=2)
        devs = jax.devices()
        t.start(devs[:8])            # 4x2 mesh
        m1 = t.train_steps(3)
        t.resize(devs[:4])           # shrink to 2x2 (WS spike reclaimed 4)
        m2 = t.train_steps(2)
        t.resize(devs[:8])           # grow back
        m3 = t.train_steps(2)
        assert m3["step"] == 7, m3
        assert t.resizes == 2
        losses = [m["loss"] for m in t.metrics_log]
        assert all(np.isfinite(l) for l in losses), losses
        # training progresses: loss at the end lower than at the start
        print("LOSSES", losses)
        print("OK")
    """)
    assert "OK" in out


def test_restart_after_failure_resumes_from_checkpoint(tmp_path):
    body = f"""
        import jax
        from repro.configs import ARCHS, reduced_config
        from repro.configs.base import TrainConfig
        from repro.runtime.elastic import ElasticTrainer
        cfg = reduced_config(ARCHS["qwen2-7b"])
        t = ElasticTrainer(cfg, TrainConfig(), global_batch=4, seq_len=16,
                           ckpt_dir={str(tmp_path)!r}, model_size=1)
        t.start(jax.devices()[:4])
        t.train_steps(2)
        t.checkpoint()
        print("STEP", t.step)
    """
    out1 = run_with_devices(body, n=4)
    assert "STEP 2" in out1
    # "node failure": a fresh process restores and continues on FEWER devices
    out2 = run_with_devices(f"""
        import jax
        from repro.configs import ARCHS, reduced_config
        from repro.configs.base import TrainConfig
        from repro.runtime.elastic import ElasticTrainer
        cfg = reduced_config(ARCHS["qwen2-7b"])
        t = ElasticTrainer(cfg, TrainConfig(), global_batch=4, seq_len=16,
                           ckpt_dir={str(tmp_path)!r}, model_size=1)
        t.start(jax.devices()[:2])   # two devices lost
        assert t.step == 2, t.step
        m = t.train_steps(1)
        assert m["step"] == 3
        print("RESUMED", m["step"])
    """, n=4)
    assert "RESUMED 3" in out2


def test_orchestrator_policy_shrinks_and_grows_trainer(tmp_path):
    out = run_with_devices(f"""
        import jax, numpy as np
        from repro.configs import ARCHS, reduced_config
        from repro.configs.base import TrainConfig
        from repro.runtime.elastic import ElasticTrainer
        from repro.runtime.serving_pool import ServingPool
        from repro.runtime.orchestrator import MultiTenantOrchestrator
        from repro.models import model as M

        cfg = reduced_config(ARCHS["deepseek-7b"])
        trainer = ElasticTrainer(cfg, TrainConfig(), global_batch=8,
                                 seq_len=16, ckpt_dir={str(tmp_path)!r},
                                 model_size=1)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        pool = ServingPool(cfg, params, capacity_tokens_per_replica=100.0)
        orch = MultiTenantOrchestrator(policy="paper")
        orch.add_batch("st", trainer, priority=1, min_devices=2)
        orch.add_latency("ws", pool, priority=0)
        orch.start()                       # all 8 devices -> trainer
        assert len(orch.devs.groups["st"]) == 8
        orch.train_steps("st", 1)
        orch.latency_tick("ws", 90.0)            # util>0.8 -> scale up
        assert len(pool.replicas) == 2
        assert len(orch.devs.groups["st"]) == 6  # trainer shrank
        orch.train_steps("st", 1)
        # serve a request through the balancer
        outp = pool.submit(np.array([[1,2,3,4]], dtype=np.int32), 4)
        assert outp.shape == (1, 4)
        orch.latency_tick("ws", 0.0)             # scale down
        assert len(pool.replicas) == 1           # floor n=1
        m = orch.train_steps("st", 1)
        assert np.isfinite(m["loss"])
        print("EVENTS", orch.events)
        print("OK")
    """)
    assert "OK" in out
