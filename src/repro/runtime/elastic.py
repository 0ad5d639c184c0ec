"""Elastic training runtime: the JAX bridge of the paper's ST CMS.

An ``ElasticTrainer`` is the payload of one ST "job": it trains a model on a
rectangular sub-mesh of the shared device pool. When the Phoenix provision
policy reclaims devices (WS spike) or grants more (WS trough), the trainer

  1. checkpoints at the current step (synchronous, atomic),
  2. rebuilds the mesh over the new device set (the data axis grows or
     shrinks; the model axis is preserved so TP groups stay intact),
  3. restores state with every leaf resharded onto the new topology,
  4. re-jits the train step and continues from the same step counter.

This is the TPU-native analogue of the paper's "kill job with minimum size /
reallocate nodes in seconds": instead of losing the job's work, the job
shrinks. The checkpoint/restore path doubles as the fault-tolerance story
(restart-after-failure = restore on whatever devices remain).
"""
from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpointer as ckpt
from repro.configs.base import ModelConfig, TrainConfig
from repro.sharding import partitioning as pt
from repro.training.optimizer import OptState
from repro.training.train_step import TrainState, init_state, make_train_step


def _mesh_from_devices(devices: Sequence, model_size: int,
                       global_batch: Optional[int] = None) -> Mesh:
    """Largest usable rectangular mesh over `devices`.

    The DP extent is rounded DOWN to a divisor of the global batch (an
    elastic grant is rarely a perfect divisor; surplus devices idle until
    the next resize — they are not lost, just unused this interval).
    """
    n = len(devices)
    dp = n // model_size
    assert dp >= 1, (n, model_size)
    if global_batch is not None:
        while dp > 1 and global_batch % dp:
            dp -= 1
    arr = np.asarray(devices[:dp * model_size]).reshape(dp, model_size)
    return Mesh(arr, ("data", "model"))


class ElasticTrainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, *,
                 global_batch: int, seq_len: int, ckpt_dir: str,
                 model_size: int = 1, data_fn: Optional[Callable] = None,
                 seed: int = 0):
        self.cfg = cfg
        self.tcfg = tcfg
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.ckpt_dir = ckpt_dir
        self.model_size = model_size
        self.data_fn = data_fn
        self.seed = seed
        self.step = 0
        self.mesh: Optional[Mesh] = None
        self.state: Optional[TrainState] = None
        self._jit_step = None
        self.resizes = 0
        self.metrics_log: List[Dict] = []

    # ------------------------------------------------------------- topology
    def start(self, devices: Sequence):
        """Initial launch (fresh init or restore-if-checkpoint-exists)."""
        self.mesh = _mesh_from_devices(devices, self.model_size,
                                       self.global_batch)
        restored = self._try_restore()
        if not restored:
            # initialize straight into the sharded layout: no device ever
            # holds the whole state (at published widths it outgrows a chip)
            init = jax.jit(lambda k: init_state(k, self.cfg),
                           out_shardings=self._state_shardings())
            self.state = init(jax.random.PRNGKey(self.seed))
        self._compile()

    def resize(self, devices: Sequence):
        """Elastic resize: checkpoint -> new mesh -> restore -> re-jit."""
        assert self.state is not None
        self.checkpoint()
        self.mesh = _mesh_from_devices(devices, self.model_size,
                                       self.global_batch)
        self.state = None   # free old-buffers before restore
        self._try_restore(require=True)
        self._compile()
        self.resizes += 1

    # ---------------------------------------------------------- checkpoints
    def checkpoint(self):
        ckpt.save(self.ckpt_dir, self.state, step=self.step)

    def _state_shardings(self):
        shapes = jax.eval_shape(lambda: self.state) if self.state is not None \
            else jax.eval_shape(lambda k: init_state(k, self.cfg),
                                jax.random.PRNGKey(self.seed))
        pspecs = pt.param_specs(shapes.params, self.cfg, self.mesh)
        opt_specs = pt.zero1_specs(pspecs, shapes.params, self.mesh) \
            if self.tcfg.zero1 else pspecs
        specs = TrainState(params=pspecs,
                           opt=OptState(step=P(), m=opt_specs, v=opt_specs,
                                        master=opt_specs))
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def _try_restore(self, require: bool = False) -> bool:
        step = ckpt.latest_step(self.ckpt_dir)
        if step is None:
            if require:
                raise FileNotFoundError(self.ckpt_dir)
            return False
        shapes = jax.eval_shape(lambda k: init_state(k, self.cfg),
                                jax.random.PRNGKey(self.seed))
        self.state = ckpt.restore(self.ckpt_dir, shapes, step=step,
                                  shardings=self._state_shardings())
        self.step = step
        return True

    # -------------------------------------------------------------- compute
    def _compile(self):
        constrain = pt.make_constrain(
            self.mesh, sequence_parallel=self.tcfg.sequence_parallel)
        step_fn = make_train_step(self.cfg, self.tcfg, constrain=constrain,
                                  moe_groups=max(1, self.mesh.shape["data"]))
        sspec = self._state_shardings()
        bspec = NamedSharding(self.mesh, P("data", None))
        self._jit_step = jax.jit(
            step_fn,
            in_shardings=(sspec, {"tokens": bspec, "labels": bspec}),
            out_shardings=(sspec, None),
            donate_argnums=(0,))

    def _batch(self):
        if self.data_fn is not None:
            return self.data_fn(self.step, self.global_batch, self.seq_len)
        rng = np.random.default_rng(self.seed * 1_000_003 + self.step)
        toks = rng.integers(0, self.cfg.vocab_size,
                            (self.global_batch, self.seq_len), dtype=np.int32)
        return {"tokens": jax.numpy.asarray(toks),
                "labels": jax.numpy.asarray(np.roll(toks, -1, axis=1))}

    def train_steps(self, n: int) -> Dict:
        """Run n steps on the current mesh; returns the last metrics."""
        assert self._jit_step is not None, "call start() first"
        metrics = {}
        for _ in range(n):
            batch = self._batch()
            self.state, metrics = self._jit_step(self.state, batch)
            self.step += 1
        metrics = {k: float(v) for k, v in metrics.items()}
        metrics["step"] = self.step
        metrics["devices"] = self.mesh.size
        self.metrics_log.append(metrics)
        return metrics
