"""Serving replica pool: the runtime analogue of the paper's WS CMS.

Each replica holds model params on one device and serves batched greedy
decoding. The balancer routes requests to the replica with the fewest
outstanding tokens (the paper's LVS least-connection policy); the §III-C
80% utilization rule decides replica count against the pool's capacity.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.serving import spans


def init_host_params(cfg: ModelConfig, seed: int = 0):
    """A serving department's master copy of the weights, made from a seed
    in host memory: replicas copy it to their own devices, so no chip holds
    a copy that serves nothing."""
    with jax.default_device(jax.devices("cpu")[0]):
        return jax.jit(M.init_params, static_argnums=1)(
            jax.random.PRNGKey(seed), cfg)


def serve_programs(cfg: ModelConfig):
    """A replica's two device programs: ``prefill(params, tokens[B,S],
    max_len)`` and ``decode(params, cache, tokens[B,1], pos)``. The decode
    step takes the cache as donated and updates it in place; a caller
    rebinds the cache it returns and uses the old one no more."""
    # named, so that the device trace reads jit_serve_prefill(<id>)
    # and jit_serve_decode(<id>) for the two programs
    def serve_prefill(p, t, ml):
        return M.prefill(p, t, cfg, max_len=ml)

    def serve_decode(p, c, t, pos):
        return M.decode_step(p, c, t, pos, cfg)

    return (jax.jit(serve_prefill, static_argnums=(2,)),
            jax.jit(serve_decode, donate_argnums=(1,)))


class Replica:
    def __init__(self, cfg: ModelConfig, params_host, device):
        self.cfg = cfg
        self.device = device
        # the programs run where their committed inputs live: the weights
        # here, the prompt below, the cache and tokens they produce after
        self.params = jax.device_put(params_host, device)
        self.outstanding = 0
        self._prefill, self._decode = serve_programs(cfg)

    def generate(self, prompt: np.ndarray, max_new: int) -> np.ndarray:
        """prompt: [B, S] int32. Greedy decode max_new tokens."""
        self.outstanding += prompt.size + max_new
        try:
            B, S = prompt.shape
            with spans.span("serve.prefill"):
                logits, cache = self._prefill(
                    self.params, jax.device_put(prompt, self.device),
                    S + max_new)
                toks = [jnp.argmax(logits, axis=-1)]
            spans.add("serve.prefills")
            spans.add("serve.prompt_tokens", B * S)
            with spans.span("serve.decode"):
                for i in range(max_new - 1):
                    nxt, cache = self._decode(self.params, cache,
                                              toks[-1][:, None],
                                              jnp.int32(S + i))
                    toks.append(jnp.argmax(nxt, axis=-1))
            spans.add("serve.decode_steps", max_new - 1)
            spans.add("serve.decode_rows", B * (max_new - 1))
            with spans.span("serve.fetch"):
                return np.stack([np.asarray(t) for t in toks], axis=1)
        finally:
            self.outstanding -= prompt.size + max_new


class ServingPool:
    """Least-outstanding routing + utilization-rule autoscaling."""

    def __init__(self, cfg: ModelConfig, params_host, *,
                 capacity_tokens_per_replica: float = 4096.0):
        self.cfg = cfg
        self.params_host = params_host
        self.capacity = capacity_tokens_per_replica
        self.replicas: List[Replica] = []

    # -------------------------------------------------------------- scaling
    def scale_to(self, devices: Sequence):
        """Reconcile replicas with the granted device set."""
        want = {id(d): d for d in devices}
        self.replicas = [r for r in self.replicas if id(r.device) in want]
        have = {id(r.device) for r in self.replicas}
        for d in devices:
            if id(d) not in have:
                self.replicas.append(Replica(self.cfg, self.params_host, d))

    def desired_replicas(self, offered_load_tokens: float) -> int:
        """Paper §III-C rule against token throughput capacity."""
        n = max(1, len(self.replicas))
        util = offered_load_tokens / (n * self.capacity)
        if util > 0.80:
            return n + 1
        if n > 1 and util < 0.80 * (n - 1) / n:
            return n - 1
        return n

    # -------------------------------------------------------------- serving
    def submit(self, prompt: np.ndarray, max_new: int) -> np.ndarray:
        assert self.replicas, "no replicas provisioned"
        replica = min(self.replicas, key=lambda r: r.outstanding)
        return replica.generate(prompt, max_new)
