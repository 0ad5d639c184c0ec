"""Spans and counters of the serving path: batcher, pool, replica.

Spans are ``jax.profiler.TraceAnnotation`` host events named ``serve.*``,
one per layer boundary per generation round (none per decode step). They
land in the profiler's own trace, on the device trace's clock, so an idle
gap on the chip lies under the span the host was in at the time:

- ``serve.round``: ``ContinuousBatcher.run_round``; its arguments are the
  round's number, its batch size and its request ids (space-separated),
  which join a request's spans;
- ``serve.pack``: the padding and stacking of the round's prompts;
- ``serve.prefill``: ``Replica.generate`` copying the prompt to the chip
  and dispatching the prefill and the argmax of its first token;
- ``serve.decode``: the dispatch of the token loop;
- ``serve.fetch``: the tokens brought to the host.

Wall-clock spans do not go into ``core/telemetry``: that trace is on the
simulator's virtual clock and must stay deterministic.

Counters are process-wide, like the profiler and ``jax.monitoring``, so a
reader needs no handle on the batcher or the pool. They stay on with the
profiler off and are added to once per request or per round, under a
lock, since replicas may serve from threads of their own:

- ``serve.requests_queued``: requests submitted to a batcher;
- ``serve.rounds``, ``serve.requests_batched``: rounds picked and the
  requests in them;
- ``serve.queue_wait_s``: the sum over batched requests of the seconds
  from ``submit`` to ``next_round`` (``Request.batched_at - queued_at``);
- ``serve.prefills``, ``serve.prompt_tokens``: prefill calls and their
  batch x prompt tokens, pads included;
- ``serve.decode_steps``, ``serve.decode_rows``: decode steps run
  (answer length - 1 per call) and batch rows over them;
- ``serve.moe_experts_hit``, ``serve.moe_max_expert_rows`` (models with
  experts): over the decode steps and MoE layers, the experts that got at
  least one row and the busiest expert's rows. The decode program sums
  them into its cache (``cache["moe_stats"]``); the replica reads them
  with a round's tokens, so they cost no program or host sync a step.
"""
from __future__ import annotations

import contextlib
import sys
import threading
from typing import Dict

COUNTERS = ("serve.requests_queued", "serve.rounds",
            "serve.requests_batched", "serve.queue_wait_s", "serve.prefills",
            "serve.prompt_tokens", "serve.decode_steps", "serve.decode_rows",
            "serve.moe_experts_hit", "serve.moe_max_expert_rows")

_counts: Dict[str, float] = dict.fromkeys(COUNTERS, 0)
_lock = threading.Lock()


def add(name: str, n: float = 1) -> None:
    with _lock:
        _counts[name] += n


def snapshot() -> Dict[str, float]:
    """A copy of every counter."""
    with _lock:
        return dict(_counts)


def reset() -> None:
    with _lock:
        for k in _counts:
            _counts[k] = 0


def span(name: str, **args):
    """A profiler span ``name`` with ``args`` as its metadata. Where JAX is
    not loaded no profiler can be running, so the batcher stays free of
    JAX for the simulator, which imports it for ``ServiceTimeModel``."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **args)
