"""Sampler + continuous batcher + replica + data pipeline unit tests."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:       # container without hypothesis: property tests skip
    HAS_HYPOTHESIS = False

from repro.serving.sampler import SamplerConfig, sample
from repro.serving.batching import ContinuousBatcher, Request


KEY = jax.random.PRNGKey(0)


def test_greedy_sampling():
    logits = jnp.array([[0.1, 5.0, 0.2], [3.0, 0.0, -1.0]])
    out = sample(logits, KEY, SamplerConfig(greedy=True))
    assert out.tolist() == [1, 0]


def test_top_k_restricts_support():
    logits = jnp.array([10.0, 9.0, -50.0, -50.0])
    cfg = SamplerConfig(top_k=2, temperature=1.0)
    toks = [int(sample(logits, jax.random.PRNGKey(i), cfg))
            for i in range(50)]
    assert set(toks) <= {0, 1}


def test_top_p_restricts_support():
    logits = jnp.log(jnp.array([0.6, 0.3, 0.05, 0.05]))
    cfg = SamplerConfig(top_p=0.85)
    toks = [int(sample(logits, jax.random.PRNGKey(i), cfg))
            for i in range(80)]
    assert set(toks) <= {0, 1}


def test_temperature_zero_ish_is_greedy():
    logits = jnp.array([1.0, 1.5, 0.2])
    cfg = SamplerConfig(temperature=1e-5)
    toks = {int(sample(logits, jax.random.PRNGKey(i), cfg))
            for i in range(20)}
    assert toks == {1}


if not HAS_HYPOTHESIS:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_batcher_serves_everything():
        pass
else:
    @given(st.lists(st.integers(1, 63), min_size=1, max_size=20),
           st.integers(1, 8))
    @settings(max_examples=30, deadline=None)
    def test_batcher_serves_everything(prompt_lens, max_batch):
        batcher = ContinuousBatcher(max_batch=max_batch, bucket=64)
        for i, L in enumerate(prompt_lens):
            batcher.submit(Request(i, np.arange(L, dtype=np.int32), 4))
        served = []

        def gen(prompts, max_new):
            served.append(prompts.shape[0])
            return np.zeros((prompts.shape[0], max_new), np.int32)

        while batcher.queue:
            reqs = batcher.next_round()
            assert 0 < len(reqs) <= max_batch
            batcher.run_round(reqs, gen)
        assert len(batcher.completed) == len(prompt_lens)
        assert sum(served) == len(prompt_lens)


def test_data_pipeline_deterministic_and_resumable():
    from repro.configs import ARCHS, reduced_config
    from repro.data.pipeline import SyntheticLM
    cfg = reduced_config(ARCHS["deepseek-7b"])
    d1 = SyntheticLM(cfg, seed=3)
    d2 = SyntheticLM(cfg, seed=3)
    b1 = d1.batch(17, 4, 32)
    b2 = d2.batch(17, 4, 32)   # fresh instance, same step -> same batch
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = d1.batch(18, 4, 32)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_data_pipeline_host_sharding_partitions_batch():
    from repro.configs import ARCHS, reduced_config
    from repro.data.pipeline import SyntheticLM
    cfg = reduced_config(ARCHS["qwen2-7b"])
    d = SyntheticLM(cfg, seed=0)
    full_rows = 8
    shards = [d.batch(5, full_rows, 16, host_id=h, host_count=2)
              for h in range(2)]
    assert all(s["tokens"].shape == (4, 16) for s in shards)
    # different hosts draw different rows
    assert not np.array_equal(np.asarray(shards[0]["tokens"]),
                              np.asarray(shards[1]["tokens"]))


def test_replica_decode_takes_the_prefill_cache_in_place():
    """One ``generate`` hands the prefill's cache to the donated decode
    step: its buffers are consumed, and JAX finds every one usable."""
    from repro.configs import ARCHS, reduced_config
    from repro.runtime.serving_pool import Replica, init_host_params
    cfg = reduced_config(ARCHS["gemma3-12b"])
    replica = Replica(cfg, init_host_params(cfg), jax.devices()[0])
    caches, prefill = [], replica._prefill

    def kept(*args):
        out = prefill(*args)
        caches.append(out[1])
        return out
    replica._prefill = kept
    prompt = np.arange(2 * 12, dtype=np.int32).reshape(2, 12) % cfg.vocab_size
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*donated buffers")
        out = replica.generate(prompt, 8)
    assert out.shape == (2, 8)
    (cache,) = caches
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(cache))
