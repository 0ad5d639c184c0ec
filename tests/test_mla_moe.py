"""Latent attention (MLA) and the dropless shared-expert MoE, at a small size
on the CPU in float32: the served path (``ServingPool``/``Replica``, prefill
then decode through the latent cache) against the plain DeepSeek-V2
reference (``benchmarks/chip/references/deepseek_v2.py``) on seeded random
weights, the absorbed decode against the expanded attention, the dropless
routing against an all-experts oracle, the YaRN numbers against hand
computation, and the routing counters against routing recomputed here.
"""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.chip.kinds import serve_open_loop_deepseek_v2 as K  # noqa: E402
from benchmarks.chip.references import deepseek_v2 as ref  # noqa: E402
from repro.configs import ARCHS, reduced_config  # noqa: E402
from repro.models import mla  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.moe import init_moe, moe_forward  # noqa: E402
from repro.runtime.serving_pool import ServingPool  # noqa: E402
from repro.serving import spans  # noqa: E402

# DeepSeek-V2-Lite's structure at tiny widths: a dense layer, then MoE
# layers of 8 experts (top 2, raw gates) with 2 shared, latent rank 32
SIZES = {
    "name": "tiny-v2", "hidden_size": 64, "num_attention_heads": 4,
    "intermediate_size": 128, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "first_k_dense_replace": 1, "num_hidden_layers": 3, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096},
    "torch_dtype": "float32",
}
# float32 throughout: the program and the reference differ only in the
# order of float32 sums (absorbed vs expanded attention, grouped vs dense
# experts, chunked softmax), ~1e-6 of logits of order 1
TOL = 2e-4


def _weights(seed=3):
    return ref.init_weights(jax.random.PRNGKey(seed), SIZES, jnp.float32)


def _pool(w):
    cfg = K.program_config(SIZES)
    pool = ServingPool(cfg, K.program_params(w),
                       capacity_tokens_per_replica=float("inf"))
    pool.scale_to(jax.devices()[:1])
    return cfg, pool


def test_served_prefill_and_decode_match_the_reference():
    """The replica's two programs, prefill then decode through the latent
    cache (teacher-forced), give the reference's logits at every position;
    ``Replica.generate``'s greedy tokens are the reference's first choices."""
    w = _weights()
    cfg, pool = _pool(w)
    rep = pool.replicas[0]
    B, P, S = 3, 13, 21
    seq = np.random.default_rng(0).integers(0, 256, (B, S)).astype(np.int32)
    want = np.asarray(ref.logits(w, seq, SIZES, P - 1))        # [B, S-P+1, V]
    lg, cache = rep._prefill(rep.params, jnp.asarray(seq[:, :P]), S)
    got = [np.asarray(lg)]
    for t in range(P, S):
        lg, cache = rep._decode(rep.params, cache, jnp.asarray(seq[:, t:t + 1]),
                                jnp.int32(t))
        got.append(np.asarray(lg))
    np.testing.assert_allclose(np.stack(got, 1), want, rtol=TOL, atol=TOL)

    out = pool.submit(seq[:, :P], S - P)
    full = np.concatenate([seq[:, :P], out[:, :-1]], 1)
    best = np.asarray(ref.logits(w, full, SIZES, P - 1)).argmax(-1)
    np.testing.assert_array_equal(out, best)


def test_absorbed_decode_matches_expanded_attention_on_the_same_cache():
    cfg = K.program_config(SIZES)
    p = mla.init_mla(jax.random.PRNGKey(1), cfg, jnp.float32)
    p["kv_norm"]["scale"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                    (32,))
    B, S = 2, 17
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S + 1, cfg.d_model))
    full = mla.mla_forward(p, x, cfg, jnp.arange(S + 1))
    _, cache = mla.mla_prefill(p, x[:, :S], cfg, jnp.arange(S), max_len=S + 5)
    assert cache["lat"].shape == (B, S + 5, 32 + 8)
    assert np.asarray(cache["pos"]).tolist() == list(range(S)) + [-1] * 5
    y, rows = mla.mla_decode(p, x[:, S:], cache, cfg, jnp.int32(S))
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(full[:, S]),
                               rtol=TOL, atol=TOL)
    # the row the step hands back is the latent prefill would have cached
    _, longer = mla.mla_prefill(p, x, cfg, jnp.arange(S + 1), max_len=S + 5)
    written = mla.write_latent_rows(cache, rows, jnp.int32(S))
    np.testing.assert_allclose(np.asarray(written["lat"]),
                               np.asarray(longer["lat"]), rtol=TOL, atol=TOL)
    np.testing.assert_array_equal(written["pos"], longer["pos"])


def _all_experts_oracle(p, x, cfg):
    """Every expert on every token, weighted by the raw top-k probabilities,
    plus the shared MLP."""
    from repro.models.layers import mlp
    m = cfg.moe
    probs = jax.nn.softmax(x @ p["router"]["kernel"], -1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    g = jnp.einsum("...ke,...k->...e", jax.nn.one_hot(top_i, m.num_experts),
                   top_p)
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["wi_gate"])) \
        * jnp.einsum("bsd,edf->bsef", x, p["wi_up"])
    y = jnp.einsum("bsef,efd,bse->bsd", h, p["wo"], g)
    return y + mlp(p["shared"], x, cfg.act)


def test_dropless_moe_drops_nothing_when_every_token_routes_to_one_expert():
    """All tokens choose experts 3 and 5: a capacity-bound dispatch drops
    most of them, the dropless path matches the all-experts oracle."""
    cfg = reduced_config(ARCHS["deepseek-v2-lite"])
    assert cfg.moe.capacity_factor is None and not cfg.moe.norm_topk_prob
    p = init_moe(jax.random.PRNGKey(4), cfg, jnp.float32)
    router = np.zeros((cfg.d_model, 8), np.float32)
    router[:, 3], router[:, 5] = 0.05, 0.03
    p["router"]["kernel"] = jnp.asarray(router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model)))
    y, aux = moe_forward(p, x, cfg)
    want = _all_experts_oracle(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=TOL,
                               atol=TOL)
    assert int(aux["moe_hit"]) == 2 and int(aux["moe_max_rows"]) == 64
    capped = cfg.with_(moe=cfg.moe.__class__(
        num_experts=8, top_k=2, d_ff_expert=32, capacity_factor=1.0,
        num_shared_experts=2, norm_topk_prob=False))
    y_cap, _ = moe_forward(p, x, capped, num_groups=1)
    assert float(jnp.max(jnp.abs(y_cap - want))) > 100 * TOL


def test_dropless_moe_reads_a_layer_from_the_whole_stack():
    """Given the whole stack of expert weights and a layer index, the
    grouped matmul computes that layer's experts."""
    cfg = reduced_config(ARCHS["deepseek-v2-lite"])
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    layers = [init_moe(k, cfg, jnp.float32) for k in keys]
    stack = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, cfg.d_model))
    for i, p in enumerate(layers):
        whole = dict(p, layer=jnp.int32(i), **{
            n: stack[n] for n in ("wi_gate", "wi_up", "wo")})
        np.testing.assert_allclose(np.asarray(moe_forward(whole, x, cfg)[0]),
                                   np.asarray(moe_forward(p, x, cfg)[0]),
                                   rtol=1e-6, atol=1e-6)


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """DeepSeek-V2-Lite's published rope settings: dim 64, theta 1e4, YaRN
    x40 over 4096 with beta 32/1 put the correction range at dimensions
    floor(10.47) = 10 and ceil(22.52) = 23; frequencies below 10 are
    theta's, above 23 theta's over 40, linear between. The scale is
    (0.1 * 0.707 * ln 40 + 1)^2 / sqrt(192)."""
    m = ARCHS["deepseek-v2-lite"].mla
    base = [10000.0 ** (-2 * i / 64) for i in range(32)]
    lo = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000))
    hi = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000))
    assert (math.floor(lo), math.ceil(hi)) == (10, 23)
    want = []
    for i, f in enumerate(base):
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        want.append(f / 40 * ramp + f * (1 - ramp))
    got = np.asarray(mla.rope_inv_freq(m, 10000.0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[10] == pytest.approx(base[10]) and got[23] == pytest.approx(
        base[23] / 40)
    np.testing.assert_allclose(np.asarray(ref.inv_freq(
        {"qk_rope_head_dim": 64, "rope_theta": 10000,
         "rope_scaling": SIZES["rope_scaling"]})), want, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert mla.softmax_scale(m) == pytest.approx(mscale ** 2 / math.sqrt(192))
    assert mla.rope_mscale(m.rope_scaling) == 1.0


def _host_routing(w, seq):
    """The experts each MoE layer picks for each token of ``seq``, from the
    reference's layers: [moe layers][B, S, k]."""
    s = SIZES
    x = w["embed"][seq].astype(jnp.float32)
    for i in range(w["dense"]["wq"].shape[0]):
        x = ref._dense_layer(x, jax.tree.map(lambda a: a[i], w["dense"]), s,
                             None)
    picks = []
    for i in range(w["moe"]["wq"].shape[0]):
        lw = jax.tree.map(lambda a: a[i].astype(jnp.float32), w["moe"])
        x = ref._attention(x, lw, s, None)
        h = ref._rmsnorm(x, lw["mlp_norm"], s["rms_norm_eps"])
        probs = jax.nn.softmax(ref._mm(h, lw["router"], None), -1)
        picks.append(np.asarray(jax.lax.top_k(probs, 2)[1]))
        x = x + ref._moe(h, lw, s, None)
    return picks


def test_routing_counters_match_routing_recomputed_on_the_host():
    w = _weights(seed=8)
    cfg, pool = _pool(w)
    B, P, new = 3, 11, 6
    prompt = np.random.default_rng(1).integers(0, 256, (B, P)).astype(np.int32)
    spans.reset()
    out = pool.submit(prompt, new)
    c = spans.snapshot()
    spans.reset()
    seq = np.concatenate([prompt, out[:, :-1]], 1)
    hit = rows = 0
    for picks in _host_routing(w, seq):
        for t in range(P, P + new - 1):           # the decode steps' tokens
            counts = np.bincount(picks[:, t].ravel(), minlength=8)
            hit += int((counts > 0).sum())
            rows += int(counts.max())
    assert c["serve.decode_steps"] == new - 1
    assert (c["serve.moe_experts_hit"], c["serve.moe_max_expert_rows"]) == \
        (hit, rows)
    assert 0 < hit <= 2 * (new - 1) * B * 2


def test_dense_models_have_no_counters_in_their_cache():
    cfg = reduced_config(ARCHS["deepseek-7b"])
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    _, cache = M.prefill(params, jnp.zeros((1, 4), jnp.int32), cfg,
                         max_len=6)
    assert set(cache) == {"repeats", "tail"}
    assert set(M.init_cache(cfg, 1, 6)) == {"repeats", "tail"}
