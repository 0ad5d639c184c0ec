"""Deep unit tests: MoE dispatch semantics and chunked attention oracles."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced_config
from repro.configs.base import MoEConfig
from repro.models.moe import _capacity, _combine_group, _dispatch_group, \
    moe_forward

KEY = jax.random.PRNGKey(11)


# ------------------------------------------------------------------- MoE


def dense_moe_oracle(p, x, cfg, act_name="silu"):
    """Compute-every-expert oracle: y = sum_k prob_k * expert_k(x)."""
    from repro.models.layers import activation
    act = activation(act_name)
    logits = x.astype(jnp.float32) @ p["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.moe.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    # all experts on all tokens: [B,S,E,ff]
    h = act(jnp.einsum("bsd,edf->bsef", x, p["wi_gate"])) * \
        jnp.einsum("bsd,edf->bsef", x, p["wi_up"])
    y_all = jnp.einsum("bsef,efd->bsed", h, p["wo"])
    onehot = jax.nn.one_hot(top_i, cfg.moe.num_experts)       # [B,S,k,E]
    w = jnp.einsum("bske,bsk->bse", onehot, top_p)
    return jnp.einsum("bsed,bse->bsd", y_all, w)


def test_moe_matches_dense_oracle_when_dropless():
    cfg = reduced_config(ARCHS["qwen3-moe-30b-a3b"])  # cf=8 => dropless here
    from repro.models.moe import init_moe
    p = init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32)
    y, aux = moe_forward(p, x, cfg, num_groups=2)
    y_ref = dense_moe_oracle(p, x, cfg, cfg.act)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 2e-4
    assert float(aux["moe_lb"]) > 0.0


def _router_precisions(cfg, p, x):
    """The precision of each matmul onto the router's [D, E] kernel."""
    jaxpr = jax.make_jaxpr(lambda p, x: moe_forward(p, x, cfg))(p, x)
    E = cfg.moe.num_experts
    return [e.params["precision"] for e in jaxpr.eqns
            if e.primitive.name == "dot_general"
            and e.invars[1].aval.shape == (cfg.d_model, E)]


def test_router_precision_follows_the_path():
    """The capacity path's router keeps the default matmul precision; only
    the dropless path asks for HIGHEST."""
    import dataclasses
    cfg = reduced_config(ARCHS["qwen3-moe-30b-a3b"])
    from repro.models.moe import init_moe
    p = init_moe(KEY, cfg, jnp.float32)
    x = jnp.ones((1, 8, cfg.d_model), jnp.float32)
    assert _router_precisions(cfg, p, x) == [None]
    dropless = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                 capacity_factor=None))
    highest = jax.lax.Precision.HIGHEST
    assert _router_precisions(dropless, p, x) == [(highest, highest)]


def test_dispatch_drops_beyond_capacity():
    E, cap, d = 4, 2, 8
    n, k = 6, 1
    xg = jnp.arange(n * d, dtype=jnp.float32).reshape(n, d)
    # all tokens want expert 0: only `cap` survive
    eidx = jnp.zeros((n, k), jnp.int32)
    probs = jnp.ones((n, k), jnp.float32)
    buf, coords = _dispatch_group(xg, probs, eidx, E, cap)
    keep = coords[3]
    assert int(keep.sum()) == cap
    # kept tokens are the FIRST cap tokens (stable sort preserves order)
    np.testing.assert_array_equal(np.asarray(buf[0, 0]), np.asarray(xg[0]))
    np.testing.assert_array_equal(np.asarray(buf[0, 1]), np.asarray(xg[1]))
    # combine returns zeros for dropped tokens
    y = _combine_group(buf, coords, n)
    assert float(jnp.abs(y[cap:]).max()) == 0.0


def test_capacity_is_mxu_aligned():
    m = MoEConfig(num_experts=8, top_k=2, d_ff_expert=16, capacity_factor=1.0)
    assert _capacity(100, m) % 8 == 0
    assert _capacity(1, m) == 8              # floor


def test_moe_group_invariance():
    """Group count changes dispatch locality, not (dropless) results."""
    cfg = reduced_config(ARCHS["dbrx-132b"])
    from repro.models.moe import init_moe
    p = init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (4, 8, cfg.d_model), jnp.float32)
    y1, _ = moe_forward(p, x, cfg, num_groups=1)
    y2, _ = moe_forward(p, x, cfg, num_groups=4)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 2e-4


# ------------------------------------------------ chunked attention oracle


def naive_causal_attention(q, k, v, positions, window=0):
    B, S, H, hd = q.shape
    K = k.shape[2]
    g = H // K
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(hd)
    qp, kp = positions[:, None], positions[None, :]
    mask = kp <= qp
    if window:
        mask &= kp > qp - window
    s = jnp.where(mask[None, None], s.astype(jnp.float32), -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(vv.dtype), vv)


@pytest.mark.parametrize("S,window,qc", [(64, 0, 16), (128, 0, 64),
                                         (64, 24, 16), (128, 32, 32)])
def test_chunked_attention_matches_naive(S, window, qc):
    from repro.models.attention import chunked_causal_attention
    B, H, K, hd = 2, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    pos = jnp.arange(S)
    out = chunked_causal_attention(q, k, v, pos, window=window, q_chunk=qc)
    ref = naive_causal_attention(q, k, v, pos, window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_chunked_attention_chunk_size_invariance():
    from repro.models.attention import chunked_causal_attention
    B, S, H, K, hd = 1, 128, 2, 1, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    pos = jnp.arange(S)
    a = chunked_causal_attention(q, k, v, pos, q_chunk=32)
    b = chunked_causal_attention(q, k, v, pos, q_chunk=128)
    assert float(jnp.max(jnp.abs(a - b))) < 2e-5


@pytest.mark.parametrize("window", [0, 24])
def test_chunked_attention_takes_a_value_width_and_a_scale(window):
    """A value width other than the query/key width and an explicit
    softmax scale (latent attention's prefill: qk 24, v 16 here) match the
    naive attention; the old call, without them, gives bit for bit what
    the explicit 1/sqrt(hd) gives."""
    from repro.models.attention import chunked_causal_attention
    B, S, H, K, hd, dv, scale = 2, 64, 4, 2, 24, 16, 0.37
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, dv), jnp.float32)
    pos = jnp.arange(S)
    out = chunked_causal_attention(q, k, v, pos, window=window, q_chunk=16,
                                   scale=scale)
    assert out.shape == (B, S, H, dv)
    ref = naive_causal_attention(q * scale * math.sqrt(hd), k, v, pos,
                                 window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    old = chunked_causal_attention(q, k, k, pos, window=window, q_chunk=16)
    explicit = chunked_causal_attention(q, k, k, pos, window=window,
                                        q_chunk=16,
                                        scale=1.0 / math.sqrt(hd))
    np.testing.assert_array_equal(np.asarray(old), np.asarray(explicit))


# --------------------------------------------------------- optimizer units


def test_int8_grad_compression_bounded_error():
    from repro.training.optimizer import quantize_int8
    g = {"w": jax.random.normal(KEY, (64, 64)) * 0.01}
    gq = quantize_int8(g)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert float(jnp.max(jnp.abs(gq["w"] - g["w"]))) <= scale * 0.5 + 1e-9


def test_adamw_decreases_loss_on_quadratic():
    from repro.configs.base import TrainConfig
    from repro.training.optimizer import adamw_update, init_opt_state
    tcfg = TrainConfig(learning_rate=0.1, weight_decay=0.0, z_loss=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    opt = init_opt_state(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}       # d/dw ||w||^2
        params, opt, _ = adamw_update(opt, grads, params, tcfg)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_moe_expert_parallel_same_math_on_single_device():
    """EP changes sharding, not semantics: identical outputs on one device."""
    import dataclasses
    cfg = reduced_config(ARCHS["qwen3-moe-30b-a3b"])
    cfg_ep = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                               expert_parallel=True))
    from repro.models.moe import init_moe
    p = init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32)
    y1, _ = moe_forward(p, x, cfg, num_groups=2)
    y2, _ = moe_forward(p, x, cfg_ep, num_groups=2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)


@pytest.mark.parametrize("S,window,max_len", [
    (8, 0, 12),      # global cache longer than the prompt
    (8, 0, 8),       # global cache exactly full
    (8, 16, 12),     # window wider than the cache: plain prefix
    (8, 4, 12),      # ring of 4 wrapped twice
    (8, 6, 12),      # ring of 6, prompt wraps mid-ring
    (5, 8, 12),      # ring not yet full
])
def test_prefill_cache_matches_stepwise_decode_cache(S, window, max_len):
    """Prefill's cache puts position p at ring slot p % L, exactly where
    decoding would have written it one token at a time."""
    from repro.models import attention as attn
    cfg = reduced_config(ARCHS["deepseek-7b"])
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    p = attn.init_attention(k1, cfg, jnp.float32)
    x = jax.random.normal(k2, (2, S, cfg.d_model), jnp.float32)
    _, cache = attn.attention_prefill(p, x, cfg, jnp.arange(S),
                                      window=window, max_len=max_len)
    step = attn.init_kv_cache(cfg, 2, max_len, window=window,
                              dtype=jnp.float32)
    for t in range(S):
        _, rows = attn.attention_decode(p, x[:, t:t + 1], step, cfg, t,
                                        window=window)
        step = attn.write_kv_rows(step, rows, t)
    np.testing.assert_array_equal(cache["pos"], step["pos"])
    np.testing.assert_allclose(cache["k"], step["k"], atol=1e-5)
    np.testing.assert_allclose(cache["v"], step["v"], atol=1e-5)
