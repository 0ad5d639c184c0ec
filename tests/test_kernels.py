"""Pallas kernel validation: shape/dtype sweeps, interpret mode vs oracle."""
import jax
import jax.numpy as jnp
import pytest

KEY = jax.random.PRNGKey(7)


# ------------------------------------------------------------ flash attention


@pytest.mark.parametrize("B,S,H,K,hd,win", [
    (2, 256, 4, 2, 128, 0),
    (1, 512, 4, 4, 128, 0),
    (2, 256, 8, 2, 128, 128),
    (1, 256, 2, 1, 128, 64),      # MQA + window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_oracle(B, S, H, K, hd, win, dtype):
    from repro.kernels.flash_attention.ops import flash_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
    o_ref = flash_attention(q, k, v, impl="ref", window=win)
    o_pal = flash_attention(q, k, v, impl="interpret", window=win,
                            block_q=128, block_k=128)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert float(jnp.max(jnp.abs(o_ref.astype(jnp.float32)
                                 - o_pal.astype(jnp.float32)))) < tol


def test_flash_attention_block_shape_independent():
    from repro.kernels.flash_attention.ops import flash_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 512, 4, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, 512, 2, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, 512, 2, 128), jnp.float32)
    a = flash_attention(q, k, v, impl="interpret", block_q=128, block_k=256)
    b = flash_attention(q, k, v, impl="interpret", block_q=256, block_k=128)
    assert float(jnp.max(jnp.abs(a - b))) < 2e-5


# ----------------------------------------------------------- decode attention


@pytest.mark.parametrize("B,H,K,hd,L,win,fill", [
    (2, 8, 2, 128, 1024, 0, 1024),
    (2, 8, 4, 128, 1024, 0, 700),       # partially-filled cache
    (1, 4, 1, 128, 512, 256, 512),      # MQA ring window
    (1, 2, 2, 128, 512, 0, 512),
])
def test_decode_attention_matches_oracle(B, H, K, hd, L, win, fill):
    from repro.kernels.decode_attention.ops import decode_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (B, L, K, hd), jnp.float32)
    cv = jax.random.normal(ks[2], (B, L, K, hd), jnp.float32)
    sp = jnp.where(jnp.arange(L) < fill, jnp.arange(L), -1)
    o_ref = decode_attention(q, ck, cv, sp, fill - 1, window=win, impl="ref")
    o_pal = decode_attention(q, ck, cv, sp, fill - 1, window=win,
                             impl="interpret", block_k=256)
    assert float(jnp.max(jnp.abs(o_ref - o_pal))) < 2e-5


# ----------------------------------------------------------------- rglru scan


@pytest.mark.parametrize("B,S,W,bs,bw", [
    (2, 512, 512, 128, 256),
    (1, 256, 1024, 256, 512),
    (3, 128, 512, 64, 512),
])
def test_rglru_scan_matches_oracle(B, S, W, bs, bw):
    from repro.kernels.rglru_scan.ops import rglru_scan
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.2 + 0.79
    b = jax.random.normal(ks[1], (B, S, W)) * 0.1
    h0 = jax.random.normal(ks[2], (B, W))
    h_ref = rglru_scan(a, b, h0, impl="ref")
    h_pal = rglru_scan(a, b, h0, impl="interpret", block_s=bs, block_w=bw)
    assert float(jnp.max(jnp.abs(h_ref - h_pal))) < 2e-4


def test_rglru_scan_respects_initial_state():
    from repro.kernels.rglru_scan.ops import rglru_scan
    a = jnp.full((1, 4, 256), 0.5)
    b = jnp.zeros((1, 4, 256))
    h0 = jnp.ones((1, 256))
    h = rglru_scan(a, b, h0, impl="interpret", block_s=4, block_w=256)
    assert float(jnp.max(jnp.abs(h[:, 0] - 0.5))) < 1e-6     # 0.5 * h0
    assert float(jnp.max(jnp.abs(h[:, 3] - 0.5 ** 4))) < 1e-6


# ---------------------------------------------------------------- mlstm chunk


@pytest.mark.parametrize("B,S,H,dqk,dv,chunk", [
    (1, 256, 2, 128, 256, 128),
    (2, 512, 4, 128, 128, 128),
    (1, 256, 2, 256, 512, 64),
])
def test_mlstm_chunk_matches_oracle(B, S, H, dqk, dv, chunk):
    from repro.kernels.mlstm_chunk.ops import mlstm_chunk
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, H, dqk), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, dqk), jnp.float32) / dqk ** 0.5
    v = jax.random.normal(ks[2], (B, S, H, dv), jnp.float32)
    il = jax.random.normal(ks[3], (B, S, H), jnp.float32)
    fl = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, S, H)) + 2.0)
    o_ref = mlstm_chunk(q, k, v, il, fl, impl="ref", chunk=chunk)
    o_pal = mlstm_chunk(q, k, v, il, fl, impl="interpret", chunk=chunk)
    rel = float(jnp.max(jnp.abs(o_ref - o_pal))) / \
        max(float(jnp.max(jnp.abs(o_ref))), 1e-9)
    assert rel < 1e-4


def test_mlstm_chunkwise_matches_stepwise_decode():
    """Chunkwise train path == sequential decode recurrence (models/xlstm)."""
    from repro.configs import ARCHS, reduced_config
    from repro.models import xlstm as xl
    cfg = reduced_config(ARCHS["xlstm-1.3b"])
    p = xl.init_mlstm_block(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32)
    y_seq, cache = xl.mlstm_block_prefill(p, x, cfg, chunk=8)
    y_dec, cache2 = xl.mlstm_block_decode(
        p, x[:, -1:], {**{k: v for k, v in cache.items()}}, cfg)
    # decode of the last token from the prefix-(S-1) state:
    y_pre, cache_pre = xl.mlstm_block_prefill(p, x[:, :-1], cfg, chunk=5)
    y_last, _ = xl.mlstm_block_decode(p, x[:, -1:], cache_pre, cfg)
    assert float(jnp.max(jnp.abs(y_last - y_seq[:, -1:]))) < 1e-3


# ------------------------------------------------------------ moe decode


def _routing(case, N, E=64, k=6):
    """[N, k] distinct experts per token for each routing case."""
    perm = jax.vmap(lambda kk: jax.random.permutation(kk, E))(
        jax.random.split(jax.random.fold_in(KEY, N), N))
    if case == "every_token_hits_expert_7":
        rest = jnp.where(perm == 7, perm[:, k:k + 1], perm)
        return jnp.concatenate([jnp.full((N, 1), 7), rest[:, :k - 1]], 1)
    if case == "all_but_one_token_on_experts_0_5":
        same = jnp.broadcast_to(jnp.arange(k), (N, k))
        return same.at[-1].set(jnp.arange(E - k, E))
    return perm[:, :k]


@pytest.mark.parametrize("case,N,R,layer", [
    ("random", 1, 1, 0),
    ("random", 4, 1, 0),
    ("random", 16, 1, 0),
    ("every_token_hits_expert_7", 16, 1, 0),
    ("all_but_one_token_on_experts_0_5", 16, 1, 0),
    ("random", 4, 3, 0),              # a layer of the whole stack
    ("random", 4, 3, 1),
    ("random", 16, 3, 2),
])
def test_moe_decode_matches_ragged_dot_and_dense_reference(case, N, R, layer):
    """Top-6 of 64 experts at widths of whole 128-lane tiles (F = 3 x 128,
    like 1408 = 11 x 128), in bfloat16: the kernel in the interpreter
    against the dense per-token reference, and both against ragged_dot over
    the stack's R*E groups as prefill computes it. Most experts get no
    row."""
    from repro.kernels.moe_decode import expert_mlp, expert_mlp_reference
    E, D, F, k = 64, 256, 384, 6
    ks = jax.random.split(jax.random.fold_in(KEY, 99), 4)
    wg, wu = (jax.random.normal(kk, (R, E, D, F)) / D ** 0.5
              for kk in ks[:2])
    wo = jax.random.normal(ks[2], (R, E, F, D)) / F ** 0.5
    wg, wu, wo = (w.astype(jnp.bfloat16) for w in (wg, wu, wo))
    x = jax.random.normal(ks[3], (N, D)).astype(jnp.bfloat16)
    top_i = _routing(case, N)
    assert all(len(set(r)) == k for r in top_i.tolist())
    flat = top_i.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    rows = x[order // k]
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    assert int(jnp.sum(sizes == 0)) > 0

    got = expert_mlp(rows, sizes, wg, wu, wo, jnp.int32(layer),
                     act=jax.nn.silu, max_rows=N, impl="interpret",
                     tk=128, tn=128)
    ref = expert_mlp_reference(rows, sizes, wg, wu, wo, jnp.int32(layer),
                               act=jax.nn.silu)
    grouped = jnp.bincount(flat + layer * E, length=R * E).astype(jnp.int32)
    flat_w = [w.reshape((R * E,) + w.shape[2:]) for w in (wg, wu, wo)]
    h = jax.nn.silu(jax.lax.ragged_dot(rows, flat_w[0], grouped)) \
        * jax.lax.ragged_dot(rows, flat_w[1], grouped)
    ragged = jax.lax.ragged_dot(h, flat_w[2], grouped,
                                preferred_element_type=jnp.float32)
    scale = float(jnp.max(jnp.abs(ref)))
    assert got.shape == ref.shape == (N * k, D) and got.dtype == jnp.float32
    # the kernel and the reference round act(g) * u once, from float32
    # sums taken in another order; ragged_dot rounds g and u first
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-2 * scale
    assert float(jnp.max(jnp.abs(got - ragged))) < 3e-2 * scale
    assert float(jnp.max(jnp.abs(ref - ragged))) < 3e-2 * scale


@pytest.mark.parametrize("N,kernel", [(1, True), (16, True), (113, True),
                                      (114, False), (2048, False)])
def test_dropless_routed_takes_the_kernel_for_few_rows(N, kernel,
                                                      monkeypatch):
    """A call of few tokens (a decode step's) holds the few-row kernel and
    no ragged_dot; one of many (a prefill row block's) only ragged_dot.
    Traced as for a TPU, where the kernel is the Pallas one."""
    from repro.kernels.moe_decode import ops
    from repro.models.moe import dropless_routed, few_rows
    monkeypatch.setattr(ops, "AUTO_IMPL", "pallas")
    E, D, F, k = 64, 256, 128, 6
    p = {"wi_gate": jnp.zeros((3, E, D, F), jnp.bfloat16),
         "wi_up": jnp.zeros((3, E, D, F), jnp.bfloat16),
         "wo": jnp.zeros((3, E, F, D), jnp.bfloat16),
         "layer": jnp.int32(1)}
    jaxpr = str(jax.make_jaxpr(
        lambda p, x, tp, ti: dropless_routed(p, x, tp, ti, jax.nn.silu, E))(
        p, jnp.zeros((N, D), jnp.bfloat16), jnp.zeros((N, k)),
        jnp.zeros((N, k), jnp.int32)))
    assert few_rows(N) == kernel
    assert ("pallas_call" in jaxpr) == kernel
    assert ("ragged_dot" in jaxpr) == (not kernel)
