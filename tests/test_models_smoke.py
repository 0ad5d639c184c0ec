"""Per-architecture smoke tests on reduced configs (CPU, 1 device).

For each assigned architecture: instantiate a reduced same-family config, run
one forward and one SGD train step, assert output shapes and no NaNs; check
prefill+decode consistency against the full-sequence oracle.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced_config
from repro.models import model as M

ARCH_NAMES = sorted(ARCHS)


def _inputs(cfg, key, B=2, S=24):
    if cfg.input_mode == "embeddings":
        return jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
    return jax.random.randint(key, (B, S), 0, cfg.vocab_size)


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_shapes_no_nan(name, rng):
    cfg = reduced_config(ARCHS[name])
    params = M.init_params(rng, cfg)
    B, S = 2, 24
    x = _inputs(cfg, rng, B, S)
    logits, aux = M.forward(params, x, cfg, moe_groups=2)
    if cfg.num_codebooks:
        assert logits.shape == (B, S, cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    for v in aux.values():
        assert not bool(jnp.isnan(v).any())


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_one_train_step(name, rng):
    cfg = reduced_config(ARCHS[name])
    params = M.init_params(rng, cfg)
    B, S = 2, 16
    x = _inputs(cfg, rng, B, S)
    if cfg.num_codebooks:
        labels = jax.random.randint(rng, (B, S, cfg.num_codebooks), 0,
                                    cfg.vocab_size)
    else:
        labels = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)

    def loss_fn(p):
        logits, aux = M.forward(p, x, cfg, moe_groups=2)
        ll = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(ll, labels[..., None], axis=-1).mean()
        return nll + 0.01 * sum(aux.values())

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert jnp.isfinite(loss)
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
    # grads reach the embedding (or the head for embedding-input archs)
    probe = grads["head"]["kernel"] if cfg.input_mode == "embeddings" \
        else grads["embed"]["table"]
    assert float(jnp.abs(probe).max()) > 0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_prefill_decode_matches_forward(name, rng):
    cfg = reduced_config(ARCHS[name])
    params = M.init_params(rng, cfg)
    B, S, P = 2, 24, 20
    full = _inputs(cfg, rng, B, S)
    logits_full, _ = M.forward(params, full, cfg, moe_groups=1)
    lp, cache = M.prefill(params, full[:, :P], cfg, moe_groups=1, max_len=S)
    assert float(jnp.max(jnp.abs(lp - logits_full[:, P - 1]))) < 2e-3
    for t in range(P, S):
        ld, cache = M.decode_step(params, cache, full[:, t:t + 1],
                                  jnp.int32(t), cfg, moe_groups=1)
        assert float(jnp.max(jnp.abs(ld - logits_full[:, t]))) < 2e-3, t


@pytest.mark.parametrize("name,layers", [("gemma3-12b", 8),
                                         ("recurrentgemma-2b", 7)])
def test_decode_past_the_window_matches_forward(name, layers, rng):
    """Decoding twice around a "local" layer's ring buffer (window 16),
    from a prompt that already wrapped it, matches the full-sequence
    forward. gemma3 at 8 layers puts two local layers in the unscanned
    tail; recurrentgemma at 7 stacks two scanned repeats beside a
    recurrent tail."""
    cfg = reduced_config(ARCHS[name]).with_(num_layers=layers)
    assert cfg.window_size == 16
    params = M.init_params(rng, cfg)
    B, S, P = 2, 52, 20
    full = _inputs(cfg, rng, B, S)
    logits_full, _ = M.forward(params, full, cfg)
    lp, cache = M.prefill(params, full[:, :P], cfg, max_len=S)
    assert float(jnp.max(jnp.abs(lp - logits_full[:, P - 1]))) < 2e-3
    step = jax.jit(lambda p, c, t, i: M.decode_step(p, c, t, i, cfg))
    for t in range(P, S):
        ld, cache = step(params, cache, full[:, t:t + 1], jnp.int32(t))
        assert float(jnp.max(jnp.abs(ld - logits_full[:, t]))) < 2e-3, t


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_param_count_analytic_close(name, rng):
    """Analytic param_count (used for MODEL_FLOPS) tracks actual leaves."""
    cfg = reduced_config(ARCHS[name])
    params = M.init_params(rng, cfg)
    actual = sum(x.size for x in jax.tree.leaves(params))
    analytic = cfg.param_count()
    assert abs(actual - analytic) / actual < 0.35, (actual, analytic)
