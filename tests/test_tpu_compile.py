"""Compile the main path for a described TPU v5e, without a chip.

JAX's TPU compiler compiles for a topology it is only told about, so these
tests catch what the chip's compiler would refuse (a fused scatter it
aborts on, a kernel tile Mosaic cannot lower, a program that outgrows the
chip's memory) at no chip time. Nothing runs: they say nothing about
results or times. The topology is described inside a fixture, never at
import, so that under several test workers only the worker given this file
loads the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _kernels_as_on_a_tpu(monkeypatch):
    """Programs here are lowered for a TPU from a host that may have none:
    the kernels that pick their implementation by the default backend
    take the Pallas one, as on the chip."""
    from repro.kernels.moe_decode import ops
    monkeypatch.setattr(ops, "AUTO_IMPL", "pallas")


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return used


# ------------------------------------------------------------- queue cores


@pytest.mark.parametrize("kind", ["const", "pw"])
def test_queue_core_compiles_at_campaign_scale(one_chip, kind):
    from repro.workloads.queueing import (FOLD_COLS, _kw_batched_core,
                                          _pw_batched_core)
    B, n_pad, e_pad, k_pad = 64, 98304, 32, 64

    def row(*tail, dtype=jnp.float32):
        return jax.ShapeDtypeStruct((B,) + tail, dtype, sharding=one_chip)

    trace = (row(n_pad), row(n_pad))               # arrival, service times
    tail = (row(), row(dtype=jnp.int32), row())    # horizon, n_valid, slo
    if kind == "const":
        core = _kw_batched_core(n_pad, k_pad)
        args = trace + (row(k_pad),) + tail        # slot free times
    else:
        core = _pw_batched_core(n_pad, e_pad, k_pad)
        args = trace + (row(e_pad), row(e_pad, dtype=jnp.int32),
                        row(e_pad)) + tail         # capacity steps
    compiled = core.lower(*args).compile()
    assert compiled.out_info.shape == (B, len(FOLD_COLS))
    _fits(compiled)


# ------------------------------------------------------------------ models


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "deepseek-7b"])
def test_prefill_shorter_than_cache_compiles(one_chip, arch):
    """The shape ``Replica.generate`` builds: a prompt of S=128 into a cache
    of S + max_new = 160 slots, at published widths, cut to 3 layers (one
    recurrentgemma period)."""
    from repro.configs import ARCHS
    from repro.models import model as M
    cfg = ARCHS[arch].with_(num_layers=3)
    params = _on(jax.eval_shape(lambda k: M.init_params(k, cfg),
                                jax.random.PRNGKey(0)), one_chip)
    tokens = jax.ShapeDtypeStruct((4, 128), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t: M.prefill(p, t, cfg, max_len=160)) \
        .lower(params, tokens).compile()
    _fits(compiled)


def test_full_width_decode_step_fits_one_chip(one_chip):
    from repro.configs import ARCHS
    from repro.models import model as M
    cfg = ARCHS["recurrentgemma-2b"]
    params = _on(jax.eval_shape(lambda k: M.init_params(k, cfg),
                                jax.random.PRNGKey(0)), one_chip)
    cache = _on(M.init_cache(cfg, 4, 160, abstract=True), one_chip)
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, c, t, i: M.decode_step(p, c, t, i, cfg)) \
        .lower(params, cache, tok, pos).compile()
    used = _fits(compiled)
    assert used > 5 * 2**30          # the whole 2.7B-parameter model is there


_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+)\s*=\s*(.*?)\s([\w-]+)\(")


def _slab_copies(hlo: str, slab: str):
    """Instructions of ``hlo`` whose result holds a ``slab``-shaped array
    and that copy it: ``copy``, ``copy-start`` or a ``copy*`` fusion."""
    found = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m is None or slab not in m.group(2):
            continue
        name, op = m.group(1), m.group(3)
        if op in ("copy", "copy-start") or (op == "fusion"
                                            and name.startswith("copy")):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("max_len", [640, 1088])
def test_served_decode_step_updates_its_cache_in_place(one_chip, max_len):
    """The replica's decode program at the deepseek-7b serving cell's
    widths (15 layers, 32 heads of 128, batch 12, the chat and document
    caches): the donated cache is aliased to the one returned, the step
    needs no cache-sized scratch, and no op copies a layer's cache slab.
    The same step undonated must show such a copy, so that the search
    is seen to find one."""
    from repro.configs import ARCHS
    from repro.models import model as M
    from repro.runtime.serving_pool import serve_programs
    cfg = ARCHS["deepseek-7b"].with_(num_layers=15)
    B = 12
    params = _on(jax.eval_shape(lambda k: M.init_params(k, cfg),
                                jax.random.PRNGKey(0)), one_chip)
    cache = _on(M.init_cache(cfg, B, max_len, abstract=True), one_chip)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _, decode = serve_programs(cfg)
    compiled = decode.lower(params, cache, tok, pos).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(cache))
    # all of it aliased; the chip pads the [layers, L] position table to
    # its (8, 128) tiles, a few KiB over the cache's own bytes
    assert 0 <= mem.alias_size_in_bytes - cache_bytes < 1e-3 * cache_bytes
    assert mem.temp_size_in_bytes < 0.25e9, mem.temp_size_in_bytes
    slab = f",{max_len},{cfg.num_kv_heads},{cfg.head_dim}]"
    assert _slab_copies(compiled.as_text(), slab) == []
    _fits(compiled)
    undonated = jax.jit(lambda p, c, t, i: M.decode_step(p, c, t, i, cfg)) \
        .lower(params, cache, tok, pos).compile()
    assert _slab_copies(undonated.as_text(), slab)


def _v2lite(one_chip):
    from repro.configs import ARCHS
    from repro.models import model as M
    cfg = ARCHS["deepseek-v2-lite"].with_(num_layers=7)
    params = _on(jax.eval_shape(lambda k: M.init_params(k, cfg),
                                jax.random.PRNGKey(0)), one_chip)
    return cfg, params


@pytest.mark.parametrize("B", [16, 3])
def test_v2lite_decode_step_updates_its_latent_cache_in_place(one_chip, B):
    """The replica's decode program at the deepseek-v2-lite serving cell's
    widths (7 layers, the longdoc cache of 4224 positions; batch 16, and 3,
    whose 18 routed rows fill no whole number of tiles): the donated
    latent cache is aliased to the one returned, the step needs no
    cache-sized scratch (nor a copy of a layer's experts for the expert
    kernels), and no op copies a layer's latent slab in HBM (a
    prefetch into the chip's fast memory, S(1), is not a copy)."""
    from repro.models import model as M
    from repro.runtime.serving_pool import serve_programs
    cfg, params = _v2lite(one_chip)
    L = 4224
    cache = _on(M.init_cache(cfg, B, L, abstract=True), one_chip)
    assert set(cache) == {"repeats", "tail", "lead", "moe_stats"}
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _, decode = serve_programs(cfg)
    compiled = decode.lower(params, cache, tok, pos).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(cache))
    assert 0 <= mem.alias_size_in_bytes - cache_bytes < 1e-3 * cache_bytes
    assert mem.temp_size_in_bytes < 0.05 * cache_bytes, mem.temp_size_in_bytes
    slab = f",{L},{cfg.mla.latent_dim}]"
    copies = [c for c in _slab_copies(compiled.as_text(), slab)
              if "S(1)} copy" not in c and "S(1)}, " not in c]
    assert copies == []
    _fits(compiled)


@pytest.mark.parametrize("B", [1, 16])
def test_v2lite_decode_step_runs_its_experts_through_the_kernels(one_chip, B):
    """The replica's decode program at the deepseek-v2-lite serving cell's
    widths and longdoc cache (4224 positions), at batch 1 and 16 (6 and 96
    routed rows): the routed experts run through the few-row expert
    kernel, no ragged_dot is left, and it fits one chip. The longdoc
    round's prefill keeps ragged_dot."""
    from repro.models import model as M
    from repro.runtime.serving_pool import serve_programs
    cfg, params = _v2lite(one_chip)
    cache = _on(M.init_cache(cfg, B, 4224, abstract=True), one_chip)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    prefill, decode = serve_programs(cfg)
    compiled = decode.lower(params, cache, tok, pos).compile()
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines()
               if "tpu_custom_call" in line and "custom_call_target" in line]
    assert any("moe_decode" in k for k in kernels), kernels
    assert "ragged" not in hlo
    _fits(compiled)
    tokens = jax.ShapeDtypeStruct((16, 4096), jnp.int32, sharding=one_chip)
    lowered = prefill.lower(params, tokens, 4224).as_text()
    assert "ragged_dot" in lowered and "moe_decode" not in lowered


def test_v2lite_longdoc_prefill_fits_one_chip(one_chip):
    """The longdoc round's prefill at full batch (16 prompts of 4096 into
    caches of 4224) fits one chip beside the 8 GB of weights: the MoE and
    the dense MLP run over blocks of rows."""
    from repro.runtime.serving_pool import serve_programs
    cfg, params = _v2lite(one_chip)
    prefill, _ = serve_programs(cfg)
    tokens = jax.ShapeDtypeStruct((16, 4096), jnp.int32, sharding=one_chip)
    compiled = prefill.lower(params, tokens, 4224).compile()
    used = _fits(compiled)
    assert used > 8e9                # the weights are all there
    assert compiled.memory_analysis().temp_size_in_bytes < 5e9


# ------------------------------------------------------------------ kernels


def _kernel_case(name, one_chip):
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if name == "rglru_scan":             # recurrentgemma-2b: W = 2560
        from repro.kernels.rglru_scan.kernel import rglru_scan_fwd
        return rglru_scan_fwd, (sds((1, 2048, 2560)), sds((1, 2048, 2560)),
                                sds((1, 2560)))
    if name == "mlstm_chunk":            # xlstm-1.3b: 4 heads, dqk 512, dv 1024
        from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_fwd
        return mlstm_chunk_fwd, (sds((4, 1024, 512)), sds((4, 1024, 512)),
                                 sds((4, 1024, 1024)),
                                 sds((4, 1024), jnp.float32),
                                 sds((4, 1024), jnp.float32))
    if name == "flash_attention":        # head_dim 256, 10 heads
        from repro.kernels.flash_attention.kernel import flash_attention_fwd
        return flash_attention_fwd, (sds((10, 2048, 256)),) * 3
    if name == "moe_decode":             # deepseek-v2-lite: 16 tokens, top-6
        from repro.kernels.moe_decode.kernel import (expert_mlp_fwd,
                                                     window_rows)
        N, S, R, E, D, F = 16, 64, 6, 64, 2048, 1408
        Mp = N * 6 + window_rows(N)
        i32 = functools.partial(sds, dtype=jnp.int32)
        return (lambda x, ids, off, cnt, nhit, layer, wg, wu, wo:
                expert_mlp_fwd(x, ids, off, cnt, nhit, layer, wg, wu, wo,
                               act=jax.nn.silu, window=window_rows(N)),
                (sds((Mp, D)), i32((S,)), i32((S,)), i32((S,)), i32((1,)),
                 i32((1,)), sds((R, E, D, F)), sds((R, E, D, F)),
                 sds((R, E, F, D))))
    from repro.kernels.decode_attention.kernel import decode_attention_fwd
    return (lambda q, k, v, sp, cp: decode_attention_fwd(q, k, v, sp, cp,
                                                          window=2048),
            (sds((4, 10, 256)), sds((4, 2048, 256)), sds((4, 2048, 256)),
             sds((1, 2048), jnp.int32), sds((1,), jnp.int32)))


@pytest.mark.parametrize("name", ["rglru_scan", "mlstm_chunk",
                                  "flash_attention", "decode_attention",
                                  "moe_decode"])
def test_pallas_kernel_lowers_to_mosaic(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_kernel_cases_cover_every_kernel():
    import repro.kernels as K
    here = os.path.dirname(K.__file__)
    kernels = sorted(d for d in os.listdir(here)
                     if os.path.isfile(os.path.join(here, d, "kernel.py")))
    assert kernels == sorted(["rglru_scan", "mlstm_chunk", "flash_attention",
                              "decode_attention", "moe_decode"]), kernels
