"""Parameter/activation partitioning rules (logical rules -> PartitionSpec).

Axes:
  model : tensor parallelism (Megatron-style column/row parallel + expert-TP)
  data  : data parallelism; with ``fsdp=True`` parameters are additionally
          sharded over `data` on a free dimension (ZeRO-3 / weight-gather);
          optimizer state is always sharded over `data` (ZeRO-1) when possible
  pod   : outer data-parallel axis of the multi-pod mesh (batch only)

Rules are path-based over the parameter pytree produced by
``repro.models.model.init_params``. Parameter names are unique per role:
column-parallel projections, row-parallel projections, rglru channel params,
and xLSTM mixers (replicated baseline — 4 heads give no useful TP; revisited
in the perf hillclimb). XLA GSPMD propagates everything else.
"""
from __future__ import annotations

import re
from typing import Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

# output dim -> model (column parallel); FSDP shards a free dim over data
_COL_NAMES = {"wq", "wk", "wv", "wi_gate", "wi_up", "w_gate_in", "w_rnn_in",
              "w_ff_up", "head"}
# input dim -> model (row parallel)
_ROW_NAMES = {"wo", "w_out", "w_down", "w_ff_down"}
# rglru per-channel params: last dim follows the model-sharded rnn width
_RG_CHANNEL = {"rg_conv_w", "rg_conv_b", "lam"}
# rglru gate matrices [W, W]: row-parallel (contract the sharded channel dim)
_RG_GATES = {"w_rg", "w_ig"}
# xLSTM mixer params: replicated baseline
_XLSTM = {"w_up", "w_gate", "w_q", "w_k", "w_v", "w_i", "w_f", "rec",
          "out_scale", "conv_w", "conv_b", "w_z", "w_o"}


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _owner(path: str) -> str:
    """Name of the parameter (dict key above the kernel/bias/scale leaf)."""
    parts = path.split("/")
    return parts[-2] if parts[-1] in ("kernel", "bias", "scale") else parts[-1]


def _shard_free_dim(shape, spec, axis: str, size: int):
    best, best_dim = -1, -1
    for i, s in enumerate(shape):
        if spec[i] is None and s % size == 0 and s > best:
            best, best_dim = s, i
    if best_dim >= 0:
        spec[best_dim] = axis
    return spec


def param_specs(shape_tree, cfg: ModelConfig, mesh: Mesh, *, fsdp: bool = False,
                tp: int = 0):
    """Tree of PartitionSpec matching a params (or ShapeDtypeStruct) tree.

    tp=1 selects the pure-FSDP layout: no tensor parallelism; parameters are
    sharded over the combined (data, model) axes and the batch uses both
    axes as data parallelism (see dp_axes). Default tp=0 means full-width TP.
    """
    msz = mesh.shape["model"] if tp == 0 else tp
    dsz = mesh.shape["data"]
    if tp == 1:
        fs_axis = ("data", "model")
        fs_size = mesh.shape["data"] * mesh.shape["model"]

        def one_fsdp(path, leaf):
            spec = [None] * len(leaf.shape)
            if fsdp and leaf.size >= 1 << 16:
                _shard_free_dim(leaf.shape, spec, fs_axis, fs_size)
            return P(*spec)

        return jax.tree_util.tree_map_with_path(one_fsdp, shape_tree)

    def one(path, leaf):
        p = _path_str(path)
        shape = leaf.shape
        ndim = len(shape)
        spec = [None] * ndim
        name = _owner(p)
        leafname = p.split("/")[-1]
        is_moe = "/moe/" in p

        if name == "router":
            return P(*spec)                                   # replicated
        if name in _XLSTM and not is_moe:
            if fsdp and leaf.size >= 1 << 20:
                _shard_free_dim(shape, spec, "data", dsz)     # generic ZeRO-3
            return P(*spec)
        if "embed/table" in p:
            if shape[0] % msz == 0:
                spec[0] = "model"
            if fsdp and shape[1] % dsz == 0:
                spec[1] = "data"
        elif is_moe and leafname != "kernel":
            # stacked expert weights [R?, E, in, out]-style
            if name in ("wi_gate", "wi_up") and shape[-1] % msz == 0:
                spec[-1] = "model"
            elif name == "wo" and shape[-2] % msz == 0:
                spec[-2] = "model"
            if cfg.moe is not None and cfg.moe.expert_parallel:
                off = 1 if "repeats/" in p else 0
                if shape[off] % dsz == 0:
                    spec[off] = "data"       # expert parallelism
                elif fsdp:
                    _shard_free_dim(shape, spec, "data", dsz)
            elif fsdp:
                _shard_free_dim(shape, spec, "data", dsz)
        elif name in _COL_NAMES:
            if leafname == "kernel":
                if shape[-1] % msz == 0:
                    spec[-1] = "model"
                if fsdp:
                    _shard_free_dim(shape, spec, "data", dsz)
            elif leafname == "bias" and shape[-1] % msz == 0:
                spec[-1] = "model"
        elif name in _ROW_NAMES:
            if leafname == "kernel":
                if shape[-2] % msz == 0:
                    spec[-2] = "model"
                if fsdp:
                    _shard_free_dim(shape, spec, "data", dsz)
        elif name in _RG_CHANNEL or leafname in _RG_CHANNEL:
            if shape[-1] % msz == 0:
                spec[-1] = "model"
        elif name in _RG_GATES:
            if leafname == "kernel" and shape[-2] % msz == 0:
                spec[-2] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, shape_tree)


# ----------------------------------------------------------------- batches


def batch_axes(mesh: Mesh, tp: int = 0):
    axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    if tp == 1:
        axes = axes + ("model",)
    return axes


def dp_size(mesh: Mesh, tp: int = 0) -> int:
    total = 1
    for a in batch_axes(mesh, tp):
        total *= mesh.shape[a]
    return total


def data_spec(mesh: Mesh, shape: Tuple[int, ...], *, batch_dim: int = 0,
              tp: int = 0) -> P:
    """Shard the batch dim over the widest divisible prefix of the DP axes
    (e.g. global_batch=256 on the 2x16x16 mesh with tp=1 shards over
    (data, model) = 256 and replicates over pod)."""
    axes = batch_axes(mesh, tp)
    spec = [None] * len(shape)
    candidates = [axes]
    if len(axes) > 1:
        candidates += [axes[1:], axes[:-1], axes[1:-1] or axes[-1:],
                       axes[-1:], axes[:1]]
    for cand in candidates:
        size = 1
        for a in cand:
            size *= mesh.shape[a]
        if size and shape[batch_dim] % size == 0:
            spec[batch_dim] = cand if len(cand) > 1 else cand[0]
            return P(*spec)
    return P(*spec)


def cache_specs(cache_tree, cfg: ModelConfig, mesh: Mesh, *, tp: int = 0):
    """Specs for a KV/recurrent cache tree.

    k/v [R?, B, L, K, hd]: batch over data axes when divisible; otherwise the
    kv-head dim (K % model == 0) or a large length dim goes over `model`.
    With tp=1 the model axis joins the batch axes instead.
    """
    msz = mesh.shape["model"] if tp == 0 else tp

    def one(path, leaf):
        p = _path_str(path)
        shape = leaf.shape
        ndim = len(shape)
        off = 1 if "repeats/" in p else 0
        name = p.split("/")[-1]
        spec = [None] * ndim
        if name in ("k", "v"):
            bs = data_spec(mesh, shape, batch_dim=off, tp=tp)
            spec[off] = bs[off]
            L, K = shape[off + 1], shape[off + 2]
            if tp != 1:
                if K % msz == 0:
                    spec[off + 2] = "model"
                elif L % msz == 0 and L >= 8192:
                    spec[off + 1] = "model"
        elif name in ("pos", "moe_stats"):
            pass
        elif name in ("h", "conv") and shape[-1] in (cfg.lru_width,):
            bs = data_spec(mesh, shape, batch_dim=off, tp=tp)
            spec[off] = bs[off]
            if tp != 1 and shape[-1] % msz == 0:
                spec[-1] = "model"
        else:  # xlstm states: batch-shard only
            bs = data_spec(mesh, shape, batch_dim=off, tp=tp)
            spec[off] = bs[off]
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache_tree)


# ------------------------------------------------------------- activations


def make_constrain(mesh: Mesh, *, sequence_parallel: bool = False,
                   tp: int = 0):
    """Residual-stream constraint hook passed into the model."""
    axes = batch_axes(mesh, tp)
    baxis = axes if len(axes) > 1 else axes[0]

    def _bspec(x):
        # widest divisible DP-axis prefix (same fallback chain as data_spec)
        return data_spec(mesh, x.shape, batch_dim=0, tp=tp)[0]

    def constrain(x, kind: str):
        if x.ndim == 3 and kind in ("residual", "moe_group"):
            seq = None
            if (tp != 1 and kind == "residual" and sequence_parallel
                    and x.shape[1] % mesh.shape["model"] == 0):
                seq = "model"
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(_bspec(x), seq, None)))
        if kind in ("moe_local", "moe_ff"):
            # MoE dispatch intermediates: group dim 0 stays on the data
            # axes, everything else local — GSPMD otherwise loses the
            # sharding through sort/scatter and replicates TB-scale dispatch
            # buffers (the "involuntary full rematerialization" warnings).
            spec = [_bspec(x)] + [None] * (x.ndim - 1)
            if (kind == "moe_ff" and tp != 1
                    and x.shape[-1] % mesh.shape["model"] == 0):
                spec[-1] = "model"   # expert-TP: ffn dim on the model axis
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        if kind in ("moe_ep_buf", "moe_ep_ff"):
            # expert parallelism: resharding [G, E, ...] from group-sharded
            # to expert-sharded makes GSPMD emit the all-to-all; the expert
            # matmuls then run on data-axis-local experts.
            spec = [None] * x.ndim
            if x.shape[1] % mesh.shape["data"] == 0:
                spec[1] = "data"
            if (kind == "moe_ep_ff" and tp != 1
                    and x.shape[-1] % mesh.shape["model"] == 0):
                spec[-1] = "model"
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        return x

    return constrain


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def zero1_specs(param_spec_tree, shape_tree, mesh: Mesh):
    """Optimizer-state specs: param spec + extra `data` sharding (ZeRO-1)."""
    dsz = mesh.shape["data"]

    def one(spec: P, leaf):
        shape = leaf.shape
        s = list(spec) + [None] * (len(shape) - len(spec))
        used = set()
        for a in s:
            used.update(a if isinstance(a, tuple) else (a,))
        if "data" not in used:
            _shard_free_dim(shape, s, "data", dsz)
        return P(*s)

    return jax.tree.map(one, param_spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))
