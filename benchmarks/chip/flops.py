"""Operations and bytes a dense decoder's serving steps need, from shapes.

The sizes are a configuration file's keys (``hidden_size``,
``num_hidden_layers``, ...). Counts are of what the algorithm needs, not of
what a given program does: a matrix multiplication of [m, k] by [k, n] is
2mkn operations, a query at position p attends to p + 1 keys, weights are
read once per step, and only the keys and values of positions already
written are read. A program that reads a whole preallocated cache, or
copies it, spends bytes that are not counted here, and its roofline share
shows that.
"""
from __future__ import annotations


def _dims(s: dict):
    d = s["hidden_size"]
    h = s["num_attention_heads"]
    hd = s.get("head_dim") or d // h
    kv = s.get("num_key_value_heads") or h
    return d, h, hd, kv


def layer_matmul_params(s: dict) -> int:
    """Weights of one attention + gated-MLP layer that multiply activations."""
    d, h, hd, kv = _dims(s)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * s["intermediate_size"]


def matmul_params(s: dict) -> int:
    """All layers' matmul weights plus the output head (the embedding is a
    gather, not a multiplication)."""
    return (s["num_hidden_layers"] * layer_matmul_params(s)
            + s["hidden_size"] * s["vocab_size"])


def weight_bytes(s: dict, bytes_per_param: int = 2) -> int:
    """Bytes of the weights a decode step reads: every matmul weight and the
    head, in the served type; the embedding rows are counted per token."""
    return matmul_params(s) * bytes_per_param


def prefill_flops(s: dict, batch: int, prompt: int) -> float:
    """Prefill of ``batch`` prompts of ``prompt`` tokens, with logits for the
    last position only (the only ones the next token needs)."""
    d, h, hd, _ = _dims(s)
    L = s["num_hidden_layers"]
    per_tok = 2 * L * layer_matmul_params(s)
    attn = L * 4 * h * hd * prompt * (prompt + 1) / 2    # causal: sum of p+1
    head = 2 * d * s["vocab_size"]
    return batch * (prompt * per_tok + attn + head)


def decode_flops(s: dict, batch: int, pos: int) -> float:
    """One decode step of ``batch`` sequences whose new token sits at
    position ``pos`` (0-based): it attends to pos + 1 keys."""
    d, h, hd, _ = _dims(s)
    L = s["num_hidden_layers"]
    return batch * (2 * matmul_params(s) + L * 4 * h * hd * (pos + 1))


def decode_bytes(s: dict, batch: int, pos: int, kv_bytes: int = 2,
                 bytes_per_param: int = 2) -> float:
    """One decode step: the weights once, each sequence's embedding row, the
    keys and values of the ``pos`` earlier positions read, the new ones
    written, and float32 logits written."""
    d, h, hd, kv = _dims(s)
    L = s["num_hidden_layers"]
    kv_row = 2 * kv * hd * kv_bytes                      # one position, k and v
    return (weight_bytes(s, bytes_per_param)
            + batch * d * bytes_per_param
            + L * batch * (pos + 1) * kv_row             # pos read + 1 written
            + batch * s["vocab_size"] * 4)


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """The roofline: the larger of compute time and memory time at the
    chip's peaks, and which of the two bounds it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
