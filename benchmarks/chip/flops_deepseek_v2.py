"""Operations and bytes DeepSeek-V2's serving steps need, from shapes.

The sizes are a configuration file's keys (``hidden_size``,
``kv_lora_rank``, ``n_routed_experts``, ...). As in ``flops.py``, counts
are of what the algorithm needs: a matrix multiplication of [m, k] by
[k, n] is 2mkn operations, a query at position p attends to p + 1 keys,
and a step reads its weights once. A token passes through its top-k
routed experts and the shared ones; the router's logits count too.
Prefill computes attention in the expanded form (per-head keys and
values from the latent), decode in the absorbed one (the query folded
into the latent space, attending over cached latents). A decode step
reads the experts that got at least one row, counted by the program
(``serve.moe_experts_hit``): never all of them, so a grouped matmul that
skips idle experts is not read above 100%.
"""
from __future__ import annotations


def _dims(s: dict):
    nope, rope = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
    return dict(d=s["hidden_size"], h=s["num_attention_heads"], nope=nope,
                rope=rope, qk=nope + rope, v=s["v_head_dim"],
                r=s["kv_lora_rank"], ff=s["intermediate_size"],
                e=s["n_routed_experts"], f=s["moe_intermediate_size"],
                k=s["num_experts_per_tok"], shared=s["n_shared_experts"],
                V=s["vocab_size"], L=s["num_hidden_layers"],
                dense=s["first_k_dense_replace"],
                moe=s["num_hidden_layers"] - s["first_k_dense_replace"])


def attention_params(s: dict) -> int:
    """One layer's latent-attention weights: q, kv_a, kv_b and o."""
    m = _dims(s)
    return (m["d"] * m["h"] * m["qk"] + m["d"] * (m["r"] + m["rope"])
            + m["r"] * m["h"] * (m["nope"] + m["v"]) + m["h"] * m["v"] * m["d"])


def expert_params(s: dict) -> int:
    """One routed expert's gate, up and down projections."""
    m = _dims(s)
    return 3 * m["d"] * m["f"]


def nonexpert_weight_bytes(s: dict, bytes_per_param: int = 2,
                           router_bytes: int = 4) -> int:
    """Every weight a decode step reads once besides the routed experts:
    attention, the dense layers' MLPs, the shared experts, the routers (in
    float32) and the output head (the embedding is a gather)."""
    m = _dims(s)
    params = (m["L"] * attention_params(s) + m["dense"] * 3 * m["d"] * m["ff"]
              + m["moe"] * m["shared"] * expert_params(s) + m["d"] * m["V"])
    return params * bytes_per_param + m["moe"] * m["d"] * m["e"] * router_bytes


def latent_row_bytes(s: dict, cache_bytes: int = 2) -> int:
    """Cache bytes a token holds over all layers: latent and rotary key."""
    m = _dims(s)
    return m["L"] * (m["r"] + m["rope"]) * cache_bytes


def _ffn_flops_per_token(s: dict) -> float:
    m = _dims(s)
    dense = 2 * 3 * m["d"] * m["ff"]
    moe = (2 * expert_params(s) * (m["k"] + m["shared"])
           + 2 * m["d"] * m["e"])
    return m["dense"] * dense + m["moe"] * moe


def prefill_flops(s: dict, batch: int, prompt: int) -> float:
    """Prefill of ``batch`` prompts of ``prompt`` tokens in the expanded
    form, with logits for the last position only."""
    m = _dims(s)
    per_tok = 2 * m["L"] * attention_params(s) + _ffn_flops_per_token(s)
    attn = m["L"] * 2 * m["h"] * (m["qk"] + m["v"]) * prompt * (prompt + 1) / 2
    return batch * (prompt * per_tok + attn + 2 * m["d"] * m["V"])


def decode_flops(s: dict, batch: int, pos: int) -> float:
    """One absorbed decode step of ``batch`` sequences whose new token sits
    at position ``pos``: per layer the q and kv_a projections, the query
    through W_uk, scores over pos + 1 latent rows [c, k_pe], the attended
    latent, W_uv and o; then the MLP or experts, and the head."""
    m = _dims(s)
    lat = m["r"] + m["rope"]
    proj = 2 * (m["d"] * m["h"] * m["qk"] + m["d"] * lat
                + m["h"] * m["nope"] * m["r"] + m["h"] * m["r"] * m["v"]
                + m["h"] * m["v"] * m["d"])
    attend = 2 * m["h"] * (lat + m["r"]) * (pos + 1)
    return batch * (m["L"] * (proj + attend) + _ffn_flops_per_token(s)
                    + 2 * m["d"] * m["V"])


def decode_bytes(s: dict, batch: int, pos: int, experts_hit: float,
                 bytes_per_param: int = 2) -> float:
    """One decode step (or, with totals, many): the non-expert weights
    once, ``experts_hit`` experts' weights (summed over MoE layers), and
    the latent rows of positions 0..pos of every sequence."""
    return (nonexpert_weight_bytes(s, bytes_per_param)
            + experts_hit * expert_params(s) * bytes_per_param
            + batch * (pos + 1) * latent_row_bytes(s))
