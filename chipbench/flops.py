"""FLOPs a model *needs*, from its shapes. Never from the implementation:
no recompute, no padding rows, no context beyond the live one, only the
experts a token is routed to. Used by the ``mfu`` reader.

``model`` is the configuration file's ``model`` group (HF key names).
"""

from __future__ import annotations


def layer_matmul_flops(model: dict) -> float:
    """Forward matmul FLOPs of one layer for one token (no attention
    scores)."""
    d = model["hidden_size"]
    hq = model["num_attention_heads"]
    hkv = model["num_key_value_heads"]
    dh = model.get("head_dim") or d // hq
    f = model["intermediate_size"]
    attn_proj = 2 * d * (hq * dh + 2 * hkv * dh) + 2 * hq * dh * d
    experts = model.get("num_local_experts", 0)
    if experts:
        k = model["num_experts_per_tok"]
        mlp = k * 2 * 3 * d * f + 2 * d * experts
    else:
        mlp = 2 * 3 * d * f
    return float(attn_proj + mlp)


def attention_flops(model: dict, context: float) -> float:
    """Forward QK^T and PV FLOPs of one layer for one token that sees
    ``context`` keys (itself included)."""
    hq = model["num_attention_heads"]
    dh = model.get("head_dim") or model["hidden_size"] // hq
    return 4.0 * hq * dh * context


def head_flops(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward (3x forward) for one token of a packed causal
    sequence of ``seq`` tokens: mean context (seq + 1) / 2."""
    n_layers = model["num_hidden_layers"]
    fwd = n_layers * (layer_matmul_flops(model)
                      + attention_flops(model, (seq + 1) / 2.0)) \
        + head_flops(model)
    return 3.0 * fwd


def serve_flops(model: dict, prefill_tokens: float,
                prefill_context_sum: float, decode_tokens: float,
                decode_context_sum: float, sampled_tokens: float) -> float:
    """Forward FLOPs for ``prefill_tokens`` prompt tokens that had to be
    computed (their contexts summing to ``prefill_context_sum``),
    ``decode_tokens`` tokens fed back one at a time, and the head for the
    ``sampled_tokens`` positions whose logits were needed."""
    n_layers = model["num_hidden_layers"]
    per_tok = n_layers * layer_matmul_flops(model)
    attn = n_layers * attention_flops(model, 1.0)
    return (per_tok * (prefill_tokens + decode_tokens)
            + attn * (prefill_context_sum + decode_context_sum)
            + head_flops(model) * sampled_tokens)
