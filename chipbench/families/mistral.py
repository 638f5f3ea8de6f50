"""``model_type: mistral`` (and ``mixtral``, through ``mixtral.py``): the
published ``MistralForCausalLM`` / ``MixtralForCausalLM``. Pre-norm
RMSNorm, grouped-query causal attention with split-half rotary
embeddings, SwiGLU MLP, untied head. Mixtral: softmax router, top-2
experts, gate weights renormalised over the chosen two, no token dropped.
The sliding window is off (``sliding_window: null`` in both sources).

One uniform stack of layers in the layout ``hadoop_tpu.models.decoder``
takes (layer-stacked leaves); the program builds it through
``family="llama"`` / ``"mixtral"``.
"""

from __future__ import annotations

import functools
from collections import defaultdict

import jax
import jax.numpy as jnp

from chipbench import reference
from chipbench import weights as W
from chipbench.reference import HI, mm, rms_norm


# ------------------------------------------------------- the program's form

def model_config(model: dict, harness: dict):
    from hadoop_tpu.models.config import ModelConfig
    m = model
    experts = m.get("num_local_experts", 0)
    return ModelConfig(
        family="mixtral" if experts else "llama",
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        max_seq=harness["context"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], tie_embeddings=m["tie_word_embeddings"],
        n_experts=experts, top_k=m.get("num_experts_per_tok", 2),
        capacity_factor=harness.get("moe_capacity_factor", 1.25),
        dtype=m["torch_dtype"])


# ------------------------------------------------------------------ weights

def dims(model: dict) -> dict:
    d = model["hidden_size"]
    hq = model["num_attention_heads"]
    return {"D": d, "Hq": hq, "Hkv": model["num_key_value_heads"],
            "Dh": model.get("head_dim") or d // hq,
            "F": model["intermediate_size"], "V": model["vocab_size"],
            "L": model["num_hidden_layers"],
            "E": model.get("num_local_experts", 0)}


def layer_leaves(model: dict) -> dict:
    """name -> (matrix shape, fan_in, matrices per layer). fan_in None
    marks a norm vector."""
    m = dims(model)
    d, f, e = m["D"], m["F"], m["E"]
    qo, kv = m["Hq"] * m["Dh"], m["Hkv"] * m["Dh"]
    leaves = {
        "attn_norm_w": ((d,), None, 1),
        "wq": ((d, qo), d, 1), "wk": ((d, kv), d, 1),
        "wv": ((d, kv), d, 1), "wo": ((qo, d), qo, 1),
        "mlp_norm_w": ((d,), None, 1),
    }
    per = e or 1
    if e:
        leaves["router"] = ((d, e), d, 1)
    leaves["w_gate"] = ((d, f), d, per)
    leaves["w_up"] = ((d, f), d, per)
    leaves["w_down"] = ((f, d), f, per)
    return leaves


def top_leaves(model: dict) -> dict:
    m = dims(model)
    return {"embed": ((m["V"], m["D"]), m["D"], 1),
            "final_norm_w": ((m["D"],), None, 1),
            "lm_head": ((m["D"], m["V"]), m["D"], 1)}


def layer_params(model: dict, key, layer, dtype) -> dict:
    return W.one_layer(layer_leaves(model), key, layer, dtype)


def make_params(model: dict, key, dtype) -> dict:
    tree = W.flat(top_leaves(model), key, dtype)
    tree["layers"] = W.stack(layer_leaves(model), key, dims(model)["L"],
                             dtype)
    return tree


def make_leaf(model: dict, key, path: tuple, dtype):
    """``("embed",)`` or ``("layers", "wq")``."""
    if path[0] != "layers":
        return W.flat({path[0]: top_leaves(model)[path[0]]}, key,
                      dtype)[path[0]]
    return W.stacked_leaf(layer_leaves(model), key, path[1],
                          dims(model)["L"], dtype)


def leaf_paths(model: dict):
    paths = [("embed",), ("final_norm_w",)]
    paths += [("layers", n) for n in sorted(layer_leaves(model))]
    paths.append(("lm_head",))
    return paths


# ---------------------------------------------------- the plain reference

def rope(x, theta):
    """x [S, H, Dh] at positions 0..S-1, split-half rotation."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c], axis=-1)


def attention(q, k, v):
    """One sequence. q [S, Hq, Dh], k/v [S, Hkv, Dh] -> [S, Hq, Dh].
    One KV head's group at a time so the [S, S] scores stay small."""
    s, hq, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, dh).transpose(1, 2, 0, 3)
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def group(args):
        qh, kh, vh = args               # [G,S,Dh], [S,Dh], [S,Dh]
        sc = jnp.einsum("gqd,kd->gqk", qh, kh, precision=HI) / (dh ** 0.5)
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vh, precision=HI)

    out = jax.lax.map(group, (qg, kg, vg))      # [Hkv, G, S, Dh]
    return out.transpose(2, 0, 1, 3).reshape(s, hq, dh)


def mlp(h, lp, model, quant):
    """h [T, D]."""
    if not model.get("num_local_experts"):
        g = mm(h, lp["w_gate"], quant)
        u = mm(h, lp["w_up"], quant)
        return mm(jax.nn.silu(g) * u, lp["w_down"], quant)
    k = model["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(h, lp["router"], quant), axis=-1)
    top_v, top_i = jax.lax.top_k(probs, k)
    top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    n_exp = probs.shape[-1]
    gate = jnp.sum(jax.nn.one_hot(top_i, n_exp) * top_v[..., None],
                   axis=1)                                  # [T, E]

    @jax.checkpoint
    def expert(acc, xs):
        wg, wu, wd, ge = xs
        y = mm(jax.nn.silu(mm(h, wg, quant)) * mm(h, wu, quant), wd,
               quant)
        return acc + ge[:, None] * y, None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"], gate.T))
    return out


def layer(x, lp, model, quant=None):
    """x [B, S, D] float32, positions 0..S-1 in every row."""
    m = dims(model)
    b, s, d = x.shape
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    # float32 before anything closes over the weights, so that what a
    # map or a scan accumulates for them it accumulates in float32
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}

    def one_row(xr):
        h = rms_norm(xr, lp["attn_norm_w"], eps)
        q = mm(h, lp["wq"], quant).reshape(s, m["Hq"], m["Dh"])
        k = mm(h, lp["wk"], quant).reshape(s, m["Hkv"], m["Dh"])
        v = mm(h, lp["wv"], quant).reshape(s, m["Hkv"], m["Dh"])
        a = attention(rope(q, theta), rope(k, theta), v)
        xr = xr + mm(a.reshape(s, m["Hq"] * m["Dh"]), lp["wo"], quant)
        h = rms_norm(xr, lp["mlp_norm_w"], eps)
        return xr + mlp(h, lp, model, quant)

    return jax.lax.map(one_row, x)


@functools.partial(jax.jit, static_argnames=("model_key", "quant"))
def _serve_layer(x, key, lyr, model_key, quant):
    model = dict(model_key)
    lp = layer_params(model, key, lyr, jnp.bfloat16)
    return layer(x, lp, model, quant)


@functools.partial(jax.jit, static_argnames=("model_key",))
def _top(key, model_key):
    return W.flat(top_leaves(dict(model_key)), key, jnp.bfloat16)


def hidden_states(model: dict, seed: int, tokens, quant=None):
    """Final-layer hidden states [N, S, D] of ``tokens`` [N, S] (each row
    a prompt followed by what was served for it; padding after that is
    never looked at, the mask is causal). Weights are regenerated from
    the seed one layer at a time."""
    mkey = W.freeze(model)
    key = W.seed_key(seed)
    embed = _top(key, mkey)["embed"]
    x = embed[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for lyr in range(model["num_hidden_layers"]):
        x = _serve_layer(x, key, jnp.int32(lyr), mkey, quant)
    return x


def score(model: dict, seed: int, x, positions, tokens_at, quant=None):
    top = _top(W.seed_key(seed), W.freeze(model))
    return reference.score(x, positions, tokens_at, top["final_norm_w"],
                           top["lm_head"], model["rms_norm_eps"], quant)


def loss_fn(params, tokens, targets, model, quant=None, ce_chunk=512,
            keep=1.0):
    """Mean cross-entropy of ``targets`` given ``tokens`` ([B, S])."""
    x = params["embed"].astype(jnp.float32)[tokens]
    head_w = params["lm_head"].astype(jnp.float32)

    @jax.checkpoint
    def body(x, lp):
        return layer(x, lp, model, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm_w"], model["rms_norm_eps"])
    return reference.token_cross_entropy(x, head_w, targets, quant,
                                         ce_chunk, keep)


def follow(model: dict, seed: int, batches, quant=None, keep=1.0):
    return reference.follow(make_params, loss_fn, model, seed, batches,
                            quant, keep)


# ------------------------------------------------- the work a model needs
# From its shapes, never from the implementation: no recompute, no padding
# rows, no context beyond the live one, only the experts a token is routed
# to.

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


def serve_work(model: dict, requests) -> dict:
    """Forward FLOPs for what ``requests`` (``families.Served``) had
    computed inside the window. Full causal attention: a prompt's tokens
    past its matched share see every token before them, and output token
    ``j`` of a prompt of ``p`` tokens sees ``p + j``."""
    n_tok, dec_tok, dec_ctx = 0, 0, 0.0
    pre_by_share, pre_ctx = defaultdict(int), 0.0
    for r in requests:
        p, hit = r.prompt_len, r.matched_share
        for j in r.outputs:
            n_tok += 1
            if j == 0:
                pre_by_share[hit] += p
                pre_ctx += (p * (p + 1) - (hit * p) * (hit * p + 1)) / 2.0
            else:
                dec_tok += 1
                dec_ctx += p + j
    pre_tok = sum(n * (1.0 - hit) for hit, n in pre_by_share.items())
    return {"flops": serve_flops(model, pre_tok, pre_ctx, dec_tok, dec_ctx,
                                 n_tok),
            "bytes": None}
