"""``model_type: deepseek_v32``: the published ``DeepseekV32ForCausalLM``
as one chip of an expert-parallel deployment serves it. Pre-norm RMSNorm
blocks; multi-head latent attention (MLA) in its absorbed form over the
entries that a light indexer selects (top ``index_topk`` of each query's
causal context, exact, ties to the lower position); a SwiGLU MLP in the
first ``first_k_dense_replace`` layers and after them a sigmoid,
group-limited, bias-corrected top-k router over ``router_width`` experts
with one shared expert; YaRN rotary frequencies; untied head.

**The share.** The configuration holds ``n_routed_experts`` routed experts
of the router's ``router_width`` (those of rank ``ep_rank``): the router
scores and chooses over all of them, the layer computes the chosen
experts it holds plus the shared expert, and that partial sum goes on —
here exactly as in the program. Nothing stands in for the other chips.

**Departures from the published code** (also in the configuration file's
``assumed``): the indexer's Hadamard rotation and FP8 quantisation are
left out (the rotation is orthogonal and leaves ``q . k`` unchanged; the
configuration serves in bfloat16); rotary pairs are split-half in the
attention and in the indexer alike (weights are random, q and k share
the convention); the multi-token-prediction module is not part of the
main model's logits and is not held.

The configuration file's top-level scalars are the model (``harness.Cell
.model`` drops nested groups), so YaRN's numbers are repeated there as
``yarn_*`` beside the source's ``rope_scaling`` group.

Serving only: ``follow`` and ``train_flops_per_token`` raise.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import peaks, reference
from chipbench import weights as W
from chipbench.reference import HI, mm, rms_norm

QUERY_BLOCK = 64        # query rows attended at a time (the reference)
ROW_BLOCK = 2048        # rows through an MLP at a time
BIAS_FAN_IN = 100       # a bias leaf: zero-mean, std 0.1
ROOM = 8                # rows gathered for a held expert, in uniform shares


class NotBuilt(NotImplementedError):
    """Asked for the training path of a family built for serving."""


# ------------------------------------------------------- the program's form

KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "intermediate_size", "rope_theta", "rms_norm_eps", "tie_word_embeddings",
    "torch_dtype", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim",
    "index_topk", "first_k_dense_replace", "moe_intermediate_size",
    "n_shared_experts", "n_routed_experts", "router_width", "ep_rank",
    "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor",
    "yarn_factor", "yarn_original_max_position_embeddings", "yarn_beta_fast",
    "yarn_beta_slow", "yarn_mscale")


def model_config(model: dict, harness: dict):
    from chipbench import families
    from hadoop_tpu.models.config import ModelConfig
    m = model
    missing = [k for k in KEYS if k not in m]
    if missing:
        raise SystemExit(
            f"model_type 'deepseek_v32' reads {missing} and the "
            "configuration file has none of them at its top level (of "
            f"the families {families.names()} this one takes the "
            "source's keys plus router_width, ep_rank and yarn_*)")
    try:
        return ModelConfig(
            family="deepseek_v32", vocab_size=m["vocab_size"],
            d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
            n_heads=m["num_attention_heads"], n_kv_heads=1,
            d_ff=m["intermediate_size"], max_seq=harness["context"],
            rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
            tie_embeddings=m["tie_word_embeddings"],
            n_experts=m["n_routed_experts"], top_k=m["num_experts_per_tok"],
            dtype=m["torch_dtype"],
            q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
            qk_nope_head_dim=m["qk_nope_head_dim"],
            qk_rope_head_dim=m["qk_rope_head_dim"],
            v_head_dim=m["v_head_dim"], index_n_heads=m["index_n_heads"],
            index_head_dim=m["index_head_dim"], index_topk=m["index_topk"],
            n_dense_layers=m["first_k_dense_replace"],
            d_ff_expert=m["moe_intermediate_size"],
            n_shared_experts=m["n_shared_experts"],
            n_routed_experts=m["router_width"],
            experts_from=m["ep_rank"] * m["n_routed_experts"],
            n_group=m["n_group"], topk_group=m["topk_group"],
            routed_scaling_factor=m["routed_scaling_factor"],
            rope_factor=float(m["yarn_factor"]),
            rope_original_max_seq=m["yarn_original_max_position_embeddings"],
            rope_beta_fast=float(m["yarn_beta_fast"]),
            rope_beta_slow=float(m["yarn_beta_slow"]),
            rope_mscale=float(m["yarn_mscale"]))
    except TypeError as e:
        raise SystemExit(
            "this checkout's hadoop_tpu has no family 'deepseek_v32' "
            f"(models/config.ModelConfig: {e}); the cell needs the program "
            "of the PR that added it") from None


# ------------------------------------------------------------------ weights

def dims(model: dict) -> dict:
    m = model
    return {"D": m["hidden_size"], "H": m["num_attention_heads"],
            "Rq": m["q_lora_rank"], "C": m["kv_lora_rank"],
            "dn": m["qk_nope_head_dim"], "dr": m["qk_rope_head_dim"],
            "dv": m["v_head_dim"], "Hi": m["index_n_heads"],
            "Di": m["index_head_dim"], "K": m["index_topk"],
            "F": m["intermediate_size"], "Fe": m["moe_intermediate_size"],
            "Fs": m["moe_intermediate_size"] * m["n_shared_experts"],
            "E": m["n_routed_experts"], "N": m["router_width"],
            "lo": m["ep_rank"] * m["n_routed_experts"],
            "V": m["vocab_size"], "L": m["num_hidden_layers"],
            "Ld": m["first_k_dense_replace"]}


def attention_leaves(model: dict) -> dict:
    """name -> (matrix shape, fan_in, matrices per layer) of a layer's
    attention half, alike in both kinds of layer. fan_in None marks a
    norm weight, BIAS_FAN_IN a bias."""
    m = dims(model)
    d, h, rq, c = m["D"], m["H"], m["Rq"], m["C"]
    return {
        "attn_norm_w": ((d,), None, 1),
        "wq_a": ((d, rq), d, 1), "q_norm_w": ((rq,), None, 1),
        "wq_b": ((rq, h * (m["dn"] + m["dr"])), rq, 1),
        "wkv_a": ((d, c + m["dr"]), d, 1), "kv_norm_w": ((c,), None, 1),
        "wkv_b": ((c, h * (m["dn"] + m["dv"])), c, 1),
        "wo": ((h * m["dv"], d), h * m["dv"], 1),
        "idx_wq_b": ((rq, m["Hi"] * m["Di"]), rq, 1),
        "idx_wk": ((d, m["Di"]), d, 1),
        "idx_k_norm_w": ((m["Di"],), None, 1),
        "idx_k_norm_b": ((m["Di"],), BIAS_FAN_IN, 1),
        "idx_w_proj": ((d, m["Hi"]), d, 1),
    }


def ffn_leaves(model: dict, kind: str) -> dict:
    """The other half, of a ``dense`` or a ``moe`` layer."""
    m = dims(model)
    d = m["D"]
    leaves = {"mlp_norm_w": ((d,), None, 1)}
    if kind == "dense":
        f = m["F"]
        leaves.update({"w_gate": ((d, f), d, 1), "w_up": ((d, f), d, 1),
                       "w_down": ((f, d), f, 1)})
    else:
        e, f, fs = m["E"], m["Fe"], m["Fs"]
        leaves.update({
            "router": ((d, m["N"]), d, 1),
            "router_bias": ((m["N"],), BIAS_FAN_IN, 1),
            "w_gate": ((d, f), d, e), "w_up": ((d, f), d, e),
            "w_down": ((f, d), f, e),
            "ws_gate": ((d, fs), d, 1), "ws_up": ((d, fs), d, 1),
            "ws_down": ((fs, d), fs, 1)})
    return leaves


def layer_leaves(model: dict, kind: str) -> dict:
    return {**attention_leaves(model), **ffn_leaves(model, kind)}


def top_leaves(model: dict) -> dict:
    m = dims(model)
    return {"embed": ((m["V"], m["D"]), m["D"], 1),
            "final_norm_w": ((m["D"],), None, 1),
            "lm_head": ((m["D"], m["V"]), m["D"], 1)}


def kind_of(model: dict, layer: int) -> str:
    return "dense" if layer < model["first_k_dense_replace"] else "moe"


def runs(model: dict):
    """(stack name, kind, first layer, layers) of each run of like
    layers; every matrix is keyed by its layer's number in the model."""
    m = dims(model)
    out = []
    if m["Ld"]:
        out.append(("dense_layers", "dense", 0, m["Ld"]))
    if m["L"] > m["Ld"]:
        out.append(("moe_layers", "moe", m["Ld"], m["L"] - m["Ld"]))
    return out


def layer_params(model: dict, key, layer, dtype, kind: str = None) -> dict:
    """One layer's leaves. ``layer`` may be traced when ``kind`` is
    given."""
    return W.one_layer(layer_leaves(model, kind or kind_of(model, layer)),
                       key, layer, dtype)


def make_params(model: dict, key, dtype) -> dict:
    tree = W.flat(top_leaves(model), key, dtype)
    for name, kind, start, n in runs(model):
        tree[name] = W.stack(layer_leaves(model, kind), key, n, dtype, start)
    return tree


def leaf_paths(model: dict):
    """In the tree's flatten order (keys sorted at each level)."""
    stacks = {name: [(name, leaf)
                     for leaf in sorted(layer_leaves(model, kind))]
              for name, kind, _, _ in runs(model)}
    paths = []
    for name in sorted(list(stacks) + list(top_leaves(model))):
        paths += stacks.get(name, [(name,)])
    return paths


def make_leaf(model: dict, key, path: tuple, dtype):
    """``("embed",)`` or ``("moe_layers", "wq_a")``."""
    if len(path) == 1:
        return W.flat({path[0]: top_leaves(model)[path[0]]}, key,
                      dtype)[path[0]]
    kind, start, n = next((k, s, n) for name, k, s, n in runs(model)
                          if name == path[0])
    return W.stacked_leaf(layer_leaves(model, kind), key, path[1], n, dtype,
                          start)


# ---------------------------------------------------- the plain reference

def yarn_inv_freq(model: dict):
    """``f_i = theta^(-2i/dr)``, blended towards ``f_i / factor`` by the
    ramp ``r_i = clip((i - lo) / (hi - lo), 0, 1)`` between the
    correction dimensions of ``beta_fast`` and ``beta_slow`` turns over
    the original context."""
    dr, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    factor = float(model["yarn_factor"])
    orig = model["yarn_original_max_position_embeddings"]

    def correction_dim(turns):
        return dr * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(correction_dim(model["yarn_beta_fast"])), 0)
    hi = min(math.ceil(correction_dim(model["yarn_beta_slow"])), dr - 1)
    if lo == hi:
        hi += 0.001
    i = np.arange(dr // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dr)
    r = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return jnp.asarray(f / factor * r + f * (1.0 - r), jnp.float32)


def softmax_scale(model: dict) -> float:
    m = 0.1 * model["yarn_mscale"] * math.log(model["yarn_factor"]) + 1.0 \
        if model["yarn_factor"] > 1 else 1.0
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def rope(x, pos, inv_freq):
    """x [S, ..., dr] at positions ``pos`` [S], split-half rotation."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def route(h, lp, model, quant):
    """h [T, D] -> (chosen experts [T, K] of the router's whole width,
    weights [T, K]): sigmoid scores; the correction bias is added to
    CHOOSE only; groups scored by the sum of their two largest; the best
    ``topk_group`` groups kept; top-k inside them; weights the chosen
    scores renormalised times ``routed_scaling_factor``."""
    n, g = model["router_width"], model["n_group"]
    s = jax.nn.sigmoid(mm(h, lp["router"], quant))
    sb = (s + lp["router_bias"]).reshape(-1, g, n // g)
    group = jnp.sum(jax.lax.top_k(sb, 2)[0], axis=-1)
    kept = jax.lax.top_k(group, model["topk_group"])[1]             # [T, kg]
    keep = jnp.any(kept[:, :, None] == jnp.arange(g)[None, None, :], axis=1)
    sb = jnp.where(keep[:, :, None], sb, -jnp.inf).reshape(-1, n)
    chosen = jax.lax.top_k(sb, model["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True) \
        * model["routed_scaling_factor"]


def swiglu_mlp(h, wg, wu, wd, quant):
    """h [T, D], a block of rows at a time."""
    t = h.shape[0]
    rb = ROW_BLOCK if t % ROW_BLOCK == 0 else t

    def block(hb):
        return mm(jax.nn.silu(mm(hb, wg, quant)) * mm(hb, wu, quant), wd,
                  quant)
    return jax.lax.map(block, h.reshape(t // rb, rb, -1)).reshape(t, -1)


def expert_layer(h, lp, model, quant):
    """This chip's share for rows h [T, D]: the chosen experts it holds
    (``lo .. lo + E`` of the router's width) plus the shared expert. The
    rows routed to one held expert are gathered (room for ROOM times a
    uniform router's share), run through it and added back; an expert
    that more rows chose than there is room for — a correction bias can
    make one that popular — runs over all the rows instead, with a zero
    weight where it was not chosen. Either way every assignment is
    computed."""
    m = dims(model)
    t = h.shape[0]
    chosen, w = route(h, lp, model, quant)
    local = chosen - m["lo"]
    cap = min(t, ROOM * t * model["num_experts_per_tok"] // m["N"] + 8)

    @jax.checkpoint
    def one(acc, xs):
        wg, wu, wd, e = xs
        gate = jnp.sum(jnp.where(local == e, w, 0.0), axis=-1)      # [T]
        hit = jnp.any(local == e, axis=-1)

        def ffn(x):
            return mm(jax.nn.silu(mm(x, wg, quant)) * mm(x, wu, quant), wd,
                      quant)

        def gathered(acc):
            rows = jnp.nonzero(hit, size=cap, fill_value=t)[0]
            y = ffn(jnp.take(h, rows, axis=0, mode="fill", fill_value=0.0))
            y = y * jnp.take(gate, rows, mode="fill",
                             fill_value=0.0)[:, None]
            return acc.at[rows].add(y, mode="drop")

        def every_row(acc):
            return acc + swiglu_mlp(h, wg, wu, wd, quant) * gate[:, None]

        return jax.lax.cond(jnp.sum(hit) > cap, every_row, gathered,
                            acc), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"], jnp.arange(m["E"])))
    return out + swiglu_mlp(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                            quant)


def attention(x, lp, model, quant, rows=None):
    """One sequence: x [S, D] (already normed) at positions 0..S-1 ->
    the attention's output for the query rows ``rows`` [P] (all S when
    None). The key side (latent, rotary key, index key) of all S first;
    then QUERY_BLOCK query rows at a time: dense index scores against
    every key, an explicit top-k among the causal ones, a gather of the
    chosen latents, attention in the absorbed form."""
    m = dims(model)
    s = x.shape[0]
    eps = model["rms_norm_eps"]
    h_, c, dn, dr, dv = m["H"], m["C"], m["dn"], m["dr"], m["dv"]
    inv = yarn_inv_freq(model)
    scale = softmax_scale(model)
    pos = jnp.arange(s)
    kv = mm(x, lp["wkv_a"], quant)
    lat = jnp.concatenate([rms_norm(kv[:, :c], lp["kv_norm_w"], eps),
                           rope(kv[:, c:], pos, inv)], axis=-1)    # [S,C+dr]
    ki = layer_norm(mm(x, lp["idx_wk"], quant), lp["idx_k_norm_w"],
                    lp["idx_k_norm_b"], eps)
    ki = jnp.concatenate([rope(ki[:, :dr], pos, inv), ki[:, dr:]], axis=-1)
    w_kv = lp["wkv_b"].reshape(c, h_, dn + dv)
    k = min(m["K"], s)
    rows = pos if rows is None else rows
    p = rows.shape[0]
    qb = QUERY_BLOCK if p % QUERY_BLOCK == 0 else p

    def block(pb):                                      # [qb] positions
        xb = x[pb]
        qr = rms_norm(mm(xb, lp["wq_a"], quant), lp["q_norm_w"], eps)
        q = mm(qr, lp["wq_b"], quant).reshape(qb, h_, dn + dr)
        q_abs = jnp.einsum("bhn,chn->bhc", q[..., :dn], w_kv[..., :dn],
                           precision=HI)
        q_cat = jnp.concatenate([q_abs, rope(q[..., dn:], pb, inv)], -1)
        # the indexer: rotary part first in query and key
        qi = mm(qr, lp["idx_wq_b"], quant).reshape(qb, m["Hi"], m["Di"])
        qi = jnp.concatenate([rope(qi[..., :dr], pb, inv), qi[..., dr:]], -1)
        wi = mm(xb, lp["idx_w_proj"], quant) \
            * (m["Hi"] ** -0.5 * m["Di"] ** -0.5)
        dots = jnp.einsum("bhd,sd->bhs", qi, ki, precision=HI)
        index = jnp.sum(jax.nn.relu(dots) * wi[:, :, None], axis=1)  # [qb,S]
        causal = pos[None, :] <= pb[:, None]
        _, chosen = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), k)
        valid = chosen <= pb[:, None]           # a short context has < k
        got = lat[chosen]                                   # [qb, k, C+dr]
        sc = jnp.einsum("bhd,bkd->bhk", q_cat, got, precision=HI) * scale
        sc = jnp.where(valid[:, None, :], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhk,bkc->bhc", pr, got[..., :c], precision=HI)
        o = jnp.einsum("bhc,chv->bhv", o, w_kv[..., dn:], precision=HI)
        return mm(o.reshape(qb, h_ * dv), lp["wo"], quant)

    return jax.lax.map(block, rows.reshape(p // qb, qb)).reshape(p, -1)


def _f32(lp):
    # float32 before anything closes over the weights, so that what a
    # map or a scan accumulates for them it accumulates in float32
    return {k: v.astype(jnp.float32) for k, v in lp.items()}


def attention_half(x, lp, model, quant=None, rows=None):
    """``x[rows] + Attn(norm(x))[rows]`` of one sequence x [S, D]."""
    lp = _f32(lp)
    a = attention(rms_norm(x, lp["attn_norm_w"], model["rms_norm_eps"]), lp,
                  model, quant, rows)
    return (x if rows is None else x[rows]) + a


def ffn_half(h, lp, model, kind, quant=None):
    """``h + FFN(norm(h))`` for rows h [P, D]."""
    lp = _f32(lp)
    x = rms_norm(h, lp["mlp_norm_w"], model["rms_norm_eps"])
    if kind == "dense":
        return h + swiglu_mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                              quant)
    return h + expert_layer(x, lp, model, quant)


# The two halves are compiled apart: the attention half is alike in both
# kinds of layer (one program for every layer; the layer's number is
# traced), and it is by far the larger program.

@functools.partial(jax.jit, static_argnames=("model_key", "quant"))
def _attend(x, row, rows, key, lyr, model_key, quant):
    """Rows ``rows`` [P] of sequence ``row`` of x [N, S, D], after layer
    ``lyr``'s attention half."""
    model = dict(model_key)
    lp = W.one_layer(attention_leaves(model), key, lyr, jnp.bfloat16)
    return attention_half(
        jax.lax.dynamic_index_in_dim(x, row, 0, keepdims=False), lp, model,
        quant, rows)


@functools.partial(jax.jit, static_argnames=("model_key", "kind", "quant"))
def _ffn(h, key, lyr, model_key, kind, quant):
    model = dict(model_key)
    lp = W.one_layer(ffn_leaves(model, kind), key, lyr, jnp.bfloat16)
    return ffn_half(h, lp, model, kind, quant)


@functools.partial(jax.jit, donate_argnums=(0,))
def _put(x, y, row):
    return jax.lax.dynamic_update_index_in_dim(x, y, row, 0)


@functools.partial(jax.jit, static_argnames=("model_key",))
def _top(key, model_key):
    return W.flat(top_leaves(dict(model_key)), key, jnp.bfloat16)


def hidden_states(model: dict, seed: int, tokens, quant=None):
    """Hidden states [N, S, D] of ``tokens`` [N, S] ENTERING THE LAST
    LAYER (each row a prompt followed by what was served for it; padding
    after that is never looked at, the selection is causal). Every layer
    but the last feeds the keys of the next at every position; the last
    layer's output matters only where a token is scored, so ``score``
    (``final_states``) runs it there alone — a fifth of the reference's
    time at the cell's 34,304-token rows. One row and one layer at a
    time, weights regenerated from the seed; handed back in host memory,
    so that a second pass (the control) finds the device free."""
    mkey = W.freeze(model)
    key = W.seed_key(seed)
    tokens = np.asarray(tokens)
    pad = -tokens.shape[1] % QUERY_BLOCK
    embed = _top(key, mkey)["embed"]
    x = embed[jnp.asarray(np.pad(tokens, ((0, 0), (0, pad))),
                          jnp.int32)].astype(jnp.float32)
    del embed
    every = jnp.arange(x.shape[1], dtype=jnp.int32)
    for lyr in range(model["num_hidden_layers"] - 1):
        for row in range(x.shape[0]):
            h = _attend(x, jnp.int32(row), every, key, jnp.int32(lyr), mkey,
                        quant)
            x = _put(x, _ffn(h, key, jnp.int32(lyr), mkey,
                             kind_of(model, lyr), quant), jnp.int32(row))
    return np.asarray(x)


def final_states(model: dict, seed: int, x, at, quant=None):
    """Final hidden states [N, P, D] at positions ``at`` [N, P] of what
    ``hidden_states`` returned: the last layer, for those rows alone."""
    last = jnp.int32(model["num_hidden_layers"] - 1)
    kind = kind_of(model, model["num_hidden_layers"] - 1)
    key, mkey = W.seed_key(seed), W.freeze(model)
    at = np.asarray(at)
    pad = -at.shape[1] % QUERY_BLOCK
    rows = jnp.asarray(np.pad(at, ((0, 0), (0, pad))), jnp.int32)
    x = jnp.asarray(x)
    out = [np.asarray(_ffn(
        _attend(x, jnp.int32(n), rows[n], key, last, mkey, quant), key, last,
        mkey, kind, quant)) for n in range(x.shape[0])]
    return np.stack(out)[:, :at.shape[1]]


def score(model: dict, seed: int, x, positions, tokens_at, quant=None):
    """``reference.score`` over the final hidden states that predict the
    asked positions: the last layer runs for those rows alone, and only
    they go back to the device."""
    top = _top(W.seed_key(seed), W.freeze(model))
    positions = np.asarray(positions)
    at = np.clip(positions - 1, 0, x.shape[1] - 1)
    rows = final_states(model, seed, x, at, quant)
    moved = np.where(positions >= 0, np.arange(positions.shape[1]) + 1, -1)
    return reference.score(jnp.asarray(rows), moved, tokens_at,
                           top["final_norm_w"], top["lm_head"],
                           model["rms_norm_eps"], quant)


def follow(model: dict, seed: int, batches, quant=None, keep=1.0):
    raise NotBuilt("deepseek_v32 is built for serving: training at 16 "
                   "bytes a parameter fits no cut inside the floors "
                   "(PERF.md section 4)")


def train_flops_per_token(model: dict, seq: int) -> float:
    raise NotBuilt("deepseek_v32 has no training cell (PERF.md section 4)")


# ------------------------------------------------- the work a model needs
# From its shapes, never from the implementation: the layers held, the
# local share of the routed experts, index scores over the live context,
# attention over the entries kept, the head where a token is sampled.

def token_matmul_flops(model: dict) -> float:
    """Forward weight-matmul FLOPs of all layers held for one token."""
    m = dims(model)
    d, h, rq, c = m["D"], m["H"], m["Rq"], m["C"]
    mla = d * rq + rq * h * (m["dn"] + m["dr"]) + d * (c + m["dr"]) \
        + h * m["dn"] * c + h * c * m["dv"] + h * m["dv"] * d
    indexer = rq * m["Hi"] * m["Di"] + d * m["Di"] + d * m["Hi"]
    dense = 3 * d * m["F"]
    held = model["num_experts_per_tok"] * m["E"] / m["N"]
    moe = d * m["N"] + 3 * d * m["Fs"] + held * 3 * d * m["Fe"]
    return 2.0 * (m["L"] * (mla + indexer) + m["Ld"] * dense
                  + (m["L"] - m["Ld"]) * moe)


def index_flops(model: dict, context: float) -> float:
    """All layers' index scores of one token against ``context`` keys."""
    m = dims(model)
    return 2.0 * m["L"] * m["Hi"] * m["Di"] * context


def attention_flops(model: dict, entries: float) -> float:
    """All layers' absorbed attention of one token over ``entries``
    latents: scores over C + dr, output over C, for H heads."""
    m = dims(model)
    return 2.0 * m["L"] * m["H"] * (2 * m["C"] + m["dr"]) * entries


def head_flops(model: dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def _kept(lo: float, hi: float, k: int) -> float:
    """sum over contexts n = lo+1 .. hi of min(n, k)."""
    a = min(hi, k)
    small = (a * (a + 1) - lo * (lo + 1)) / 2.0 if a > lo else 0.0
    return small + k * max(0.0, hi - max(lo, k))


def serve_work(model: dict, requests) -> dict:
    """Forward FLOPs for what ``requests`` (``families.Served``) had
    computed inside the window: a prompt's tokens past its matched share
    each score every key before them and attend to ``min(context,
    index_topk)`` of them; output token ``j`` of a prompt of ``p`` tokens
    has the context ``p + j``."""
    k = model["index_topk"]
    tokens = sampled = live = kept = 0.0
    for r in requests:
        p, hit = r.prompt_len, r.matched_share
        for j in r.outputs:
            sampled += 1
            if j == 0:
                done = hit * p
                tokens += p - done
                live += (p * (p + 1) - done * (done + 1)) / 2.0
                kept += _kept(done, p, k)
            else:
                tokens += 1
                live += p + j
                kept += min(p + j, k)
    flops = token_matmul_flops(model) * tokens + index_flops(model, live) \
        + attention_flops(model, kept) + head_flops(model) * sampled
    return {"flops": flops, "bytes": None}


# ----------------------------------------------------- roofline readers
# max(FLOPs / peak FLOP/s, bytes / peak B/s) / device seconds of the
# kernel's scopes. The work is the LEAST any implementation must do, from
# the engine's counters over the whole window, scaled to the traced
# slice; the seconds are the slice's. Where a counter or a scope is
# missing (a parent without them), nothing is read.

def _slice_work(out, model, flops: float, nbytes: float, scopes):
    t = out.trace or {}
    seconds = sum((t.get("scopes") or {}).get("scopes", {}).get(s, 0.0)
                  for s in scopes)
    window = out.obs.get("window_s")
    if not seconds or not window or not t.get("window_s"):
        return None
    kind = out.devices[0].device_kind
    needed = max(flops / peaks.peak(kind, "bf16_flops"),
                 nbytes / peaks.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * needed * (t["window_s"] / window) / seconds


def _counters(out, *names):
    vals = [out.obs.get("counter." + n) for n in names]
    return None if any(v is None for v in vals) else vals


def _rows(model, assignments: float) -> float:
    m = dims(model)
    return assignments / (model["num_experts_per_tok"]
                          * max(1, m["L"] - m["Ld"]))


def _item(model) -> int:
    return jnp.dtype(model["torch_dtype"]).itemsize


def _block_size(cell) -> int:
    """The pages' size: the configuration's, else the program's default."""
    import ast

    from hadoop_tpu.conf.registry import KEYS
    key = "serving.kv.block.size"
    return cell.harness["conf"].get(
        key, ast.literal_eval(KEYS[key]["defaults"][0]))


def _read_index_roofline(spec, out, cell):
    """Scope ``dsa_index``: the indexer's projections for every live row
    and its scores against every live key; bytes: the projections'
    weights once a step and layer, each distinct live page's index keys
    once a step and layer."""
    got = _counters(out, "attn_entries_live", "moe_assignments",
                    "attn_pages_distinct")
    if got is None:
        return None
    live, assignments, pages = got
    model, m = cell.model, dims(cell.model)
    proj = m["Rq"] * m["Hi"] * m["Di"] + m["D"] * m["Di"] + m["D"] * m["Hi"]
    flops = index_flops(model, live) \
        + 2.0 * m["L"] * proj * _rows(model, assignments)
    block = _block_size(cell)
    nbytes = m["L"] * _item(model) * (
        out.obs["steps"] * proj + pages * block * m["Di"])
    return _slice_work(out, model, flops, nbytes, ("dsa_index",))


def _read_attn_roofline(spec, out, cell):
    """Scopes ``dsa_select`` + ``attn``: attention over the kept
    entries; bytes: each kept latent once (no more than the distinct
    live pages hold)."""
    got = _counters(out, "attn_entries_selected", "attn_pages_distinct")
    if got is None:
        return None
    kept, pages = got
    model, m = cell.model, dims(cell.model)
    block = _block_size(cell)
    nbytes = m["L"] * _item(model) * (m["C"] + m["dr"]) \
        * min(kept, pages * block)
    return _slice_work(out, model, attention_flops(model, kept), nbytes,
                       ("dsa_select", "attn"))


def _read_moe_roofline(spec, out, cell):
    """Scope ``moe``: router and shared expert for every live row, a
    routed expert for every assignment that fell on one held here;
    bytes: router and shared expert once a step and layer, the weights
    of the held experts actually hit."""
    got = _counters(out, "moe_assignments", "moe_assignments_local",
                    "moe_local_experts_hit")
    if got is None:
        return None
    assignments, local, hit = got
    model, m = cell.model, dims(cell.model)
    n_moe = m["L"] - m["Ld"]
    always = m["D"] * m["N"] + 3 * m["D"] * m["Fs"]
    expert = 3 * m["D"] * m["Fe"]
    flops = 2.0 * (always * _rows(model, assignments) * n_moe
                   + expert * local)
    nbytes = _item(model) * (always * out.obs["steps"] * n_moe
                             + expert * hit)
    return _slice_work(out, model, flops, nbytes, ("moe",))


READERS = {"dsa-index-roofline": _read_index_roofline,
           "mla-attn-roofline": _read_attn_roofline,
           "moe-roofline": _read_moe_roofline}
