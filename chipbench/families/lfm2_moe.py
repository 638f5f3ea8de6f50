"""``model_type: lfm2_moe``: the published ``Lfm2MoeForCausalLM`` as one
chip serves it. Pre-norm RMSNorm blocks (``operator_norm``, ``ffn_norm``);
the operator of a layer is a gated short convolution (``conv``: ``[B, C, X]
= split3(u W_in)``, ``z = B * X``, a causal depthwise kernel of
``conv_L_cache`` taps over ``z``, ``(C * c) W_out``) or grouped-query
attention with an RMSNorm of every q and k head before the rotary
embedding (``full_attention``); the FFN is a SwiGLU MLP in the first
``num_dense_layers`` layers and after them a sigmoid top-k router with a
choice-only bias over ``num_experts`` experts, every one held, the chosen
scores normalised by their sum plus ``router_norm_eps`` times
``routed_scaling_factor``, no shared expert; final RMSNorm
(``embedding_norm``); the head is the embedding.

**Departures from the published code** (also in the configuration file's
``assumed``): rotary pairs are split-half (weights are random, q and k
share the convention); the depthwise kernel is kept ``[K, D]``; embedding
and head are tied because the family's convention says so (the catalog row
has no key for it).

The configuration file's top-level scalars are the model (``harness.Cell
.model`` drops nested groups), so the kinds of the layers are repeated
there as the string ``layer_kinds`` beside the source's ``layer_types``
list, and ``rope_theta`` beside its ``rope_parameters`` group.

A layer is an operator and an FFN, stacked apart in the program's tree
(``conv_ops``, ``attn_ops``, ``dense_layers``, ``moe_layers``); every
matrix is keyed by its place in its own stack.

Serving only: ``follow`` and ``train_flops_per_token`` raise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import peaks, reference
from chipbench import weights as W
from chipbench.reference import HI, mm, rms_norm

QUERY_BLOCK = 128       # query rows attended at a time
ROW_BLOCK = 2048        # rows through a dense MLP at a time
BIAS_FAN_IN = 100       # a bias leaf: zero-mean, std 0.1
ROOM = 8                # rows gathered for an expert, in uniform shares
OPS = {"conv": "conv_ops", "full_attention": "attn_ops"}
FFNS = {"dense": "dense_layers", "moe": "moe_layers"}


class NotBuilt(NotImplementedError):
    """Asked for the training path of a family built for serving."""


# ------------------------------------------------------- the program's form

KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "moe_intermediate_size",
    "num_experts", "num_experts_per_tok", "num_dense_layers", "conv_L_cache",
    "norm_eps", "routed_scaling_factor", "router_norm_eps", "rope_theta",
    "layer_kinds", "tie_word_embeddings", "torch_dtype")


def kinds(model: dict):
    return model["layer_kinds"].split(",")


def model_config(model: dict, harness: dict):
    from chipbench import families
    from hadoop_tpu.models.config import ModelConfig
    m = model
    missing = [k for k in KEYS if k not in m]
    if missing:
        raise SystemExit(
            f"model_type 'lfm2_moe' reads {missing} and the configuration "
            "file has none of them at its top level (of the families "
            f"{families.names()} this one takes the source's keys plus "
            "layer_kinds, rope_theta and router_norm_eps)")
    try:
        return ModelConfig(
            family="lfm2_moe", vocab_size=m["vocab_size"],
            d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"],
            d_ff=m["intermediate_size"], max_seq=harness["context"],
            rope_theta=float(m["rope_theta"]), norm_eps=m["norm_eps"],
            tie_embeddings=m["tie_word_embeddings"],
            n_experts=m["num_experts"], n_routed_experts=m["num_experts"],
            top_k=m["num_experts_per_tok"], dtype=m["torch_dtype"],
            n_dense_layers=m["num_dense_layers"],
            d_ff_expert=m["moe_intermediate_size"],
            routed_scaling_factor=m["routed_scaling_factor"],
            router_norm_eps=m["router_norm_eps"],
            layer_types=tuple(kinds(m)), conv_kernel=m["conv_L_cache"])
    except TypeError as e:
        raise SystemExit(
            "this checkout's hadoop_tpu has no family 'lfm2_moe' "
            f"(models/config.ModelConfig: {e}); the cell needs the program "
            "of the PR that added it") from None


# ------------------------------------------------------------------ weights

def dims(model: dict) -> dict:
    m = model
    ks = kinds(m)
    return {"D": m["hidden_size"], "H": m["num_attention_heads"],
            "Hkv": m["num_key_value_heads"],
            "dh": m["hidden_size"] // m["num_attention_heads"],
            "K": m["conv_L_cache"], "F": m["intermediate_size"],
            "Fe": m["moe_intermediate_size"], "E": m["num_experts"],
            "k": m["num_experts_per_tok"], "V": m["vocab_size"],
            "L": m["num_hidden_layers"], "Ld": m["num_dense_layers"],
            "Lc": ks.count("conv"), "La": ks.count("full_attention")}


def stack_leaves(model: dict, stack: str) -> dict:
    """name -> (matrix shape, fan_in, matrices per layer) of one layer of
    ``conv_ops`` | ``attn_ops`` | ``dense_layers`` | ``moe_layers``.
    fan_in None marks a norm weight, BIAS_FAN_IN a bias. ``conv_norm_w``
    and ``attn_norm_w`` are the published ``operator_norm`` of a layer of
    that kind."""
    m = dims(model)
    d, h, hkv, dh = m["D"], m["H"], m["Hkv"], m["dh"]
    if stack == "conv_ops":
        return {"conv_norm_w": ((d,), None, 1),
                "in_proj": ((d, 3 * d), d, 1),
                "conv_w": ((m["K"], d), m["K"], 1),
                "out_proj": ((d, d), d, 1)}
    if stack == "attn_ops":
        return {"attn_norm_w": ((d,), None, 1), "wq": ((d, h * dh), d, 1),
                "wk": ((d, hkv * dh), d, 1), "wv": ((d, hkv * dh), d, 1),
                "q_norm_w": ((dh,), None, 1), "k_norm_w": ((dh,), None, 1),
                "wo": ((h * dh, d), h * dh, 1)}
    if stack == "dense_layers":
        f = m["F"]
        return {"ffn_norm_w": ((d,), None, 1), "w_gate": ((d, f), d, 1),
                "w_up": ((d, f), d, 1), "w_down": ((f, d), f, 1)}
    e, f = m["E"], m["Fe"]
    return {"ffn_norm_w": ((d,), None, 1), "router": ((d, e), d, 1),
            "router_bias": ((e,), BIAS_FAN_IN, 1),
            "w_gate": ((d, f), d, e), "w_up": ((d, f), d, e),
            "w_down": ((f, d), f, e)}


def top_leaves(model: dict) -> dict:
    m = dims(model)
    return {"embed": ((m["V"], m["D"]), m["D"], 1),
            "final_norm_w": ((m["D"],), None, 1)}


def stack_sizes(model: dict) -> dict:
    m = dims(model)
    sizes = {"conv_ops": m["Lc"], "attn_ops": m["La"],
             "dense_layers": m["Ld"], "moe_layers": m["L"] - m["Ld"]}
    return {k: n for k, n in sizes.items() if n}


def places(model: dict):
    """Per layer: (operator stack, its place there, FFN stack, its place
    there)."""
    seen = {s: 0 for s in list(OPS.values()) + list(FFNS.values())}
    out = []
    for l, kind in enumerate(kinds(model)):
        op = OPS[kind]
        ffn = FFNS["dense" if l < model["num_dense_layers"] else "moe"]
        out.append((op, seen[op], ffn, seen[ffn]))
        seen[op] += 1
        seen[ffn] += 1
    return out


def layer_params(model: dict, key, layer: int, dtype) -> dict:
    """One layer's leaves: its operator's and its FFN's."""
    op, oi, ffn, fi = places(model)[layer]
    return {**W.one_layer(stack_leaves(model, op), key, oi, dtype),
            **W.one_layer(stack_leaves(model, ffn), key, fi, dtype)}


def make_params(model: dict, key, dtype) -> dict:
    tree = W.flat(top_leaves(model), key, dtype)
    for stack, n in stack_sizes(model).items():
        tree[stack] = W.stack(stack_leaves(model, stack), key, n, dtype)
    return tree


def leaf_paths(model: dict):
    """In the tree's flatten order (keys sorted at each level)."""
    stacks = {s: [(s, leaf) for leaf in sorted(stack_leaves(model, s))]
              for s in stack_sizes(model)}
    paths = []
    for name in sorted(list(stacks) + list(top_leaves(model))):
        paths += stacks.get(name, [(name,)])
    return paths


def make_leaf(model: dict, key, path: tuple, dtype):
    """``("embed",)`` or ``("moe_layers", "router")``."""
    if len(path) == 1:
        return W.flat({path[0]: top_leaves(model)[path[0]]}, key,
                      dtype)[path[0]]
    return W.stacked_leaf(stack_leaves(model, path[0]), key, path[1],
                          stack_sizes(model)[path[0]], dtype)


# ---------------------------------------------------- the plain reference

def rope(x, pos, theta: float):
    """x [S, H, dh] at positions ``pos`` [S], split-half rotation."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def conv_operator(u, lp, model, quant):
    """One sequence u [S, D] (already normed) -> the operator's output."""
    k = model["conv_L_cache"]
    s = u.shape[0]
    gate_b, gate_c, x = jnp.split(mm(u, lp["in_proj"], quant), 3, axis=-1)
    z = jnp.pad(gate_b * x, ((k - 1, 0), (0, 0)))       # z_{<0} = 0
    c = sum(lp["conv_w"][j] * z[j:j + s] for j in range(k))
    return mm(gate_c * c, lp["out_proj"], quant)


def attention_operator(u, lp, model, quant):
    """One sequence u [S, D] (already normed): causal GQA, q and k
    RMS-normed per head before the rotary embedding, QUERY_BLOCK query
    rows at a time."""
    m = dims(model)
    s = u.shape[0]
    h, hkv, dh = m["H"], m["Hkv"], m["dh"]
    eps, theta = model["norm_eps"], float(model["rope_theta"])
    pos = jnp.arange(s)
    q = rms_norm(mm(u, lp["wq"], quant).reshape(s, h, dh), lp["q_norm_w"],
                 eps)
    k = rms_norm(mm(u, lp["wk"], quant).reshape(s, hkv, dh),
                 lp["k_norm_w"], eps)
    v = mm(u, lp["wv"], quant).reshape(s, hkv, dh)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def block(pb):                                      # [qb] positions
        qg = q[pb].reshape(qb, hkv, h // hkv, dh)
        sc = jnp.einsum("bgrd,sgd->bgrs", qg, k, precision=HI) * dh ** -0.5
        sc = jnp.where((pos[None, :] <= pb[:, None])[:, None, None, :], sc,
                       -jnp.inf)
        o = jnp.einsum("bgrs,sgd->bgrd", jax.nn.softmax(sc, axis=-1), v,
                       precision=HI)
        return o.reshape(qb, h * dh)

    o = jax.lax.map(block, pos.reshape(s // qb, qb)).reshape(s, h * dh)
    return mm(o, lp["wo"], quant)


def route(h, lp, model, quant):
    """h [T, D] -> (chosen experts [T, k], weights [T, k]): sigmoid scores;
    the bias is added to CHOOSE only; the chosen scores over their sum
    plus ``router_norm_eps``, times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(mm(h, lp["router"], quant))
    chosen = jax.lax.top_k(s + lp["router_bias"],
                           model["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / (jnp.sum(w, axis=-1, keepdims=True)
                        + model["router_norm_eps"]) \
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
    """``sum_i w_i E_i(h)`` for rows h [T, D]: the rows routed to an
    expert are gathered (room for ROOM times a uniform router's share),
    run through it and added back; an expert that more rows chose than
    there is room for — the bias can make one that popular — runs over
    all the rows instead, with a zero weight where it was not chosen.
    Either way every assignment is computed: no token is dropped."""
    m = dims(model)
    t = h.shape[0]
    chosen, w = route(h, lp, model, quant)
    cap = min(t, ROOM * t * m["k"] // m["E"] + 8)

    @jax.checkpoint
    def one(acc, xs):
        wg, wu, wd, e = xs
        gate = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)     # [T]
        hit = jnp.any(chosen == e, axis=-1)

        def gathered(acc):
            rows = jnp.nonzero(hit, size=cap, fill_value=t)[0]
            x = jnp.take(h, rows, axis=0, mode="fill", fill_value=0.0)
            y = mm(jax.nn.silu(mm(x, wg, quant)) * mm(x, wu, quant), wd,
                   quant)
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
    return out


def _f32(lp):
    # float32 before anything closes over the weights, so that what a
    # map or a scan accumulates for them it accumulates in float32
    return {k: v.astype(jnp.float32) for k, v in lp.items()}


def layer(x, lp, model, op: str, ffn: str, quant=None):
    """One sequence x [S, D] through one layer (``lp``: its operator's
    and its FFN's leaves)."""
    lp = _f32(lp)
    eps = model["norm_eps"]
    if op == "conv_ops":
        r = x + conv_operator(rms_norm(x, lp["conv_norm_w"], eps), lp,
                              model, quant)
    else:
        r = x + attention_operator(rms_norm(x, lp["attn_norm_w"], eps), lp,
                                   model, quant)
    u = rms_norm(r, lp["ffn_norm_w"], eps)
    if ffn == "dense_layers":
        return r + swiglu_mlp(u, lp["w_gate"], lp["w_up"], lp["w_down"],
                              quant)
    return r + expert_layer(u, lp, model, quant)


# One compiled program per pair of kinds (three in the published pattern),
# the places in the stacks traced: a layer's weights are regenerated from
# the seed once, and the rows go through it one at a time.

@functools.partial(jax.jit, static_argnames=("model_key", "op", "ffn",
                                             "quant"), donate_argnums=(0,))
def _layer(x, key, oi, fi, model_key, op, ffn, quant):
    model = dict(model_key)
    lp = {**W.one_layer(stack_leaves(model, op), key, oi, jnp.bfloat16),
          **W.one_layer(stack_leaves(model, ffn), key, fi, jnp.bfloat16)}
    return jax.lax.map(lambda row: layer(row, lp, model, op, ffn, quant), x)


@functools.partial(jax.jit, static_argnames=("model_key",))
def _top(key, model_key):
    return W.flat(top_leaves(dict(model_key)), key, jnp.bfloat16)


def hidden_states(model: dict, seed: int, tokens, quant=None):
    """Hidden states [N, S, D] of ``tokens`` [N, S] after the last layer
    (each row a prompt followed by what was served for it; padding after
    that is never looked at: every operator is causal). The whole
    sequence at once, no cache and no state carried; handed back in host
    memory, so that a second pass (the control) finds the device free."""
    mkey = W.freeze(model)
    key = W.seed_key(seed)
    tokens = np.asarray(tokens)
    pad = -tokens.shape[1] % QUERY_BLOCK
    embed = _top(key, mkey)["embed"]
    x = embed[jnp.asarray(np.pad(tokens, ((0, 0), (0, pad))),
                          jnp.int32)].astype(jnp.float32)
    del embed
    for op, oi, ffn, fi in places(model):
        x = _layer(x, key, jnp.int32(oi), jnp.int32(fi), mkey, op, ffn,
                   quant)
    return np.asarray(x)[:, :tokens.shape[1]]


def score(model: dict, seed: int, x, positions, tokens_at, quant=None):
    """``reference.score`` under the final norm and the tied head."""
    top = _top(W.seed_key(seed), W.freeze(model))
    return reference.score(jnp.asarray(x), positions, tokens_at,
                           top["final_norm_w"], top["embed"].T,
                           model["norm_eps"], quant)


def follow(model: dict, seed: int, batches, quant=None, keep=1.0):
    raise NotBuilt("lfm2_moe is built for serving: its cell holds every "
                   "expert of every layer, which training at 16 bytes a "
                   "parameter cannot (PERF.md section 4)")


def train_flops_per_token(model: dict, seq: int) -> float:
    raise NotBuilt("lfm2_moe has no training cell (PERF.md section 4)")


# ------------------------------------------------- the work a model needs
# From its shapes, never from the implementation.

def operator_params(model: dict) -> dict:
    m = dims(model)
    d = m["D"]
    return {"conv": d * 3 * d + m["K"] * d + d * d,
            "attn": 2 * d * m["H"] * m["dh"] + 2 * d * m["Hkv"] * m["dh"]
            + 2 * m["dh"]}


def parameter_counts(model: dict) -> dict:
    """Parameters by part (norm weights with the part they precede), and
    in all: what PERF.md's table of the cut holds as numbers."""
    m = dims(model)
    d = m["D"]
    ops = operator_params(model)
    parts = {"conv_operator": ops["conv"] + d,
             "attention_operator": ops["attn"] + d,
             "expert_ffn": m["E"] * 3 * d * m["Fe"] + d * m["E"] + m["E"]
             + d,
             "dense_ffn": 3 * d * m["F"] + d,
             "embedding": m["V"] * d, "final_norm": d}
    ffn = m["Ld"] * parts["dense_ffn"] \
        + (m["L"] - m["Ld"]) * parts["expert_ffn"]
    parts["total"] = m["Lc"] * parts["conv_operator"] \
        + m["La"] * parts["attention_operator"] + ffn \
        + parts["embedding"] + parts["final_norm"]
    return parts


def token_matmul_flops(model: dict) -> float:
    """Forward weight-matmul FLOPs of all layers held for one token: the
    operators, the dense FFN, the router and the k chosen experts."""
    m = dims(model)
    d = m["D"]
    ops = operator_params(model)
    conv = ops["conv"] - m["K"] * d
    attn = ops["attn"] - 2 * m["dh"]
    moe = d * m["E"] + m["k"] * 3 * d * m["Fe"]
    return 2.0 * (m["Lc"] * conv + m["La"] * attn + m["Ld"] * 3 * d * m["F"]
                  + (m["L"] - m["Ld"]) * moe)


def attention_flops(model: dict, entries: float) -> float:
    """All attention layers' scores and outputs of one token over
    ``entries`` cached tokens."""
    m = dims(model)
    return 4.0 * m["La"] * m["H"] * m["dh"] * entries


def _item(model) -> int:
    return jnp.dtype(model["torch_dtype"]).itemsize


def serve_work(model: dict, requests) -> dict:
    """Forward FLOPs and the least bytes for what ``requests``
    (``families.Served``) had computed inside the window. A prompt's
    tokens past its matched share each attend to the tokens before them;
    output token ``j`` of a prompt of ``p`` tokens has the context ``p +
    j``. Bytes: every computed token reads the K and V of its context in
    the attention layers and reads and writes a convolution layer's
    state; the weights are read once for each of the steps the window
    cannot do without (a request's tokens follow one another: the longest
    run of them), the dense leaves whole and of each expert layer the
    experts that a step's rows hit in expectation under a uniform
    router."""
    m = dims(model)
    tokens = sampled = live = 0.0
    steps = 0
    for r in requests:
        p, hit = r.prompt_len, r.matched_share
        steps = max(steps, len(r.outputs))
        for j in r.outputs:
            sampled += 1
            if j == 0:
                done = hit * p
                tokens += p - done
                live += (p * (p + 1) - done * (done + 1)) / 2.0
            else:
                tokens += 1
                live += p + j
    flops = token_matmul_flops(model) * tokens \
        + attention_flops(model, live) \
        + 2.0 * m["D"] * m["V"] * sampled
    item = _item(model)
    cache = item * (m["La"] * 2 * m["Hkv"] * m["dh"] * (live + tokens)
                    + m["Lc"] * 2 * (m["K"] - 1) * m["D"] * tokens)
    counts = parameter_counts(model)
    experts = m["E"] * 3 * m["D"] * m["Fe"]
    dense = counts["total"] - (m["L"] - m["Ld"]) * experts
    rows = tokens / steps if steps else 0.0
    hit_share = 1.0 - (1.0 - m["k"] / m["E"]) ** rows
    weights = item * steps * (dense + (m["L"] - m["Ld"]) * experts
                              * hit_share)
    return {"flops": flops, "bytes": cache + weights}


# ----------------------------------------------------- roofline readers
# max(FLOPs / peak FLOP/s, bytes / peak B/s) / device seconds of the
# scope. The work is the LEAST any implementation must do, from the
# engine's counters over the whole window, scaled to the traced slice; the
# seconds are the slice's. Where a counter or a scope is missing (a parent
# without them), nothing is read.

def _slice_work(out, flops: float, nbytes: float, scope: str):
    t = out.trace or {}
    seconds = (t.get("scopes") or {}).get("scopes", {}).get(scope, 0.0)
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


def _read_moe_roofline(spec, out, cell):
    """Scope ``moe``: the router for every live row, an expert for every
    assignment; bytes: the router once a step and layer, the weights of
    the experts actually hit."""
    got = _counters(out, "moe_assignments", "moe_assignments_local",
                    "moe_local_experts_hit")
    if got is None:
        return None
    assignments, local, hit = got
    model, m = cell.model, dims(cell.model)
    router, expert = m["D"] * m["E"], 3 * m["D"] * m["Fe"]
    flops = 2.0 * (router * assignments / m["k"] + expert * local)
    nbytes = _item(model) * (router * out.obs["steps"] * (m["L"] - m["Ld"])
                             + expert * hit)
    return _slice_work(out, flops, nbytes, "moe")


def _read_conv_roofline(spec, out, cell):
    """Scope ``conv``: ``in_proj`` and ``out_proj`` for every live row of
    every conv layer; bytes: their weights once a step and layer."""
    got = _counters(out, "moe_assignments")
    if got is None:
        return None
    model, m = cell.model, dims(cell.model)
    rows = got[0] / (m["k"] * max(1, m["L"] - m["Ld"]))
    proj = 4 * m["D"] * m["D"]
    flops = 2.0 * m["Lc"] * proj * rows
    nbytes = _item(model) * out.obs["steps"] * m["Lc"] \
        * (proj + m["K"] * m["D"])
    return _slice_work(out, flops, nbytes, "conv")


def _read_state_restores(spec, out, cell):
    """Lanes started from a cached page's state tail, of all lane starts
    in the window."""
    got = _counters(out, "recurrent_state_restores",
                    "recurrent_state_cold_starts")
    if got is None or not sum(got):
        return None
    return 100.0 * got[0] / sum(got)


READERS = {"moe-roofline": _read_moe_roofline,
           "conv-roofline": _read_conv_roofline,
           "state-restore-share": _read_state_restores}
