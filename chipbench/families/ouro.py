"""``model_type: ouro``: the published ``OuroForCausalLM`` (ByteDance
Ouro-2.6B; arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language
Models"). ``num_hidden_layers`` layers run ``total_ut_steps`` times a
token over ONE set of weights::

    x = Embed(tokens)
    for t in 1 .. total_ut_steps:
        for l in 1 .. num_hidden_layers:
            x = x + RMSNorm(Attn_l(RMSNorm(x; g1_l)); g2_l)
            x = x + RMSNorm(MLP_l(RMSNorm(x; g3_l)); g4_l)
        x = RMSNorm(x; g_final)                 # closes every pass
    logits = W_head x

``Attn``: causal, ``num_attention_heads`` heads of ``head_dim`` with as
many K/V heads (no grouping), split-half rotary embedding on q and k;
pass ``t`` attends to what pass ``t`` made of the earlier tokens and to
nothing else (the source caches it in slot ``(t-1) * layers + l``; this
reference has no cache and recomputes it). ``MLP``: ``W_down(silu(W_gate
u) * W_up u)``. No bias but the exit gate's, no window.

**The one departure from the published code**: the exit gate ``lambda_t =
sigmoid(w_exit . x + b_exit)`` after each pass is not evaluated. At the
published ``early_exit_threshold`` of 1 the gate's distribution ``p_t =
lambda_t prod_{j<t} (1 - lambda_j)`` (the remaining mass on the last
pass) never reaches the threshold before the last pass, so every token's
logits are the last pass's and the gate enters none of them. Its two
leaves are in the tree (``exit_gate_w``, ``exit_gate_b``): they are in
the checkpoint. Any other threshold ends the run.

``hidden_states`` hands back the state after the last pass's layers and
BEFORE the norm that closes it: ``score`` applies that norm with the head
(``reference.score``), as for every family.

One uniform stack of layers in the layout ``hadoop_tpu.models.decoder``
takes plus two norm leaves a layer (``models/ouro.py``); the program
builds it through ``family="ouro"``.

Serving only: ``follow`` raises; ``train_flops_per_token`` counts what a
training step of the looped stack would need and no cell uses it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import peaks, reference
from chipbench import weights as W
from chipbench.families.mistral import attention, rope
from chipbench.reference import mm, rms_norm

BIAS_FAN_IN = 100       # a bias leaf: zero-mean, std 0.1


class NotBuilt(NotImplementedError):
    """Asked for the training path of a family built for serving."""


# ------------------------------------------------------- the program's form

KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "rms_norm_eps",
    "rope_theta", "total_ut_steps", "early_exit_threshold",
    "tie_word_embeddings", "torch_dtype")


def model_config(model: dict, harness: dict):
    from chipbench import families
    from hadoop_tpu.models.config import ModelConfig
    m = model
    missing = [k for k in KEYS if k not in m]
    if missing:
        raise SystemExit(
            f"model_type 'ouro' reads {missing} and the configuration file "
            "has none of them at its top level (of the families "
            f"{families.names()} this one takes the source's keys plus "
            "torch_dtype)")
    if m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise SystemExit(
            f"model_type 'ouro': head_dim {m['head_dim']} x "
            f"{m['num_attention_heads']} heads is not hidden_size "
            f"{m['hidden_size']}; the program's heads divide the width")
    if m.get("sliding_window") or m.get("rope_scaling"):
        raise SystemExit("model_type 'ouro': a sliding window or a rope "
                         "scaling is not in the published configuration "
                         "and not built")
    try:
        return ModelConfig(
            family="ouro", vocab_size=m["vocab_size"],
            d_model=m["hidden_size"], n_layers=m["num_hidden_layers"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"],
            d_ff=m["intermediate_size"], max_seq=harness["context"],
            rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"],
            tie_embeddings=m["tie_word_embeddings"], dtype=m["torch_dtype"],
            n_passes=m["total_ut_steps"], sandwich_norm=True,
            early_exit_threshold=float(m["early_exit_threshold"]))
    except TypeError as e:
        raise SystemExit(
            "this checkout's hadoop_tpu has no family 'ouro' "
            f"(models/config.ModelConfig: {e}); the cell needs the program "
            "of the PR that added it") from None


# ------------------------------------------------------------------ weights

def dims(model: dict) -> dict:
    m = model
    return {"D": m["hidden_size"], "H": m["num_attention_heads"],
            "Hkv": m["num_key_value_heads"], "dh": m["head_dim"],
            "F": m["intermediate_size"], "V": m["vocab_size"],
            "L": m["num_hidden_layers"], "P": m["total_ut_steps"]}


def layer_leaves(model: dict) -> dict:
    """name -> (matrix shape, fan_in, matrices per layer). fan_in None
    marks a norm vector. The four norms of a layer, in the order the
    equations name them: ``attn_norm_w`` (g1), ``attn_post_norm_w`` (g2),
    ``mlp_norm_w`` (g3), ``mlp_post_norm_w`` (g4)."""
    m = dims(model)
    d, f = m["D"], m["F"]
    qo, kv = m["H"] * m["dh"], m["Hkv"] * m["dh"]
    return {
        "attn_norm_w": ((d,), None, 1),
        "wq": ((d, qo), d, 1), "wk": ((d, kv), d, 1),
        "wv": ((d, kv), d, 1), "wo": ((qo, d), qo, 1),
        "attn_post_norm_w": ((d,), None, 1),
        "mlp_norm_w": ((d,), None, 1),
        "w_gate": ((d, f), d, 1), "w_up": ((d, f), d, 1),
        "w_down": ((f, d), f, 1),
        "mlp_post_norm_w": ((d,), None, 1),
    }


def top_leaves(model: dict) -> dict:
    m = dims(model)
    return {"embed": ((m["V"], m["D"]), m["D"], 1),
            "final_norm_w": ((m["D"],), None, 1),
            "lm_head": ((m["D"], m["V"]), m["D"], 1),
            "exit_gate_w": ((m["D"], 1), m["D"], 1),
            "exit_gate_b": ((1,), BIAS_FAN_IN, 1)}


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
    """In the tree's flatten order (keys sorted at each level)."""
    layers = [("layers", n) for n in sorted(layer_leaves(model))]
    paths = []
    for name in sorted(list(top_leaves(model)) + ["layers"]):
        paths += layers if name == "layers" else [(name,)]
    return paths


# ---------------------------------------------------- the plain reference

def layer(x, lp, model, quant=None):
    """x [B, S, D] float32, positions 0..S-1 in every row: one
    sandwich-normed layer."""
    m = dims(model)
    s = x.shape[1]
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    # float32 before anything closes over the weights, so that what a
    # map or a scan accumulates for them it accumulates in float32
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}

    def one_row(xr):
        u = rms_norm(xr, lp["attn_norm_w"], eps)
        q = mm(u, lp["wq"], quant).reshape(s, m["H"], m["dh"])
        k = mm(u, lp["wk"], quant).reshape(s, m["Hkv"], m["dh"])
        v = mm(u, lp["wv"], quant).reshape(s, m["Hkv"], m["dh"])
        a = attention(rope(q, theta), rope(k, theta), v)
        a = mm(a.reshape(s, m["H"] * m["dh"]), lp["wo"], quant)
        xr = xr + rms_norm(a, lp["attn_post_norm_w"], eps)
        u = rms_norm(xr, lp["mlp_norm_w"], eps)
        y = mm(jax.nn.silu(mm(u, lp["w_gate"], quant))
               * mm(u, lp["w_up"], quant), lp["w_down"], quant)
        return xr + rms_norm(y, lp["mlp_post_norm_w"], eps)

    return jax.lax.map(one_row, x)


@functools.partial(jax.jit, static_argnames=("model_key", "quant"),
                   donate_argnums=(0,))
def _serve_layer(x, key, lyr, model_key, quant):
    model = dict(model_key)
    lp = layer_params(model, key, lyr, jnp.bfloat16)
    return layer(x, lp, model, quant)


@functools.partial(jax.jit, static_argnames=("eps",), donate_argnums=(0,))
def _close_pass(x, norm_w, eps):
    return rms_norm(x, norm_w, eps)


@functools.partial(jax.jit, static_argnames=("model_key",))
def _top(key, model_key):
    return W.flat(top_leaves(dict(model_key)), key, jnp.bfloat16)


def hidden_states(model: dict, seed: int, tokens, quant=None):
    """Hidden states [N, S, D] of ``tokens`` [N, S] after the LAST pass's
    layers, before the norm that closes it (each row a prompt followed by
    what was served for it; padding after that is never looked at, the
    mask is causal). The whole sequence at once, no cache: the passes are
    a Python loop, a layer's weights are regenerated from the seed every
    time it runs."""
    if model["early_exit_threshold"] != 1:
        raise SystemExit("model_type 'ouro': the reference takes every "
                         "token through all total_ut_steps passes, which "
                         "is the model only at early_exit_threshold 1")
    mkey = W.freeze(model)
    key = W.seed_key(seed)
    top = _top(key, mkey)
    x = top["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    passes = model["total_ut_steps"]
    for t in range(passes):
        for lyr in range(model["num_hidden_layers"]):
            x = _serve_layer(x, key, jnp.int32(lyr), mkey, quant)
        if t < passes - 1:
            x = _close_pass(x, top["final_norm_w"], model["rms_norm_eps"])
    return x


def score(model: dict, seed: int, x, positions, tokens_at, quant=None):
    top = _top(W.seed_key(seed), W.freeze(model))
    return reference.score(x, positions, tokens_at, top["final_norm_w"],
                           top["lm_head"], model["rms_norm_eps"], quant)


def follow(model: dict, seed: int, batches, quant=None, keep=1.0):
    raise NotBuilt("ouro is built for serving: the published training loss "
                   "is an entropy-regularised expectation over exit passes "
                   "whose coefficient the configuration does not give "
                   "(PERF.md section 4)")


# ------------------------------------------------- the work a model needs
# From its shapes, never from the implementation.

def parameter_counts(model: dict) -> dict:
    """Parameters by part, and in all: what PERF.md's table holds as
    numbers."""
    m = dims(model)
    d = m["D"]
    attn = 2 * d * m["H"] * m["dh"] + 2 * d * m["Hkv"] * m["dh"]
    parts = {"attention": attn, "mlp": 3 * d * m["F"], "layer_norms": 4 * d}
    parts["layer"] = attn + parts["mlp"] + parts["layer_norms"]
    parts.update(layers=m["L"] * parts["layer"], embedding=m["V"] * d,
                 head=d * m["V"], final_norm=d, exit_gate=d + 1)
    parts["total"] = parts["layers"] + parts["embedding"] + parts["head"] \
        + parts["final_norm"] + parts["exit_gate"]
    return parts


def kv_bytes_per_token(model: dict) -> int:
    """A K and a V for every (pass, layer)."""
    m = dims(model)
    return m["P"] * m["L"] * 2 * m["Hkv"] * m["dh"] * _item(model)


def layer_matmul_flops(model: dict) -> float:
    """Forward weight-matmul FLOPs of one layer for one token, once."""
    c = parameter_counts(model)
    return 2.0 * (c["attention"] + c["mlp"])


def attention_flops(model: dict, entries: float) -> float:
    """Scores and outputs of one token over ``entries`` cached tokens in
    every layer of every pass."""
    m = dims(model)
    return 4.0 * m["P"] * m["L"] * m["H"] * m["dh"] * entries


def _item(model) -> int:
    return jnp.dtype(model["torch_dtype"]).itemsize


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward (3x forward) for one token of a packed causal
    sequence through all the passes. No cell uses it."""
    m = dims(model)
    fwd = m["P"] * m["L"] * layer_matmul_flops(model) \
        + attention_flops(model, (seq + 1) / 2.0) + 2.0 * m["D"] * m["V"]
    return 3.0 * fwd


def serve_work(model: dict, requests) -> dict:
    """Forward FLOPs and the least bytes for what ``requests``
    (``families.Served``) had computed inside the window. A prompt's
    tokens past its matched share each attend to the tokens before them;
    output token ``j`` of a prompt of ``p`` tokens has the context ``p +
    j``; every computed token runs every layer ``total_ut_steps`` times.
    Bytes: every computed token reads the K and V of its context in every
    (pass, layer) slot and writes its own; the layers' weights are read
    once A PASS and the head once for each of the steps the window cannot
    do without (a request's tokens follow one another: the longest run of
    them) — pass t + 1 needs all of pass t, and the stack stays in no
    on-chip memory."""
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
    counts = parameter_counts(model)
    flops = m["P"] * m["L"] * layer_matmul_flops(model) * tokens \
        + attention_flops(model, live) + 2.0 * m["D"] * m["V"] * sampled
    cache = kv_bytes_per_token(model) * (live + tokens)
    weights = _item(model) * steps * (m["P"] * counts["layers"]
                                      + counts["head"])
    return {"flops": flops, "bytes": cache + weights}


# ----------------------------------------------------- roofline readers
# max(FLOPs / peak FLOP/s, bytes / peak B/s) / device seconds of the
# scopes. The work is the LEAST any implementation must do, a step: the
# engine's counters over the whole window divided by its steps, times the
# whole runs of the step program inside the traced slice (``scopes.reduce``
# leaves the two runs the profiler cut out of that count and their
# operations in the seconds, so a share reads a step in ~180 low, never
# high). Scaling the window's counters by slice / window instead read 13%
# high here: steps are slower late in a window, when more lanes are live
# (my chip run, PR 35). Where a counter or a scope is missing (a parent
# without them), nothing is read.

def _slice_share(out, flops_a_step: float, bytes_a_step: float, scopes):
    t = out.trace or {}
    red = t.get("scopes") or {}
    by_scope = red.get("scopes", {})
    seconds = sum(by_scope.get(s, 0.0) for s in scopes)
    runs = red.get("modules", {}).get("_step_impl", {}).get("count")
    if not seconds or not runs:
        return None
    kind = out.devices[0].device_kind
    needed = max(flops_a_step / peaks.peak(kind, "bf16_flops"),
                 bytes_a_step / peaks.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * needed * runs / seconds


def _read_loop_roofline(spec, out, cell):
    """Scopes ``attn_proj`` + ``mlp`` + ``loop_norm`` + ``scan_carry``:
    the layers' weights read once a pass (``counter.loop_passes``
    passes), their matmuls for every row computed, once a pass.
    ``scan_carry`` is what the two loops do for themselves, and here that
    is reading the weights too: each layer's ``wq`` / ``wk`` / ``wv`` is
    copied out of its stack before its matmul reads the copy. Without it
    the three scopes alone read 102.5: their matmuls stream at 840 GB/s,
    above the published 819 (my chip run, PR 35)."""
    passes = out.obs.get("counter.loop_passes")
    steps = out.obs.get("steps")
    if not passes or not steps:
        return None
    o = out.obs
    rows = o["prompt_tokens_seen"] - o["prompt_tokens_matched"] \
        + o["tokens_out"] - o.get("first_tokens", 0)
    params = parameter_counts(cell.model)
    matrices = params["layers"] - dims(cell.model)["L"] \
        * params["layer_norms"]
    per_step = passes / steps
    flops = 2.0 * matrices * per_step * rows / steps
    nbytes = _item(cell.model) * params["layers"] * per_step
    return _slice_share(out, flops, nbytes, ("attn_proj", "mlp",
                                             "loop_norm", "scan_carry"))


def _read_attn_roofline(spec, out, cell):
    """Scope ``attn``: the K and V of every page the steps' live rows
    attended to (``counter.attn_pages_read`` pages of the block table,
    each ``total_ut_steps x layers`` slots deep), each read once a step
    by each row that attends to it. (A page that lanes share is counted
    once a lane: a kernel that read it once for all of them would need
    less.)"""
    pages = out.obs.get("counter.attn_pages_read")
    steps = out.obs.get("steps")
    if not pages or not steps:
        return None
    from chipbench import serve_cell
    conf = cell.harness["conf"]
    block = conf.get("serving.kv.block.size") \
        or serve_cell._default("serving.kv.block.size")
    nbytes = pages / steps * block * kv_bytes_per_token(cell.model)
    # (the scores' FLOPs over those entries are 1/240 of the bytes' time)
    return _slice_share(out, 0.0, nbytes, ("attn",))


READERS = {"loop-roofline": _read_loop_roofline,
           "attn-kv-roofline": _read_attn_roofline}
