"""The plain reference: Mistral / Mixtral in straightforward ``jax.numpy``,
float32 at ``highest`` matmul precision, no kernels, no cache, no
batching tricks. It imports nothing of the program and takes nothing the
program made: weights come from ``chipbench.weights`` and the seed.

Architecture (the published ones, huggingface ``MistralForCausalLM`` /
``MixtralForCausalLM``): pre-norm RMSNorm, grouped-query causal
attention with split-half rotary embeddings, SwiGLU MLP, untied head.
Mixtral: softmax router, top-2 experts, gate weights renormalised over
the chosen two, no token dropped. Training: mean token cross-entropy,
global-norm clip at 1.0, AdamW (lr 3e-4, betas 0.9 / 0.95, eps 1e-8,
decoupled decay 0.1 on matrices, none on norm vectors), parameters kept
in the dtype the configuration states (bfloat16), moments in float32.

Departures, each because the configuration states it: parameters are
rounded to bfloat16 after every update; the sliding window is off
(``sliding_window: null`` in both sources).

``quant`` switches the *control*: the same mathematics with every
weight matmul's operands rounded to a lower precision (``int8``:
per-token activations and per-output-channel weights, symmetric
abs-max; ``fp8``: float8_e4m3fn casts). It exists to show that the
comparison deciding ``correct`` fails a lower precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
LR, B1, B2, EPS, DECAY, CLIP = 3e-4, 0.9, 0.95, 1e-8, 0.1, 1.0


# ------------------------------------------------------------ primitives

def _fake_quant(x, axis, quant):
    if quant == "int8":
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        q = jnp.round(x / s) * s
    elif quant == "fp8":
        q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        raise ValueError(f"unknown control precision {quant!r}")
    # straight-through, so the control also has gradients
    return x + jax.lax.stop_gradient(q - x)


def mm(x, w, quant=None):
    """x [..., K] @ w [K, N] in float32 at highest precision."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x = _fake_quant(x, -1, quant)
        w = _fake_quant(w, 0, quant)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


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
    m = W.dims(model)
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


# ---------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnames=("model_key", "quant"))
def _serve_layer(x, key, lyr, model_key, quant):
    model = dict(model_key)
    lp = W.layer_params(model, key, lyr, jnp.bfloat16)
    return layer(x, lp, model, quant)


@functools.partial(jax.jit, static_argnames=("model_key",))
def _top(key, model_key):
    return W.top_params(dict(model_key), key, jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("model_key", "quant"))
def _head(x, idx, served, norm_w, head_w, model_key, quant):
    model = dict(model_key)
    h = jnp.take_along_axis(x, idx[:, :, None], axis=1)
    h = rms_norm(h, norm_w, model["rms_norm_eps"])
    logits = mm(h, head_w, quant)                           # [N, P, V]
    got = jnp.take_along_axis(logits, served[:, :, None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - got, jnp.argmax(logits, axis=-1)


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
    """At each of ``positions`` [N, P] (-1 = none), the best logit minus
    the logit of ``tokens_at`` [N, P] (``gaps``, -1 where there is no
    position), and the token this pass puts first there. The logits that
    predict position p come from the hidden state at p - 1."""
    import numpy as np
    mkey = W.freeze(model)
    top = _top(W.seed_key(seed), mkey)
    positions = np.asarray(positions)
    valid = positions >= 0
    idx = np.clip(positions - 1, 0, x.shape[1] - 1)
    gaps, first = _head(x, jnp.asarray(idx, jnp.int32),
                        jnp.asarray(np.where(valid, tokens_at, 0),
                                    jnp.int32),
                        top["final_norm_w"], top["lm_head"], mkey, quant)
    return (np.where(valid, np.asarray(gaps), -1.0),
            np.where(valid, np.asarray(first), -1))


# --------------------------------------------------------------- training

def loss_fn(params, tokens, targets, model, quant=None, ce_chunk=512,
            keep=1.0):
    """Mean cross-entropy of ``targets`` given ``tokens`` ([B, S]).
    ``keep`` < 1 plants a fault for the proofs: only the first ``keep``
    of each row's positions count, the mean taken over those."""
    x = params["embed"].astype(jnp.float32)[tokens]
    head_w = params["lm_head"].astype(jnp.float32)

    @jax.checkpoint
    def body(x, lp):
        return layer(x, lp, model, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm_w"], model["rms_norm_eps"])
    b, s, d = x.shape
    n = s // ce_chunk if s % ce_chunk == 0 else 1
    xc = x.reshape(b, n, s // n, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n, s // n).transpose(1, 0, 2)
    kept = (jnp.arange(s) < int(s * keep)).astype(jnp.float32)
    wc = jnp.broadcast_to(kept, (b, s)).reshape(b, n, s // n).transpose(
        1, 0, 2)

    @jax.checkpoint
    def piece(acc, xs):
        xi, ti, wi = xs
        logits = mm(xi, head_w, quant)
        lse = jax.nn.logsumexp(logits, axis=-1)
        got = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(wi * (lse - got)), None

    total, _ = jax.lax.scan(piece, jnp.zeros((), jnp.float32),
                            (xc, tc, wc))
    return total / (b * int(s * keep))


def _is_matrix(path) -> bool:
    """Decay applies to weight matrices, not to norm vectors (whatever
    axes stacking adds)."""
    return "norm" not in jax.tree_util.keystr(path)


def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)


@functools.partial(jax.jit, static_argnames=("model_key", "quant", "keep"),
                   donate_argnums=(0, 1, 2))
def train_step(params, mu, nu, count, tokens, targets, model_key,
               quant=None, keep=1.0):
    """One step. Returns (params, mu, nu, count, loss, per-leaf norms of
    the clipped gradient)."""
    model = dict(model_key)
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets,
                                              model, quant, keep=keep)
    gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree_util.tree_leaves(grads))
    scale = jnp.minimum(1.0, CLIP / jnp.maximum(jnp.sqrt(gsq), 1e-12))
    count = count + 1
    cf = count.astype(jnp.float32)
    bc1, bc2 = 1.0 - B1 ** cf, 1.0 - B2 ** cf

    def leaf(path, p, g, m, n):
        g = g.astype(jnp.float32) * scale
        m = B1 * m + (1 - B1) * g
        n = B2 * n + (1 - B2) * jnp.square(g)
        upd = (m / bc1) / (jnp.sqrt(n / bc2) + EPS)
        if _is_matrix(path):
            upd = upd + DECAY * p.astype(jnp.float32)
        newp = (p.astype(jnp.float32) - LR * upd).astype(p.dtype)
        return newp, m, n, jnp.sqrt(jnp.sum(jnp.square(g)))

    out = jax.tree_util.tree_map_with_path(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(        # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), count, loss, pick(3)


def follow(model: dict, seed: int, batches, quant=None, keep=1.0):
    """Drive the reference from the seed through ``batches`` (a list of
    (tokens, targets)). Returns each step's loss, the per-leaf norms of
    the first clipped gradient, and the per-leaf norms of the
    parameters' change over all the steps."""
    key = W.freeze(model)
    params = make_params(W.seed_key(seed), key)
    zeros = lambda: jax.tree_util.tree_map(         # noqa: E731
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    mu, nu = zeros(), zeros()
    count = jnp.zeros((), jnp.int32)
    losses, grad1 = [], None
    for tokens, targets in batches:
        params, mu, nu, count, loss, gn = train_step(
            params, mu, nu, count, tokens, targets, key, quant, keep)
        losses.append(float(loss))
        if grad1 is None:
            grad1 = jax.tree_util.tree_map(float, gn)
    del mu, nu
    delta = change_norms(params, make_params(W.seed_key(seed), key))
    return losses, grad1, jax.tree_util.tree_map(float, delta)


@functools.partial(jax.jit, static_argnames=("model_key",))
def make_params(key, model_key):
    return W.make_params(dict(model_key), key, jnp.bfloat16)


@jax.jit
def change_norms(after, before):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
        after, before)
