"""What every family's plain reference is made of: the matmul at float32
``highest`` with its ``quant`` control, RMSNorm, the scoring of served
tokens under a head, chunked token cross-entropy, and AdamW as the
configurations state it. No kernels, no cache, no batching tricks; it
imports nothing of the program and takes nothing the program made. The
layer equations, the leaf names and the loss of an architecture live in
its family module (``chipbench/families/``), which calls these.

Training: mean token cross-entropy, global-norm clip at 1.0, AdamW (lr
3e-4, betas 0.9 / 0.95, eps 1e-8, decoupled decay 0.1 on matrices, none
on norm vectors), parameters kept in the dtype the configuration states
(bfloat16) and rounded to it after every update, moments in float32.

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


# ---------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, idx, served, norm_w, head_w, eps, quant):
    h = jnp.take_along_axis(x, idx[:, :, None], axis=1)
    h = rms_norm(h, norm_w, eps)
    logits = mm(h, head_w, quant)                           # [N, P, V]
    got = jnp.take_along_axis(logits, served[:, :, None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - got, jnp.argmax(logits, axis=-1)


def score(x, positions, tokens_at, norm_w, head_w, eps, quant=None):
    """Under a final RMSNorm and a head: at each of ``positions`` [N, P]
    (-1 = none), the best logit minus the logit of ``tokens_at`` [N, P]
    (``gaps``, -1 where there is no position), and the token this pass
    puts first there. The logits that predict position p come from the
    hidden state ``x`` [N, S, D] at p - 1."""
    import numpy as np
    positions = np.asarray(positions)
    valid = positions >= 0
    idx = np.clip(positions - 1, 0, x.shape[1] - 1)
    gaps, first = _head(x, jnp.asarray(idx, jnp.int32),
                        jnp.asarray(np.where(valid, tokens_at, 0),
                                    jnp.int32),
                        norm_w, head_w, eps, quant)
    return (np.where(valid, np.asarray(gaps), -1.0),
            np.where(valid, np.asarray(first), -1))


# --------------------------------------------------------------- training

def token_cross_entropy(x, head_w, targets, quant=None, ce_chunk=512,
                        keep=1.0):
    """Mean cross-entropy of ``targets`` [B, S] under ``head_w`` given the
    normed hidden states ``x`` [B, S, D], a chunk of positions at a time.
    ``keep`` < 1 plants a fault for the proofs: only the first ``keep``
    of each row's positions count, the mean taken over those."""
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


@functools.partial(jax.jit, static_argnames=("loss_fn", "model_key", "quant",
                                             "keep"),
                   donate_argnums=(0, 1, 2))
def train_step(params, mu, nu, count, tokens, targets, loss_fn, model_key,
               quant=None, keep=1.0):
    """One step of ``loss_fn(params, tokens, targets, model, quant,
    keep=)`` (a family's). Returns (params, mu, nu, count, loss, per-leaf
    norms of the clipped gradient)."""
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


def follow(make_tree, loss_fn, model: dict, seed: int, batches, quant=None,
           keep=1.0):
    """Drive a family's reference (``make_tree(model, key, dtype)`` its
    whole tree, ``loss_fn`` its loss) from the seed through ``batches``
    (a list of (tokens, targets)). Returns each step's loss, the per-leaf
    norms of the first clipped gradient, and the per-leaf norms of the
    parameters' change over all the steps."""
    key = W.freeze(model)
    params = _make_params(W.seed_key(seed), key, make_tree)
    zeros = lambda: jax.tree_util.tree_map(         # noqa: E731
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    mu, nu = zeros(), zeros()
    count = jnp.zeros((), jnp.int32)
    losses, grad1 = [], None
    for tokens, targets in batches:
        params, mu, nu, count, loss, gn = train_step(
            params, mu, nu, count, tokens, targets, loss_fn, key, quant,
            keep)
        losses.append(float(loss))
        if grad1 is None:
            grad1 = jax.tree_util.tree_map(float, gn)
    del mu, nu
    delta = change_norms(params,
                         _make_params(W.seed_key(seed), key, make_tree))
    return losses, grad1, jax.tree_util.tree_map(float, delta)


@functools.partial(jax.jit, static_argnames=("model_key", "make_tree"))
def _make_params(key, model_key, make_tree):
    return make_tree(dict(model_key), key, jnp.bfloat16)


@jax.jit
def change_norms(after, before):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
        after, before)
