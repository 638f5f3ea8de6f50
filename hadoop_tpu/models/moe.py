"""Mixture-of-experts MLPs: a capacity-padded dispatch for training and
the dense serving family, and a dropless sorted dispatch for one chip's
share of a router.

**The capacity path** (:func:`route`, :func:`moe_mlp`): routing is
expressed as dense one-hot einsums (Switch-Transformer style) so
dispatch/combine run on the MXU with static shapes. Expert parallelism
is an ``all_to_all`` over the ``ep`` mesh axis (ICI), the direct analogue
of the reference's all-to-all shuffle plane (ref: MapReduce shuffle,
Fetcher.java:305 / ShuffleHandler.java:145 — hash-partitioned exchange),
here device-resident instead of HTTP. Semantics: top-k routing with
renormalized gate weights; tokens beyond an expert's capacity C =
ceil(T * k / E * capacity_factor) are dropped (their MLP output is 0,
residual passes through) — standard capacity semantics. The
single-device path uses the identical dispatch math with a local expert
stack, so parallel-vs-reference tests match bit-for-bit. The serving
engine's dense family reuses :func:`route` and :func:`_expert_ffn`
directly (serving/families/gqa.py ``moe_mlp``) — the capacity padding is
what keeps THAT step's shapes static, so it MUST share this module's
dispatch math or the two planes drift. :func:`capacity` is the public
twin of the capacity rule for the engine/bench observability surfaces.

**The share path** (:func:`route_grouped`, :func:`moe_share`) serves one
chip's share of a wider expert-parallel deployment
(``family="deepseek_v32"``; ``family="lfm2_moe"`` is its case of one
group, every expert held and no shared expert): :func:`route_grouped`
scores ALL ``n_routed_experts`` with a sigmoid, chooses by score plus a
correction bias inside the best ``topk_group`` of ``n_group`` groups,
and weights the chosen by their renormalised scores times
``routed_scaling_factor``; :func:`moe_share` computes every assignment
to an expert held here (``experts_from .. experts_from + n_experts``) —
no capacity, no token dropped — plus the shared expert, and returns that
partial sum. Assignments to experts held elsewhere are theirs to add.
Its shapes are static too, by another means: the ``T * K`` assignments
are sorted by held expert and the experts run as grouped matmuls over
the sorted rows (``ops/grouped_matmul.py``), whose device time follows
the experts that were HIT — their weights are read out of the stacked
leaf where it lies, an expert nobody chose is not read, and no row is
multiplied by an expert it did not choose.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.ops import swiglu
from hadoop_tpu.ops.grouped_matmul import (grouped_matmul, grouped_swiglu,
                                           row_tile)


# the leaves of an expert layer that are one matrix an expert: what
# ``moe_share`` takes stacked over layers and reads where it lies
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def split_experts(layers):
    """(the stacked expert leaves of an expert-layer stack, its other
    leaves): a layer scan slices the second and hands ``moe_share`` the
    first whole — a layer's experts sliced out of the stack and handed to
    its kernel would be copied."""
    experts = {n: layers[n] for n in EXPERT_LEAVES}
    return experts, {n: a for n, a in layers.items() if n not in experts}


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(4, int(c))


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    """Per-expert slot count C for a ``n_tokens``-row dispatch — the
    one capacity rule, published so the serving engine's health block
    and the bench report the same C the routing math pads to."""
    return _capacity(n_tokens, cfg)


def route(x2d: jnp.ndarray, router_w: jnp.ndarray, cfg: ModelConfig):
    """Compute dispatch/combine tensors.

    x2d: [T, D]. Returns (dispatch [T, E, C] 0/1, combine [T, E, C] float).
    """
    T = x2d.shape[0]
    E, K, C = cfg.n_experts, cfg.top_k, _capacity(x2d.shape[0], cfg)
    logits = (x2d @ router_w).astype(jnp.float32)          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, K)            # [T, K]
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # one-hot expert choice per (token, k): [T, K, E]
    choice = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
    # position of each (t, k) within its expert queue, token-major priority
    flat = choice.reshape(T * K, E)
    pos = jnp.cumsum(flat, axis=0) - flat                  # 0-based slot
    pos = pos.reshape(T, K, E)
    keep = (pos < C) & (choice > 0)
    # slot one-hot: [T, K, E, C]
    slot = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    slot = slot * keep[..., None].astype(jnp.float32)
    dispatch = jnp.sum(slot, axis=1)                       # [T, E, C]
    combine = jnp.sum(slot * top_vals[:, :, None, None], axis=1)
    return dispatch, combine


def _expert_ffn(xe: jnp.ndarray, lp, cfg: ModelConfig) -> jnp.ndarray:
    """Apply each (local) expert's SwiGLU MLP. xe: [E_local, C', D]."""
    gate = jnp.einsum("ecd,edf->ecf", xe, lp["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", xe, lp["w_up"])
    return jnp.einsum("ecf,efd->ecd", swiglu(gate, up), lp["w_down"])


@jax.named_scope("moe")
def moe_mlp(h: jnp.ndarray, lp, cfg: ModelConfig, ctx) -> jnp.ndarray:
    """Routed MLP. h: [B, S, D] (full sequence). Returns [B, S, D] —
    a *partial* sum over tp when expert weights are ff-sharded (caller
    psums, same contract as the dense row-parallel down-projection)."""
    B, S, D = h.shape
    x2d = h.reshape(B * S, D)
    dispatch, combine = route(x2d, lp["router"], cfg)
    dtype = h.dtype
    # [E, C, D] expert input batches
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(dtype), x2d)

    ep_axis = getattr(ctx, "ep_axis", None)
    if ep_axis is not None:
        # Exchange: every rank computed input batches for all E experts;
        # after the all_to_all each rank holds only its E/ep local experts'
        # batches, one capacity-block per peer, concatenated along the
        # capacity dim: [E, C, D] -> [E/ep, ep*C, D]. (tiled=True form —
        # the untiled form's transpose miscompiles in current JAX.)
        xe = jax.lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)
        ye = _expert_ffn(xe, lp, cfg)
        # reverse exchange restores [E, C, D] with experts in order
        ye = jax.lax.all_to_all(ye, ep_axis, split_axis=1, concat_axis=0,
                                tiled=True)
    else:
        ye = _expert_ffn(xe, lp, cfg)

    y2d = jnp.einsum("tec,ecd->td", combine.astype(jnp.float32),
                     ye.astype(jnp.float32))
    return y2d.reshape(B, S, D).astype(dtype)


def route_grouped(x2d: jnp.ndarray, router_w: jnp.ndarray,
                  router_bias: jnp.ndarray, cfg: ModelConfig):
    """Sigmoid, group-limited top-k over the whole router.

    x2d ``[T, D]``, router_w ``[D, N]``, router_bias ``[N]`` (the
    correction bias: it moves the CHOICE, never the weights). Returns
    (``idx [T, K]`` chosen experts of ``N = n_routed_experts``,
    ``w [T, K]`` float32 weights). Scores in float32."""
    n, g = cfg.n_routed_experts, cfg.n_group
    scores = jax.nn.sigmoid(jnp.dot(x2d, router_w,
                                    preferred_element_type=jnp.float32))
    biased = (scores + router_bias.astype(jnp.float32)).reshape(-1, g, n // g)
    group_score = jnp.sum(jax.lax.top_k(biased, 2)[0], axis=-1)    # [T, g]
    _, best = jax.lax.top_k(group_score, cfg.topk_group)
    keep = jnp.sum(jax.nn.one_hot(best, g, dtype=jnp.int32), axis=1) > 0
    inside = jnp.where(keep[:, :, None], biased, -jnp.inf).reshape(-1, n)
    _, idx = jax.lax.top_k(inside, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    total = jnp.sum(w, axis=-1, keepdims=True)
    if cfg.router_norm_eps:
        total = total + cfg.router_norm_eps
    w = w / total * cfg.routed_scaling_factor
    return idx, w


@jax.named_scope("moe")
def moe_share(x2d: jnp.ndarray, lp, cfg: ModelConfig, valid=None,
              busiest: bool = False, layer=0, interpret: bool = False):
    """This replica's share of the expert layer for rows ``x2d [T, D]``:
    ``sum_i w_i E_i(x)`` over the chosen experts HELD here plus the
    shared expert, where the layer has one. Dropless sorted dispatch:
    the ``T * K`` assignments are keyed by held expert — an assignment
    to an expert held elsewhere, or of a row ``valid`` does not mark,
    gets a key past the last group, so it reads no weight and multiplies
    nothing — sorted (stable), their rows gathered, and ONE grouped
    matmul a weight runs each hit expert over its own rows
    (``ops.grouped_matmul``: an expert nobody chose is not read); each
    assignment's output times its weight is summed back per token in
    float32. Every assignment to a held expert is computed, none
    dropped, none capped.

    ``lp``: ``router [D, N]``, ``router_bias [N]``, a shared expert's
    ``ws_gate/ws_up [D, Fs]``, ``ws_down [Fs, D]``, and the experts
    ``w_gate/w_up [E, D, F]``, ``w_down [E, F, D]`` — or the STACKED
    leaves ``[L, E, ...]`` with ``layer`` (a traced index is fine) naming
    the layer: the stack is viewed ``[L * E, ...]`` and read where it
    lies, the groups offset by ``layer * E``. Returns (``y [T, D]``,
    ``stats`` int32 ``[2]``: assignments to held experts and held
    experts hit, over the rows ``valid`` marks — the group sizes the
    matmul was given; with ``busiest`` a third: the rows of the held
    expert that most of them chose). A row ``valid`` does not mark gets
    the shared expert's output alone (zero where there is none).
    ``interpret`` runs the TPU kernel in Pallas's interpreter, for tests
    off the chip."""
    e, lo, k = cfg.n_experts, cfg.experts_from, cfg.top_k
    t, d = x2d.shape
    idx, w = route_grouped(x2d, lp["router"], lp["router_bias"], cfg)
    local = idx - lo
    held = (local >= 0) & (local < e)
    if valid is not None:
        held = held & valid[:, None]
    # [T * K] keys: the held expert, or ``e`` — past the last group
    key = jnp.where(held, local, e).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, e, dtype=jnp.int32), axis=0)
    # whole row tiles for the kernel: the pad sorts last and names row 0
    m = t * k
    pad = -m % row_tile(m)
    rows = x2d[jnp.pad(order // k, (0, pad))]
    first = layer * e
    gate, up, down = (lp[n].reshape((-1,) + lp[n].shape[-2:])
                      for n in EXPERT_LEAVES)
    hidden = grouped_swiglu(rows, gate, up, sizes, first,
                            interpret=interpret)
    ye = grouped_matmul(hidden, down, sizes, first, interpret=interpret)
    # un-sort; an assignment past the last group was not computed: what
    # lies there is masked, never multiplied by a zero weight
    ye = ye[jnp.argsort(order)].reshape(t, k, d)
    y = jnp.sum(jnp.where(held[..., None], ye * w[..., None], 0.0), axis=1)
    if "ws_gate" in lp:
        shared = swiglu(x2d @ lp["ws_gate"],
                        x2d @ lp["ws_up"]) @ lp["ws_down"]
        y = y + shared.astype(jnp.float32)
    stats = [jnp.sum(sizes), jnp.sum(sizes > 0)]
    if busiest:
        stats.append(jnp.max(sizes))
    return y.astype(x2d.dtype), jnp.stack(stats).astype(jnp.int32)
