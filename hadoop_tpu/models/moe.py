"""Mixture-of-experts MLP with capacity-based one-hot dispatch.

TPU-first formulation: routing is expressed as dense one-hot einsums
(Switch-Transformer style) so dispatch/combine run on the MXU with static
shapes — no gather/scatter with data-dependent sizes. Expert parallelism
is an ``all_to_all`` over the ``ep`` mesh axis (ICI), the direct analogue
of the reference's all-to-all shuffle plane (ref: MapReduce shuffle,
Fetcher.java:305 / ShuffleHandler.java:145 — hash-partitioned exchange),
here device-resident instead of HTTP.

Semantics: top-k routing with renormalized gate weights; tokens beyond an
expert's capacity C = ceil(T * k / E * capacity_factor) are dropped (their
MLP output is 0, residual passes through) — standard capacity semantics.
The single-device path uses the identical dispatch math with a local
expert stack, so parallel-vs-reference tests match bit-for-bit.

The serving engine's fused step reuses :func:`route` and
:func:`_expert_ffn` directly (serving/engine.py ``_moe_mlp``) — the
capacity padding is what keeps the step's shapes static, so serving
MUST share this module's dispatch math or the two planes drift.
:func:`capacity` is the public twin of the capacity rule for the
engine/bench observability surfaces.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.ops import swiglu


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
