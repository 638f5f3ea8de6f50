"""Rotary position embeddings (RoPE).

Frequencies are precomputed once per model config (static shapes) and the
rotation is a pure elementwise op, so XLA folds it into the QK projection
epilogue. Rotation is applied in float32 for accuracy, then cast back.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0):
    """Return (cos, sin) tables of shape [max_seq, head_dim // 2], float32."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(max_seq, dtype=jnp.float32)
    angles = jnp.outer(pos, inv_freq)  # [S, D/2]
    return jnp.cos(angles), jnp.sin(angles)


def yarn_inv_frequencies(dim: int, theta: float, factor: float,
                         original_max_seq: int, beta_fast: float = 32.0,
                         beta_slow: float = 1.0):
    """YaRN's per-pair inverse frequencies ``[dim // 2]``: pair ``i``
    keeps ``theta^(-2i/dim)`` where it turns more than ``beta_fast``
    times over the original context, is divided by ``factor`` where it
    turns fewer than ``beta_slow`` times, and is blended linearly
    between (the published ramp: ``lo = floor``, ``hi = ceil`` of the
    two correction dimensions)."""
    def correction_dim(rotations: float) -> float:
        return dim * math.log(original_max_seq /
                              (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(correction_dim(beta_fast)), 0)
    hi = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def yarn_frequencies(dim: int, max_seq: int, theta: float, factor: float,
                     original_max_seq: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0):
    """(cos, sin) ``[max_seq, dim // 2]`` under YaRN frequencies."""
    angles = jnp.outer(jnp.arange(max_seq, dtype=jnp.float32),
                       yarn_inv_frequencies(dim, theta, factor,
                                            original_max_seq, beta_fast,
                                            beta_slow))
    return jnp.cos(angles), jnp.sin(angles)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """The softmax-scale correction that goes with YaRN: attention's
    scale is multiplied by its square."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray | None = None) -> jnp.ndarray:
    """Rotate q or k of shape [..., S, H, D] by position.

    ``positions``: optional [S] int array of absolute positions (used by
    sequence-parallel shards that own a slice of the sequence); defaults to
    0..S-1.
    """
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq]
        s = sin[:seq]
    else:
        c = cos[positions]
        s = sin[positions]
    # [S, D/2] -> [S, 1, D/2] to broadcast over heads.
    c = c[:, None, :]
    s = s[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)
