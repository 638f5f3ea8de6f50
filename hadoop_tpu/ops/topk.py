"""The k-th largest value of each row, exactly and without a sort.

A float32's bit pattern, with the sign handled, orders as the float does
(``sortable``); the k-th largest of a row of such keys is the largest
``t`` with ``count(key >= t) >= k``, and its 32 bits are found one at a
time from the top, each by one compare-and-count pass over the row
(``kth_largest``). No data-dependent shape, no scatter, no gather; ties
are whatever the caller makes of ``key == t``. The sparse selection
(``ops/sparse_mla``) keeps its top-k context entries by it, the sampler
(``serving/engine._mask_and_scale``) its top-k logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sortable(x):
    """float32 -> uint32 with the same order (``-0.0`` counted as
    ``0.0``, as a float compare counts it). Every finite value and both
    infinities map above 0, which is kept for dead positions."""
    x = jnp.where(x == 0, 0.0, x).astype(jnp.float32)
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def kth_largest(key, want):
    """The ``want``-th largest of each row of ``key`` (uint32 ``[..., S]``,
    ``want`` int32 ``[...]``, at least 1 for a row whose answer is read):
    the largest ``t`` with ``count(key >= t) >= want``, built bit by bit
    from the top. A row with fewer than ``want`` keys above 0 reads 0."""

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        c = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(c >= want, cand, t)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(key.shape[:-1], jnp.uint32))
