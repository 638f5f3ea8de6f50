"""Weights from ``--seed``: what every family (``chipbench/families/``)
makes its leaves from. Nothing here knows an architecture, and nothing
imports the program: the reference regenerates the same values from the
same seed, one layer at a time, after the program's state is gone. Every
matrix has a key of its own — ``fold_in(fold_in(seed_key, crc32(leaf)),
index)`` with ``index`` the layer (times the expert count, plus the
expert, for an expert stack) — so a stack is a sequential map over
matrices and the largest float32 transient is one matrix.

A family describes its leaves in *tables*: ``name -> (matrix shape,
fan_in, matrices per layer)``, ``fan_in`` ``None`` marking a norm vector.
``stack`` / ``one_layer`` / ``stacked_leaf`` make a run of layers that
share one table, ``flat`` the leaves that stand alone; a family whose
layers differ calls them once per run of like layers, with the run's
first layer as ``start``, and lays the results out as its program takes
them.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

# Values come from integer arithmetic alone, so that two programs that
# generate the same leaf (the program's init, the reference's, one leaf
# regenerated alone) agree bit for bit. A normal drawn through erf_inv did
# not: on the v5e 6% of a leaf's bfloat16 values came out one ulp apart
# between two jitted programs (my chip run, PR 24). k is the sum of four
# uniform 6-bit fields of one random word, centred: an integer in
# [-126, 126] with standard deviation K_STD, close to normal (Irwin-Hall).
K_STD = (4 * (64 ** 2 - 1) / 12.0) ** 0.5       # 36.95
NORM_STEP = 2.0 ** -10   # norm weights are 1 + k / 1024 (std 0.036), exact
#                          in float32: an all-ones weight would hide a
#                          dropped multiply


def seed_key(seed: int):
    """A key from any whole number up to 2**62 (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def freeze(model: dict) -> tuple:
    """The model group as a hashable static argument (scalars only)."""
    return tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, bool, type(None)))))


def _matrix(key, index, shape, fan_in, dtype):
    bits = jax.random.bits(jax.random.fold_in(key, index), shape,
                           jnp.uint32)
    k = sum(((bits >> (6 * i)) & 63).astype(jnp.int32) for i in range(4))
    k = (k - 126).astype(jnp.float32)
    if fan_in is None:
        return (1.0 + k * NORM_STEP).astype(dtype)
    # one float32 multiply of an exact integer, then one rounding
    return (k * jnp.float32(fan_in ** -0.5 / K_STD)).astype(dtype)


def _stack(key, start, count, shape, fan_in, dtype):
    """Matrices ``start .. start+count`` of one leaf, one at a time."""
    return jax.lax.map(
        lambda i: _matrix(key, i, shape, fan_in, dtype),
        start + jnp.arange(count))


def one_layer(table: dict, key, layer, dtype) -> dict:
    """One layer's leaves (no leading layer axis; an expert stack keeps
    its expert axis). ``key`` is ``seed_key(seed)``; it and ``layer``
    may be traced, so one compiled program serves every seed."""
    out = {}
    for name, (shape, fan_in, per) in table.items():
        k = _leaf_key(key, name)
        if per == 1:
            out[name] = _matrix(k, layer, shape, fan_in, dtype)
        else:
            out[name] = _stack(k, layer * per, per, shape, fan_in, dtype)
    return out


def flat(table: dict, key, dtype) -> dict:
    """Leaves that stand alone (an embedding, a head): matrix 0 of each."""
    return {name: _matrix(_leaf_key(key, name), 0, shape, fan_in, dtype)
            for name, (shape, fan_in, _) in table.items()}


def stacked_leaf(table: dict, key, name: str, n_layers: int, dtype,
                 start: int = 0):
    """Layers ``start .. start+n_layers`` of one leaf, stacked."""
    shape, fan_in, per = table[name]
    out = _stack(_leaf_key(key, name), start * per, n_layers * per, shape,
                 fan_in, dtype)
    return out.reshape((n_layers, per) + shape) if per > 1 else out


def stack(table: dict, key, n_layers: int, dtype, start: int = 0) -> dict:
    """Every leaf of the table for layers ``start .. start+n_layers``,
    layer-stacked. Call under ``jax.jit``."""
    return {name: stacked_leaf(table, key, name, n_layers, dtype, start)
            for name in table}
