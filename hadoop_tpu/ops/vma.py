"""Helpers for shard_map's varying-manual-axes (vma) tracking.

Under ``shard_map`` with vma checking on (the default, and load-bearing
for correct collective transposes — see parallel.train), ``lax.scan``
requires carry input and output to agree on which mesh axes they vary
over. These helpers up-cast a carry to a target vma set, casting only the
missing axes (``lax.pcast`` rejects redundant casts). Outside shard_map
they are no-ops.
"""

from __future__ import annotations

import jax


def vma_of(x) -> frozenset:  # lint: static-fn — vma is trace-time metadata
    try:
        return frozenset(jax.typeof(x).vma)
    except (AttributeError, TypeError):
        return frozenset()


def pvary_to(x, axes):
    """Make x varying over at least ``axes`` (adds only missing ones)."""
    missing = tuple(sorted(set(axes) - vma_of(x)))
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


def tree_vma(tree) -> frozenset:
    out: frozenset = frozenset()
    for leaf in jax.tree_util.tree_leaves(tree):
        out = out | vma_of(leaf)
    return out
