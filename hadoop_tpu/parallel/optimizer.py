"""Fused AdamW on local parameter shards, with optional ZeRO-1 sharding.

Two tiers of state distribution:

1. Model-parallel sharding (always): the update runs inside ``shard_map``
   on whatever slice of each parameter the rank owns, so moment state is
   sharded exactly like the parameters over tp/pp/ep.
2. ZeRO-1 over the DATA axes (``zero1=True`` in make_train_step): a
   parameter replicated across N data-parallel ranks keeps only 1/N of
   its moment state (and update work) per rank; the updated slices are
   reassembled with one ``all_gather`` per leaf. This is the TPU-native
   equivalent of Megatron's distributed optimizer (param/grad/state
   partitioning + gather), expressed as slice/gather inside the one
   shard_map instead of bespoke bucketing code.

ZeRO-1 state layout: each leaf's local shard is flattened and padded to
``Z*K`` (Z = product of that leaf's data-axis sizes); the state leaf is a
global array of shape ``(*spec_axis_sizes, *data_axis_sizes, K)`` whose
PartitionSpec names every one of those axes — local piece: just ``(K,)``.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    count: jnp.ndarray     # scalar int32
    mu: Any                # tree like params, float32
    nu: Any                # tree like params, float32


def adamw_init(params) -> AdamWState:
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamWState(jnp.zeros((), jnp.int32), zeros,
                      jax.tree_util.tree_map(jnp.copy, zeros))


@jax.named_scope("optimizer")
def adamw_update(params, grads, state: AdamWState, lr: float,
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, grad_clip: float = 1.0,
                 gsq=None):
    """One AdamW step; master math in f32, params cast back to their dtype.

    ``gsq``: squared global grad norm. Inside shard_map the local tree is
    only a shard, so the caller must supply the correctly-reduced value
    (see parallel.train._global_grad_sq); default computes it locally.
    """
    count = state.count + 1
    cf = count.astype(jnp.float32)
    if gsq is None:
        gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in jax.tree_util.tree_leaves(grads))
    return _apply(params, grads, state, count, cf, gsq, lr, b1, b2, eps,
                  weight_decay, grad_clip)


def _apply(params, grads, state, count, cf, gsq, lr, b1, b2, eps,
           weight_decay, grad_clip):
    gnorm = jnp.sqrt(gsq)
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12))

    bc1 = 1.0 - b1 ** cf
    bc2 = 1.0 - b2 ** cf

    def leaf(p, g, m, n):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * jnp.square(g)
        update = (m / bc1) / (jnp.sqrt(n / bc2) + eps)
        # decoupled weight decay on matrices only (ndim >= 2), like the
        # usual no-decay-on-norms/bias convention
        if p.ndim >= 2:
            update = update + weight_decay * p.astype(jnp.float32)
        newp = p.astype(jnp.float32) - lr * update
        return newp.astype(p.dtype), m, n

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_n = treedef.flatten_up_to(state.nu)
    out = [leaf(p, g, m, n)
           for p, g, m, n in zip(flat_p, flat_g, flat_m, flat_n)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_n = treedef.unflatten([o[2] for o in out])
    return new_p, AdamWState(count, new_m, new_n), gnorm


# ------------------------------------------------------------------ ZeRO-1

def _pad_len(local_size: int, z: int) -> int:
    """Per-data-rank slice length K (local shard padded to Z*K)."""
    return (local_size + z - 1) // z


def zero1_leaf_plan(spec_axes: Sequence[str], data_axes: Sequence[str]
                    ) -> Tuple[str, ...]:
    """Data axes a leaf's state is partitioned over = the data axes the
    leaf is NOT already sharded on (an expert weight sharded on ep keeps
    only dp)."""
    return tuple(a for a in data_axes if a not in spec_axes)


def zero1_init_local(local_shape, z: int):
    """Zeros for one leaf's per-rank moment slice."""
    k = _pad_len(int(jnp.prod(jnp.array(local_shape))) if local_shape
                 else 1, z)
    return jnp.zeros((k,), jnp.float32)


@jax.named_scope("optimizer")
def zero1_update(params, grads, state: AdamWState, lr: float, *,
                 leaf_axes, mesh_axis_sizes: Dict[str, int],
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, grad_clip: float = 1.0,
                 gsq=None, grads_sliced: bool = False,
                 gather_bucket_bytes: int = 0, gather_relaxed=None):
    """ZeRO-1 AdamW step (inside shard_map). ``leaf_axes``: pytree like
    params whose leaves are the tuple of data axes partitioning that
    leaf's state (see zero1_leaf_plan). State mu/nu leaves are the local
    (K,) slices. Ref intent: Megatron's DistributedOptimizer — param
    update computed on 1/Z of each replicated leaf, then gathered.

    ``grads_sliced``: the grad leaves are already this rank's reduced
    (K,) slices (the overlap pass reduce-scatters them straight into
    the state layout — parallel/overlap.py); the clip scale still
    applies here. ``gather_bucket_bytes`` > 0 reassembles the updated
    params through bucketed psum-of-scatters (one collective per
    bucket, bitwise identical to the per-leaf form) instead of one
    collective per leaf. ``gather_relaxed`` (relaxed parity tier only,
    parallel/lowp) quantizes that reassembly's wire payload; the
    master mu/nu/param slices this rank updates stay full precision —
    only the broadcast working copy is quantized."""
    count = state.count + 1
    cf = count.astype(jnp.float32)
    gnorm = jnp.sqrt(gsq)
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12))
    bc1 = 1.0 - b1 ** cf
    bc2 = 1.0 - b2 ** cf

    # the slice layout (Z, K, rank index) has ONE definition, shared
    # with the overlap pass's reduce-scatter/gather so the layouts can
    # never silently fork (parallel/overlap.py)
    from hadoop_tpu.parallel.overlap import (zero1_slice_index,
                                             zero1_slice_meta)

    def leaf_slice(p, g, m, n, axes):
        """(new_slice, m2, n2) for this rank's (K,) piece of one leaf."""
        z, k = zero1_slice_meta(p, axes, mesh_axis_sizes)
        flat = p.reshape(-1)
        if z == 1:
            idx = jnp.zeros((), jnp.int32)
        else:
            idx = zero1_slice_index(axes, mesh_axis_sizes)
        pad = z * k - flat.size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        pslice = jax.lax.dynamic_slice(flat, (idx * k,), (k,))
        if grads_sliced:
            gslice = g.astype(jnp.float32) * scale
        else:
            gflat = g.reshape(-1).astype(jnp.float32) * scale
            if pad:
                gflat = jnp.pad(gflat, (0, pad))
            gslice = jax.lax.dynamic_slice(gflat, (idx * k,), (k,))
        m2 = b1 * m + (1 - b1) * gslice
        n2 = b2 * n + (1 - b2) * jnp.square(gslice)
        update = (m2 / bc1) / (jnp.sqrt(n2 / bc2) + eps)
        if p.ndim >= 2:  # decay matrices only, same rule as _apply
            update = update + weight_decay * pslice.astype(jnp.float32)
        new_slice = (pslice.astype(jnp.float32) - lr * update).astype(
            p.dtype)
        return new_slice, m2, n2, z, k, idx

    def gather_leaf(p, new_slice, z, k, idx, axes):
        if z == 1:
            return new_slice[:p.size].reshape(p.shape)
        # gather expressed as psum of disjoint scatters: numerically
        # identical to all_gather(tiled) over the slice layout, and
        # provably replication-invariant under shard_map's vma
        # checking (all_gather's output can't be statically shown
        # invariant; a psum's can).
        full = jnp.zeros((z * k,), new_slice.dtype)
        full = jax.lax.dynamic_update_slice(full, new_slice, (idx * k,))
        full = jax.lax.psum(full, axes)
        return full[:p.size].reshape(p.shape)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_n = treedef.flatten_up_to(state.nu)
    flat_a = treedef.flatten_up_to(leaf_axes)
    out = [leaf_slice(p, g, m, n, a) for p, g, m, n, a in
           zip(flat_p, flat_g, flat_m, flat_n, flat_a)]
    new_m = treedef.unflatten([o[1] for o in out])
    new_n = treedef.unflatten([o[2] for o in out])
    if gather_bucket_bytes > 0:
        from hadoop_tpu.parallel.overlap import bucketed_gather_slices
        new_p = bucketed_gather_slices(
            treedef.unflatten([o[0] for o in out]), params, leaf_axes,
            mesh_axis_sizes, gather_bucket_bytes,
            relaxed=gather_relaxed)
    else:
        new_p = treedef.unflatten([
            gather_leaf(p, o[0], o[3], o[4], o[5], a)
            for p, o, a in zip(flat_p, out, flat_a)])
    return new_p, AdamWState(count, new_m, new_n), gnorm
