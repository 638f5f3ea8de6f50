"""Device-resident MapReduce: shuffle + reduce as compiled collectives.

The host MR engine (job.py/appmaster.py) moves IFile segments between
containers (ref: ShuffleHandler.java:145, Fetcher.java:305, the
merge in ReduceTask.java:320). When records are numeric tensors already
living on a TPU mesh, that whole machinery collapses into one jitted
program: partition-by-key → ``lax.all_to_all`` over ICI → sorted
segment reduction. This module is that program, layered on
``hadoop_tpu.parallel.collectives``:

- :func:`device_group_reduce` — the shuffle+reduce of a wordcount-class
  job: every key's values meet on one device and are combined there.
- :func:`device_terasort` — the canonical sort benchmark: sampled
  range partition + exchange + local sort ⇒ a globally sorted,
  device-sharded run (ref: examples/terasort/TeraSort.java).

Capacity semantics (XLA static shapes): results are padded; ``valid``
masks real rows and ``dropped`` counts send-side overflow — see
collectives.device_shuffle. Callers needing exactly-once records check
``dropped == 0`` (tests do; a skewed workload retries with a larger
``capacity_factor``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from hadoop_tpu.parallel.collectives import (ShuffleResult, device_shuffle,
                                             device_sorted, hash_partitioner,
                                             range_partitioner,
                                             sample_split_points)

__all__ = [
    "ShuffleResult", "device_shuffle", "device_sorted",
    "hash_partitioner", "range_partitioner", "sample_split_points",
    "device_group_reduce", "device_terasort",
]


def _segment_reduce_sorted(keys, values, valid, op: str):
    """Combine equal-key runs of a SORTED, padded shard. Returns
    (keys, combined, first_mask): row i holds the reduction of key
    keys[i]'s whole run iff first_mask[i] (other rows are dead)."""
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                             keys[1:] != keys[:-1]]) & valid
    seg = jnp.cumsum(first) - 1  # run index per row
    n = keys.shape[0]
    if op == "sum":
        combined = jax.ops.segment_sum(
            jnp.where(valid.reshape((-1,) + (1,) * (values.ndim - 1)),
                      values, 0),
            seg, num_segments=n)
    elif op == "max":
        combined = jax.ops.segment_max(
            jnp.where(valid.reshape((-1,) + (1,) * (values.ndim - 1)),
                      values, jnp.iinfo(values.dtype).min
                      if jnp.issubdtype(values.dtype, jnp.integer)
                      else -jnp.inf),
            seg, num_segments=n)
    elif op == "min":
        combined = jax.ops.segment_min(
            jnp.where(valid.reshape((-1,) + (1,) * (values.ndim - 1)),
                      values, jnp.iinfo(values.dtype).max
                      if jnp.issubdtype(values.dtype, jnp.integer)
                      else jnp.inf),
            seg, num_segments=n)
    else:
        raise ValueError(f"unsupported reduce op {op!r}")
    # scatter each run's total back to its first row
    out = jnp.where(first.reshape((-1,) + (1,) * (values.ndim - 1)),
                    combined[seg], 0)
    return keys, out, first


def device_group_reduce(mesh, axis: str, keys: jax.Array,
                        values: jax.Array, op: str = "sum",
                        capacity_factor: float = 2.0) -> ShuffleResult:
    """Group-by-key + combine across the mesh — the numeric wordcount.

    Hash-partitions records so all occurrences of a key land on one
    device (exactly the contract HashPartitioner gives reducers), then
    reduces each key's sorted run in place. Returned rows with ``valid``
    set are (key, reduced value) pairs; every key appears on exactly
    one device, once.
    """
    res = device_shuffle(mesh, axis, keys, values,
                         partition=hash_partitioner(mesh.shape[axis]),
                         capacity_factor=capacity_factor,
                         sort_output=True)
    from functools import partial
    from jax.sharding import PartitionSpec as P

    from hadoop_tpu.parallel.collectives import _PROGRAM_CACHE

    spec = P(axis)
    vspec = P(axis, *([None] * (values.ndim - 1)))
    ck = ("segreduce", mesh, axis, op, res.keys.shape,
          str(res.keys.dtype), res.values.shape[1:],
          str(res.values.dtype))
    prog = _PROGRAM_CACHE.get(ck)
    if prog is None:
        body = partial(_segment_reduce_sorted, op=op)
        prog = _PROGRAM_CACHE.setdefault(ck, jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(spec, vspec, spec),
            out_specs=(spec, vspec, spec))))
    k, v, first = prog(res.keys, res.values, res.valid)
    return ShuffleResult(k, v, first, res.dropped)


def device_terasort(mesh, axis: str, keys: jax.Array,
                    values: jax.Array,
                    capacity_factor: float = 2.0) -> ShuffleResult:
    """Globally sort device-resident (key, value) records: the TeraSort
    pipeline (sample → TotalOrderPartitioner → sort) as collectives.
    Device d's valid run is sorted and every valid key on device d is
    ≤ every valid key on device d+1."""
    return device_sorted(mesh, axis, keys, values,
                         capacity_factor=capacity_factor)
