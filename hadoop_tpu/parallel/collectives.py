"""Device-resident exchange primitives: the ICI data plane's shuffle.

The reference's shuffle moves map output between hosts over HTTP
(ref: hadoop-mapreduce-project/.../ShuffleHandler.java:145 serving
IFile segments; reduce-side Fetcher.java:305 pulling them). When the
records are numeric and already device-resident, that exchange is
literally an all-to-all over the mesh (SURVEY.md §5.8) — so here it is
as one: a hash/range partitioned ``lax.all_to_all`` inside a
``shard_map`` program, with static shapes (capacity-bounded send
buckets + validity masks) so XLA can compile the whole exchange into
ICI DMAs.

Design notes (TPU/XLA constraints drive the shape of this code):

- **Static capacity.** XLA needs static shapes; a real shuffle has
  skew. Each device therefore sends at most ``cap`` records to each
  peer, buckets are padded with a sentinel, and the program returns a
  per-device overflow count so callers can detect truncation and retry
  with a bigger capacity factor (the MR host shuffle solves the same
  problem with spill files; here memory is pre-committed).
- **Sort as the grouping engine.** Host shuffles group by hashing into
  per-partition buffers; on the MXU/VPU the cheap grouping primitive
  is sort. Records are bucketed by ``argsort(dest)`` and positioned
  with a ``searchsorted`` prefix — no scatter with data-dependent
  shapes anywhere.
- **One collective.** The exchange is a single ``lax.all_to_all`` on
  a ``[n_dev, cap, ...]`` buffer — exactly the transpose the ICI
  fabric is optimized for (same collective the MoE dispatch uses,
  models/moe.py).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class ShuffleResult(NamedTuple):
    """Per-device post-exchange shard (leading dim = n_dev * cap,
    padded; ``valid`` marks real records, ``dropped`` counts records
    that exceeded a bucket's capacity on the SEND side)."""
    keys: jax.Array
    values: jax.Array
    valid: jax.Array
    dropped: jax.Array


def hash_partitioner(n_parts: int) -> Callable[[jax.Array], jax.Array]:
    """key → partition via a multiplicative hash (ref: the default
    HashPartitioner.getPartition — ``hash % parts`` — but mixed first:
    sequential integer keys would otherwise stripe, not spread)."""
    def part(keys: jax.Array) -> jax.Array:
        h = keys.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        h ^= h >> 15
        return (h % jnp.uint32(n_parts)).astype(jnp.int32)
    # program-cache identity: every hash_partitioner(n) compiles (and
    # caches) the same exchange program
    part.cache_key = ("hash", n_parts)
    return part


def range_partitioner(splits: jax.Array) -> Callable[[jax.Array], jax.Array]:
    """key → partition by cut points (ref: TeraSort's
    TotalOrderPartitioner over sampled split points): partition i gets
    keys in (splits[i-1], splits[i]]. ``splits`` has n_parts-1 entries,
    ascending."""
    def part(keys: jax.Array) -> jax.Array:
        return jnp.searchsorted(splits, keys, side="left").astype(jnp.int32)
    # splits ride into the cached program as a TRACED argument — new cut
    # points (every device_sorted call samples fresh ones) reuse the
    # same compiled exchange
    part.cache_key = ("range", splits.shape[0], str(splits.dtype))
    part.splits = splits
    return part


# Compiled-program cache: jax.jit memoizes on the wrapped callable's
# identity, so rebuilding shard_map(partial(...)) per call would retrace
# and recompile the whole exchange every time — the opposite of the
# "one compiled collective" this module exists for. Keyed on everything
# that changes the lowered program.
_PROGRAM_CACHE: dict = {}


def _bucketize(keys, values, dest, n_dev: int, cap: int, pad_key):
    """Group local records into a [n_dev, cap] send buffer (+mask) by
    destination, dropping per-bucket overflow. Runs under jit: the
    grouping is argsort + searchsorted, both static-shaped."""
    n = keys.shape[0]
    order = jnp.argsort(dest, stable=True)
    dest_s = dest[order]
    keys_s = keys[order]
    vals_s = values[order]
    # start offset of each destination's run in the sorted order
    starts = jnp.searchsorted(dest_s, jnp.arange(n_dev), side="left")
    slot = jnp.arange(n) - starts[dest_s]
    ok = slot < cap
    dropped = jnp.sum(~ok)
    # overflow records get an out-of-bounds index; mode="drop" discards
    # the write entirely (an in-bounds clamp would clobber a bucket's
    # slot 0 with a masked record)
    flat = jnp.where(ok, dest_s * cap + slot, n_dev * cap)
    send_k = jnp.full((n_dev * cap,), pad_key, keys.dtype)
    send_v = jnp.zeros((n_dev * cap,) + values.shape[1:], values.dtype)
    send_m = jnp.zeros((n_dev * cap,), jnp.bool_)
    send_k = send_k.at[flat].set(keys_s, mode="drop")
    send_v = send_v.at[flat].set(vals_s, mode="drop")
    send_m = send_m.at[flat].set(True, mode="drop")
    return (send_k.reshape(n_dev, cap),
            send_v.reshape((n_dev, cap) + values.shape[1:]),
            send_m.reshape(n_dev, cap), dropped)


def _exchange_local(keys, values, splits, partition, n_dev: int, cap: int,
                    pad_key, axis: str, sort_output: bool):
    """Per-device body (under shard_map): bucket → all_to_all → merge.
    ``splits`` is the traced range-partition operand (a dummy scalar for
    non-range partitioners)."""
    if splits.ndim:  # range partition: cut points are data, not code
        dest = jnp.searchsorted(splits, keys,
                                side="left").astype(jnp.int32)
    else:
        dest = partition(keys)
    dest = jnp.clip(dest, 0, n_dev - 1)
    send_k, send_v, send_m, dropped = _bucketize(
        keys, values, dest, n_dev, cap, pad_key)
    # [n_dev, cap,...] → peer p receives our row p; we end with row j
    # holding what peer j sent us.
    recv_k = lax.all_to_all(send_k, axis, split_axis=0, concat_axis=0,
                            tiled=False)
    recv_v = lax.all_to_all(send_v, axis, split_axis=0, concat_axis=0,
                            tiled=False)
    recv_m = lax.all_to_all(send_m, axis, split_axis=0, concat_axis=0,
                            tiled=False)
    out_k = recv_k.reshape(n_dev * cap)
    out_v = recv_v.reshape((n_dev * cap,) + values.shape[1:])
    out_m = recv_m.reshape(n_dev * cap)
    if sort_output:
        # pads carry pad_key = +max so they sort to the tail; the mask
        # travels with the permutation.
        order = jnp.argsort(out_k, stable=True)
        out_k, out_v, out_m = out_k[order], out_v[order], out_m[order]
    return out_k, out_v, out_m, dropped[None]


def device_shuffle(mesh: Mesh, axis: str, keys: jax.Array,
                   values: jax.Array,
                   partition: Optional[Callable] = None,
                   capacity_factor: float = 2.0,
                   sort_output: bool = True) -> ShuffleResult:
    """All-to-all hash-partition exchange of device-resident records.

    ``keys``/``values`` are globally-sharded arrays (leading dim sharded
    over ``axis``); each record goes to the device ``partition(key)``
    names, then each device optionally sorts its received run. This is
    the map-output → reduce-input movement of the MR shuffle executed
    as one compiled collective instead of N² HTTP fetches (ref:
    ShuffleHandler.java:145 / Fetcher.java:305).

    Returns a ShuffleResult of globally-sharded arrays; row counts are
    padded to ``n_dev * cap`` per device with ``valid`` marking real
    records and ``dropped[d]`` counting device d's send-side overflow
    (0 for well-sized capacity factors; callers retry bigger on >0).
    """
    n_dev = mesh.shape[axis]
    n_local = keys.shape[0] // n_dev
    cap = max(1, int(n_local * capacity_factor / n_dev))
    if not jnp.issubdtype(keys.dtype, jnp.integer):
        raise TypeError("device_shuffle keys must be integers (numeric "
                        "record exchange; host shuffle covers the rest)")
    pad_key = jnp.iinfo(keys.dtype).max
    if partition is None:
        partition = hash_partitioner(n_dev)
    part_key = getattr(partition, "cache_key", None)
    is_range = bool(part_key) and part_key[0] == "range"
    splits = partition.splits if is_range \
        else jnp.zeros((), jnp.int32)  # 0-d sentinel: "not range"

    spec = P(axis)
    vspec = P(axis, *([None] * (values.ndim - 1)))

    def build():
        return jax.jit(jax.shard_map(
            partial(_exchange_local, partition=partition, n_dev=n_dev,
                    cap=cap, pad_key=pad_key, axis=axis,
                    sort_output=sort_output),
            mesh=mesh, in_specs=(spec, vspec, P()),
            out_specs=(spec, vspec, spec, spec)))

    if part_key is None:
        prog = build()  # custom partitioner: identity unknown, no cache
    else:
        ck = ("shuffle", mesh, axis, n_dev, cap, sort_output, part_key,
              keys.shape, str(keys.dtype), values.shape[1:],
              str(values.dtype))
        prog = _PROGRAM_CACHE.get(ck)
        if prog is None:
            prog = _PROGRAM_CACHE.setdefault(ck, build())
    out_k, out_v, out_m, dropped = prog(keys, values, splits)
    return ShuffleResult(out_k, out_v, out_m, dropped)


def sample_split_points(mesh: Mesh, axis: str, keys: jax.Array,
                        n_parts: int, n_samples: int = 1024) -> jax.Array:
    """Sampled range-partition cut points (ref: TeraInputFormat's
    client-side sampling feeding TotalOrderPartitioner): every device
    contributes an evenly-strided sample of its local keys; the merged,
    sorted sample's quantiles become the n_parts-1 split points."""
    n_dev = mesh.shape[axis]
    per_dev = max(1, n_samples // n_dev)

    def body(local):
        stride = max(1, local.shape[0] // per_dev)
        sample = jnp.sort(local[::stride][:per_dev])
        # gather-as-psum: scatter my sample into my row and sum — the
        # result is *statically known replicated*, which keeps
        # shard_map's vma checking on (an all_gather's replication
        # can't be inferred and would force check_vma=False).
        row = lax.axis_index(axis)
        buf = jnp.zeros((n_dev,) + sample.shape, sample.dtype)
        allsamp = lax.psum(buf.at[row].set(sample), axis).reshape(-1)
        allsamp = jnp.sort(allsamp)
        idx = (jnp.arange(1, n_parts) * allsamp.shape[0]) // n_parts
        return allsamp[idx]

    ck = ("sample", mesh, axis, n_parts, per_dev, keys.shape,
          str(keys.dtype))
    prog = _PROGRAM_CACHE.get(ck)
    if prog is None:
        prog = _PROGRAM_CACHE.setdefault(
            ck, jax.jit(jax.shard_map(body, mesh=mesh,
                                      in_specs=(P(axis),),
                                      out_specs=P())))
    return prog(keys)


def device_sorted(mesh: Mesh, axis: str, keys: jax.Array,
                  values: jax.Array,
                  capacity_factor: float = 2.0) -> ShuffleResult:
    """Global sort of device-resident records — TeraSort as collectives:
    sample → range-partition all_to_all → local sort. After this, valid
    keys on device d are all ≤ valid keys on device d+1 and each
    device's run is internally sorted."""
    n_dev = mesh.shape[axis]
    splits = sample_split_points(mesh, axis, keys, n_dev)
    return device_shuffle(mesh, axis, keys, values,
                          partition=range_partitioner(splits),
                          capacity_factor=capacity_factor,
                          sort_output=True)
