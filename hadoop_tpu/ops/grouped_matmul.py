"""Grouped matmuls over a stack of expert matrices, read where it lies.

``rows [M, K]`` are sorted by group; group ``g`` of ``sizes [G]`` owns
the next ``sizes[g]`` of them and multiplies them by ``stack[first +
g]``. The stack is the whole stacked leaf viewed ``[layers * experts, K,
N]`` — a free reshape — and ``first`` picks one layer's experts out of
it by OFFSETTING THE GROUP INDEX, never by slicing: a slice handed to a
custom call is materialised (403 MB a leaf a layer at the agent cell's
widths, compiled for a described v5e).

The HBM traffic of these ops follows the groups that have rows: a group
with none is never visited, so its ``K * N`` weights are not read, and a
group's weights are multiplied by its own rows' tiles only. Rows past
the last group (``M - sum(sizes)`` of them) are not computed: what lies
there is undefined and the caller masks it.

Two ops — :func:`grouped_swiglu` (``silu(rows @ gate) * (rows @ up)``,
both weights streamed by one kernel, the product rounded once) and
:func:`grouped_matmul` — each with two implementations of one contract,
chosen by backend as ``ops.paged_attention.paged_attention`` chooses:

- a TPU backend: a Pallas kernel over a grid of (N tile, visit, K tile).
  A *visit* is a (group, row tile) pair that holds rows; the visits are
  listed on the device from ``sizes`` by a handful of dense compares (no
  sort, no scatter) and scalar-prefetched, a visit's weight block is
  indexed ``[first + group]`` by the index map, and the grid's visit
  axis is as long as the list — the stack stays in HBM and only the hit
  groups' blocks are DMA'd. The grid is megablox's (``jax.experimental.
  pallas.ops.tpu.megablox``); its metadata — a histogram and two
  ``repeat`` over all ``layers * experts`` groups, 0.17 ms a layer on a
  v5e where a hit expert's weights take 0.025 — is not.
- elsewhere: ``jax.lax.ragged_dot`` over the same flattened stack, the
  sizes zero outside ``first .. first + G``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows a visit multiplies: the MXU's height; a group of one row costs a
# tile's FLOPs, still under its weights' read (K * N * 2 bytes at 819
# GB/s against 2 * 128 * K * N FLOPs at 197 TFLOP/s)
ROW_TILE = 128
# a weight block: N up to 1024 wide (2 KB runs in HBM) and 1 Mi elements
# (2 MB in bf16): the best of the tiles timed on a v5e at both cells'
# widths; two weights double-buffered are 8 MB of the 16 MB of VMEM
N_TILE, BLOCK_ELEMS = 1024, 1 << 20


def row_tile(m: int) -> int:
    """The row tile for ``m`` sorted rows: ``ROW_TILE``, or all of a
    shorter call rounded up to the 16 sublanes of a bf16 tile."""
    return min(ROW_TILE, -(-m // 16) * 16)


def _tile(n: int, most: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``most``; all of ``n`` where there is none."""
    for t in range(min(most, n) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def _visits(sizes, m: int, tm: int):
    """The (group, row tile) pairs that hold rows, in order: (``offsets
    [G + 1]`` the row each group starts at, ``groups [V]``, ``tiles
    [V]``, the number of visits) with ``V = M / tm + G - 1`` the most
    there can be. Entries past the number of visits are never run."""
    g = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    visit_end = jnp.cumsum(n_tiles)
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    # the group of visit v: as many groups as end at or before it
    groups = jnp.minimum(
        jnp.sum(visit_end[None, :] <= v[:, None], axis=1), g - 1)
    mine = groups[:, None] == jnp.arange(g)[None, :]
    tiles = v + jnp.sum(
        jnp.where(mine, (first_tile - visit_end + n_tiles)[None, :], 0),
        axis=1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), groups.astype(jnp.int32),
            tiles.astype(jnp.int32), visit_end[-1].astype(jnp.int32))


def _kernel(offsets, groups, tiles, first, rows, *refs, tm, tn, n_k,
            n_weights):
    """One (N tile, visit, K tile) step: the visit's row tile times its
    group's weight block(s), accumulated over K in float32; at the last
    K tile the rows of the tile that belong to the group are stored."""
    del first
    weights, out = refs[:n_weights], refs[n_weights]
    accs = refs[n_weights + 1:]
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    x = rows[...]
    for w, acc in zip(weights, accs):
        acc[...] += jnp.dot(x, w[...], preferred_element_type=jnp.float32)

    @pl.when(k_i == n_k - 1)
    def _():
        group = groups[visit]
        row = tiles[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= offsets[group]) & (row < offsets[group + 1])
        y = accs[0][...]
        if n_weights == 2:
            y = jax.nn.silu(y) * accs[1][...]
        out[...] = jnp.where(mine, y, out[...].astype(jnp.float32)
                             ).astype(out.dtype)


def _grouped_kernel(rows, stacks, sizes, first, out_dtype, interpret):
    m, k = rows.shape
    n = stacks[0].shape[-1]
    tm = row_tile(m)
    if m % tm:
        raise ValueError(f"{m} sorted rows are not whole tiles of {tm}")
    tn = _tile(n, N_TILE)
    tk = _tile(k, max(128, BLOCK_ELEMS // tn))
    offsets, groups, tiles, n_visits = _visits(sizes.astype(jnp.int32),
                                               m, tm)
    first = jnp.asarray(first, jnp.int32).reshape(1)

    def row_block(n_i, v, k_i, offsets, groups, tiles, first):
        return tiles[v], k_i

    def weight_block(n_i, v, k_i, offsets, groups, tiles, first):
        return first[0] + groups[v], k_i, n_i

    def out_block(n_i, v, k_i, offsets, groups, tiles, first):
        return tiles[v], n_i

    kernel = functools.partial(_kernel, tm=tm, tn=tn, n_k=k // tk,
                               n_weights=len(stacks))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, tk), row_block)]
            + [pl.BlockSpec((None, tk, tn), weight_block)] * len(stacks),
            out_specs=pl.BlockSpec((tm, tn), out_block),
            # N outermost: a row tile's output block is revisited by
            # consecutive visits only
            grid=(n // tn, n_visits, k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)
                            for _ in stacks]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="grouped_swiglu" if len(stacks) == 2 else "grouped_matmul",
    )(offsets, groups, tiles, first, rows, *stacks)


def _on_tpu() -> bool:
    return jax.default_backend() not in ("cpu", "gpu")


def _flat_sizes(sizes, first, n_groups):
    return jax.lax.dynamic_update_slice(
        jnp.zeros((n_groups,), jnp.int32), sizes.astype(jnp.int32),
        (first,))


def grouped_matmul(rows: jnp.ndarray, stack: jnp.ndarray,
                   sizes: jnp.ndarray, first, out_dtype=jnp.float32,
                   interpret: bool = False) -> jnp.ndarray:
    """``out[i] = rows[i] @ stack[first + group of row i]``, float32
    accumulation, ``[M, N]`` in ``out_dtype``. ``sizes [G]`` int32, the
    rows of groups ``0 .. G - 1`` in order; ``first`` (a traced scalar
    is fine) the stack index of group 0. Rows past ``sum(sizes)`` are
    undefined. The kernel (a TPU backend, or ``interpret``: Pallas's
    interpreter, for tests off the chip) wants ``M`` a multiple of
    ``row_tile(M)``."""
    if interpret or _on_tpu():
        return _grouped_kernel(rows, (stack,), sizes, first, out_dtype,
                               interpret)
    return jax.lax.ragged_dot(
        rows, stack, _flat_sizes(sizes, first, stack.shape[0]),
        preferred_element_type=jnp.float32).astype(out_dtype)


def grouped_swiglu(rows: jnp.ndarray, gate: jnp.ndarray, up: jnp.ndarray,
                   sizes: jnp.ndarray, first,
                   interpret: bool = False) -> jnp.ndarray:
    """``silu(rows @ gate[g]) * (rows @ up[g])`` for each row's group
    ``g``, as :func:`grouped_matmul` takes its arguments; both products
    accumulated in float32 and their gated product rounded once, to
    ``rows``'s dtype."""
    if interpret or _on_tpu():
        return _grouped_kernel(rows, (gate, up), sizes, first, rows.dtype,
                               interpret)
    flat = _flat_sizes(sizes, first, gate.shape[0])
    g = jax.lax.ragged_dot(rows, gate, flat,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(rows, up, flat,
                           preferred_element_type=jnp.float32)
    return (jax.nn.silu(g) * u).astype(rows.dtype)
