"""Paged latent attention with a learned sparse selection.

Every row of a serving step is one query token whose context lives in
pages of two pools that share one block table: **one latent a token**
(``kv_lora_rank`` compressed key/value entries followed by one rotary key
shared by all heads) and **one small index key a token**. A row does not
attend to its whole context:

1. **Index scores.** A few light heads score the row against every live
   index key: ``I[s] = sum_j w[j] * relu(q_idx[j] . k_idx[s])``. The walk
   over the context reads whole pages, ends at the call's longest live
   context, and reads a page once for all the rows that share its table
   (the rows of one prompt chunk; the draft rows of one lane).
2. **Exact top-k.** The row keeps the ``k`` highest scores among its live
   positions, ties broken by the lower position; a row with at most ``k``
   live positions keeps them all. No sort: the k-th largest value is found
   by a 32-step search over the scores' bit patterns (each step one
   compare-and-count pass; ``ops/topk.py``, which the sampler shares),
   then the kept positions are compacted into
   ``[k]`` indices block by block with compares and one-hot products
   alone — no scatter, no gather, no data-dependent shape. ``approx_max_k`` or a sampled selection would be a
   different result, not a faster one.
3. **Attention over the kept latents**, in the absorbed (multi-query)
   form: the key up-projection is folded into the query, so a head's score
   against a latent is ``q_abs . c + q_pe . k_pe`` and its output is a
   weighted sum of latents ``c`` — a row reads ``k`` latents, never its
   whole context. Softmax in float32, operands in the pool's dtype.

This file is the portable ``jax.numpy`` implementation (``impl="ref"``:
CPU, one chip); which implementation a call site got is recorded at trace
time like ``paged_attention``'s (``attention_impl_traces``, site
``sparse_mla``). A Pallas kernel would be one more ``impl`` of the same
contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hadoop_tpu.ops.attention import _NEG_INF, record_attention_impl
from hadoop_tpu.ops.topk import kth_largest, sortable

# positions per block of the compaction (and of the blocked prefix sums)
BLOCK = 256
# query rows selected, gathered and attended at a time: bounds the
# [rows, k, latent] gather and the [rows, k, BLOCK] compaction compare
ROW_BLOCK = 32
# context tokens scored per trip of the index walk: per-row tables walk
# short chunks (each row gathers its own pages), one shared table a
# longer one (a real matmul against one run of keys)
CHUNK_TOKENS = 256
CHUNK_TOKENS_SHARED = 1024


# ======================================================== exact selection

def _blocked_counts(mask):
    """mask ``[n, S]`` (S a multiple of BLOCK) -> (``incl [n, nb, BLOCK]``:
    how many are set in the block up to and including each position,
    ``before [n, nb]``: how many are set in earlier blocks). The prefix
    sum inside a block is one matmul with a triangle of ones — exact:
    0/1 operands, float32 accumulation, sums at most BLOCK."""
    n, s = mask.shape
    m = mask.reshape(n, s // BLOCK, BLOCK)
    i = jnp.arange(BLOCK)
    tri = (i[:, None] <= i[None, :]).astype(jnp.bfloat16)
    incl = jnp.einsum("nbi,ij->nbj", m.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
    cnt = incl[..., -1]
    return incl, jnp.cumsum(cnt, axis=-1) - cnt


def exact_topk(scores, lens, k: int):
    """The ``min(k, lens)`` highest of ``scores[:lens]`` per row, ties
    broken by the lower position. scores ``[n, S]`` float32, lens ``[n]``.
    Returns (``idx [n, k]`` int32 ascending positions, ``valid [n, k]``:
    slot ``j`` holds a position iff ``j < min(k, lens)``; the others
    read 0)."""
    at_b, off, valid = _select(scores, lens, k)
    b = jnp.argmax(at_b, axis=-1).astype(jnp.int32)
    return jnp.where(valid, b * BLOCK + off, 0), valid


def _select(scores, lens, k: int):
    """``exact_topk``'s positions as (``at_b [n, k, nb]``: the one-hot of
    each kept position's BLOCK, ``off [n, k]``: its place inside the
    block, ``valid [n, k]``), so that a caller can look further rows up
    by block with a product instead of a gather."""
    n, s = scores.shape
    pad = -s % BLOCK
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, pad)))
        s += pad
    nb = s // BLOCK
    live = jnp.arange(s)[None, :] < lens[:, None]
    key = jnp.where(live, sortable(scores), jnp.uint32(0))
    want = jnp.minimum(lens, k).astype(jnp.int32)
    t = kth_largest(key, want)
    above = key > t[:, None]
    tied = (key == t[:, None]) & live
    need = want - jnp.sum(above, axis=-1, dtype=jnp.int32)
    t_incl, t_before = _blocked_counts(tied)
    t_rank = (t_incl + t_before[..., None]).reshape(n, s)
    sel = above | (tied & (t_rank <= need[:, None]))

    # compaction: output slot j lies in the block b whose kept positions
    # cover ranks before[b] .. before[b] + cnt[b] - 1, at the position of
    # that block whose inclusive count is j - before[b] + 1. Looking a
    # block's row up by b is a product with b's one-hot, not a gather
    # (exact: counts up to BLOCK in bfloat16, offsets in float32): a
    # gather of 65,536 short rows cost more than the whole search above
    incl, before = _blocked_counts(sel)
    tag = jnp.where(sel.reshape(n, nb, BLOCK), incl, 0).astype(jnp.bfloat16)
    j = jnp.arange(k, dtype=jnp.int32)
    ends = before + incl[..., -1]                               # [n, nb]
    passed = ends[:, None, :] <= j[None, :, None]               # [n, k, nb]
    b = jnp.minimum(jnp.sum(passed, axis=-1, dtype=jnp.int32), nb - 1)
    at_b = jax.nn.one_hot(b, nb, dtype=jnp.bfloat16)            # [n, k, nb]
    r = j[None, :] + 1 - jnp.einsum(
        "nkb,nb->nk", at_b.astype(jnp.float32), before.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    rows = jnp.einsum("nkb,nbi->nki", at_b, tag,
                      preferred_element_type=jnp.float32)       # [n,k,BLOCK]
    hit = rows == r[:, :, None].astype(jnp.float32)
    off = jnp.sum(jnp.where(hit, jnp.arange(BLOCK, dtype=jnp.int32), 0),
                  axis=-1)
    return at_b, off, j[None, :] < want[:, None]


# ============================================================ index scores

def index_scores(q_idx, w_idx, idx_pool, tables, lens):
    """``I[r, q, s] = sum_j w[r,q,j] * relu(q_idx[r,q,j] . k_idx[r][s])``
    over the pages of each table row. q_idx ``[R, Q, Hi, Di]``, w_idx
    ``[R, Q, Hi]`` float32, idx_pool ``[P, bs, Di]``, tables ``[R, bps]``,
    lens ``[R, Q]``. Returns ``[R, Q, S]`` float32 with S the tables'
    span rounded up to whole chunks; positions past the longest live
    context are not computed (they read 0 and lie past every ``lens``)."""
    r, q, hi, di = q_idx.shape
    _, bs, _ = idx_pool.shape
    bps = tables.shape[1]
    chunk = CHUNK_TOKENS if r > 1 else CHUNK_TOKENS_SHARED
    ppc = max(1, min(chunk // bs, bps))
    tables = jnp.pad(tables, ((0, 0), (0, -bps % ppc)))
    ctok = ppc * bs
    s_all = tables.shape[1] * bs
    n_chunks = (jnp.max(lens) + ctok - 1) // ctok
    q_idx = q_idx.astype(idx_pool.dtype)

    def chunk_scores(carry):
        i, out = carry
        pages = jax.lax.dynamic_slice_in_dim(tables, i * ppc, ppc, axis=1)
        keys = idx_pool[pages].reshape(r, ctok, di)
        s = jnp.einsum("rqhd,rkd->rqhk", q_idx, keys,
                       preferred_element_type=jnp.float32)
        s = jnp.sum(jax.nn.relu(s) * w_idx[..., None], axis=2)  # [R,Q,ctok]
        return i + 1, jax.lax.dynamic_update_slice_in_dim(
            out, s, i * ctok, axis=2)

    _, out = jax.lax.while_loop(
        lambda c: c[0] < n_chunks, chunk_scores,
        (jnp.int32(0), jnp.zeros((r, q, s_all), jnp.float32)))
    return out


# ======================================================= absorbed attention

def _attend_rows(q_cat, scores, lens, page_rows, lat_flat, bs, kv_rank, k,
                 scale):
    """One block of rows: select, gather, attend. q_cat ``[n, H, W]``,
    scores ``[n, S]``, lens ``[n]``, page_rows ``[n, bps]`` (each row's
    table), lat_flat ``[P * bs, W]``. -> ``[n, H, kv_rank]`` float32."""
    with jax.named_scope("dsa_select"):
        at_b, off, valid = _select(scores, lens, k)
        # the page of each kept position, again by block: a BLOCK of
        # positions is BLOCK // bs consecutive entries of the row's table
        nb, ppb = at_b.shape[-1], BLOCK // bs
        table = jnp.pad(page_rows, ((0, 0), (0, nb * ppb
                                             - page_rows.shape[1])))
        pages = jnp.einsum(
            "nkb,nbp->nkp", at_b.astype(jnp.float32),
            table.reshape(-1, nb, ppb).astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)                # exact ints
        page = jnp.sum(jnp.where(
            jnp.arange(ppb)[None, None, :] == (off // bs)[:, :, None],
            pages, 0.0), axis=-1).astype(jnp.int32)
        token = jnp.where(valid, page * bs + off % bs, 0)
        lat = lat_flat[token]                                   # [n, k, W]
    with jax.named_scope("attn"):
        s = jnp.einsum("nhd,nkd->nhk", q_cat, lat,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, :], s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(valid[:, None, :], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("nhk,nkc->nhc", p.astype(lat.dtype),
                       lat[..., :kv_rank],
                       preferred_element_type=jnp.float32)
        return o / jnp.where(l > 0, l, 1.0)


def sparse_mla_attention(q_abs, q_pe, q_idx, w_idx, lat_pool, idx_pool,
                         tables, lens, *, topk: int, scale: float,
                         impl: str = "auto"):
    """Attention of ``R x Q`` single-token query rows — ``Q`` rows for
    each of ``R`` block tables — over the ``topk`` entries of their paged
    contexts that the index heads score highest.

    q_abs ``[R, Q, H, C]`` (the nope query with the key up-projection
    absorbed, ``C = kv_lora_rank``), q_pe ``[R, Q, H, Dr]`` (rotated),
    q_idx ``[R, Q, Hi, Di]`` (rotated), w_idx ``[R, Q, Hi]`` float32;
    lat_pool ``[P, bs, W]``, ``W >= C + Dr`` (latent, rotary key, then
    zeros up to the pool's width), idx_pool ``[P, bs, Di]``; tables ``[R, bps]`` int32 pool pages, page ``j``
    holding positions ``j*bs .. j*bs + bs - 1``; lens ``[R, Q]``: a row
    attends among positions ``< lens`` (0: to nothing, and gets zeros).
    Read AFTER the step's scatter, so a chunk row sees the rows before
    it. Returns ``[R, Q, H, C]`` in ``q_abs``'s dtype: the weighted sum
    of latents, for the caller's value up-projection.
    """
    if impl not in ("auto", "ref"):
        raise ValueError(f"sparse_mla_attention impl={impl!r} "
                         "(there are: auto, ref)")
    record_attention_impl("sparse_mla", "ref", q_abs.shape, lat_pool.shape)
    r, q, h, c = q_abs.shape
    _, bs, w = lat_pool.shape
    if BLOCK % bs:
        raise ValueError(f"page size {bs} must divide {BLOCK}")
    n = r * q
    with jax.named_scope("dsa_index"):
        scores = index_scores(q_idx, w_idx, idx_pool, tables, lens)
    scores = scores.reshape(n, -1)
    q_cat = jnp.concatenate(
        [q_abs, q_pe, jnp.zeros((r, q, h, w - c - q_pe.shape[-1]),
                                q_abs.dtype)], axis=-1).astype(
        lat_pool.dtype).reshape(n, h, w)
    lens_n = lens.reshape(n)
    row_table = jnp.repeat(jnp.arange(r), q)                    # [n]
    lat_flat = lat_pool.reshape(-1, w)

    def block(xs):
        qb, sb, lb, tb = xs
        return _attend_rows(qb, sb, lb, tables[tb], lat_flat, bs, c, topk,
                            scale)

    rb = ROW_BLOCK if n % ROW_BLOCK == 0 else n
    if n == rb:
        out = block((q_cat, scores, lens_n, row_table))
    else:
        split = lambda a: a.reshape((n // rb, rb) + a.shape[1:])  # noqa: E731
        out = jax.lax.map(block, (split(q_cat), split(scores),
                                  split(lens_n), split(row_table)))
    return out.reshape(r, q, h, c).astype(q_abs.dtype)
