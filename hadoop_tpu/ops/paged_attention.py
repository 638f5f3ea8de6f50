"""Paged decode attention: each row reads its own live pages, once per
KV head, in the pool's dtype.

Every row of a serving step is one query token at one position whose
context lives in pages of a shared pool, named by the row's block
table. The HBM traffic of this op is set by how long the live contexts
*are*, not by how long they may become:

- **Only live pages.** The context is walked in chunks of whole pages —
  a trip count computed in-graph from ``lens``, so one compiled program
  serves every mix of lengths.
- **GQA groups stay together.** Each K/V byte is read once for all the
  query heads that share it; nothing is repeated to ``hq`` heads.
- **No float32 copy of K/V.** Operands stay in the pool's dtype with
  float32 accumulation (``preferred_element_type``); the softmax is the
  online one (running max / running sum, float32), with P cast to the
  pool's dtype for the P·V product as the flash kernels do.

Two implementations of that one contract (``paged_attention``'s
``impl``), which of them a call site got recorded at trace time
(``ops.attention.attention_impl_traces``, site ``paged``):

- ``ref`` — plain ``jax.numpy`` / ``lax``: a ``while_loop`` that gathers
  a chunk's pages for every row and walks to the call's *longest* live
  context. Runs on the CPU, on one chip, and under GSPMD on a mesh that
  shards the pool over KV heads.
- ``flash`` — a Pallas TPU kernel, one program per row: block table and
  lengths are scalar-prefetched, the pool stays in HBM, and a row's own
  pages (each one contiguous ``[bs, hkv, dh]`` slab) are DMA'd into
  VMEM double-buffered; a row with ``lens == 0`` moves nothing. A
  token's ``hkv`` heads fill the sublanes of one tile, so the kernel
  contracts all heads at once against a ``[tokens * hkv, dh]`` view and
  masks the cross-head products (the MXU is idle in decode; the bytes
  are what cost). Picked on a TPU backend for qualifying shapes.

Pools whose pages keep a token's KV heads side by side in one row
(``paged_attention_packed``: heads narrower than a tile's 128 lanes)
have the same two, recorded as ``packed`` and ``packed_flash``: the
walk, and ``_packed_kernel``, one program per GROUP of rows that share
a block table — a decode lane alone, reading its pages up to its own
length; a prefill chunk's rows ``group_rows`` at a time, sharing one
fetch of their request's pages.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hadoop_tpu.ops.attention import _NEG_INF, record_attention_impl

# tokens of context read per trip of either loop: long enough that a
# trip's fixed cost is paid a few times a call, short enough that the
# walk overshoots a live context by little
CHUNK_TOKENS = 256


def _chunked_tables(tables, bs):
    """(tables padded to whole chunks, pages per chunk). The pad names
    page 0 and lies past every row's ``lens``."""
    bps = tables.shape[1]
    ppc = max(1, min(CHUNK_TOKENS // bs, bps))
    return jnp.pad(tables, ((0, 0), (0, -bps % ppc))), ppc


# ================================================================ portable

def _paged_ref(q, kc, vc, tables, lens, scale):
    t, hq, dh = q.shape
    _, bs, hkv, _ = kc.shape
    n_rep = hq // hkv
    tables, ppc = _chunked_tables(tables, bs)
    ctok = ppc * bs
    qg = q.reshape(t, hkv, n_rep, dh)
    n_chunks = (jnp.max(lens) + ctok - 1) // ctok
    ktok = jnp.arange(ctok)

    def chunk(carry):
        i, m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(tables, i * ppc, ppc, axis=1)
        with jax.named_scope("kv_gather"):
            k = kc[pages].reshape(t, ctok, hkv, dh)
            v = vc[pages].reshape(t, ctok, hkv, dh)
        s = jnp.einsum("thgd,tkhd->thgk", qg, k,
                       preferred_element_type=jnp.float32) * scale
        live = (i * ctok + ktok)[None, :] < lens[:, None]        # [t, k]
        live = live[:, None, None, :]
        s = jnp.where(live, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a row with nothing live in this chunk has s == m_new ==
        # _NEG_INF and exp(0) == 1: the mask, not the exponent, is what
        # keeps dead positions out of the sums
        p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "thgk,tkhd->thgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return i + 1, m_new, l, acc

    stat = jnp.zeros((t, hkv, n_rep), jnp.float32)
    _, _, l, acc = jax.lax.while_loop(
        lambda c: c[0] < n_chunks, chunk,
        (jnp.int32(0), stat + _NEG_INF, stat,
         jnp.zeros((t, hkv, n_rep, dh), jnp.float32)))
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.reshape(t, hq, dh)


def _packed_ref(qp, kc, vc, tables, lens, scale):
    """The walk of ``_paged_ref`` over packed pages, for a widened query
    ``qp [t, hq, width]``: to the call's longest live context, a chunk of
    pages at a time, every row gathering its own. Returns the float32
    ``[t, hq, width]`` rows (``paged_attention_packed`` keeps a head's
    own lanes)."""
    t, hq, width = qp.shape
    _, bs, _ = kc.shape
    tables, ppc = _chunked_tables(tables, bs)
    ctok = ppc * bs
    n_chunks = (jnp.max(lens) + ctok - 1) // ctok
    ktok = jnp.arange(ctok)

    def chunk(carry):
        i, m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(tables, i * ppc, ppc, axis=1)
        with jax.named_scope("kv_gather"):
            k = kc[pages].reshape(t, ctok, width)
            v = vc[pages].reshape(t, ctok, width)
        s = jnp.einsum("tqe,tke->tqk", qp, k,
                       preferred_element_type=jnp.float32) * scale
        live = ((i * ctok + ktok)[None, :] < lens[:, None])[:, None, :]
        s = jnp.where(live, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # (the mask, not the exponent, keeps dead positions out: see
        # _paged_ref)
        p = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "tqk,tke->tqe", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return i + 1, m_new, l, acc

    stat = jnp.zeros((t, hq), jnp.float32)
    _, _, l, acc = jax.lax.while_loop(
        lambda c: c[0] < n_chunks, chunk,
        (jnp.int32(0), stat + _NEG_INF, stat,
         jnp.zeros((t, hq, width), jnp.float32)))
    return acc / jnp.where(l > 0, l, 1.0)[..., None]


# ============================================================ Pallas (TPU)

def kernel_supported(q_shape, kc_shape, dtype) -> bool:
    """Shapes the kernel handles; callers fall back otherwise: a token's
    KV heads fill whole 8-sublane tiles, ``dh`` whole lanes, and the two
    double-buffered chunks fit VMEM beside the scores."""
    _, hq, dh = q_shape
    _, bs, hkv, _ = kc_shape
    chunk_bytes = max(CHUNK_TOKENS, bs) * hkv * dh * jnp.dtype(dtype).itemsize
    return (dh % 128 == 0 and hkv % 8 == 0 and hq % hkv == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and 4 * chunk_bytes <= 8 << 20)


def _kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, *, scale: float):
    row = pl.program_id(0)
    n = lens_ref[row]
    _, ppc, bs, hkv, dh = kbuf.shape
    hq = q_ref.shape[1]
    n_rep = hq // hkv
    ctok = ppc * bs
    n_chunks = (n + ctok - 1) // ctok

    def copies(c, slot):
        """The DMAs of chunk ``c`` into buffer ``slot``: one per page,
        all in flight together. A chunk is fetched whole; pages past the
        row's length are valid pool pages the mask discards."""
        out = []
        for j in range(ppc):
            page = tables_ref[row, c * ppc + j]
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], kbuf.at[slot, j], sem.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], vbuf.at[slot, j], sem.at[1, slot]))
        return out

    @pl.when(n == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _live():
        for cp in copies(0, 0):
            cp.start()
        q = q_ref[0]                                       # [hq, dh]

        def chunk(c, carry):
            m, l, acc = carry
            slot = c % 2

            @pl.when(c + 1 < n_chunks)
            def _prefetch():
                for cp in copies(c + 1, 1 - slot):
                    cp.start()

            for cp in copies(c, slot):
                cp.wait()
            # column (token, head'): every query head meets every KV
            # head of every token; only head' == its own is kept
            k = kbuf[slot].reshape(ctok * hkv, dh)
            v = vbuf[slot].reshape(ctok * hkv, dh)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // n_rep
            live = (col % hkv == head) & (c * ctok + col // hkv < n)
            s = jnp.where(live, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l, acc

        # position 0 is live in chunk 0, so l > 0 at the end
        _, l, acc = jax.lax.fori_loop(
            0, n_chunks, chunk,
            (jnp.full((hq, 1), _NEG_INF, jnp.float32),
             jnp.zeros((hq, 1), jnp.float32),
             jnp.zeros((hq, dh), jnp.float32)))
        o_ref[0] = (acc / l).astype(o_ref.dtype)


def _paged_flash(q, kc, vc, tables, lens, scale, interpret):
    t, hq, dh = q.shape
    _, bs, hkv, _ = kc.shape
    tables, ppc = _chunked_tables(tables, bs)
    row_block = pl.BlockSpec((1, hq, dh), lambda i, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(t,),
        in_specs=[row_block,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_block,
        scratch_shapes=[pltpu.VMEM((2, ppc, bs, hkv, dh), kc.dtype),
                        pltpu.VMEM((2, ppc, bs, hkv, dh), vc.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec, interpret=interpret,
        name="paged_attention")(tables, lens, q, kc, vc)


# ============================================== Pallas (TPU), packed pages

# query rows (rows x heads) one program of a chunk's group holds: its
# float32 scores over a chunk of tokens and its accumulator stay in VMEM
# beside the two double-buffered chunks
GROUP_QUERY_ROWS = 1024


def packed_kernel_supported(q_shape, kc_shape, dtype) -> bool:
    """Shapes ``_packed_kernel`` handles: a packed row fills whole lanes,
    a group's query rows and a page's tokens whole sublane tiles, and
    the two double-buffered chunks fit VMEM beside the scores."""
    _, hq, dh = q_shape
    _, bs, width = kc_shape
    item = jnp.dtype(dtype).itemsize
    chunk_bytes = max(CHUNK_TOKENS, bs) * width * item
    return (width % 128 == 0 and width % dh == 0
            and hq % (width // dh) == 0 and hq % 8 == 0
            and bs % (32 // item) == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and 4 * chunk_bytes <= 8 << 20)


def group_rows(chunk_rows: int, hq: int) -> int:
    """Rows of a prefill chunk that one program serves: the most that
    divide the chunk with ``GROUP_QUERY_ROWS`` query rows or fewer."""
    return max(r for r in range(1, chunk_rows + 1)
               if chunk_rows % r == 0 and r * hq <= max(GROUP_QUERY_ROWS, hq))


def packed_pages_fetched(lane_lens, chunk_lens, bs: int, bps: int,
                         hq: int) -> int:
    """Table pages ``_packed_kernel`` DMAs for one call (host side, for
    the counters): a decode lane fetches whole chunks of pages up to its
    own length; a prefill chunk's rows, ``group_rows`` at a time, up to
    the longest of the group. ``lane_lens``: the lanes' lengths;
    ``chunk_lens``: every row of the chunk, 0 for an inactive one."""
    ppc = max(1, min(CHUNK_TOKENS // bs, bps))
    ctok = ppc * bs
    lens = np.asarray(lane_lens, np.int64)
    chunk_lens = np.asarray(chunk_lens, np.int64)
    if chunk_lens.size:
        r = group_rows(chunk_lens.size, hq)
        lens = np.concatenate([lens, chunk_lens.reshape(-1, r).max(axis=1)])
    return int(np.sum(-(-lens // ctok))) * ppc


def _packed_kernel(tables_ref, lens_ref, top_ref, q_ref, k_hbm, v_hbm,
                   o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *,
                   scale: float, rows: int):
    """One program: a group of ``rows`` rows that share the block table
    of its first row, query rows ``[rows * hq, width]``. The group's
    pages are DMA'd once, a chunk at a time, up to its longest length
    (``top_ref``); each query row keeps the positions under its own
    row's length."""
    g = pl.program_id(0)
    n = top_ref[g]
    _, ppc, bs, width = kbuf.shape
    ctok = ppc * bs
    n_chunks = (n + ctok - 1) // ctok
    heads = q_ref.shape[0] // rows

    def copies(c, slot, act):
        """``act`` ("start" / "wait") on the DMAs of chunk ``c`` into
        buffer ``slot``: one per page, all in flight together. A loop,
        not unrolled (nor the rows' lengths below): every serving process
        traces and lowers the kernel anew in its set-up, and an op a page
        and a row, three sites a call, made that several times longer."""
        def page(j, _):
            p = tables_ref[g, c * ppc + j]
            for pool, buf, i in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                getattr(pltpu.make_async_copy(
                    pool.at[p], buf.at[slot, j], sem.at[i, slot]), act)()
            return 0
        jax.lax.fori_loop(0, ppc, page, 0)

    @pl.when(n == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _live():
        copies(0, 0, "start")
        # each query row's length: that of its row of the group
        row = jax.lax.broadcasted_iota(jnp.int32, (rows * heads, 1),
                                       0) // heads
        own = jax.lax.fori_loop(
            0, rows, lambda r, own: jnp.where(row == r, lens_ref[g * rows + r],
                                              own),
            jnp.zeros((rows * heads, 1), jnp.int32))
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[...]

        def chunk(c, _):
            slot = c % 2

            @pl.when(c + 1 < n_chunks)
            def _prefetch():
                copies(c + 1, 1 - slot, "start")

            copies(c, slot, "wait")
            k = kbuf[slot].reshape(ctok, width)
            v = vbuf[slot].reshape(ctok, width)
            # positions past the group's length are fetched with their
            # chunk and never read: P is 0 there, and so is V
            tok = c * ctok + jax.lax.broadcasted_iota(jnp.int32, (ctok, 1), 0)
            v = jnp.where(tok < n, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            pos = c * ctok + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            live = pos < own
            s = jnp.where(live, s, _NEG_INF)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            # (the mask, not the exponent, keeps dead positions out: see
            # _paged_ref)
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return 0

        jax.lax.fori_loop(0, n_chunks, chunk, 0)
        # a row of the group with nothing live (an inactive chunk row)
        # has l == 0 and reads zeros
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def _packed_call(qp, kc, vc, tables, lens, scale, rows, interpret):
    """``_packed_kernel`` over ``qp [t * hq, width]``, groups of ``rows``
    consecutive rows, each reading the table of its first row."""
    t = lens.shape[0]
    n_groups, heads = t // rows, qp.shape[0] // t
    _, bs, width = kc.shape
    tables, ppc = _chunked_tables(tables[::rows], bs)
    top = jnp.max(lens.reshape(n_groups, rows), axis=1)
    block = pl.BlockSpec((rows * heads, width), lambda g, *_: (g, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n_groups,),
        in_specs=[block, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block,
        scratch_shapes=[pltpu.VMEM((2, ppc, bs, width), kc.dtype),
                        pltpu.VMEM((2, ppc, bs, width), vc.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((rows * heads, 1), jnp.float32),
                        pltpu.VMEM((rows * heads, 1), jnp.float32),
                        pltpu.VMEM((rows * heads, width), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_packed_kernel, scale=scale, rows=rows),
        out_shape=jax.ShapeDtypeStruct(qp.shape, qp.dtype),
        grid_spec=grid_spec, interpret=interpret,
        name="paged_attention_packed")(tables, lens, top, qp, kc, vc)


def _packed_flash(qp, kc, vc, tables, lens, scale, lanes, interpret):
    """The lanes, a group each, then the chunk's rows in groups of
    ``group_rows``: two calls of one kernel. Returns ``[t, hq, width]``
    in the pool's dtype."""
    t, hq, width = qp.shape
    qp = qp.reshape(t * hq, width)
    out = [_packed_call(qp[:lanes * hq], kc, vc, tables[:lanes],
                        lens[:lanes], scale, 1, interpret)] if lanes else []
    if lanes < t:
        out.append(_packed_call(qp[lanes * hq:], kc, vc, tables[lanes:],
                                lens[lanes:], scale,
                                group_rows(t - lanes, hq), interpret))
    return jnp.concatenate(out).reshape(t, hq, width)


# ================================================================== public

def paged_attention(q: jnp.ndarray, kc: jnp.ndarray, vc: jnp.ndarray,
                    tables: jnp.ndarray, lens: jnp.ndarray, scale: float,
                    impl: str = "auto",
                    interpret: bool = False) -> jnp.ndarray:
    """Attention of ``t`` single-token rows over their paged contexts.

    q: ``[t, hq, dh]``; kc, vc: ``[blocks, bs, hkv, dh]`` (a pool
    addressed by page index, ``hq`` a multiple of ``hkv``; a pool
    stacked over layers is addressed ``layer * blocks + page``);
    tables: ``[t, bps]`` int32 pool indices, page ``j`` of a row holding
    its positions ``j*bs .. j*bs + bs - 1``; lens: ``[t]`` int32, the
    row attends to positions ``< lens`` (``pos + 1`` for a live row). A
    row with ``lens == 0`` attends to nothing and gets zeros — never
    NaN. Table entries past a row's live pages never reach a softmax but
    may be fetched, so every entry names a valid page. Returns
    ``[t, hq, dh]`` in ``q``'s dtype.

    ``impl``: "auto" picks the Pallas kernel on a TPU backend when the
    shapes qualify and the portable path otherwise; "flash" / "ref"
    force (a caller whose pool is sharded over a mesh forces "ref": the
    kernel is a single-device program). ``interpret`` runs the kernel in
    Pallas's interpreter, for tests off the chip.
    """
    out_dtype = q.dtype
    q = q.astype(kc.dtype)
    ok = kernel_supported(q.shape, kc.shape, kc.dtype)
    if impl == "flash" and not ok:
        raise ValueError("impl='flash' forced but the paged kernel does "
                         f"not support q={q.shape} pool={kc.shape} "
                         f"{kc.dtype}")
    if impl == "flash" or (impl == "auto" and ok and
                           jax.default_backend() not in ("cpu", "gpu")):
        record_attention_impl("paged", "flash", q.shape, kc.shape)
        out = _paged_flash(q, kc, vc, tables, lens, scale, interpret)
    else:
        record_attention_impl("paged", "ref", q.shape, kc.shape)
        out = _paged_ref(q, kc, vc, tables, lens, scale)
    return out.astype(out_dtype)


def paged_attention_packed(q: jnp.ndarray, kc: jnp.ndarray,
                           vc: jnp.ndarray, tables: jnp.ndarray,
                           lens: jnp.ndarray, scale: float,
                           lanes: Optional[int] = None, impl: str = "auto",
                           interpret: bool = False) -> jnp.ndarray:
    """``paged_attention`` over pools whose pages keep a token's KV heads
    side by side in ONE row: kc, vc ``[blocks, bs, hkv * dh]``. For heads
    narrower than the 128 lanes of a tile (``dh`` 64): a pool shaped
    ``[..., hkv, dh]`` is then laid out with the blocks minor on the
    device and converted whole on the way in and out of every step, and
    a gathered chunk reshaped to heads is relaid every trip. Here a
    query head is widened to the packed row with zeros outside its own
    KV head's lanes, so both contractions run over whole rows as they
    lie (``hkv`` times the FLOPs of the MXU; the bytes are the same).
    q ``[t, hq, dh]``; returns ``[t, hq, dh]`` in ``q``'s dtype.

    ``lanes``: rows ``0 .. lanes - 1`` each read their own table; the
    rows after them are consecutive positions of ONE request (a prefill
    chunk) and share the table of row ``lanes``. None: every row is a
    lane.

    ``impl``: "auto" takes ``_packed_kernel`` on a TPU backend when the
    shapes qualify, and the portable walk (``_packed_ref``: every row
    gathers its pages, to the call's longest context) otherwise; "flash"
    / "ref" force. The kernel DMAs each lane's pages up to the lane's
    own length, and the chunk's pages once for each group of
    ``group_rows`` rows; ``interpret`` runs it in Pallas's interpreter,
    for tests off the chip.
    """
    out_dtype = q.dtype
    t, hq, dh = q.shape
    _, bs, width = kc.shape
    hkv = width // dh
    ok = packed_kernel_supported(q.shape, kc.shape, kc.dtype)
    if impl == "flash" and not ok:
        raise ValueError("impl='flash' forced but the packed kernel does "
                         f"not support q={q.shape} pool={kc.shape} "
                         f"{kc.dtype}")
    # own[h, g]: query head h reads KV head g
    own = (jnp.arange(hq)[:, None] // (hq // hkv)
           == jnp.arange(hkv)[None, :]).astype(kc.dtype)
    qp = (q.astype(kc.dtype)[:, :, None, :]
          * own[None, :, :, None]).reshape(t, hq, width)
    if impl == "flash" or (impl == "auto" and ok and
                           jax.default_backend() not in ("cpu", "gpu")):
        record_attention_impl("paged", "packed_flash", q.shape, kc.shape)
        out = _packed_flash(qp, kc, vc, tables, lens, scale,
                            t if lanes is None else lanes, interpret)
    else:
        record_attention_impl("paged", "packed", q.shape, kc.shape)
        out = _packed_ref(qp, kc, vc, tables, lens, scale)
    # a head's own lanes of the packed row
    out = jnp.einsum("tqgd,qg->tqd", out.reshape(t, hq, hkv, dh),
                     own.astype(out.dtype))
    return out.astype(out_dtype)
