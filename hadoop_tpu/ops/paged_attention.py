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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
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


def paged_attention_packed(q: jnp.ndarray, kc: jnp.ndarray,
                           vc: jnp.ndarray, tables: jnp.ndarray,
                           lens: jnp.ndarray, scale: float) -> jnp.ndarray:
    """``paged_attention`` over pools whose pages keep a token's KV heads
    side by side in ONE row: kc, vc ``[blocks, bs, hkv * dh]``. For heads
    narrower than the 128 lanes of a tile (``dh`` 64): a pool shaped
    ``[..., hkv, dh]`` is then laid out with the blocks minor on the
    device and converted whole on the way in and out of every step, and
    a gathered chunk reshaped to heads is relaid every trip. Here a
    query head is widened to the packed row with zeros outside its own
    KV head's lanes, so both contractions run over whole rows as they
    lie (``hkv`` times the FLOPs of the MXU, which decode leaves idle;
    the bytes are the same). Portable ``jax.numpy`` / ``lax``, the walk
    of ``_paged_ref``: to the call's longest live context, a chunk of
    pages at a time. q ``[t, hq, dh]``; returns ``[t, hq, dh]``."""
    out_dtype = q.dtype
    t, hq, dh = q.shape
    _, bs, width = kc.shape
    hkv = width // dh
    record_attention_impl("paged", "packed", q.shape, kc.shape)
    # own[h, g]: query head h reads KV head g
    own = (jnp.arange(hq)[:, None] // (hq // hkv)
           == jnp.arange(hkv)[None, :]).astype(kc.dtype)
    qp = (q.astype(kc.dtype)[:, :, None, :]
          * own[None, :, :, None]).reshape(t, hq, width)
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
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    # a head's own lanes of the packed row
    out = jnp.einsum("tqgd,qg->tqd", out.reshape(t, hq, hkv, dh),
                     own.astype(jnp.float32))
    return out.astype(out_dtype)


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
