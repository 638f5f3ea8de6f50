"""``ops.paged_attention``: the serving step's attention over paged KV.

The plain reference is the formula the engine used before it: gather
every row's whole block table into a ``[s_max]`` context, repeat the KV
heads to the query heads, mask ``kpos < lens``, softmax in float32. It
lives here only. The op must agree with it while walking only the live
pages, with the GQA group kept together and K/V left in their dtype —
the portable path as it runs, the Pallas kernel through the interpreter.
"""

import dataclasses
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.decoder import init_params
from hadoop_tpu.ops.attention import _repeat_kv, attention_impl_traces
from hadoop_tpu.ops.paged_attention import (kernel_supported,
                                            paged_attention,
                                            paged_attention_packed)
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams
from hadoop_tpu.serving.metrics import ServingMetrics

BPS = 6
# KV heads and head size by implementation: the kernel wants a token's
# heads to fill an (8, 128) tile; the portable path takes anything
SHAPE = {"ref": (2, 16), "flash": (8, 128)}


def dense_reference(q, kc, vc, tables, lens, scale):
    """Whole table, repeated heads, masked float32 softmax. A row with
    nothing to attend to reads zeros."""
    t, hq, dh = q.shape
    _, bs, hkv, _ = kc.shape
    s_max = tables.shape[1] * bs
    f32 = jnp.float32
    kr = _repeat_kv(kc[tables].reshape(t, s_max, hkv, dh), hq // hkv)
    vr = _repeat_kv(vc[tables].reshape(t, s_max, hkv, dh), hq // hkv)
    logits = jnp.einsum("bhd,bkhd->bhk", q.astype(f32), kr.astype(f32),
                        precision="highest") * scale
    mask = jnp.arange(s_max)[None, :] < lens[:, None]
    logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
    top = jnp.where(lens > 0, jnp.max(logits, axis=-1).T, 0.0).T
    e = jnp.where(mask[:, None, :], jnp.exp(logits - top[..., None]), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhk,bkhd->bhd", probs, vr.astype(f32),
                      precision="highest")


def _case(bs):
    """Rows and their (table, lens): every boundary the engine meets."""
    s_max = BPS * bs
    own = lambda r: [1 + (r * BPS + j) % 40 for j in range(BPS)]
    rows = [(own(0), 0),                 # inactive: zeros, finite
            (own(1), 1),
            (own(2), bs - 1), (own(3), bs), (own(4), bs + 1),
            (own(5), s_max),             # the table full
            (own(6), 0)]
    # speculation: one lane's rows share a table at consecutive positions
    rows += [(own(7), 2 * bs - 1 + j) for j in range(3)]
    # a 16-row prompt chunk of one request, crossing pages
    rows += [(own(8), bs + 3 + j) for j in range(16)]
    tables = np.asarray([r[0] for r in rows], np.int32)
    lens = np.asarray([r[1] for r in rows], np.int32)
    return tables, lens


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("impl", ["ref", "flash"])
def test_paged_attention_matches_dense_reference(impl, n_rep, dtype, bs,
                                                 monkeypatch):
    from hadoop_tpu.ops import paged_attention as mod
    # two pages a chunk: the 6-page tables are walked in several trips
    monkeypatch.setattr(mod, "CHUNK_TOKENS", 2 * bs)
    dtype = jnp.dtype(dtype)
    hkv, dh = SHAPE[impl]
    tables, lens = _case(bs)
    t, hq = len(lens), hkv * n_rep
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(bs + n_rep), 3)
    q = jax.random.normal(kq, (t, hq, dh), jnp.float32).astype(dtype)
    kc = jax.random.normal(kk, (41, bs, hkv, dh), jnp.float32).astype(dtype)
    vc = jax.random.normal(kv, (41, bs, hkv, dh), jnp.float32).astype(dtype)
    scale = dh ** -0.5
    before = attention_impl_traces()[f"paged_{impl}"]
    got = jax.jit(paged_attention, static_argnums=(5, 6, 7))(
        q, kc, vc, tables, lens, scale, impl, True)
    assert attention_impl_traces()[f"paged_{impl}"] == before + 1
    assert got.shape == (t, hq, dh) and got.dtype == dtype
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(dense_reference(q, kc, vc, tables, lens, scale))
    assert np.isfinite(got).all()
    assert not got[lens == 0].any(), "a row with no context reads zeros"
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hkv,n_rep,dh", [(2, 1, 16), (8, 4, 64)])
def test_packed_pages_match_the_dense_reference(hkv, n_rep, dh, dtype, bs,
                                                monkeypatch):
    """A token's KV heads side by side in one row of the page (heads
    narrower than a tile's 128 lanes): the same attention, every
    boundary of ``_case``, walked in several trips."""
    from hadoop_tpu.ops import paged_attention as mod
    monkeypatch.setattr(mod, "CHUNK_TOKENS", 2 * bs)
    dtype = jnp.dtype(dtype)
    tables, lens = _case(bs)
    t, hq = len(lens), hkv * n_rep
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(bs + hkv), 3)
    q = jax.random.normal(kq, (t, hq, dh), jnp.float32).astype(dtype)
    kc = jax.random.normal(kk, (41, bs, hkv, dh), jnp.float32).astype(dtype)
    vc = jax.random.normal(kv, (41, bs, hkv, dh), jnp.float32).astype(dtype)
    scale = dh ** -0.5
    before = attention_impl_traces().get("paged_packed", 0)
    got = jax.jit(paged_attention_packed, static_argnums=(5,))(
        q, kc.reshape(41, bs, hkv * dh), vc.reshape(41, bs, hkv * dh),
        tables, lens, scale)
    assert attention_impl_traces()["paged_packed"] == before + 1
    assert got.shape == (t, hq, dh) and got.dtype == dtype
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(dense_reference(q, kc, vc, tables, lens, scale))
    assert np.isfinite(got).all()
    assert not got[lens == 0].any(), "a row with no context reads zeros"
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_kernel_is_chosen_by_shape_and_backend():
    """``auto`` takes the portable path off the TPU; ``flash`` forced on
    a shape the kernel cannot tile says so instead of falling back."""
    q = jnp.zeros((2, 4, 16))
    kc = jnp.zeros((3, 4, 2, 16))
    tables, lens = jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32)
    assert not kernel_supported(q.shape, kc.shape, kc.dtype)
    assert kernel_supported((16, 32, 128), (3072, 16, 8, 128), jnp.bfloat16)
    assert not kernel_supported((16, 12, 64), (64, 16, 12, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="does not support"):
        paged_attention(q, kc, kc, tables, lens, 1.0, impl="flash")
    before = attention_impl_traces()
    paged_attention(jnp.zeros((2, 32, 128)), jnp.zeros((3, 16, 8, 128)),
                    jnp.zeros((3, 16, 8, 128)), tables, lens, 1.0)
    after = attention_impl_traces()
    assert after["paged_ref"] == before["paged_ref"] + 1
    assert after["paged_flash"] == before["paged_flash"]


def test_paged_attention_walks_only_to_the_longest_live_context():
    """Pages past the call's longest context are never read: poisoning
    them changes nothing, and neither does a table that ends in a
    ragged chunk (blocks_per_seq not a multiple of the chunk)."""
    bs, bps = 4, 70                         # 280 tokens: 256 + a tail
    lens = np.asarray([5, 0, 9], np.int32)
    tables = np.tile(np.arange(1, bps + 1, dtype=np.int32), (3, 1))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (3, 2, 16))
    kc = jax.random.normal(kk, (bps + 1, bs, 1, 16))
    vc = jax.random.normal(kv, (bps + 1, bs, 1, 16))
    got = paged_attention(q, kc, vc, tables, lens, 0.25)
    want = dense_reference(q, kc, vc, tables, lens, 0.25)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    nan = kc.at[65:].set(jnp.nan)           # the second chunk's pages
    again = paged_attention(q, nan, nan, tables, lens, 0.25)
    assert np.isfinite(np.asarray(again)).all()
    lens_full = np.asarray([5, 0, bps * bs], np.int32)
    got = paged_attention(q, kc, vc, tables, lens_full, 0.25)
    want = dense_reference(q, kc, vc, tables, lens_full, 0.25)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_engine_counts_attended_and_dense_pages():
    """``attn_pages_read`` / ``attn_pages_dense`` grow by what the
    steps' live rows attend to and by what whole tables would cost."""
    cfg = get_config("tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    bs, chunk, lanes, ctx = 4, 8, 2, 32
    eng = DecodeEngine(params, cfg, max_batch=lanes, block_size=bs,
                       max_context=ctx, prefill_chunk=chunk,
                       metrics=ServingMetrics())
    prompt, new = list(range(1, 11)), 4     # 10 tokens: chunks of 8 + 2
    eng.generate([prompt], SamplingParams(max_new_tokens=new))
    pages = lambda n: -(-n // bs)
    # two fused steps prefill positions 0..9 (the second samples the
    # first token), then new - 1 decode-only steps at positions 10..12
    read = sum(pages(p + 1) for p in range(len(prompt)))
    read += sum(pages(len(prompt) + i + 1) for i in range(new - 1))
    dense = (2 * (lanes + chunk) + (new - 1) * lanes) * (ctx // bs)
    assert eng.steps == 2 + new - 1
    assert eng.metrics.attn_pages_read.value() == read
    assert eng.metrics.attn_pages_dense.value() == dense


# ------------------------------------------- the chip's compiler, no chip

@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: its compiler refuses what
    interpret mode cannot see — tiling, VMEM, DMA shapes."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows,hq,hkv,pages,dtype", [
    (16, 32, 8, 3072, "bfloat16"), (32, 32, 8, 3072, "bfloat16"),
    (16, 16, 8, 3072, "float32"),
    (16, 16, 16, 192 * 352, "bfloat16"), (80, 16, 16, 192 * 352, "bfloat16")])
def test_kernel_compiles_for_v5e_at_serving_widths(one_chip, rows, hq, hkv,
                                                   pages, dtype):
    """Mistral / Mixtral (32 query heads on 8 KV heads of 128) and the
    flagship presets (16 on 8), decode-only and fused row counts, the
    benchmark's 3072-page pool of 16-token pages, context 2048; and the
    looped family's (16 heads on 16 KV heads, no grouping) over its pool
    viewed ``[192 slots * 352 pages, ...]``, at 16 and 16 + 64 rows."""
    from jax.experimental.compilation_cache import compilation_cache
    dtype = jnp.dtype(dtype)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((pages, 16, hkv, 128), dtype)
    fn = jax.jit(lambda q, kc, vc, tables, lens: paged_attention(
        q, kc, vc, tables, lens, 128 ** -0.5, impl="flash"))
    # what is compiled for a described chip cannot be read back here
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = fn.lower(sds((rows, hq, 128), dtype), pool, pool,
                            sds((rows, 128), jnp.int32),
                            sds((rows,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    # the pool is read where it lies: no copy of it, no gathered context
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("rows", [64, 192])
def test_packed_pages_compile_for_v5e_with_no_pool_copied(one_chip, rows):
    """The short-convolution family's attention layers at the cell's
    widths: 32 query heads on 8 KV heads of 64, two layers' 10,240 pages
    of 16 tokens in rows of 512, context 8192, decode-only and fused row
    counts. A pool shaped ``[..., 8, 64]`` was given a layout with the
    blocks minor on the device and converted whole on the way in and out
    of every step (2.0 GB of temporaries for the step; 0.02 like this)."""
    from jax.experimental.compilation_cache import compilation_cache
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((2 * 10240, 16, 512), bf16)
    fn = jax.jit(lambda q, kc, vc, tables, lens: paged_attention_packed(
        q, kc, vc, tables, lens, 64 ** -0.5))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = fn.lower(sds((rows, 32, 64), bf16), pool, pool,
                            sds((rows, 512), jnp.int32),
                            sds((rows,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    # a pool is 336 MB: what is made is a chunk's gathered pages
    assert compiled.memory_analysis().temp_size_in_bytes < 300 << 20


# the two dense paged cells' layers at their published widths, two layers
# of them: (family, d_model, heads, KV heads, d_ff, passes, pool pages)
STEP_SHAPES = {
    "mistral-7b": ("llama", 4096, 32, 8, 14336, 1, 3072),
    "ouro-2.6b": ("ouro", 2048, 16, 16, 5632, 4, 352),
}


def _compile_layers_for(one_chip, cfg, lanes, chunk, pages, context):
    """(abstract params as the engine places them, the family's
    ``run_layers`` over ``lanes + chunk`` abstract rows compiled for the
    described chip): the engine's own choices on a chip — the Pallas
    kernels, the pools donated."""
    from jax.experimental.compilation_cache import compilation_cache

    from hadoop_tpu.models import init_params_for
    from hadoop_tpu.serving import families
    family = families.family_for(cfg, {})
    bs, bps = 16, context // 16
    t = lanes + chunk

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda k: family.place_weights(init_params_for(cfg)(k, cfg), False),
        jax.random.PRNGKey(0)))
    pools = tuple(sds((slots, pages) + tuple(page), cfg.jax_dtype)
                  for slots, page in family.pools(bs))
    lane = family.lane_state(lanes)
    lane = None if lane is None else sds(lane, cfg.jax_dtype)
    i32 = jnp.int32

    def step(params, pools, lane, h, pos, blk, off, lens, tables, tables_s,
             c):
        cos, sin = family.rope_tables()
        return family.run_layers(params, h, pools, lane, {
            "pos": pos, "blk": blk, "off": off, "active": lens > 0,
            "lens": lens, "tables": tables, "tables_s": tables_s,
            "B": lanes, "G": 1, "block": bs, "cos": cos, "sin": sin,
            "chunk_slot": c[0] if chunk else None,
            "chunk_n": c[1] if chunk else None})

    # what is compiled for a described chip cannot be read back here
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            lowered = jax.jit(step, donate_argnums=(1,)).lower(
                params, pools, lane, sds((t, cfg.d_model), cfg.jax_dtype),
                sds((t,), i32), sds((t,), i32), sds((t,), i32),
                sds((t,), i32), sds((t, bps), i32), sds((lanes, bps), i32),
                sds((2,), i32))
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return params, compiled


@pytest.mark.parametrize("chunk", [0, 64], ids=["decode", "fused"])
@pytest.mark.parametrize("model", sorted(STEP_SHAPES))
def test_the_step_relays_no_projection_weight_on_v5e(one_chip, model, chunk):
    """The family's layers over abstract arguments, the tree placed as
    the engine places it (``place_weights``), compiled for the described
    chip: no ``copy`` of a stacked or sliced projection weight, and fewer
    temporaries than one stack. As loaded, ``(x @ wq).reshape(t, hq,
    dh)`` wants ``wq`` K-minor: the looped family's step relaid three
    whole stacks a step (hoisted out of both loops, their results
    temporaries), the plain one a layer's ``wq``, ``wk``, ``wv`` in VMEM
    in every layer."""
    fam, d, hq, hkv, dff, passes, pages = STEP_SHAPES[model]
    base = get_config("tiny-ouro" if fam == "ouro" else "tiny")
    cfg = dataclasses.replace(
        base, d_model=d, n_heads=hq, n_kv_heads=hkv, d_ff=dff, n_layers=2,
        n_passes=passes, max_seq=2048, dtype="bfloat16")
    params, compiled = _compile_layers_for(one_chip, cfg, 16, chunk, pages,
                                           2048)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layers = params["layers"]
    assert "wqkv" in layers and "wq" not in layers
    weights = {(n,) + tuple(dims)
               for w in (layers["wqkv"], layers["wo"])
               for n in (1, w.shape[0])
               for dims in (w.shape[1:], w.shape[:0:-1])}
    # the loaded tree's, which the parent relaid
    weights |= {(n, d, width) for n in (1, 2)
                for width in (hq * cfg.head_dim, hkv * cfg.head_dim)}
    copied = {tuple(int(v) for v in m.split(","))
              for m in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)}
    assert not copied & weights, copied & weights
    assert compiled.memory_analysis().temp_size_in_bytes \
        < layers["wo"].size * layers["wo"].dtype.itemsize


# the two held-expert cells' layers at their published widths, two expert
# layers and no dense one: (preset, the widths, lanes, chunk, pool pages,
# context, the most temporaries in MB). One leaf of a layer's experts is
# 403 MB in the agent cell and 470 MB in longdoc; longdoc's attention
# makes 137 (decode-only) / 259 MB (fused) of its own, with or without
# experts (the index scores, the gathered latents, ``wo``)
EXPERT_STEP_SHAPES = {
    "lfm2-24b-a2b": ("tiny-lfm2", dict(
        d_model=2048, n_heads=32, n_kv_heads=8, d_ff=11776, n_experts=64,
        n_routed_experts=64, top_k=4, d_ff_expert=1536,
        layer_types=("full_attention", "conv")), 64, 128, 1024, 8192, 64),
    "deepseek-v3.2": ("tiny-dsv32", dict(
        d_model=7168, n_heads=128, d_ff=18432, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, index_n_heads=64, index_head_dim=128,
        index_topk=2048, n_experts=16, n_routed_experts=256, top_k=8,
        n_group=8, topk_group=4, d_ff_expert=2048,
        rope_original_max_seq=4096), 32, 256, 1024, 36864, 259 + 64),
}


@pytest.mark.parametrize("fused", [False, True], ids=["decode", "fused"])
@pytest.mark.parametrize("model", sorted(EXPERT_STEP_SHAPES))
def test_the_step_copies_no_expert_stack_on_v5e(one_chip, model, fused):
    """Both held-expert families' ``run_layers`` over abstract arguments
    at the cells' widths (64 / 192 and 32 / 288 rows), compiled for the
    described chip: the grouped kernel is there, no ``copy`` or
    ``dynamic-slice`` makes an expert stack or one layer's experts, and
    the temporaries stay under one layer's leaf. A layer's experts
    sliced from the stack and handed to the kernel are materialised:
    three such copies a layer."""
    preset, widths, lanes, chunk, pages, context, temp_mb = \
        EXPERT_STEP_SHAPES[model]
    cfg = dataclasses.replace(
        get_config(preset), n_layers=2, n_dense_layers=0, max_seq=context,
        dtype="bfloat16", **widths)
    params, compiled = _compile_layers_for(
        one_chip, cfg, lanes, chunk if fused else 0, pages, context)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    experts = params["moe_layers"]
    assert experts["w_gate"].shape == (2, cfg.n_experts, cfg.d_model,
                                       cfg.d_ff_expert)
    stacks = {tuple(lead) + tuple(dims)
              for shape in (experts["w_gate"].shape, experts["w_down"].shape)
              for lead in ((2, shape[1]), (2 * shape[1],), (1, shape[1]),
                           (shape[1],))
              for dims in (shape[2:], shape[:1:-1])}
    made = {tuple(int(v) for v in m.split(","))
            for m in re.findall(
                r"= \w+\[([\d,]+)\]\S* (?:copy|dynamic-slice)\(", text)}
    assert not made & stacks, made & stacks
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mb << 20
