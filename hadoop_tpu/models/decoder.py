"""Functional decoder-only transformer core.

Design (TPU-first):

- **Layer-stacked parameters**: every per-layer weight is one array with a
  leading ``n_layers`` dim. The single-device path runs layers under
  ``lax.scan`` (one compiled layer body); the pipeline-parallel path shards
  the same leading dim over the ``pp`` mesh axis. No per-layer Python
  objects, no dynamic shapes.
- **One body, many placements**: ``layer_forward`` takes a ``ParallelCtx``
  naming the mesh axes it is running under. With all axes ``None`` it is
  the single-device reference; inside ``shard_map`` the same code inserts
  the Megatron-style collectives (all-gather/reduce-scatter for sequence
  parallelism, psum after row-parallel matmuls, all-to-all for experts).
  This is the tensor-parallel semantics of Megatron's
  ColumnParallelLinear/RowParallelLinear re-expressed as SPMD collectives
  over ICI rather than NCCL calls.

Weight layout notes: qkv/gate/up projections are column-parallel (output
dim sharded over ``tp``), out/down projections are row-parallel (input dim
sharded, psum after) — so inside shard_map the local arrays are simply the
narrow slices and the math is unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from hadoop_tpu.models.config import ModelConfig, refuse_training
from hadoop_tpu.ops import (apply_rope, causal_attention, gelu, layer_norm,
                            rms_norm, rope_frequencies, swiglu)


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Names of the mesh axes the current trace runs under (None = absent).

    tp_axis:  tensor parallelism (heads / ff / vocab sharding, psum).
    megatron_sp: sequence parallelism on the tp axis (activations between
        blocks are sequence-sharded; all-gather in, reduce-scatter out).
    ep_axis:  expert parallelism (experts sharded, all_to_all dispatch).
    ring_axis: context parallelism (sequence sharded end-to-end, ring
        attention rotates K/V with ppermute).
    """
    tp_axis: Optional[str] = None
    tp_size: int = 1
    megatron_sp: bool = False
    ep_axis: Optional[str] = None
    ep_size: int = 1
    ring_axis: Optional[str] = None
    ring_size: int = 1
    # context-parallel attention strategy on ring_axis: "ring" rotates
    # K/V with ppermute; "ulysses" transposes seq<->head sharding with
    # one all_to_all pair (parallel/ulysses.py)
    sp_mode: str = "ring"
    # row-parallel matmuls issue their tp reduction in this many chunks
    # so the collective overlaps the matmul (ops/collective_matmul.py);
    # 1 = the classic single whole-tensor psum/psum_scatter
    tp_overlap_chunks: int = 1
    # relaxed parity tier (parallel/lowp): when set, row-parallel tp
    # reduces quantize their wire payload to this codec ("int8"|"fp8")
    # — values become allclose, never bitwise. None (the default) is
    # the bitwise tier: no lowp code is reachable.
    relaxed_codec: Optional[str] = None
    # relaxed tier only: chunk the row-parallel MATMUL itself so each
    # chunk's product pipelines against its reduce (T3-style). The
    # backward's weight-grad contraction reassociates — illegal under
    # the bitwise contract, covered by the lowp loss-curve guard.
    relaxed_chunk_matmul: bool = False
    # relaxed tier only: per-layer TP activation-sync schedule
    # (partially synchronized activations, parallel/lowp/syncpolicy.py)
    # — a tuple of per-layer modes ("sync"|"skip"|"stale"), one per
    # layer this trace runs (resolve_schedule output). None (the
    # default) = every layer syncs, the bitwise graph; a tuple must
    # only ever be set under parallel.parity=relaxed (enforced by the
    # make_train_step wiring + the tpulint relaxed-gated checker on the
    # syncpolicy entry points the schedule routes to).
    relaxed_sync: Optional[tuple] = None
    # relaxed tier only (serving.parity): quantized resident weights —
    # matmul leaves may arrive as weight-plane qtensors and route
    # through the dequantizing matmul (serving/weightplane.py qdot).
    # False (the default) is the bitwise tier: quantized leaves are a
    # wiring bug and fail loudly at the first shape access.
    relaxed_qweights: bool = False

    @property
    def seq_offset_fn(self):
        return None


SINGLE = ParallelCtx()


# ----------------------------------------------------------------- params

def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    """Initialize the full (unsharded) parameter pytree."""
    refuse_training(cfg, "models.decoder.init_params")
    k_embed, k_layers, k_head, k_pos = jax.random.split(rng, 4)
    dt = cfg.jax_dtype
    D, L, F, V = cfg.d_model, cfg.n_layers, cfg.d_ff, cfg.vocab_size
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def winit(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    ks = jax.random.split(k_layers, 16)
    layers: Dict[str, jnp.ndarray] = {
        "attn_norm_w": jnp.ones((L, D), dt),
        "wq": winit(ks[0], (L, D, Hq * Dh), D),
        "wk": winit(ks[1], (L, D, Hkv * Dh), D),
        "wv": winit(ks[2], (L, D, Hkv * Dh), D),
        "wo": winit(ks[3], (L, Hq * Dh, D), Hq * Dh),
        "mlp_norm_w": jnp.ones((L, D), dt),
    }
    if not cfg.use_rmsnorm:
        layers["attn_norm_b"] = jnp.zeros((L, D), dt)
        layers["mlp_norm_b"] = jnp.zeros((L, D), dt)
    if cfg.is_moe:
        E = cfg.n_experts
        layers["router"] = winit(ks[4], (L, D, E), D)
        layers["w_gate"] = winit(ks[5], (L, E, D, F), D)
        layers["w_up"] = winit(ks[6], (L, E, D, F), D)
        layers["w_down"] = winit(ks[7], (L, E, F, D), F)
    elif cfg.use_swiglu:
        layers["w_gate"] = winit(ks[5], (L, D, F), D)
        layers["w_up"] = winit(ks[6], (L, D, F), D)
        layers["w_down"] = winit(ks[7], (L, F, D), F)
    else:
        layers["w_in"] = winit(ks[5], (L, D, F), D)
        layers["b_in"] = jnp.zeros((L, F), dt)
        layers["w_out"] = winit(ks[6], (L, F, D), F)
        layers["b_out"] = jnp.zeros((L, D), dt)

    params: Dict[str, Any] = {
        "embed": winit(k_embed, (V, D), D),
        "layers": layers,
        "final_norm_w": jnp.ones((D,), dt),
    }
    if not cfg.use_rmsnorm:
        params["final_norm_b"] = jnp.zeros((D,), dt)
    if not cfg.use_rope:
        params["pos_embed"] = winit(k_pos, (cfg.max_seq, D), D)
    if not cfg.tie_embeddings:
        params["lm_head"] = winit(k_head, (D, V), D)
    return params


def count_params(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ------------------------------------------------------------------ norms

def _norm(x, w, b, cfg: ModelConfig):
    if cfg.use_rmsnorm:
        return rms_norm(x, w, cfg.norm_eps)
    return layer_norm(x, w, b, cfg.norm_eps)


# -------------------------------------------------- quantized weight seam

def _out_features(w) -> int:
    """Output width of a projection weight. Quantized leaves store
    transposed-and-grouped ({"q": int8 [.., N, G, gs], "s": [.., N, G]})
    so the output dim sits third-from-last."""
    if isinstance(w, dict):
        return w["q"].shape[-3]
    return w.shape[-1]


def _relaxed_qready(w, ctx: ParallelCtx) -> bool:
    """Should this matmul route through the weight plane's dequantizing
    contraction? Only when the trace opted in AND the leaf actually
    carries the quantized layout — and never under tp: the qtensor is
    the unsharded weight, so a tp trace would contract the full output
    dim on every rank and then psum, double-counting."""
    if not ctx.relaxed_qweights:
        return False
    from hadoop_tpu.serving.weightplane import is_qtensor
    if not is_qtensor(w):
        return False
    if ctx.tp_axis is not None:
        raise NotImplementedError(
            "quantized resident weights compose with tp-free meshes "
            "only (the serving engine / longctx CP); shard the f32 "
            "view under tensor parallelism")
    return True


# -------------------------------------------------------------- attention

@jax.named_scope("attn")
def _attention_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx, cos, sin,
                     return_kv: bool = False, relaxed_sync=None):
    """Pre-norm attention with residual. x: [B, S_local, D].

    ``return_kv=True`` also returns this layer's post-RoPE ``(k, v)``
    shard ([B, S_local, Hkv_local, Dh]) — the long-context serving
    plane streams exactly these rows into the tiered KV store, and the
    layout matches what the decode engine scatters into its paged pool
    (KV is cached post-rotation there too).

    ``relaxed_sync`` (relaxed tier only): this block's scheduled
    reduce behavior (a ``syncpolicy.SiteSync``). When given, the block
    returns ``(y, corr)`` where ``corr`` is the new stale correction
    (None unless mode == "stale")."""
    resid = x
    h = _norm(x, lp["attn_norm_w"], lp.get("attn_norm_b"), cfg)

    if ctx.megatron_sp:
        # sequence-sharded activations -> full sequence for attention
        h = jax.lax.all_gather(h, ctx.tp_axis, axis=1, tiled=True)

    B, S, _ = h.shape
    # local head counts (already sharded if tp): infer from weight shapes
    hq_local = _out_features(lp["wq"]) // cfg.head_dim
    hkv_local = _out_features(lp["wk"]) // cfg.head_dim
    if _relaxed_qready(lp["wq"], ctx):
        from hadoop_tpu.serving.weightplane import qdot
        q = qdot(h, lp["wq"]).reshape(B, S, hq_local, cfg.head_dim)
        k = qdot(h, lp["wk"]).reshape(B, S, hkv_local, cfg.head_dim)
        v = qdot(h, lp["wv"]).reshape(B, S, hkv_local, cfg.head_dim)
    else:
        q = (h @ lp["wq"]).reshape(B, S, hq_local, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(B, S, hkv_local, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(B, S, hkv_local, cfg.head_dim)

    if cfg.use_rope:
        if ctx.ring_axis is not None:
            offs = jax.lax.axis_index(ctx.ring_axis) * S
            positions = offs + jnp.arange(S)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        else:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

    if ctx.ring_axis is not None:
        if ctx.sp_mode == "ulysses":
            from hadoop_tpu.parallel.ulysses import ulysses_attention
            attn = ulysses_attention(q, k, v, axis_name=ctx.ring_axis,
                                     axis_size=ctx.ring_size)
        else:
            from hadoop_tpu.parallel.ring_attention import ring_attention
            attn = ring_attention(q, k, v, axis_name=ctx.ring_axis,
                                  axis_size=ctx.ring_size)
    else:
        attn = causal_attention(q, k, v)

    from hadoop_tpu.ops.collective_matmul import row_parallel_project
    attn_flat = attn.reshape(B, S, hq_local * cfg.head_dim)
    if _relaxed_qready(lp["wo"], ctx):
        # tp-free trace (enforced above): the row-parallel reduce is
        # the identity, so the dequantizing matmul substitutes directly
        from hadoop_tpu.serving.weightplane import qdot
        out = qdot(attn_flat, lp["wo"])
    else:
        out = row_parallel_project(attn_flat, lp["wo"], ctx,
                                   relaxed_sync=relaxed_sync)
    corr = None
    if relaxed_sync is not None and relaxed_sync.mode == "stale":
        out, corr = out
    y = resid + out.astype(resid.dtype)
    if return_kv:
        return y, (k, v)
    if relaxed_sync is not None:
        return y, corr
    return y


# -------------------------------------------------------------------- mlp

@jax.named_scope("mlp")
def _mlp_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx,
               relaxed_sync=None):
    from hadoop_tpu.ops.collective_matmul import (reduce_row_parallel,
                                                  row_parallel_project)
    resid = x
    h = _norm(x, lp["mlp_norm_w"], lp.get("mlp_norm_b"), cfg)
    if ctx.megatron_sp:
        h = jax.lax.all_gather(h, ctx.tp_axis, axis=1, tiled=True)
    if cfg.is_moe:
        # the expert matmuls stay whole inside the dispatch; the final
        # row-parallel reduce visible here chunks like every other
        # (reduce-only chunking is bit-exact in both directions)
        from hadoop_tpu.models.moe import moe_mlp
        out = reduce_row_parallel(moe_mlp(h, lp, cfg, ctx), ctx,
                                  relaxed_sync=relaxed_sync)
    elif cfg.use_swiglu:
        if _relaxed_qready(lp["w_down"], ctx):
            from hadoop_tpu.serving.weightplane import qdot
            out = qdot(swiglu(qdot(h, lp["w_gate"]),
                              qdot(h, lp["w_up"])), lp["w_down"])
        else:
            out = row_parallel_project(
                swiglu(h @ lp["w_gate"], h @ lp["w_up"]), lp["w_down"],
                ctx, relaxed_sync=relaxed_sync)
    else:
        if _relaxed_qready(lp["w_out"], ctx):
            from hadoop_tpu.serving.weightplane import qdot
            out = qdot(gelu(qdot(h, lp["w_in"]) + lp["b_in"]),
                       lp["w_out"]) + lp["b_out"]
        else:
            out = row_parallel_project(
                gelu(h @ lp["w_in"] + lp["b_in"]), lp["w_out"], ctx,
                bias=lp["b_out"], relaxed_sync=relaxed_sync)
    corr = None
    if relaxed_sync is not None and relaxed_sync.mode == "stale":
        out, corr = out
    y = resid + out.astype(resid.dtype)
    if relaxed_sync is not None:
        return y, corr
    return y


# ------------------------------------------------------------------ layer

def layer_forward(x, lp, cfg: ModelConfig, ctx: ParallelCtx, cos, sin,
                  relaxed_sync=None):
    """One transformer block. lp: this layer's weights (no leading L dim).

    ``relaxed_sync`` (relaxed tier only): a ``(attn, mlp)`` pair of
    ``syncpolicy.SiteSync`` naming each reduce site's scheduled mode;
    when given the layer returns ``(x, (attn_corr, mlp_corr))`` — the
    corrections are None except in stale mode."""
    if relaxed_sync is None:
        x = _attention_block(x, lp, cfg, ctx, cos, sin)
        x = _mlp_block(x, lp, cfg, ctx)
        return x
    a_sync, m_sync = relaxed_sync
    x, ca = _attention_block(x, lp, cfg, ctx, cos, sin,
                             relaxed_sync=a_sync)
    x, cm = _mlp_block(x, lp, cfg, ctx, relaxed_sync=m_sync)
    return x, (ca, cm)


def layer_forward_kv(x, lp, cfg: ModelConfig, ctx: ParallelCtx, cos, sin):
    """One transformer block, also returning the layer's post-RoPE
    ``(k, v)`` shard — the KV-capturing twin of ``layer_forward`` the
    long-context prefill plane scans with."""
    x, kv = _attention_block(x, lp, cfg, ctx, cos, sin, return_kv=True)
    return _mlp_block(x, lp, cfg, ctx), kv


def run_layers_kv(x, layers, cfg: ModelConfig, ctx: ParallelCtx, cos, sin):
    """scan the layer stack over x, collecting every layer's post-RoPE
    K/V as scan outputs. Returns ``(h, (k, v))`` with k/v shaped
    ``[L, B, S_local, Hkv_local, Dh]`` — the prefill side of the
    long-context serving plane (``serving/longctx``), which slices
    these into block-sized chunks for the tiered KV store. No remat:
    inference-only (nothing differentiates through it)."""
    from hadoop_tpu.ops.vma import pvary_to, tree_vma, vma_of

    def step(h, lp):
        h2, kv = layer_forward_kv(h, lp, cfg, ctx, cos, sin)
        return h2, kv

    from hadoop_tpu.obs.comm import comm_scale
    with comm_scale(jax.tree_util.tree_leaves(layers)[0].shape[0]):
        out, kvs = jax.lax.scan(
            step, pvary_to(x, vma_of(x) | tree_vma(layers)), layers)
    return out, kvs


def _remat_policy(remat):
    """THE remat-mode → checkpoint-policy mapping (None = default
    save-nothing policy). Both layer-loop paths (the scan-fused
    unscheduled body and the scheduled segment bodies) derive their
    wrapping from this one table so the policies can never fork."""
    if remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def _wrap_remat(f, remat):
    """checkpoint-wrap a layer body that closes over its static args."""
    if not remat:
        return f
    pol = _remat_policy(remat)
    if pol is not None:
        return jax.checkpoint(f, policy=pol)
    return jax.checkpoint(f)


def run_layers(x, layers, cfg: ModelConfig, ctx: ParallelCtx, cos, sin,
               remat=False, sync_state=None):
    """scan the (local slice of the) layer stack over x.

    ``remat``: False — save all activations; True/"full" — recompute the
    whole layer in backward (minimum memory, ~33% more FLOPs); "dots" —
    selective: save matmul outputs, recompute cheap elementwise/norm ops
    (near-zero FLOP overhead, most of the memory win). The selective
    policy is the TPU-idiomatic middle ground: MXU results are kept,
    VPU work is replayed.

    ``ctx.relaxed_sync`` (relaxed tier only) switches to the scheduled
    layer loop: contiguous equal-mode layer runs scan with that mode's
    reduce behavior, stale layers unroll so each consumes/emits its own
    correction. ``sync_state`` (required iff the schedule has stale
    layers): ``[n_stale, 2, *x.shape]`` — the previous step's reduced
    residual corrections, one ``[2(attn,mlp), ...]`` slab per stale
    layer in layer order. When ``sync_state`` is passed the function
    returns ``(out, new_sync_state)``.
    """
    from hadoop_tpu.ops.vma import pvary_to, tree_vma, vma_of
    sched = ctx.relaxed_sync if ctx.tp_axis is not None else None
    if sched is not None and all(m == "sync" for m in sched):
        sched = None
    if sched is None:
        body = layer_forward
        if remat:  # cfg, ctx are static pytrees
            pol = _remat_policy(remat)
            body = jax.checkpoint(
                body, static_argnums=(2, 3),
                **({"policy": pol} if pol is not None else {}))

        def step(h, lp):
            return body(h, lp, cfg, ctx, cos, sin), None

        # the carry leaves the scan varying over every axis the layer
        # weights vary over; the initial carry must match. comm_scale:
        # the scan traces ONE body for n layers — scale its trace-time
        # comm records so the per-step ledger profile counts per-step
        # hardware executions, not per-trace appearances
        from hadoop_tpu.obs.comm import comm_scale
        n_local = jax.tree_util.tree_leaves(layers)[0].shape[0]
        with comm_scale(n_local):
            out, _ = jax.lax.scan(
                step, pvary_to(x, vma_of(x) | tree_vma(layers)), layers)
        return (out, sync_state) if sync_state is not None else out

    # ---- scheduled layer loop (parallel.lowp.sync.*, relaxed tier) ----
    from hadoop_tpu.parallel.lowp.syncpolicy import SiteSync
    n_local = jax.tree_util.tree_leaves(layers)[0].shape[0]
    if len(sched) != n_local:
        raise ValueError(
            f"sync schedule names {len(sched)} layers but this trace "
            f"runs {n_local} (per-layer schedules compose with the flat "
            f"layer stack only — pp plans are refused at train-step "
            f"build)")
    if any(m == "stale" for m in sched) and sync_state is None:
        raise ValueError("stale sync schedule needs sync_state (the "
                         "previous step's corrections)")

    def plain_body(mode):
        pair = (SiteSync(mode), SiteSync(mode))

        def f(h, lp):
            y, _ = layer_forward(h, lp, cfg, ctx, cos, sin,
                                 relaxed_sync=pair)
            return y
        return _wrap_remat(f, remat)

    def stale_body():
        def f(h, lp, corr2):
            pair = (SiteSync("stale", corr2[0]),
                    SiteSync("stale", corr2[1]))
            return layer_forward(h, lp, cfg, ctx, cos, sin,
                                 relaxed_sync=pair)
        return _wrap_remat(f, remat)

    h = x
    stale_corrs = []
    si = 0
    i = 0
    while i < n_local:
        mode = sched[i]
        j = i
        while j < n_local and sched[j] == mode:
            j += 1
        seg = jax.tree_util.tree_map(lambda a: a[i:j], layers)
        if mode == "stale":
            # unrolled: each stale layer consumes ITS previous-step
            # correction and emits this step's
            fn = stale_body()
            for k in range(j - i):
                lp = jax.tree_util.tree_map(lambda a, _k=k: a[_k], seg)
                h, (ca, cm) = fn(h, lp, sync_state[si])
                stale_corrs.append(jnp.stack([ca, cm]))
                si += 1
        else:
            fn = plain_body(mode)

            def seg_step(hh, lp, _fn=fn):
                return _fn(hh, lp), None

            from hadoop_tpu.obs.comm import comm_scale
            with comm_scale(j - i):
                h, _ = jax.lax.scan(
                    seg_step, pvary_to(h, vma_of(h) | tree_vma(seg)),
                    seg)
        i = j
    if sync_state is not None:
        new_state = jnp.stack(stale_corrs) if stale_corrs else sync_state
        return h, new_state
    return h


# ------------------------------------------------------------- embeddings

@jax.named_scope("embed")
def embed_tokens(params, tokens, cfg: ModelConfig, ctx: ParallelCtx):
    """Token (+ position) embedding; vocab-parallel under tp.

    tokens: [B, S_local] int32. Returns [B, S_local, D] (sequence-scattered
    if megatron_sp).
    """
    embed = params["embed"]
    if ctx.tp_axis is not None:
        # vocab-parallel: each shard holds rows [lo, lo+Vl)
        vl = embed.shape[0]
        lo = jax.lax.axis_index(ctx.tp_axis) * vl
        local_ids = tokens - lo
        ok = (local_ids >= 0) & (local_ids < vl)
        h = jnp.where(ok[..., None],
                      embed[jnp.clip(local_ids, 0, vl - 1)], 0)
        if ctx.megatron_sp:
            h = jax.lax.psum_scatter(h.astype(jnp.float32), ctx.tp_axis,
                                     scatter_dimension=1, tiled=True)
            h = h.astype(embed.dtype)
        else:
            h = jax.lax.psum(h.astype(jnp.float32),
                             ctx.tp_axis).astype(embed.dtype)
    elif _relaxed_qready(embed, ctx):
        from hadoop_tpu.serving.weightplane import qrows
        h = qrows(embed, tokens, cfg.jax_dtype)
    else:
        h = embed[tokens]
    if not cfg.use_rope:
        S = tokens.shape[1]
        if ctx.ring_axis is not None:
            offs = jax.lax.axis_index(ctx.ring_axis) * S
            pos = params["pos_embed"][offs + jnp.arange(S)]
        elif ctx.megatron_sp:
            # h is sequence-scattered: add the matching pos-embed slice
            sl = S // ctx.tp_size
            offs = jax.lax.axis_index(ctx.tp_axis) * sl
            pos = jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], offs, sl, axis=0)
            return h + pos[None]
        else:
            pos = params["pos_embed"][:S]
        h = h + pos[None]
    return h


def final_hidden(params, h, cfg: ModelConfig, ctx: ParallelCtx = None):
    """Final norm (+ Megatron exit gather): the hidden states the LM head
    consumes. Split out so losses can fuse head-matmul + CE chunked
    (ops.cross_entropy.chunked_lm_cross_entropy) without a full [B,S,V]
    logits tensor ever existing."""
    ctx = ctx or SINGLE
    h = _norm(h, params["final_norm_w"], params.get("final_norm_b"), cfg)
    if ctx.megatron_sp:
        h = jax.lax.all_gather(h, ctx.tp_axis, axis=1, tiled=True)
    return h


def head_matrix(params, cfg: ModelConfig, dtype=None):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return head.astype(dtype) if dtype is not None else head


def lm_logits(params, h, cfg: ModelConfig, ctx: ParallelCtx = None):
    """Final norm + LM head. Under tp the head weight is vocab-sharded and
    the returned logits are the local vocab slice. Under Megatron sequence
    parallelism the final norm runs on the sequence shard and the full
    sequence is gathered just before the head (Megatron's exit gather)."""
    h = final_hidden(params, h, cfg, ctx)
    return h @ head_matrix(params, cfg, h.dtype)


# ---------------------------------------------------------------- forward

def forward_hidden(params, tokens, cfg: ModelConfig,
                   ctx: ParallelCtx = SINGLE, remat: bool = False,
                   sync_state=None):
    """Embed + layer stack (everything before the LM head).

    ``sync_state`` (relaxed stale sync schedules only) threads the
    previous step's corrections through ``run_layers``; when given the
    return is ``(h, new_sync_state)``."""
    refuse_training(cfg, "models.decoder.forward_hidden")
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    h = embed_tokens(params, tokens, cfg, ctx)
    if sync_state is not None:
        return run_layers(h, params["layers"], cfg, ctx, cos, sin,
                          remat=remat, sync_state=sync_state)
    return run_layers(h, params["layers"], cfg, ctx, cos, sin, remat=remat)


def forward(params, tokens, cfg: ModelConfig, ctx: ParallelCtx = SINGLE,
            remat: bool = False):
    """Full forward to logits. Single-device when ctx is SINGLE; inside
    shard_map the ctx axes drive collectives. (Pipeline parallelism wraps
    run_layers differently — see hadoop_tpu.parallel.pipeline.)"""
    h = forward_hidden(params, tokens, cfg, ctx, remat=remat)
    return lm_logits(params, h, cfg, ctx)
