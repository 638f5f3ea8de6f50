"""The sharded training step: one shard_map over the whole mesh.

Megatron-style *manual* SPMD: the body sees local shards, and every
cross-device exchange is an explicit XLA collective over ICI —

- tp   : psum after row-parallel matmuls, vocab-parallel CE psums
- sp(tp): all_gather / psum_scatter of sequence-sharded activations
- pp   : ppermute microbatch rotation (GPipe schedule; autodiff produces
         the backward interleave)
- ep   : all_to_all expert dispatch
- sp   : ppermute K/V ring (ring attention)
- dp/ep: psum of gradients
- grads + fused AdamW run on local shards (distributed optimizer)

Gradient reduction rule: a leaf's gradient is psum'd over every *data*
axis (dp, ep, sp, plus pp always and tp only under sequence parallelism —
the cases where ranks see different tokens or stages) that does NOT
appear in the leaf's PartitionSpec; axes in the spec mean the leaf is
sharded there and its gradient is already local-complete.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from hadoop_tpu.models.config import ModelConfig, refuse_training
from hadoop_tpu.models.decoder import (embed_tokens, final_hidden,
                                       forward_hidden, head_matrix,
                                       run_layers)
from hadoop_tpu.models.decoder import init_params as _init_params
from hadoop_tpu.ops import rope_frequencies
from hadoop_tpu.ops.cross_entropy import chunked_lm_cross_entropy
from hadoop_tpu.parallel.mesh import AXES, MeshPlan, param_specs, \
    shard_params
from hadoop_tpu.parallel.optimizer import (AdamWState, adamw_init,
                                           adamw_update, zero1_update)
from hadoop_tpu.parallel.lowp import BITWISE_PARITY, ParityConfig
from hadoop_tpu.parallel.overlap import (DEFAULT_OVERLAP, OverlapConfig,
                                         bucketed_psum,
                                         bucketed_psum_scatter)

def _smap(f, mesh, in_specs, out_specs):
    # check_vma=True (the default) is load-bearing for correctness: the
    # varying-manual-axes tracking is what makes collective TRANSPOSES
    # insert the cotangent psums for replicated values used in
    # rank-divergent pathways (residual streams feeding vocab-sliced
    # heads, embeddings feeding only stage 0, ...). With it, gradients
    # of replicated params come out fully reduced over every axis whose
    # ranks see different data — the only manual step left is the
    # mean-vs-sum scaling (see make_train_step).
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def _spec_axes(spec) -> set:
    names = set()
    for part in spec:
        if part is None:
            continue
        if isinstance(part, tuple):
            names.update(part)
        else:
            names.add(part)
    return names


def _spec_axes_ordered(spec) -> list:
    names = []
    for part in spec:
        if part is None:
            continue
        for a in (part if isinstance(part, tuple) else (part,)):
            names.append(a)
    return names


def zero1_layout(cfg: ModelConfig, plan: MeshPlan):
    """Per-leaf ZeRO-1 state layout: (data axes partitioning the state,
    global state shape, state PartitionSpec). State leaves are
    ``(*spec_axis_sizes, *data_axis_sizes, K)`` arrays whose spec names
    every leading axis, so the per-rank piece is one (K,) slice —
    optimizer memory ÷ (dp·ep) for replicated leaves."""
    import numpy as np
    shapes = jax.eval_shape(
        lambda: _init_params(jax.random.PRNGKey(0), cfg))
    specs = param_specs(cfg, plan)
    sizes = dict(zip(AXES, (plan.dp, plan.pp, plan.tp, plan.ep, plan.sp)))
    data_axes = plan.batch_axes

    class _Leaf:  # opaque (not a pytree) so tree_map treats it atomically
        __slots__ = ("z_ax", "shape", "spec")

        def __init__(self, z_ax, shape, spec):
            self.z_ax, self.shape, self.spec = z_ax, shape, spec

    def leaf(sh, spec):
        spec_ax = _spec_axes_ordered(spec)
        z_ax = tuple(a for a in data_axes if a not in spec_ax)
        denom = int(np.prod([sizes[a] for a in spec_ax])) if spec_ax else 1
        local = max(1, int(np.prod(sh.shape)) // denom)
        z = int(np.prod([sizes[a] for a in z_ax])) if z_ax else 1
        k = (local + z - 1) // z
        state_shape = tuple(sizes[a] for a in spec_ax) + \
            tuple(sizes[a] for a in z_ax) + (k,)
        return _Leaf(z_ax, state_shape, P(*spec_ax, *z_ax, None))

    layout = jax.tree_util.tree_map(leaf, shapes, specs)
    axes_tree = jax.tree_util.tree_map(lambda lo: lo.z_ax, layout)
    shape_tree = jax.tree_util.tree_map(lambda lo: lo.shape, layout)
    spec_tree = jax.tree_util.tree_map(lambda lo: lo.spec, layout)
    return axes_tree, shape_tree, spec_tree, sizes


@jax.named_scope("head_xent")
def _loss_from_h(params, h, targets, cfg: ModelConfig, ctx,
                 chunk: int = 256):
    """LM loss from pre-head hidden states, chunked over the sequence so
    the full [B,S,V] logits never materialize (the batch-size ceiling on
    large-vocab models — see chunked_lm_cross_entropy)."""
    h = final_hidden(params, h, cfg, ctx)
    head = head_matrix(params, cfg, h.dtype)
    if ctx.tp_axis is not None:
        return chunked_lm_cross_entropy(
            h, head, targets, chunk, axis_name=ctx.tp_axis,
            vocab_shard_size=cfg.vocab_size // ctx.tp_size)
    return chunked_lm_cross_entropy(h, head, targets, chunk)


def make_train_step(cfg: ModelConfig, plan: MeshPlan, mesh: Mesh, *,
                    lr: float = 3e-4, n_microbatches: int = 1,
                    remat: bool = False, donate: bool = True,
                    optimizer: str = "adamw", zero1: bool = False,
                    pipeline_schedule: str = "1f1b",
                    overlap: Optional[OverlapConfig] = None,
                    parity: Optional[ParityConfig] = None):
    """Build the jitted sharded train step.

    Returns fn(params, opt_state, tokens, targets) ->
    (params, opt_state, metrics) where tokens/targets are global
    [batch, seq] int32 arrays (batch sharded over dp×ep, sequence over sp).

    ``pipeline_schedule`` (used when plan.pp > 1): "1f1b" — the manual
    one-forward-one-backward interleave with pipeline-depth-bounded
    activation memory (parallel.pipeline); "gpipe" — all-forwards scan
    with autodiff-generated backwards (activation liveness grows with
    n_microbatches).

    ``overlap`` (default ON, parallel.overlap.* conf): communication
    overlap — chunked row-parallel tp collectives, bucketed manual-
    schedule gradient reduction (reduce-scattered into the ZeRO-1 slice
    layout when ``zero1``), bucketed ZeRO-1 param reassembly. All of it
    is loss-bit-exact against overlap-off except the zero1 manual-
    schedule (pp>1) grad-norm, whose slice-wise accumulation can move
    the clip scale by an ulp (see parallel/overlap.py).

    ``parity`` (default BITWISE, ``parallel.parity`` conf): the parity
    tier (parallel/lowp). Bitwise builds exactly the graph this
    function always built — no lowp code executes. Relaxed quantizes
    the bucketed gradient/reassembly collectives and the tp reduces
    to int8/fp8 wire payloads and unlocks the true chunked collective
    matmul; correctness is covered by the lowp loss-curve A-B guard
    instead of bit-parity. The relaxed consumers ride the overlap
    pass's bucketed collectives, so they require ``overlap.enabled``
    (the default).
    """
    refuse_training(cfg, "parallel.train.make_train_step")
    if overlap is None:
        overlap = DEFAULT_OVERLAP
    if parity is None:
        parity = BITWISE_PARITY
    if parity.relaxed and not overlap.enabled:
        # silently degrading to bitwise would label bench rows and
        # A-B arms "relaxed" while measuring the bitwise tier
        raise ValueError(
            "parallel.parity=relaxed requires the overlap pass "
            "(parallel.overlap.enabled=true): every relaxed consumer "
            "rides its bucketed/chunked collectives")
    if parity.relaxed:
        # the relaxed consumers live on the overlap pass's bucketed /
        # chunked collectives; build the quant spec once (guarded —
        # under bitwise no lowp module is touched)
        from hadoop_tpu.parallel.lowp.quant import RelaxedQuant
        from hadoop_tpu.parallel.lowp.syncpolicy import resolve_schedule
        _sizes = dict(zip(AXES,
                          (plan.dp, plan.pp, plan.tp, plan.ep, plan.sp)))
        rq_buckets = RelaxedQuant(
            codec=parity.codec, group=parity.group,
            mesh_axis_sizes=_sizes) if parity.quant_buckets else None
        rq_gather = RelaxedQuant(
            codec=parity.codec, group=parity.group,
            mesh_axis_sizes=_sizes) if parity.quant_zero1_gather \
            else None
        relaxed_codec = parity.codec if parity.quant_tp else None
        relaxed_chunk = parity.chunk_matmul
        # per-layer TP sync schedule (syncpolicy.py): resolved once
        # against the layer count; tp=1 plans have no sync to schedule
        # (plan.ctx forces None there too — by construction)
        relaxed_sched = resolve_schedule(
            parity.relaxed_sync, cfg.n_layers,
            off_mode=parity.relaxed_sync_mode) if plan.tp > 1 else None
        if relaxed_sched is not None and \
                all(m == "sync" for m in relaxed_sched):
            relaxed_sched = None
        if relaxed_sched is not None and plan.pp > 1:
            # each pp stage traces only its local layer slice and the
            # schedule indexes GLOBAL layers — refusing loudly beats a
            # schedule that silently applies per-stage
            raise ValueError(
                "parallel.lowp.sync.schedule requires a flat layer "
                "stack (pp=1); pipeline plans trace per-stage layer "
                "slices the global schedule cannot index")
    else:
        rq_buckets = rq_gather = relaxed_codec = None
        relaxed_chunk = False
        relaxed_sched = None
    ctx = plan.ctx(cfg, tp_overlap_chunks=(
        overlap.tp_chunks if overlap.enabled else 1),
        relaxed_codec=relaxed_codec,
        relaxed_chunk_matmul=relaxed_chunk,
        relaxed_sync=relaxed_sched)
    n_stale = sum(m == "stale" for m in (relaxed_sched or ()))
    specs = param_specs(cfg, plan)
    data_spec = P(("dp", "ep"), "sp")

    # Data axes: each rank's local loss covers 1/data_ranks of the global
    # batch. The autodiff objective is effectively sum-over-data-ranks (the
    # vma transpose machinery psums cotangents of replicated params), so
    # gradients of the global *mean* loss need one uniform scale.
    loss_div = plan.dp * plan.ep * plan.sp

    def _reduce_grads(grads):
        if loss_div == 1:
            return grads
        return jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) / loss_div).astype(g.dtype),
            grads)

    def _global_grad_sq(grads):
        def leaf(g, s):
            local = jnp.sum(jnp.square(g.astype(jnp.float32)))
            shard_axes = tuple(sorted(_spec_axes(s)))
            return jax.lax.psum(local, shard_axes) if shard_axes else local
        parts = jax.tree_util.tree_map(leaf, grads, specs)
        return functools.reduce(
            jnp.add, jax.tree_util.tree_leaves(parts))

    # ------------------------------------------------------------ losses

    def flat_loss(params, tokens, targets):
        h = forward_hidden(params, tokens, cfg, ctx, remat=remat)
        return _loss_from_h(params, h, targets, cfg, ctx)

    def pipelined_loss(params, tokens, targets):
        M = n_microbatches
        Pp = plan.pp
        B_l, S = tokens.shape
        tok_mb = tokens.reshape(M, B_l // M, S)
        tgt_mb = targets.reshape(M, B_l // M, S)
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq,
                                    cfg.rope_theta)
        stage = jax.lax.axis_index("pp")
        s_act = S // plan.tp if plan.megatron_sp else S
        perm = [(i, i + 1) for i in range(Pp - 1)]

        def step(recv, t):
            mb_in = jnp.clip(t, 0, M - 1)
            x0 = embed_tokens(params, jnp.take(tok_mb, mb_in, axis=0),
                              cfg, ctx)
            x_in = jnp.where(stage == 0, x0, recv)
            y = run_layers(x_in, params["layers"], cfg, ctx, cos, sin,
                           remat=remat)
            out_i = t - (Pp - 1)
            mb_out = jnp.clip(out_i, 0, M - 1)
            loss_mb = _loss_from_h(
                params, y, jnp.take(tgt_mb, mb_out, axis=0), cfg, ctx)
            take = (stage == Pp - 1) & (out_i >= 0) & (out_i < M)
            loss_t = jnp.where(take, loss_mb, 0.0)
            recv2 = jax.lax.ppermute(y, "pp", perm)
            return recv2, loss_t

        from hadoop_tpu.ops.vma import pvary_to
        from hadoop_tpu.parallel.mesh import AXES
        # activations vary over every mesh axis: dp/ep/sp from the data,
        # pp/tp from the weights (vma is tracked even on size-1 axes)
        recv0 = pvary_to(
            jnp.zeros((B_l // M, s_act, cfg.d_model), cfg.jax_dtype), AXES)
        _, losses = jax.lax.scan(step, recv0, jnp.arange(M + Pp - 1))
        return jax.lax.psum(jnp.sum(losses), "pp") / M

    loss_fn = pipelined_loss if plan.pp > 1 else flat_loss
    if pipeline_schedule == "interleaved" or \
            (plan.pp > 1 and plan.vpp > 1 and pipeline_schedule == "1f1b"):
        pipeline_schedule = "interleaved"
    use_1f1b = plan.pp > 1 and pipeline_schedule in ("1f1b",
                                                     "interleaved")

    # Manual-schedule gradient reduction (the vma transpose machinery does
    # this automatically inside value_and_grad for the autodiff paths):
    # psum each leaf over every axis its accumulated gradient actually
    # varies on and the leaf is not sharded on — those are exactly the
    # axes whose ranks contributed partial sums (different tokens or
    # stages); anything the grad does not vary on is already complete.
    # With overlap on the per-leaf psums pack into deterministic-order
    # buckets (parallel/overlap.py) — same sums per element, but few
    # large independent collectives XLA can run beside remaining compute.
    def _manual_reduce_axes(grads):
        from hadoop_tpu.ops.vma import vma_of
        return jax.tree_util.tree_map(
            lambda g, s: tuple(sorted(vma_of(g) - _spec_axes(s))),
            grads, specs)

    def _reduce_manual(grads):
        axes_tree = _manual_reduce_axes(grads)
        if overlap.enabled:
            return bucketed_psum(grads, axes_tree, overlap.bucket_bytes,
                                 relaxed=rq_buckets)
        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_a = treedef.flatten_up_to(axes_tree)
        return treedef.unflatten([
            jax.lax.psum(g, a) if a else g
            for g, a in zip(flat_g, flat_a)])

    # -------------------------------------------------------------- body

    from hadoop_tpu.ops.vma import vma_of

    # ZeRO-1 under a manual schedule: reduce-scatter the accumulated
    # grads straight into the slice layout (a rank about to update 1/Z
    # of each leaf never needs the rest) — half the grad traffic of
    # psum + local slice, bitwise-identical slice values. Only the
    # grad-norm accumulates slice-wise (± an ulp on the clip scale).
    z1_scatter = (zero1 and optimizer == "adamw" and use_1f1b and
                  overlap.enabled and overlap.zero1_reduce_scatter)

    def _global_grad_sq_sliced(slices):
        """Squared global grad norm from per-rank ZeRO-1 slices: each
        slice's local sum-of-squares psummed over every axis it still
        varies on (its scatter + shard axes)."""
        def leaf(g):
            local = jnp.sum(jnp.square(g.astype(jnp.float32)))
            axes = tuple(sorted(vma_of(local)))
            return jax.lax.psum(local, axes) if axes else local
        parts = jax.tree_util.tree_map(leaf, slices)
        return functools.reduce(
            jnp.add, jax.tree_util.tree_leaves(parts))

    def body(params, opt_state, tokens, targets):
        if use_1f1b:
            from hadoop_tpu.parallel.pipeline import (
                pipeline_1f1b_loss_and_grad,
                pipeline_interleaved_loss_and_grad)
            sched = pipeline_interleaved_loss_and_grad \
                if pipeline_schedule == "interleaved" \
                else pipeline_1f1b_loss_and_grad
            loss, grads = sched(
                params, tokens, targets, cfg=cfg, plan=plan, ctx=ctx,
                n_microbatches=n_microbatches, remat=remat,
                loss_from_h=_loss_from_h)
            if z1_scatter:
                grads = bucketed_psum_scatter(
                    grads, _manual_reduce_axes(grads), z1_axes,
                    z1_sizes, overlap.bucket_bytes, relaxed=rq_buckets)
            else:
                grads = _reduce_manual(grads)
            # Accumulators summed M per-microbatch mean-losses; the
            # objective (like the gpipe path's psum(...)/M) is their mean.
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / n_microbatches).astype(p.dtype),
                grads, params)
            rem = tuple(sorted(vma_of(loss)))
            if rem:
                loss = jax.lax.psum(loss, rem)
            loss = loss / n_microbatches
        else:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, targets)
            # sum the per-data-rank losses over whatever axes the loss
            # still varies on (real data axes, plus identity-psums on
            # size-1 axes) and turn the sum into the global batch mean
            rem = tuple(sorted(vma_of(loss)))
            if rem:
                loss = jax.lax.psum(loss, rem)
        return _tail(params, opt_state, loss, grads)

    def body_sync(params, opt_state, tokens, targets, sync_state):
        # stale sync schedule (parallel/lowp/syncpolicy.py): the step
        # additionally carries the [pp, tp, n_stale, 2, B, S, D]
        # correction state — the previous step's reduced residual
        # corrections in, this step's out (stop-gradient: state is soft
        # numerics, never part of the autodiff objective). Flat path
        # only (pp plans are refused above).
        st = sync_state.reshape(sync_state.shape[2:])

        def loss_sync(p):
            h, ns = forward_hidden(p, tokens, cfg, ctx, remat=remat,
                                   sync_state=st)
            return _loss_from_h(p, h, targets, cfg, ctx), ns

        (loss, new_st), grads = jax.value_and_grad(
            loss_sync, has_aux=True)(params)
        rem = tuple(sorted(vma_of(loss)))
        if rem:
            loss = jax.lax.psum(loss, rem)
        new_params, new_opt, metrics = _tail(params, opt_state, loss,
                                             grads)
        new_sync = jax.lax.stop_gradient(new_st).reshape(
            sync_state.shape)
        return new_params, new_opt, metrics, new_sync

    def _tail(params, opt_state, loss, grads):
        grads = _reduce_grads(grads)
        loss = loss / loss_div
        with jax.named_scope("grad_norm"):
            gsq = _global_grad_sq_sliced(grads) if z1_scatter \
                else _global_grad_sq(grads)
        if zero1 and optimizer == "adamw":
            mu_l = jax.tree_util.tree_map(
                lambda m: m.reshape(-1), opt_state.mu)
            nu_l = jax.tree_util.tree_map(
                lambda n: n.reshape(-1), opt_state.nu)
            new_params, new_opt_l, gnorm = zero1_update(
                params, grads,
                AdamWState(opt_state.count, mu_l, nu_l), lr,
                leaf_axes=z1_axes, mesh_axis_sizes=z1_sizes, gsq=gsq,
                grads_sliced=z1_scatter,
                gather_bucket_bytes=(overlap.bucket_bytes
                                     if overlap.enabled else 0),
                gather_relaxed=rq_gather)
            # restore the (1,...,1,K) local state layout for out_specs
            new_opt = AdamWState(
                new_opt_l.count,
                jax.tree_util.tree_map(
                    lambda n2, old: n2.reshape(old.shape),
                    new_opt_l.mu, opt_state.mu),
                jax.tree_util.tree_map(
                    lambda n2, old: n2.reshape(old.shape),
                    new_opt_l.nu, opt_state.nu))
            metrics = {"loss": loss, "grad_norm": gnorm}
            return new_params, new_opt, metrics
        if optimizer == "sgd":
            # plain SGD: exact-parity testing mode (no adaptive-state
            # amplification of float accumulation noise)
            new_params = jax.tree_util.tree_map(
                lambda p, g: (p.astype(jnp.float32)
                              - lr * g.astype(jnp.float32)).astype(p.dtype),
                params, grads)
            new_opt = AdamWState(opt_state.count + 1, opt_state.mu,
                                 opt_state.nu)
            gnorm = jnp.sqrt(gsq)
        else:
            new_params, new_opt, gnorm = adamw_update(
                params, grads, opt_state, lr, gsq=gsq)
        metrics = {"loss": loss, "grad_norm": gnorm}
        return new_params, new_opt, metrics

    if zero1 and optimizer == "adamw":
        z1_axes, _, z1_specs, z1_sizes = zero1_layout(cfg, plan)
        opt_specs = AdamWState(count=P(), mu=z1_specs, nu=z1_specs)
    else:
        z1_axes = z1_sizes = None
        opt_specs = AdamWState(count=P(), mu=specs, nu=specs)
    metric_specs = {"loss": P(), "grad_norm": P()}
    if n_stale:
        # stale sync schedules carry the correction state through the
        # step as an explicit donated operand: global layout
        # [pp, tp, n_stale, 2(attn,mlp), B, S_eff, D] — the leading
        # axes hold each rank's distinct partial-sum corrections, the
        # batch/seq dims shard exactly like the data. The wrapper owns
        # the buffer so every existing caller keeps the 4-arg step
        # signature; a restart (or a batch-shape change) reinitializes
        # it to zeros, which makes the next step behave as skip for
        # exactly one step — soft state, deliberately not checkpointed.
        state_spec = P("pp", "tp", None, None, ("dp", "ep"), "sp", None)
        mapped = _smap(
            body_sync, mesh,
            in_specs=(specs, opt_specs, data_spec, data_spec,
                      state_spec),
            out_specs=(specs, opt_specs, metric_specs, state_spec))
        def train_step(params, opt_state, tokens, targets, sync_state):
            return mapped(params, opt_state, tokens, targets, sync_state)

        jitted = jax.jit(train_step,
                         donate_argnums=(0, 1, 4) if donate else ())
        holder = {"shape": None, "state": None}

        def step_with_sync_state(params, opt_state, tokens, targets):
            if holder["shape"] != tuple(tokens.shape):
                b, s = tokens.shape
                s_eff = s // plan.tp if plan.megatron_sp else s
                shp = (plan.pp, plan.tp, n_stale, 2, b, s_eff,
                       cfg.d_model)
                holder["state"] = jax.device_put(
                    jnp.zeros(shp, cfg.jax_dtype),
                    jax.sharding.NamedSharding(mesh, state_spec))
                holder["shape"] = tuple(tokens.shape)
            new_p, new_o, metrics, holder["state"] = jitted(
                params, opt_state, tokens, targets, holder["state"])
            return new_p, new_o, metrics

        return step_with_sync_state
    mapped = _smap(
        body, mesh,
        in_specs=(specs, opt_specs, data_spec, data_spec),
        out_specs=(specs, opt_specs, metric_specs))

    # a fixed function name: the compiled program is "jit_train_step" on
    # the device trace's "XLA Modules" line, whatever the body is called
    def train_step(params, opt_state, tokens, targets):
        return mapped(params, opt_state, tokens, targets)

    return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())


def physical_layer_order(params, cfg: ModelConfig, plan: MeshPlan):
    """Interleaved-1F1B placement: permute the stacked layer axis so the
    contiguous 'pp' shard hands each rank its v model chunks (virtual
    stages {c·pp + rank}). Identity when vpp == 1."""
    if getattr(plan, "vpp", 1) <= 1:
        return params
    from hadoop_tpu.parallel.pipeline import interleaved_layer_permutation
    perm = jnp.asarray(interleaved_layer_permutation(
        cfg.n_layers, plan.pp, plan.vpp))
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(
        lambda a: jnp.take(a, perm, axis=0), params["layers"])
    return out


def logical_layer_order(params, cfg: ModelConfig, plan: MeshPlan):
    """Inverse of :func:`physical_layer_order` — back to checkpoint /
    single-device layer order."""
    if getattr(plan, "vpp", 1) <= 1:
        return params
    import numpy as _np

    from hadoop_tpu.parallel.pipeline import interleaved_layer_permutation
    inv = jnp.asarray(_np.argsort(interleaved_layer_permutation(
        cfg.n_layers, plan.pp, plan.vpp)))
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(
        lambda a: jnp.take(a, inv, axis=0), params["layers"])
    return out


def init_sharded(rng, cfg: ModelConfig, plan: MeshPlan, mesh: Mesh,
                 zero1: bool = False):
    """Initialize params + optimizer state and place them on the mesh.
    ``zero1``: moment state in the ZeRO-1 slice layout (must match the
    train step's flag)."""
    params = _init_params(rng, cfg)
    params = physical_layer_order(params, cfg, plan)
    specs = param_specs(cfg, plan)
    params = shard_params(params, mesh, specs)
    if zero1:
        _, z1_shapes, z1_specs, _ = zero1_layout(cfg, plan)
        def mk(shape, spec):
            return jax.device_put(
                jnp.zeros(shape, jnp.float32),
                jax.sharding.NamedSharding(mesh, spec))
        mu = jax.tree_util.tree_map(
            mk, z1_shapes, z1_specs,
            is_leaf=lambda x: isinstance(x, tuple))
        nu = jax.tree_util.tree_map(
            mk, z1_shapes, z1_specs,
            is_leaf=lambda x: isinstance(x, tuple))
        return params, AdamWState(
            count=jax.device_put(
                jnp.zeros((), jnp.int32),
                jax.sharding.NamedSharding(mesh, P())),
            mu=mu, nu=nu)
    opt = adamw_init(params)
    opt = AdamWState(
        count=jax.device_put(
            opt.count, jax.sharding.NamedSharding(mesh, P())),
        mu=shard_params(opt.mu, mesh, specs),
        nu=shard_params(opt.nu, mesh, specs))
    return params, opt


def make_data_sharding(mesh: Mesh):
    return jax.sharding.NamedSharding(mesh, P(("dp", "ep"), "sp"))
