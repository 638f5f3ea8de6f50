"""``gpt2`` / ``llama`` / ``mixtral`` on the serving path: a token's
entry is one K and one V per KV head, attention is
``ops.paged_attention`` over each row's live pages, the MLP is dense or
routed (models/moe.py's capacity-padded dispatch), every matmul is
weight-plane aware (``serving.parity=relaxed``: int8 + scale groups).
Both pools ride the ONE layer scan as its carry, viewed ``[layers *
blocks, bs, hkv, dh]``: layer ``l`` writes and reads its pages where
they lie, at ``l * blocks + page`` (``tests/test_engine_pool_carry.py``).
``cfg.sandwich_norm`` adds a norm after each sub-layer; ``looped.py``
runs this stack several times a token over pools that many times deeper.
"""

from __future__ import annotations

from dataclasses import replace as _dc_replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hadoop_tpu.models.decoder import _norm
from hadoop_tpu.models.moe import _expert_ffn, capacity, route
from hadoop_tpu.obs.comm import comm_scale
from hadoop_tpu.ops import gelu, rope_frequencies, swiglu
from hadoop_tpu.ops.paged_attention import paged_attention
# qdot/qedot and the lowp a2a codecs are RELAXED-TIER entry points —
# every call sits under an `if self._relaxed_weights ...` guard, so
# serving.parity=bitwise (the default) compiles zero quantized code
# (tpulint-enforced)
from hadoop_tpu.parallel.lowp.quant import (moe_combine_quantized,
                                            moe_dispatch_quantized)
from hadoop_tpu.serving.families import RELAXED, TP_PLAN, Family
from hadoop_tpu.serving.weightplane import (EXPERT_STACKS,
                                            expert_shard_count, qdot, qedot)


def _shard_expert_stacks(params, shards: int):
    """Split the expert FFN stacks ``[L, E, ...]`` (arrays, or a
    qtensor's payload and scales, together) over the replica's local
    chips by ``P(None, "ep")`` on a 1-axis local mesh. Dense leaves
    (attention, norms, router) stay replicated."""
    mesh = Mesh(np.asarray(jax.local_devices()[:shards]), ("ep",))
    spec = NamedSharding(mesh, P(None, "ep"))
    layers = dict(params["layers"])
    for k in EXPERT_STACKS:
        if k in layers:     # an array, or a qtensor's {"q", "s"}
            layers[k] = jax.device_put(layers[k], spec)
    return {**params, "layers": layers}, NamedSharding(mesh, P())


# the attention input projections, in the order they are joined along N
# into the ONE leaf ``wqkv`` that ``run_stack``'s matmul streams
QKV = ("wq", "wk", "wv")


@jax.jit
def _join(*stacks):
    return jnp.concatenate(stacks, axis=-1)


def _rope_at(x, cos, sin, pos):
    """Rotate one token per row: x [T, H, Dh], pos [T]."""
    c = cos[pos][:, None, :]
    s = sin[pos][:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


class PagedKVFamily(Family):
    pool_spec = P(None, None, None, "tp", None)     # KV heads over tp

    def __init__(self, cfg, asked, *, moe_capacity_factor: float = 0.0,
                 moe_shards: int = 0, moe_a2a_codec: str = "int8"):
        self.cfg = cfg
        self.salt_layout = (cfg.n_kv_heads, cfg.head_dim)
        if moe_a2a_codec not in ("int8", "none"):
            raise ValueError(f"serving.moe.a2a.codec={moe_a2a_codec!r} "
                             "(choices: int8, none)")
        self._moe_a2a_codec = moe_a2a_codec
        self.expert_shards = expert_shard_count(
            cfg.n_experts, int(moe_shards),
            jax.local_device_count()) if cfg.is_moe else 0
        self._moe_cfg = _dc_replace(
            cfg, capacity_factor=float(moe_capacity_factor)) \
            if cfg.is_moe and moe_capacity_factor else cfg
        self._relaxed_weights = bool(asked.get(RELAXED))
        # two planes read the projections by name and keep the tree as
        # loaded: int8 scale groups, and a tp cut along N (which would
        # cut a joined axis across q, k and v)
        self._joins_qkv = not (asked.get(RELAXED) or asked.get(TP_PLAN))
        # the Pallas kernel is a one-device program: a pool sharded over
        # the engine's mesh takes the portable path under GSPMD
        self._attn_impl = "ref" if asked.get(TP_PLAN) else "auto"
        if self._relaxed_weights and asked.get(TP_PLAN):
            raise NotImplementedError(
                "tp sharding of int8 resident weights is not wired yet "
                "(serving.parity=relaxed serves single-chip replicas)")

    def pools(self, block_size):
        # a token's K and V per KV head, in every layer
        page = (block_size, self.cfg.n_kv_heads, self.cfg.head_dim)
        return [(self.cfg.n_layers, page)] * 2

    def place_weights(self, params, owned: bool):
        """``wq``, ``wk``, ``wv`` ``[L, K, N*]`` joined into ``wqkv`` ``[L,
        K, Nq + Nk + Nv]``, in their place: the same bytes. Reshaped to
        heads, ``x @ wq`` wants the weight K-minor, and the compiler
        relays a stack that is not (whole, every step, under the looped
        family's pass loop: 1.2 GB of temporaries; a layer's slice in
        VMEM, every layer, without it). One matmul split after it
        streams the stack from HBM as ``w_gate`` is streamed."""
        if not self._joins_qkv:
            return params
        layers = dict(params["layers"])
        stacks = [layers.pop(k) for k in QKV]
        layers["wqkv"] = _join(*stacks)
        if owned:
            # donation cannot free them (no output has a stack's shape)
            layers["wqkv"].block_until_ready()
            for w in stacks:
                w.delete()
        return {**params, "layers": layers}

    def place_experts(self, params):
        if self.expert_shards > 1:
            return _shard_expert_stacks(params, self.expert_shards)
        return params, None

    def rope_tables(self):
        if not self.cfg.use_rope:
            return None, None
        return rope_frequencies(self.cfg.head_dim, self.cfg.max_seq,
                                self.cfg.rope_theta)

    def describe_experts(self, rows: int):
        if not self.cfg.is_moe:
            return {}
        return {"expert_capacity": capacity(rows, self._moe_cfg),
                "a2a_codec": self._moe_a2a_codec}

    def _wdot(self, x, w):
        """One serving matmul: under ``serving.parity=relaxed`` int8 +
        scale groups dequantized in-register; else the plain matmul."""
        if self._relaxed_weights:
            return qdot(x, w)
        return x @ w

    def _mlp(self, x, lp):
        if self.cfg.is_moe:
            return self.moe_mlp(x, lp)
        if self.cfg.use_swiglu:
            return self._wdot(swiglu(self._wdot(x, lp["w_gate"]),
                                     self._wdot(x, lp["w_up"])),
                              lp["w_down"])
        return self._wdot(gelu(self._wdot(x, lp["w_in"]) + lp["b_in"]),
                          lp["w_out"]) + lp["b_out"]

    @jax.named_scope("moe")
    def moe_mlp(self, x, lp):
        """Routed expert MLP over the step's rows ``x [T, D]`` through
        models/moe.py's capacity-padded one-hot dispatch: T is static
        per step shape, so the capacity is too. Tokens past an expert's
        capacity (and inactive draft rows) get an all-zero combine row:
        exact 0.0, the residual passes through. Under
        ``serving.parity=relaxed`` the experts are int8 stacks and both
        all2all legs ride the lowp codec, recorded at the
        ``moe.dispatch`` / ``moe.combine`` comm sites."""
        mcfg = self._moe_cfg
        dispatch, combine = route(x, lp["router"], mcfg)
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
        if self._relaxed_weights and self._moe_a2a_codec != "none":
            xe = moe_dispatch_quantized(xe)
        if self._relaxed_weights:
            ye = qedot(swiglu(qedot(xe, lp["w_gate"]),
                              qedot(xe, lp["w_up"])),
                       lp["w_down"])
        else:
            ye = _expert_ffn(xe, lp, mcfg)
        if self._relaxed_weights and self._moe_a2a_codec != "none":
            ye = moe_combine_quantized(ye)
        y2d = jnp.einsum("tec,ecd->td", combine.astype(jnp.float32),
                         ye.astype(jnp.float32))
        return y2d.astype(x.dtype)

    def run_stack(self, params, h, kc, vc, rows, bases):
        """Every layer once over the step's rows. ``kc`` / ``vc``: the
        pools viewed ``[slots * blocks, bs, hkv, dh]``, carried through
        the ONE layer scan; layer ``l`` writes and reads its pages at
        ``bases[l] + page``."""
        cfg = self.cfg
        t = h.shape[0]
        hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        pos, cos, sin = rows["pos"], rows["cos"], rows["sin"]
        blk, off = rows["blk"], rows["off"]
        tables, lens = rows["tables"], rows["lens"]
        scale = 1.0 / (dh ** 0.5)

        # the scopes are the step's stable names on the device trace
        def layer(carry, xs):
            h, kc, vc = carry
            lp, base = xs
            with jax.named_scope("attn_proj"):
                x = _norm(h, lp["attn_norm_w"], lp.get("attn_norm_b"), cfg)
                if "wqkv" in lp:    # placed: place_weights
                    q, k, v = jnp.split(self._wdot(x, lp["wqkv"]),
                                        [hq * dh, (hq + hkv) * dh], axis=-1)
                else:
                    q, k, v = (self._wdot(x, lp[n]) for n in QKV)
                q = q.reshape(t, hq, dh)
                k = k.reshape(t, hkv, dh)
                v = v.reshape(t, hkv, dh)
                if cfg.use_rope:
                    q = _rope_at(q, cos, sin, pos)
                    k = _rope_at(k, cos, sin, pos)
            with jax.named_scope("kv_update"):
                kc = kc.at[base + blk, off].set(k.astype(kc.dtype))
                vc = vc.at[base + blk, off].set(v.astype(vc.dtype))
            with jax.named_scope("attn"):
                # read AFTER the scatter: a draft or chunk row sees the
                # rows before it in this very step
                attn = paged_attention(q, kc, vc, base + tables, lens,
                                       scale, impl=self._attn_impl)
            with jax.named_scope("attn_proj"):
                a = self._wdot(attn.reshape(t, hq * dh), lp["wo"])
                if cfg.sandwich_norm:
                    a = _norm(a, lp["attn_post_norm_w"], None, cfg)
                h2 = h + a.astype(h.dtype)
            with jax.named_scope("mlp"):
                x2 = _norm(h2, lp["mlp_norm_w"], lp.get("mlp_norm_b"),
                           cfg)
                m = self._mlp(x2, lp)
                if cfg.sandwich_norm:
                    m = _norm(m, lp["mlp_post_norm_w"], None, cfg)
                return (h2 + m.astype(h.dtype), kc, vc), None

        # comm_scale: the trace-time comm ledgers see one body trace of
        # the scan; the hardware runs it n_layers times per step — the
        # MoE a2a sites record honest per-step executions/bytes
        with comm_scale(cfg.n_layers):
            (h, kc, vc), _ = jax.lax.scan(
                layer, (h, kc, vc), (params["layers"], bases))
        return h, kc, vc

    def run_layers(self, params, h, pools, lane, rows):
        kp, vp = pools
        pool_shape = kp.shape
        n_blocks = pool_shape[1]
        h, kp, vp = self.run_stack(
            params, h, kp.reshape((-1,) + pool_shape[2:]),
            vp.reshape((-1,) + pool_shape[2:]), rows,
            jnp.arange(self.cfg.n_layers, dtype=jnp.int32) * n_blocks)
        return h, (kp.reshape(pool_shape), vp.reshape(pool_shape)), lane, ()
