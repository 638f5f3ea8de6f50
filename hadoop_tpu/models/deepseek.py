"""``family="deepseek_v32"`` on the serving path: latent attention (MLA)
with a learned sparse selection, dense layers before expert layers, and
one replica's share of a wider expert-parallel router.

Layer (pre-norm, RMSNorm): ``h += Attn(norm(h)); h += FFN(norm(h))`` with
a SwiGLU MLP in the first ``n_dense_layers`` layers and
``models.moe.moe_share`` (sigmoid group-limited router over
``n_routed_experts``, the experts held here, a shared expert) in the
rest. The two kinds have different leaves, so the tree holds a stack per
kind — ``params["dense_layers"]``, ``params["moe_layers"]`` — and
``run_layers`` scans each run of like layers.

Attention caches, for every token, one latent (``kv_lora_rank`` entries,
RMS-normed, followed by one rotary key shared by all heads) and one index
key, in two pools that share the engine's block table; the key and value
up-projections (``wkv_b``) are absorbed into the query and the output, so
attention runs over latents (``ops.sparse_mla``) for decode rows and
prefill-chunk rows alike. Both pools are carried WHOLE through the layer
scans as ``[layers * blocks, block, width]`` and a layer addresses its
pages as ``layer * blocks + page``: a step scatters its rows in place and
gathers what it selected, and no per-layer slab is sliced out or written
back.

Training is not built for this family (``make_train_step`` and
``models.decoder`` refuse it by name).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.models.moe import moe_share, split_experts
from hadoop_tpu.ops import layer_norm, rms_norm, swiglu
from hadoop_tpu.ops.rope import yarn_frequencies, yarn_mscale
from hadoop_tpu.ops.sparse_mla import sparse_mla_attention


def latent_width(cfg: ModelConfig) -> int:
    """A token's row in the latent pool: ``kv_lora_rank`` latent entries,
    the rotary key, and zeros up to a multiple of 128. The TPU's tiled
    layout pads the minor dimension to 128 in HBM anyway; left unpadded
    (576 at the published widths) the compiler gave the pool a transposed
    layout at the step's boundary and converted the whole pool on the way
    in and on the way out of every step."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def softmax_scale(cfg: ModelConfig) -> float:
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def rope_tables(cfg: ModelConfig):
    """(cos, sin) ``[max_seq, qk_rope_head_dim // 2]``: one table for the
    attention's rotary parts and the indexer's."""
    return yarn_frequencies(cfg.qk_rope_head_dim, cfg.max_seq,
                            cfg.rope_theta, cfg.rope_factor,
                            cfg.rope_original_max_seq or cfg.max_seq,
                            cfg.rope_beta_fast, cfg.rope_beta_slow)


# ------------------------------------------------------------------ params

def layer_shapes(cfg: ModelConfig, kind: str) -> Dict[str, tuple]:
    """leaf -> (shape of one layer's leaf, fan_in or None for a norm
    weight, 0 for a bias). ``kind``: "dense" | "moe"."""
    d, h = cfg.d_model, cfg.n_heads
    rq, c = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    shapes = {
        "attn_norm_w": ((d,), None),
        "wq_a": ((d, rq), d), "q_norm_w": ((rq,), None),
        "wq_b": ((rq, h * (dn + dr)), rq),
        "wkv_a": ((d, c + dr), d), "kv_norm_w": ((c,), None),
        "wkv_b": ((c, h * (dn + dv)), c),
        "wo": ((h * dv, d), h * dv),
        "idx_wq_b": ((rq, hi * di), rq), "idx_wk": ((d, di), d),
        "idx_k_norm_w": ((di,), None), "idx_k_norm_b": ((di,), 0),
        "idx_w_proj": ((d, hi), d),
        "mlp_norm_w": ((d,), None),
    }
    if kind == "dense":
        f = cfg.d_ff
        shapes.update({"w_gate": ((d, f), d), "w_up": ((d, f), d),
                       "w_down": ((f, d), f)})
    else:
        e, f = cfg.n_experts, cfg.d_ff_expert
        fs = f * max(1, cfg.n_shared_experts)
        shapes.update({
            "router": ((d, cfg.n_routed_experts), d),
            "router_bias": ((cfg.n_routed_experts,), 0),
            "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
            "w_down": ((e, f, d), f),
            "ws_gate": ((d, fs), d), "ws_up": ((d, fs), d),
            "ws_down": ((fs, d), fs)})
    return shapes


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    """A random tree in the layout the engine takes (tests, smoke runs).
    Biases are drawn too — the router's correction bias large enough to
    move choices — so that a dropped bias shows."""
    dt = cfg.jax_dtype
    d, v = cfg.d_model, cfg.vocab_size

    def leaf(key, shape, fan_in):
        if fan_in is None:
            return (1.0 + 0.05 * jax.random.normal(key, shape)).astype(dt)
        if fan_in == 0:
            return (0.1 * jax.random.normal(key, shape)).astype(dt)
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    def stack(key, kind, n):
        shapes = layer_shapes(cfg, kind)
        keys = jax.random.split(key, len(shapes))
        return {name: leaf(k, (n,) + shape, fan_in)
                for k, (name, (shape, fan_in)) in zip(keys, shapes.items())}

    k_e, k_h, k_d, k_m = jax.random.split(rng, 4)
    params = {"embed": leaf(k_e, (v, d), d),
              "final_norm_w": jnp.ones((d,), dt),
              "lm_head": leaf(k_h, (d, v), d)}
    n_dense = cfg.n_dense_layers
    if n_dense:
        params["dense_layers"] = stack(k_d, "dense", n_dense)
    if cfg.n_layers > n_dense:
        params["moe_layers"] = stack(k_m, "moe", cfg.n_layers - n_dense)
    return params


# ----------------------------------------------------------- serving layers

def _rope_rows(x, cos, sin, pos):
    """Rotate one token per row, split-half pairs: x [T, ..., Dr]."""
    shape = (pos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    c, s = cos[pos].reshape(shape), sin[pos].reshape(shape)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


def _attention(h, lp, cfg: ModelConfig, lat_pool, idx_pool, base, rows):
    """One layer's attention over the step's rows. ``base`` is the
    layer's first page in the flat pools. Returns (h + attention,
    lat_pool, idx_pool)."""
    t = h.shape[0]
    eps = cfg.norm_eps
    nh, c = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    pos, cos, sin = rows["pos"], rows["cos"], rows["sin"]
    with jax.named_scope("attn_proj"):
        x = rms_norm(h, lp["attn_norm_w"], eps)
        qr = rms_norm(x @ lp["wq_a"], lp["q_norm_w"], eps)
        q = (qr @ lp["wq_b"]).reshape(t, nh, dn + dr)
        q_pe = _rope_rows(q[..., dn:], cos, sin, pos)
        kv = x @ lp["wkv_a"]
        latent = jnp.concatenate(
            [rms_norm(kv[:, :c], lp["kv_norm_w"], eps),
             _rope_rows(kv[:, c:], cos, sin, pos),
             jnp.zeros((t, lat_pool.shape[-1] - c - dr), kv.dtype)],
            axis=-1)                                    # [T, pool width]
        w_kv = lp["wkv_b"].reshape(c, nh, dn + dv)
        q_abs = jnp.einsum("thn,chn->thc", q[..., :dn], w_kv[..., :dn])
    with jax.named_scope("dsa_index"):
        # the indexer: rotary part FIRST in its query and key
        qi = (qr @ lp["idx_wq_b"]).reshape(t, hi, di)
        qi = jnp.concatenate([_rope_rows(qi[..., :dr], cos, sin, pos),
                              qi[..., dr:]], axis=-1)
        ki = layer_norm(x @ lp["idx_wk"], lp["idx_k_norm_w"],
                        lp["idx_k_norm_b"], eps)
        ki = jnp.concatenate([_rope_rows(ki[:, :dr], cos, sin, pos),
                              ki[:, dr:]], axis=-1)
        wi = jnp.dot(x, lp["idx_w_proj"],
                     preferred_element_type=jnp.float32) \
            * (hi ** -0.5 * di ** -0.5)
    with jax.named_scope("kv_update"):
        page = base + rows["blk"]
        lat_pool = lat_pool.at[page, rows["off"]].set(
            latent.astype(lat_pool.dtype))
        idx_pool = idx_pool.at[page, rows["off"]].set(
            ki.astype(idx_pool.dtype))
    # read AFTER the scatter: a chunk row sees the rows before it in this
    # very step. Each group is Q rows for each of R block tables.
    outs = []
    for start, tables, lens in rows["groups"]:
        r, nq = lens.shape
        take = lambda a: a[start:start + r * nq].reshape(  # noqa: E731
            (r, nq) + a.shape[1:])
        outs.append(sparse_mla_attention(
            take(q_abs), take(q_pe), take(qi), take(wi), lat_pool,
            idx_pool, base + tables, lens, topk=cfg.index_topk,
            scale=softmax_scale(cfg)).reshape(r * nq, nh, c))
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    with jax.named_scope("attn_proj"):
        o = jnp.einsum("thc,chv->thv", o, w_kv[..., dn:])
        return h + (o.reshape(t, nh * dv) @ lp["wo"]).astype(h.dtype), \
            lat_pool, idx_pool


def run_layers(params, h, lat_pool, idx_pool, cfg: ModelConfig, rows):
    """All layers over the step's rows ``h [T, D]``. lat_pool / idx_pool:
    ``[layers, blocks, block, width]``. ``rows``: ``pos``, ``blk`` (the
    page each row writes, scratch for dead rows), ``off``, ``active``
    ``[T]``; ``groups``: ``(first row, tables [R, bps], lens [R, Q])`` —
    the rows in table-sharing groups, in order, covering all ``T``;
    ``cos`` / ``sin``. Returns (h, lat_pool, idx_pool, ``stats`` int32
    ``[2]``: assignments to held experts and held experts hit, summed
    over the expert layers, live rows only)."""
    n_layers, n_blocks = lat_pool.shape[:2]
    shape_lat, shape_idx = lat_pool.shape, idx_pool.shape
    lat = lat_pool.reshape((-1,) + shape_lat[2:])
    idx = idx_pool.reshape((-1,) + shape_idx[2:])
    eps = cfg.norm_eps

    n_dense = cfg.n_dense_layers

    def dense_mlp(x, lp, layer):
        return swiglu(x @ lp["w_gate"], x @ lp["w_up"]) @ lp["w_down"], 0

    if n_layers > n_dense:
        experts, moe_layers = split_experts(params["moe_layers"])

    def expert_mlp(x, lp, layer):
        return moe_share(x, {**lp, **experts}, cfg, valid=rows["active"],
                         layer=layer - n_dense)

    def body(scope, ffn):
        def one_layer(carry, xs):
            h, lat, idx, stats = carry
            lp, layer = xs
            h, lat, idx = _attention(h, lp, cfg, lat, idx, layer * n_blocks,
                                     rows)
            with jax.named_scope(scope):
                y, st = ffn(rms_norm(h, lp["mlp_norm_w"], eps), lp, layer)
                return (h + y.astype(h.dtype), lat, idx, stats + st), None
        return one_layer

    carry = (h, lat, idx, jnp.zeros((2,), jnp.int32))
    if n_dense:
        carry, _ = jax.lax.scan(
            body("mlp", dense_mlp), carry, (params["dense_layers"],
                           jnp.arange(n_dense, dtype=jnp.int32)))
    if n_layers > n_dense:
        carry, _ = jax.lax.scan(
            body("moe", expert_mlp), carry,
            (moe_layers, jnp.arange(n_dense, n_layers, dtype=jnp.int32)))
    h, lat, idx, stats = carry
    return h, lat.reshape(shape_lat), idx.reshape(shape_idx), stats
