"""``family="lfm2_moe"`` on the serving path: a gated short convolution
in most layers, grouped-query attention with a per-head RMSNorm of q and
k in the rest, a dense SwiGLU FFN in the leading layers and after them a
sigmoid top-k router over experts that are ALL resident.

Layer ``l`` (pre-norm, RMSNorm): ``h += Op_l(norm(h)); h += FFN_l(norm(h))``.
``Op`` is ``cfg.layer_types[l]``:

- ``"conv"``: ``[B, C, X] = split3(u W_in)``; ``z = B * X``; ``c_t = sum_j
  k[j] * z_{t-(K-1)+j}`` (depthwise, causal, ``K = cfg.conv_kernel``, no
  bias); ``Op(u) = (C * c) W_out``. What a sequence carries from one
  token to the next is its last ``K - 1`` values of ``z``: a fixed-size
  recurrent state, whatever the length.
- ``"full_attention"``: GQA, q and k RMS-normed per head before the
  rotary embedding, through ``ops.paged_attention`` over the lane's pages.

``FFN`` is a SwiGLU MLP (``cfg.d_ff``) in the first ``cfg.n_dense_layers``
layers and ``models.moe.moe_share`` in the rest: one router group, the
choice-only bias, weights normalised by their sum plus
``cfg.router_norm_eps``, experts ``0 .. n_experts`` of a router exactly
that wide, no shared expert, no token dropped.

An operator and an FFN are stacked apart (``conv_ops``, ``attn_ops``,
``dense_layers``, ``moe_layers``): a layer is one of each, and
``run_layers`` scans every run of layers that pair the same two kinds.

**Where the state lives.** Three pools under the engine's one block
table: K and V ``[attention layers, blocks, block, kv heads * head]`` (a
token's KV heads side by side in one row: 64-wide heads do not fill a
tile's 128 lanes, ``ops.paged_attention.paged_attention_packed``), and
a state-tail pool ``[conv layers, blocks, K - 1, D]`` — a page's tail is
the ``z`` of its last ``K - 1`` tokens, written by the rows that fill
them. A lane's own state ``[conv layers, lanes, K - 1, D]`` rides the
step's device state: a decode row reads and shifts it, a prefill chunk's
rows read their predecessors from the rows before them in the step and
then from it. A lane that starts mid-sequence (a prefix hit, a resume
after preemption) loads it from the tail of the last page it maps
(``start_lane``), so a page that is resident has everything a sharer
needs and the radix cache, eviction and preemption know nothing new.

Training is not built for this family (``models.config.refuse_training``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.models.deepseek import _rope_rows
from hadoop_tpu.models.moe import moe_share, split_experts
from hadoop_tpu.ops import rms_norm, swiglu
from hadoop_tpu.ops.paged_attention import paged_attention_packed

OP_STACKS = {"conv": "conv_ops", "full_attention": "attn_ops"}
FFN_STACKS = {"dense": "dense_layers", "moe": "moe_layers"}
# what run_layers' stats count, in order (ServingMetrics names)
COUNTERS = ("moe_assignments_local", "moe_local_experts_hit",
            "moe_expert_rows_max")


def state_rows(cfg: ModelConfig) -> int:
    """Values of ``z`` a conv layer carries from token to token."""
    return cfg.conv_kernel - 1


def n_ops(cfg: ModelConfig, kind: str) -> int:
    return sum(1 for t in cfg.layer_types if t == kind)


def runs(cfg: ModelConfig) -> List[Tuple[str, str, int, int, int]]:
    """(operator kind, FFN kind, first operator of its stack, first FFN
    of its stack, layers) of each run of like layers, in order."""
    out: List[list] = []
    seen = {"conv": 0, "full_attention": 0, "dense": 0, "moe": 0}
    for l, op in enumerate(cfg.layer_types):
        ffn = "dense" if l < cfg.n_dense_layers else "moe"
        if out and out[-1][0] == op and out[-1][1] == ffn:
            out[-1][4] += 1
        else:
            out.append([op, ffn, seen[op], seen[ffn], 1])
        seen[op] += 1
        seen[ffn] += 1
    return [tuple(r) for r in out]


# ------------------------------------------------------------------ params

def stack_shapes(cfg: ModelConfig, stack: str) -> Dict[str, tuple]:
    """leaf -> (shape of one layer's leaf, fan_in or None for a norm
    weight, 0 for a bias) of ``conv_ops`` | ``attn_ops`` |
    ``dense_layers`` | ``moe_layers``. The depthwise kernel is kept
    ``[K, D]`` (the published layout is ``[D, 1, K]``): ``conv_w[j]``
    multiplies ``z_{t-(K-1)+j}``."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if stack == "conv_ops":
        return {"conv_norm_w": ((d,), None), "in_proj": ((d, 3 * d), d),
                "conv_w": ((cfg.conv_kernel, d), cfg.conv_kernel),
                "out_proj": ((d, d), d)}
    if stack == "attn_ops":
        return {"attn_norm_w": ((d,), None), "wq": ((d, h * dh), d),
                "wk": ((d, hkv * dh), d), "wv": ((d, hkv * dh), d),
                "q_norm_w": ((dh,), None), "k_norm_w": ((dh,), None),
                "wo": ((h * dh, d), h * dh)}
    if stack == "dense_layers":
        f = cfg.d_ff
        return {"ffn_norm_w": ((d,), None), "w_gate": ((d, f), d),
                "w_up": ((d, f), d), "w_down": ((f, d), f)}
    e, f, n = cfg.n_experts, cfg.d_ff_expert, cfg.n_routed_experts
    return {"ffn_norm_w": ((d,), None), "router": ((d, n), d),
            "router_bias": ((n,), 0), "w_gate": ((e, d, f), d),
            "w_up": ((e, d, f), d), "w_down": ((e, f, d), f)}


def stack_sizes(cfg: ModelConfig) -> Dict[str, int]:
    """Layers in each stack (a stack of none is left out of the tree)."""
    n_dense = cfg.n_dense_layers
    sizes = {"conv_ops": n_ops(cfg, "conv"),
             "attn_ops": n_ops(cfg, "full_attention"),
             "dense_layers": n_dense, "moe_layers": cfg.n_layers - n_dense}
    return {k: n for k, n in sizes.items() if n}


def init_params(rng: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    """A random tree in the layout the engine takes (tests, smoke runs):
    the router's bias large enough to move choices, so that a bias that
    leaked into the weights shows. The head is the embedding."""
    dt = cfg.jax_dtype

    def leaf(key, shape, fan_in):
        if fan_in is None:
            return (1.0 + 0.05 * jax.random.normal(key, shape)).astype(dt)
        if fan_in == 0:
            return (0.1 * jax.random.normal(key, shape)).astype(dt)
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    sizes = stack_sizes(cfg)
    k_embed, *k_stacks = jax.random.split(rng, 1 + len(sizes))
    params = {"embed": leaf(k_embed, (cfg.vocab_size, cfg.d_model),
                            cfg.d_model),
              "final_norm_w": jnp.ones((cfg.d_model,), dt)}
    for key, (stack, n) in zip(k_stacks, sizes.items()):
        shapes = stack_shapes(cfg, stack)
        keys = jax.random.split(key, len(shapes))
        params[stack] = {
            name: leaf(k, (n,) + shape, fan_in)
            for k, (name, (shape, fan_in)) in zip(keys, shapes.items())}
    return params


# ----------------------------------------------------------- serving layers

def start_lane(lane, tail, slot, page):
    """``lane [conv layers, lanes, K-1, D]`` with lane ``slot`` set for a
    request whose first row follows page ``page`` of ``tail [conv layers,
    blocks, K-1, D]``; page 0 (scratch) means it starts from nothing."""
    state = jnp.where(page > 0, tail[:, page], 0).astype(lane.dtype)
    return lane.at[:, slot].set(state)


def _conv(h, lp, cfg: ModelConfig, tail, lane, ci, n_blocks, rows):
    """One conv operator over the step's rows: ``B`` lanes' rows (one
    each), then the chunk's consecutive rows of lane ``chunk_slot``.
    ``tail`` is flat ``[conv layers * blocks, K-1, D]``; ``ci`` this
    operator's place in its stack. Returns (h + Op, tail, lane)."""
    b, s = rows["B"], state_rows(cfg)
    active = rows["active"]
    with jax.named_scope("conv"):
        u = rms_norm(h, lp["conv_norm_w"], cfg.norm_eps)
        gate_b, gate_c, x = jnp.split(u @ lp["in_proj"], 3, axis=-1)
        z = gate_b * x                                          # [T, D]
        w = lp["conv_w"].astype(z.dtype)                        # [K, D]
        mine = lane[ci]                                         # [B, s, D]
        # a lane's row follows the lane's state
        seq = jnp.concatenate([mine, z[:b, None]], axis=1)      # [B, K, D]
        c = jnp.einsum("bkd,kd->bd", seq, w)
        new = jnp.where(active[:b, None, None], seq[:, 1:], mine)
        if rows["chunk_slot"] is not None:
            # a chunk's row follows the rows before it in the chunk,
            # and they follow the lane's state
            slot, n = rows["chunk_slot"], rows["chunk_n"]
            n_c = z.shape[0] - b
            ext = jnp.concatenate([mine[slot], z[b:]], axis=0)  # [s+C, D]
            c = jnp.concatenate(
                [c, sum(w[j] * ext[j:j + n_c] for j in range(s + 1))],
                axis=0)
            new = new.at[slot].set(
                jax.lax.dynamic_slice_in_dim(ext, n, s, axis=0))
        lane = lane.at[ci].set(new)
        # the rows that fill a page's last K-1 places are its tail
        place = rows["off"] - (rows["block"] - s)
        keep = active & (place >= 0)
        tail = tail.at[ci * n_blocks + jnp.where(keep, rows["blk"], 0),
                       jnp.where(keep, place, 0)].set(z.astype(tail.dtype))
        y = (gate_c * c.astype(gate_c.dtype)) @ lp["out_proj"]
        return h + y.astype(h.dtype), tail, lane


def _attention(h, lp, cfg: ModelConfig, kc, vc, base, rows):
    """One attention operator; ``kc`` / ``vc`` flat ``[attention layers *
    blocks, block, kv heads * head]``, ``base`` this layer's first
    page."""
    t = h.shape[0]
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos, cos, sin = rows["pos"], rows["cos"], rows["sin"]
    with jax.named_scope("attn_proj"):
        x = rms_norm(h, lp["attn_norm_w"], cfg.norm_eps)
        q = rms_norm((x @ lp["wq"]).reshape(t, hq, dh), lp["q_norm_w"],
                     cfg.norm_eps)
        k = rms_norm((x @ lp["wk"]).reshape(t, hkv, dh), lp["k_norm_w"],
                     cfg.norm_eps)
        v = (x @ lp["wv"]).reshape(t, hkv, dh)
        q = _rope_rows(q, cos, sin, pos)
        k = _rope_rows(k, cos, sin, pos)
    with jax.named_scope("kv_update"):
        page = base + rows["blk"]
        kc = kc.at[page, rows["off"]].set(
            k.reshape(t, hkv * dh).astype(kc.dtype))
        vc = vc.at[page, rows["off"]].set(
            v.reshape(t, hkv * dh).astype(vc.dtype))
    with jax.named_scope("attn"):
        # read AFTER the scatter: a chunk row sees the rows before it
        attn = paged_attention_packed(q, kc, vc, base + rows["tables"],
                                      rows["lens"], dh ** -0.5)
    with jax.named_scope("attn_proj"):
        return h + (attn.reshape(t, hq * dh) @ lp["wo"]).astype(h.dtype), \
            kc, vc


def run_layers(params, h, pools, lane, cfg: ModelConfig, rows):
    """All layers over the step's rows ``h [T, D]``. ``pools``: K, V
    ``[attention layers, blocks, block, kv heads * head]`` and the tail
    ``[conv layers, blocks, K-1, D]``; ``lane [conv layers, lanes, K-1,
    D]``. ``rows`` as ``serving.families.Family.run_layers`` has them.
    Returns (h, pools, lane, ``stats`` int32 ``[3]``: ``COUNTERS``)."""
    shapes = [p.shape for p in pools]
    n_blocks = shapes[0][1]
    kc, vc, tail = (p.reshape((-1,) + p.shape[2:]) for p in pools)
    eps = cfg.norm_eps

    def dense_ffn(x, lp):
        with jax.named_scope("mlp"):
            return swiglu(x @ lp["w_gate"], x @ lp["w_up"]) @ lp["w_down"]

    def body(op, ffn):
        ops, ffns = params[OP_STACKS[op]], params[FFN_STACKS[ffn]]
        if ffn == "moe":
            experts, ffns = split_experts(ffns)

        def one_layer(carry, xs):
            h, kc, vc, tail, lane, stats = carry
            oi, fi = xs
            lp = jax.tree_util.tree_map(lambda a: a[oi], ops)
            if op == "conv":
                h, tail, lane = _conv(h, lp, cfg, tail, lane, oi, n_blocks,
                                      rows)
            else:
                h, kc, vc = _attention(h, lp, cfg, kc, vc, oi * n_blocks,
                                       rows)
            lp = jax.tree_util.tree_map(lambda a: a[fi], ffns)
            x = rms_norm(h, lp["ffn_norm_w"], eps)
            if ffn == "moe":
                y, st = moe_share(x, {**lp, **experts}, cfg,
                                  valid=rows["active"], busiest=True,
                                  layer=fi)
                # assignments and experts hit add up over layers; the
                # busiest expert's rows too (a sum of per-layer maxima)
                stats = stats + st
            else:
                y = dense_ffn(x, lp)
            return (h + y.astype(h.dtype), kc, vc, tail, lane, stats), None
        return one_layer

    carry = (h, kc, vc, tail, lane, jnp.zeros((len(COUNTERS),), jnp.int32))
    for op, ffn, op0, ffn0, n in runs(cfg):
        carry, _ = jax.lax.scan(
            body(op, ffn), carry,
            (jnp.arange(op0, op0 + n, dtype=jnp.int32),
             jnp.arange(ffn0, ffn0 + n, dtype=jnp.int32)))
    h, kc, vc, tail, lane, stats = carry
    pools = tuple(p.reshape(s) for p, s in zip((kc, vc, tail), shapes))
    return h, pools, lane, stats
