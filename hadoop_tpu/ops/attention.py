"""Causal (grouped-query) attention.

The portable path is a jnp softmax-attention that XLA maps onto the MXU;
the fused Pallas flash kernel in ``hadoop_tpu.ops.flash`` is selected
automatically on TPU backends for qualifying shapes (see
``causal_attention``'s ``impl`` arg). Which of the two a call site got is
recorded at trace time (``attention_impl_traces``), so a run that meant
to use the kernel can prove it did; serving's paged decode attention
(``hadoop_tpu.ops.paged_attention``) records its own choice the same way.

Ring attention (sequence/context parallelism over the mesh) builds on
``chunk_attention`` + ``merge_attention``: each partial result is the
*chunk-normalized* output plus its per-row log-sum-exp, and two partials
merge by log-add-exp weighting — the standard online-softmax recombination.
See ``hadoop_tpu.parallel.ring_attention``.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from hadoop_tpu.metrics import metrics_system

log = logging.getLogger(__name__)

_NEG_INF = -1e30


def _impl_counters():
    """The six ``htpu_attention_impl_traces_total{site,impl}`` counters
    (label values from these literal tuples — the bounded-set contract)."""
    reg = metrics_system().source("attention")
    for site in ("causal", "ring", "paged"):
        for impl in ("flash", "ref"):
            reg.counter(f"{site}_{impl}_traces",
                        "attention call sites traced per implementation",
                        prom_name="attention_impl_traces",
                        prom_labels={"site": site, "impl": impl})
    return reg


def record_attention_impl(site: str, impl: str, q_shape, k_shape) -> None:
    """Trace-time record of which implementation a call site got: the
    fused Pallas kernel (``flash``) or the jnp path (``ref``)."""
    _impl_counters().counter(f"{site}_{impl}_traces").incr()
    log.debug("attention[%s] -> %s (q=%s k=%s)", site, impl,
              tuple(q_shape), tuple(k_shape))


def attention_impl_traces() -> dict:
    """``{"causal_flash": n, "causal_ref": n, "ring_flash": n,
    "ring_ref": n, "paged_flash": n, "paged_ref": n}`` — call sites
    traced so far in this process."""
    snap = _impl_counters().snapshot()
    return {k[:-len("_traces")]: v for k, v in snap.items()}


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for grouped-query attention: [B,S,Hkv,D] -> [B,S,Hkv*n,D]."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     scale: float | None = None,
                     q_offset: int | jnp.ndarray = 0,
                     kv_offset: int | jnp.ndarray = 0,
                     impl: str = "auto") -> jnp.ndarray:
    """Causal self-attention.

    q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D] with Hq a multiple of Hkv
    (grouped-query). ``q_offset``/``kv_offset`` are absolute positions of the
    first query/key token — sequence-parallel shards pass their slice start
    so masking stays globally causal. Returns [B, Sq, Hq, D].

    ``impl``: "auto" picks the fused Pallas flash kernel
    (``hadoop_tpu.ops.flash``) on TPU backends when the shapes qualify and
    falls back to this portable jnp path otherwise; "flash"/"ref" force.
    """
    if impl != "ref":
        from hadoop_tpu.ops import flash
        if impl == "flash":
            if not flash.supported(q.shape, k.shape, q_offset, kv_offset):
                raise ValueError(
                    "impl='flash' forced but the fused kernel does not "
                    f"support q={q.shape} k={k.shape} q_offset={q_offset} "
                    f"kv_offset={kv_offset} (offsets must be static 0)")
            record_attention_impl("causal", "flash", q.shape, k.shape)
            return flash.flash_attention(q, k, v, scale)
        if jax.default_backend() not in ("cpu", "gpu") and \
                flash.supported(q.shape, k.shape, q_offset, kv_offset):
            record_attention_impl("causal", "flash", q.shape, k.shape)
            return flash.flash_attention(q, k, v, scale)
    record_attention_impl("causal", "ref", q.shape, k.shape)
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = q_offset + jnp.arange(sq)
    kpos = kv_offset + jnp.arange(skv)
    mask = qpos[:, None] >= kpos[None, :]
    logits = jnp.where(mask[None, None, :, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chunk_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    scale: float,
                    q_positions: jnp.ndarray,
                    kv_positions: jnp.ndarray):
    """Attention of q against one K/V chunk, as an online-softmax partial.

    Shapes: q [B,Sq,H,D]; k,v [B,Sk,H,D] (KV heads already expanded).
    Returns (out [B,Sq,H,D] float32 — normalized within this chunk,
    lse [B,Sq,H] float32 — log-sum-exp of visible logits; -inf rows, i.e.
    rows with no visible keys, produce out=0 and act as the merge identity).
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = q_positions[:, None] >= kv_positions[None, :]
    logits = jnp.where(mask[None, None, :, :], logits, -jnp.inf)
    row_max = jnp.max(logits, axis=-1, keepdims=True)            # [B,H,Sq,1]
    safe_max = jnp.where(jnp.isfinite(row_max), row_max, 0.0)
    unnorm = jnp.exp(logits - safe_max)                          # masked -> 0
    denom = jnp.sum(unnorm, axis=-1)                             # [B,H,Sq]
    out = jnp.einsum("bhqk,bkhd->bqhd", unnorm, v.astype(jnp.float32))
    out = out / jnp.maximum(denom, 1e-30).transpose(0, 2, 1)[..., None]
    lse = jnp.where(denom > 0,
                    jnp.log(jnp.maximum(denom, 1e-30)) + safe_max[..., 0],
                    -jnp.inf)
    return out, jnp.transpose(lse, (0, 2, 1))                    # lse [B,Sq,H]


def merge_attention(out_a, lse_a, out_b, lse_b):
    """Merge two (chunk-normalized out, lse) partials into one."""
    lse_new = jnp.logaddexp(lse_a, lse_b)
    safe = jnp.where(jnp.isfinite(lse_new), lse_new, 0.0)
    wa = jnp.where(jnp.isfinite(lse_a), jnp.exp(lse_a - safe), 0.0)
    wb = jnp.where(jnp.isfinite(lse_b), jnp.exp(lse_b - safe), 0.0)
    out = out_a * wa[..., None] + out_b * wb[..., None]
    return out, lse_new
