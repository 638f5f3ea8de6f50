"""Ring attention: causal attention over a sequence-sharded mesh axis.

Each rank owns a contiguous sequence shard of Q/K/V. K/V shards rotate
around the ring with ``ppermute`` (ICI neighbor exchange — the device
analogue of the reference's chained block pipeline, ref:
DataStreamer.java:1656 store-and-forward chain) while every rank
accumulates its queries' attention with the online-softmax merge from
``hadoop_tpu.ops.attention``. Causality is preserved globally because
each chunk is masked with absolute positions; fully-masked chunks merge
as the identity.

Implemented with ``lax.scan`` (not fori_loop) so the whole ring is
reverse-differentiable for training.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hadoop_tpu.ops.attention import (_repeat_kv, chunk_attention,
                                      merge_attention,
                                      record_attention_impl)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, axis_size: int,
                   impl: str = "auto") -> jnp.ndarray:
    """q,k,v: [B, S_local, H(q|kv), D] local shards. Returns [B,S_local,Hq,D].

    Must run inside shard_map with ``axis_name`` bound. ``impl="auto"``
    runs each ring step through the fused Pallas partial
    (ops.flash.flash_attention_partial) on TPU for qualifying shapes:
    the step-0 diagonal is the CAUSAL partial; later chunks run the
    non-causal partial and fold in through the merge weight (an
    invisible chunk's lse is forced to -inf, the merge identity — same
    compute shape every step, so one compiled kernel serves the whole
    ring)."""
    b, sl, hq, d = q.shape
    scale = 1.0 / (d ** 0.5)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # runtime comm ledger (obs/comm.py): each ring hop rotates the raw
    # K/V shards; the per-step total is hops x (K+V shard bytes) — a
    # static trace-time fact recorded OUTSIDE the scan (the scan body
    # traces once, but executes per hop). The flash path skips the
    # step-0 diagonal, so it pays one hop fewer.
    from hadoop_tpu.obs.comm import record_comm, static_nbytes
    kv_bytes = static_nbytes(k) + static_nbytes(v)

    from hadoop_tpu.ops import flash
    use_flash = impl == "flash" or (
        impl == "auto" and jax.default_backend() not in ("cpu", "gpu")
        and flash.partial_supported(q.shape, k.shape))

    record_attention_impl("ring", "flash" if use_flash else "ref",
                          q.shape, k.shape)

    from hadoop_tpu.ops.vma import pvary_to, vma_of
    target = vma_of(q) | vma_of(k) | vma_of(v) | {axis_name}

    if use_flash:
        record_comm("cp.ring", (axis_size - 1) * kv_bytes,
                    (axis_size - 1) * kv_bytes)
        # step 0: the causal diagonal, fused
        out, lse = flash.flash_attention_partial(q, k, v, scale, True)
        out = pvary_to(out, target)
        lse = pvary_to(lse, target)

        def step(carry, i):
            o_acc, l_acc, kc, vc = carry
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
            src = (my - i) % axis_size
            o_i, l_i = flash.flash_attention_partial(q, kc, vc, scale,
                                                     False)
            # visibility by merge weight: chunks from LATER ranks are
            # entirely in this rank's future → identity
            visible = src < my
            l_i = jnp.where(visible, l_i, -jnp.inf)
            o_acc, l_acc = merge_attention(o_acc, l_acc, o_i, l_i)
            return (o_acc, l_acc, kc, vc), None

        (out, _, _, _), _ = jax.lax.scan(
            step, (out, lse, k, v), jnp.arange(1, axis_size))
        return out.astype(q.dtype)

    record_comm("cp.ring", axis_size * kv_bytes, axis_size * kv_bytes)
    n_rep = hq // k.shape[2]
    q_pos = my * sl + jnp.arange(sl)
    out0 = pvary_to(jnp.zeros((b, sl, hq, d), jnp.float32), target)
    lse0 = pvary_to(jnp.full((b, sl, hq), -jnp.inf, jnp.float32), target)

    def step(carry, i):
        out, lse, kc, vc = carry
        src = (my - i) % axis_size          # which shard this K/V chunk is
        kv_pos = src * sl + jnp.arange(sl)
        # GQA expansion + f32 promotion happen HERE, per step: the ring
        # rotates the raw [B,S,Hkv,D] bf16 shard, so each ppermute hop
        # moves n_rep*2x fewer bytes over ICI than rotating expanded
        # float32 copies (the replication and cast are pure local
        # compute the VPU redoes for free each step)
        o_i, l_i = chunk_attention(
            q, _repeat_kv(kc, n_rep).astype(jnp.float32),
            _repeat_kv(vc, n_rep).astype(jnp.float32),
            scale, q_pos, kv_pos)
        out, lse = merge_attention(out, lse, o_i, l_i)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (out, lse, kc, vc), None

    (out, _, _, _), _ = jax.lax.scan(
        step, (out0, lse0, k, v), jnp.arange(axis_size))
    return out.astype(q.dtype)
