"""Context-parallel prefill: one monster prompt sharded over the mesh.

The engine's chunked prefill walks a prompt ``prefill_chunk`` tokens
per fused step — time linear in the prompt, HBM linear in the prompt.
This module runs the SAME prefill as a CP job instead: the prompt is
sequence-sharded over a one-axis ``sp`` mesh (``plan.cp_mesh``), every
rank runs the full layer stack on its shard with ring attention
(``parallel/ring_attention.py`` — K/V shards rotate over ICI) or the
all-to-all ulysses strategy (``parallel/ulysses.py``, conf-selectable
via ``serving.longctx.sp.mode``), and the per-layer post-RoPE K/V of
every position comes back as data (``models.decoder.run_layers_kv``)
rather than staying trapped in activations. Prefill wall time divides
by the chip count; no single chip ever holds more than ``S/sp`` of
the context.

Compile-once: the job is jitted at ONE pinned shape —
``serving.longctx.max.tokens`` rounded up to a multiple of
``sp * block_size`` — and every prompt pads up to it (causal masking
makes the padded tail invisible to real positions, and padded KV is
never streamed). ``prefill_compiles`` counts traces exactly like the
engine's step counters; a second trace is a retracing bug.

The CP softmax reassociation (online-softmax merges across ranks) is
not bitwise vs the single-chip reference, which is why every call
into this module sits behind a ``serving.parity=relaxed`` guard
(tpulint's ``parity/relaxed-gated`` checker, with this package exempt
as the tier itself) and behind the A-B guard in ``guard.py``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Iterator, List, Optional, Tuple

import numpy as np

from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.serving.longctx.plan import choose_sp_mode, cp_mesh


@dataclass
class PrefillResult:
    """Everything a CP prefill hands downstream: the last real
    token's logits (first output token samples from these), the
    full-block K/V payloads as a STREAM (the caller forwards them to
    the tiered store without ever holding the whole context), and the
    partial tail block's K/V (never stored — digest chaining only keys
    full blocks — so it seeds the decoder's device-resident tail)."""
    last_logits: np.ndarray                 # [V] float32
    n_full_blocks: int
    blocks: Iterator[Tuple[np.ndarray, np.ndarray]] = field(repr=False)
    tail_k: Optional[np.ndarray] = None     # [L, S % bs, Hkv, Dh]
    tail_v: Optional[np.ndarray] = None
    seconds: float = 0.0
    chips: int = 1
    sp_mode: str = "ring"
    prompt_tokens: int = 0


class ContextParallelPrefiller:
    """One replica's CP prefill executable: mesh + one jitted
    shard_map program at one pinned shape, reused for every monster
    prompt the plane admits."""

    def __init__(self, params, cfg: ModelConfig, *, block_size: int,
                 pad_tokens: int, sp: int = 0, sp_mode: str = "ring",
                 devices=None):
        import jax

        devs = devices if devices is not None else jax.devices()
        self.sp = int(sp) if sp else len(devs)
        self.cfg = cfg
        self.params = params
        self.block_size = int(block_size)
        self.sp_mode = choose_sp_mode(cfg, self.sp, sp_mode)
        quantum = self.sp * self.block_size
        if int(pad_tokens) > cfg.max_seq:
            raise ValueError(
                f"serving.longctx.max.tokens={pad_tokens} exceeds the "
                f"model's max_seq {cfg.max_seq} — positions past the "
                f"rope/pos tables would silently clamp")
        self.pad_tokens = -(-int(pad_tokens) // quantum) * quantum
        if self.pad_tokens > cfg.max_seq:
            # the requested budget is legal but rounding UP to the
            # chip quantum overshoots max_seq (max_seq not divisible
            # by sp*block): round DOWN instead of refusing to start —
            # prompts in the shaved tail reject per-request, loudly
            self.pad_tokens = (cfg.max_seq // quantum) * quantum
            if self.pad_tokens < self.block_size:
                raise ValueError(
                    f"max_seq {cfg.max_seq} below one sp*block "
                    f"quantum ({quantum}) — too many chips for this "
                    f"model's sequence budget")
            import logging
            logging.getLogger(__name__).warning(
                "longctx pad budget rounded DOWN to %d (max_seq %d is "
                "not divisible by sp*block %d); prompts above it are "
                "rejected per-request", self.pad_tokens, cfg.max_seq,
                quantum)
        self.mesh = cp_mesh(self.sp, devices=devs)
        self.prefill_compiles = 0     # traces of the one pinned shape
        self.head_compiles = 0
        self._fn = self._build()
        self._head = self._build_head()

    # ---------------------------------------------------- compiled body

    def _build(self):
        import jax
        from jax.sharding import PartitionSpec as P

        from hadoop_tpu.models.decoder import (ParallelCtx, embed_tokens,
                                               final_hidden, run_layers_kv)
        from hadoop_tpu.ops import rope_frequencies

        from hadoop_tpu.serving.weightplane import is_quantized_tree

        cfg, sp = self.cfg, self.sp
        # int8-resident CP weights: a quantized tree (the engine's own
        # weight plane, shared — no second resident copy) routes every
        # local matmul through the dequantizing qdot inside the decoder
        # body. The ctx flag is the relaxed-tier opt-in; a bitwise
        # deployment never loads a quantized tree in the first place.
        ctx = ParallelCtx(ring_axis="sp", ring_size=sp,
                          sp_mode=self.sp_mode,
                          relaxed_qweights=is_quantized_tree(self.params))

        def local(params, tokens):
            # tokens: this rank's [S_pad / sp] shard
            cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq,
                                        cfg.rope_theta)
            h = embed_tokens(params, tokens[None, :], cfg, ctx)
            h, (ks, vs) = run_layers_kv(h, params["layers"], cfg, ctx,
                                        cos, sin)
            h = final_hidden(params, h, cfg, ctx)
            # [S_local, D], [L, S_local, Hkv, Dh] x2 — K/V leave as
            # DATA, post-RoPE, exactly the engine's pool row layout
            return h[0], ks[:, 0], vs[:, 0]

        sharded = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P("sp")),
            out_specs=(P("sp", None), P(None, "sp", None, None),
                       P(None, "sp", None, None)))

        def impl(params, tokens):
            # python side effect at trace time only: the compile-once
            # counter (same pattern as the engine's step counters)
            self.prefill_compiles += 1
            return sharded(params, tokens)

        return jax.jit(impl)

    def _build_head(self):
        import jax

        from hadoop_tpu.models.decoder import head_matrix
        from hadoop_tpu.serving.weightplane import is_qtensor, qhead
        cfg = self.cfg

        def impl(params, row):
            self.head_compiles += 1
            head = params["embed"] if cfg.tie_embeddings \
                else params.get("lm_head")
            if is_qtensor(head):
                return qhead(params, row, cfg).astype(np.float32)
            return (row @ head_matrix(params, cfg, row.dtype)).astype(
                np.float32)

        return jax.jit(impl)

    # -------------------------------------------------------- the job

    def cp_prefill(self, tokens: List[int]) -> PrefillResult:
        """Prefill ``tokens`` across the mesh. Relaxed-tier entry
        point (``parity/relaxed-gated``): callers outside this package
        must sit under a ``serving.parity=relaxed`` guard."""
        import jax.numpy as jnp

        s = len(tokens)
        if s < 2:
            raise ValueError("longctx prefill needs at least 2 tokens")
        if s > self.pad_tokens:
            raise ValueError(
                f"prompt ({s} tokens) exceeds the pinned longctx "
                f"budget {self.pad_tokens} (serving.longctx.max.tokens)")
        padded = np.zeros((self.pad_tokens,), np.int32)
        padded[:s] = tokens
        t0 = time.monotonic()
        # runtime comm ledger dispatch seam: the first call traces the
        # CP program inside this window (binding the ring-hop /
        # all-to-all byte records to "longctx.prefill"); every prefill
        # advances the cp.* byte counters and records its host wall
        # into the htpu_comm histograms. Nothing enters the graph.
        from hadoop_tpu.obs.comm import comm_runtime
        with comm_runtime().step("longctx.prefill"):
            h, ks, vs = self._fn(self.params, jnp.asarray(padded))
            row = np.asarray(h[s - 1])
        logits = np.asarray(self._head(self.params, row))
        seconds = time.monotonic() - t0
        bs = self.block_size
        n_full = s // bs
        tail_k = tail_v = None
        tail_len = s - n_full * bs
        if tail_len:
            tail_k, tail_v = self._slice_seq(ks, vs, n_full * bs, s)
        return PrefillResult(
            last_logits=logits, n_full_blocks=n_full,
            blocks=self._iter_blocks(ks, vs, n_full),
            tail_k=tail_k, tail_v=tail_v, seconds=seconds,
            chips=self.sp, sp_mode=self.sp_mode, prompt_tokens=s)

    # -------------------------------------------- shard-order streaming

    @staticmethod
    def _seq_shards(arr):
        """(start, shard) per device shard, in sequence order — axis 1
        is the sequence axis of the [L, S_pad, Hkv, Dh] KV. The shard
        payload is NOT materialized here: callers np.asarray only the
        shards they actually consume (the tail slice must not pull the
        whole context to host on the TTFT path)."""
        shards = sorted(arr.addressable_shards,
                        key=lambda sh: sh.index[1].start or 0)
        for sh in shards:
            yield (sh.index[1].start or 0), sh

    def _iter_blocks(self, ks, vs, n_full: int
                     ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Yield full-block [L, bs, Hkv, Dh] (K, V) payloads in chain
        order, pulling ONE rank's shard to host at a time — the
        streamed-ingest shape: the full context never materializes as
        one host array on this path."""
        bs = self.block_size
        limit = n_full * bs
        for (k_off, ksh), (_, vsh) in zip(self._seq_shards(ks),
                                          self._seq_shards(vs)):
            if k_off >= limit:
                return
            k_np = np.asarray(ksh.data)
            v_np = np.asarray(vsh.data)
            for off in range(0, k_np.shape[1], bs):
                if k_off + off + bs > limit:
                    return
                yield (k_np[:, off:off + bs], v_np[:, off:off + bs])

    def _slice_seq(self, ks, vs, lo: int, hi: int):
        """Host copy of sequence positions [lo, hi) — the partial tail
        block (never crosses a shard: shard boundaries are multiples of
        block_size and hi - lo < block_size). Only the OWNING shard is
        pulled to host."""
        local = self.pad_tokens // self.sp
        for (off, ksh), (_, vsh) in zip(self._seq_shards(ks),
                                        self._seq_shards(vs)):
            if off <= lo < off + local:
                k_np = np.asarray(ksh.data)
                v_np = np.asarray(vsh.data)
                return (k_np[:, lo - off:hi - off],
                        v_np[:, lo - off:hi - off])
        raise AssertionError(f"tail [{lo},{hi}) not in any shard")
