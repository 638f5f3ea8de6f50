"""Continuous-batching decode engine over a model family's weights and
pages (``serving/families``: the engine itself names no family).

Design (TPU-first, same rules as the trainer):

- **Fixed shapes, compile once per shape.** ONE step function covers
  the whole lifetime of a replica: every row of the step is "one token
  at one position, scattered into and gathered through a block table" —
  the first ``max_batch`` rows are the running decode lanes, the last
  ``prefill_chunk`` rows are a chunk of some request's prompt. It
  compiles at exactly TWO shapes: decode-only (``[max_batch]`` rows —
  steady-state decode pays nothing for an idle chunk lane) and fused
  (``[max_batch + prefill_chunk]`` rows when a prompt chunk rides
  along). Prompts of any length, admission order and sampling mix ride
  those two executables; ``decode_compiles`` / ``prefill_compiles``
  count their traces so tests and the bench can assert exactly-once
  compilation of each.

- **Paged KV cache.** What a token caches is the family's (K and V per
  KV head; a latent and an index key; K and V in some layers and a
  page's recurrent-state tail in the others): its pools, each
  ``[layers of its kind, num_blocks, *page]``, under ONE block table per
  running request (a list of pool indices). A family may also carry
  per-lane state beside the tables (what a recurrent layer keeps of a
  sequence), set when a lane starts. Each step scatters the new tokens' entries
  into ``table[pos // bs], pos % bs`` and then attends each row to its
  own live pages through its table — requests share one pool with no
  per-request padding waste (the vLLM PagedAttention layout). Block 0
  is a write-off scratch page: inactive rows and chunk padding scatter
  there and attend to nothing, so masking never needs dynamic shapes.

- **Prefix-reuse KV cache.** The pool is refcounted and a radix index
  (block-granular trie keyed by token chunks) remembers fully-filled
  prompt blocks after prefill. A new request whose token prefix walks a
  cached path maps those blocks into its table (incref — shared,
  read-only: full blocks are never rewritten) and prefills only the
  tail; at least the last prompt token is always recomputed so the
  first output token has fresh logits. Blocks whose
  refcount drops to zero stay resident as cache and are evicted LRU
  (leaves first) when the pool runs dry — eviction composes with the
  recompute-preemption path: evict cold cache first, preempt the
  youngest request only when the cache is already dry.

- **Tiered fleet-wide cache.** Pool and radix live in
  ``serving/kvstore`` with two cold tiers behind them: zero-ref blocks
  demote to a host-RAM ring (``serving.kv.host.bytes``) when the HBM
  tier evicts them, and hot shared prefixes persist as blocks on the
  DataNodes (``serving.kv.dfs.enable``) so ANY replica — including one
  that just restarted — maps them back with hedged reads instead of
  re-prefilling. A radix miss at admission consults host, then DFS,
  before falling back to prefill; promotions ride fixed-shape jitted
  page movers (no new compiles). Policy: ``kvstore/tiered.py``.

- **Chunked prefill, fused into the step.** A prompt is prefilled
  ``prefill_chunk`` tokens per engine step in the SAME compiled step
  that advances every running decode — a long prompt cannot
  head-of-line-block the batch: admitted requests keep streaming.

- **Continuous batching.** New requests are admitted at any step
  boundary into free slots (their prefill chunks interleave with
  running decodes); finished requests free their slot and decref their
  blocks immediately. When pool + cache run dry the youngest request is
  preempted — its refs drop and it re-queues for recompute-style
  re-admission (warm: its own prompt blocks usually survive as cache).

- **Device-resident step state.** Block tables, positions, last
  tokens, active mask, sampling params, token budgets and the PRNG
  seed live ON DEVICE and are carried through the donated step. State
  changes ride small event scatters (``_SET_SLOT`` / ``_SET_TABLE``
  / ``_ARM_SLOT``) on admission, prefill completion, page growth,
  preemption and release — events, not steps. The stop-condition scan
  (max_new budget, stop_token) runs INSIDE the compiled step, and the
  host reads back one packed ``[B, k+4]`` bundle per step (sampled
  tokens, emit counts, finished mask, verifier accept lengths; then a
  column for each count the step makes on the device). In
  steady-state decode the hot loop transfers nothing host→device
  (tests pin this with a ``jax.transfer_guard``).

- **One step ahead of the read-back.** Step n+1's inputs are step n's
  DEVICE outputs, so the serving thread's loop is: admit and ensure
  pages for step n+1 → dispatch n+1 → THEN read back and deliver step
  n (the one device→host read of a step, which by then returns at
  once) → publish. The device's queue holds its next step when the
  current one ends; the host's part of an iteration runs under the
  device's instead of beside it. What the host must know at dispatch
  it knows from its own numbers: a lane's next write lands at its
  mirror position plus the step in flight; a prompt's chunks advance
  at dispatch; the lane of a prompt whose last chunk is on the device
  is armed there, from the step's own first sample; a lane at the end
  of its budget is not run again; a row of a bundle belongs to the
  request that held the slot when the step was dispatched. A token is
  delivered no later than one step after the one that made it.
  Everything that is not the steady loop drains first (``_drain``):
  preemption on a dry pool, the tier page movers, ``persist_cache``,
  ``prefill_to_store``, ``stop``, and ``step()`` itself, which runs
  the same iteration whole. An engine that speculates does not run
  ahead: its drafts for step n+1 are made on the host from the tokens
  of step n.

- **Speculative decoding.** A third lane in the SAME compiled step:
  a host-side n-gram index over each request's prompt + generated
  tokens (``serving/speculate.py``, which also states the acceptance
  rule) proposes up to ``serving.speculate.k`` draft tokens per decode
  lane; each lane becomes a group of ``k+1`` rows (last accepted token
  + k drafts at consecutive positions) and the one batched forward
  verifies them all against the paged cache. Greedy lanes accept by
  argmax equality (token-for-token identical to speculation-off),
  sampled lanes by rejection sampling (output distribution exactly the
  target's). Rejected drafts waste only the row: their entries land
  beyond the accepted tip and are rewritten by the next step before
  anything can attend to them, and the radix prefix cache only ever
  sees accepted, block-aligned tokens. The two compiled shapes stay
  two: ``[B*(k+1)]`` and ``[B*(k+1) + chunk]``.

- **Weight plane.** Resident weights follow the per-tensor policy of
  ``serving/weightplane.py``: under ``serving.parity=relaxed`` the
  matmul weights live in HBM as int8 + per-group f32 scales and every
  serving matmul dequantizes them in-register (decode is
  bandwidth-bound: ~4x fewer weight-read bytes is decode speed AND
  freed HBM). ``hbm_bytes`` turns the freed memory into capacity: the
  KV pool and the lane count are sized against the MEASURED
  resident-weight bytes, so the int8 plane admits 2-4x the lanes x
  context of the f32 plane at the same budget. Bitwise
  (the default) compiles zero quantized code: tpulint's
  ``parity/relaxed-gated`` checker holds every qdot/qrows/qhead call.
  A family may bring leaves, once, into the form its matmuls consume
  (``Family.place_weights``: the dense family's ``wq`` / ``wk`` / ``wv``
  joined into ``wqkv``), in place of the loaded ones: the bytes do not
  change. Under ``hbm_bytes`` the budget counts the weights once, so
  the tree is then the engine's own and the replaced leaves are freed
  at construction, whoever else names them; without it the caller's
  tree is left as it was.

- **Long-context lane.** With a ``serving/longctx`` plane attached
  (``attach_longctx`` — ``serving.parity=relaxed`` only), prompts of
  at least ``serving.longctx.min.tokens`` bypass the fused step: CP
  prefill across the replica's mesh, KV streamed into the host/DFS
  tiers, decode through a fixed device window — the prompt never has
  to fit this engine's pool, and the step shapes here stay two.

- **Sharding.** Pass a ``MeshPlan`` (tp only) and the engine places the
  weights with ``parallel.mesh.param_specs`` and the pools by the
  family's spec (KV heads over ``tp``); jit's SPMD partitioner inserts
  the decode collectives.
"""

from __future__ import annotations

import itertools
import logging
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.models.decoder import _norm, head_matrix
from hadoop_tpu.obs.hbm import hbm_ledger
from hadoop_tpu.ops.topk import kth_largest, sortable
# BlockPool/PrefixCache live in the kvstore package now (the tiered
# fleet-wide cache); re-exported here so `from serving.engine import
# BlockPool` keeps working for every existing consumer
from hadoop_tpu.serving.kvstore import (BlockPool, PrefixCache,
                                        TieredKVCache)
from hadoop_tpu.serving.speculate import NgramProposer
# what a token's cache entry is and the layers that read it
from hadoop_tpu.serving import families
# qrows/qhead are RELAXED-TIER entry points — every call sits under an
# `if self._relaxed_weights ...` guard, so serving.parity=bitwise (the
# default) compiles zero quantized code (tpulint-enforced)
from hadoop_tpu.serving.weightplane import (describe_tree,
                                            expert_weight_bytes,
                                            is_qtensor, is_quantized_tree,
                                            qhead, qrows)
from hadoop_tpu.tracing.tracer import (current_context, global_tracer,
                                       phase)
from hadoop_tpu.util.misc import PauseMonitor

log = logging.getLogger(__name__)

_NEG_INF = -1e30

# The process's stall witness (util.misc.PauseMonitor) as the engine runs
# it: a tick every 0.1 s, and a tick later than the threshold is a stall.
# The threshold sits above every oversleep of the chip host's sound runs
# (PERF.md §6, PR 37) and under iteration_seconds' 0.256 s bucket.
STALL_TICK_S = 0.1
STALL_THRESHOLD_S = 0.2

# the phases of one scheduler iteration. They TILE it — nothing encloses
# them — so that on a profiler trace each idle gap of the device falls in
# exactly one (tracing.tracer.phase). ``engine.wait`` is the run loop's
# park while there is nothing to do; the rest is ``step()``.
PHASES = ("engine.wait", "engine.admit", "engine.propose", "engine.pages",
          "engine.dispatch", "engine.readback", "engine.deliver",
          "engine.publish")


# fixed-shape page movers for the cold tiers: one trace each for the
# replica's lifetime (the block index is a traced scalar, the payload
# shape is pinned by the engine config), shared across engine instances
# through jit's module-level cache — tier promotions and demotions ride
# these, never a fresh compile
def _inject_impl(kp, vp, blk, k, v):
    return kp.at[:, blk].set(k), vp.at[:, blk].set(v)


def _extract_impl(kp, vp, blk):
    return kp[:, blk], vp[:, blk]


_INJECT = jax.jit(_inject_impl, donate_argnums=(0, 1))
_EXTRACT = jax.jit(_extract_impl)


# device-resident step-state event movers: the ONLY host→device traffic
# of the steady-state decode loop is these three scatters, and they fire
# on slot lifecycle events (admission, prefill completion, page growth,
# preemption, release) — never per step. Module-level jits like
# _INJECT/_EXTRACT: one trace per state layout for the process
# lifetime, outside the engine's two step-shape counters.
def _set_slot_impl(state, ints, table_row, temp):
    """Scatter one slot's full lane state. ``ints`` packs
    [slot, pos, last_token, active, top_k, out_count, max_new,
    stop_token] so one small upload carries the whole event."""
    slot = ints[0]
    return {
        **state,    # the seed, and a family's own lane state
        "tables": state["tables"].at[slot].set(table_row),
        "positions": state["positions"].at[slot].set(ints[1]),
        "last": state["last"].at[slot].set(ints[2]),
        "active": state["active"].at[slot].set(ints[3] != 0),
        "temps": state["temps"].at[slot].set(temp),
        "topks": state["topks"].at[slot].set(ints[4]),
        "outc": state["outc"].at[slot].set(ints[5]),
        "maxn": state["maxn"].at[slot].set(ints[6]),
        "stopt": state["stopt"].at[slot].set(ints[7]),
    }


def _set_table_impl(state, ints):
    """Scatter one new page into a slot's block table:
    ``ints`` = [slot, index, block]."""
    out = dict(state)
    out["tables"] = state["tables"].at[ints[0], ints[1]].set(ints[2])
    return out


def _arm_slot_impl(state, ints, first):
    """Flip a slot whose prompt is fully cached to a decode lane, from
    the fused step's own ``c_first`` — ``first`` is that device scalar,
    never read by the host on the way, so the next step can be
    dispatched before this one's read-back. ``ints`` = [slot, position,
    out_count]. A first token that is the lane's stop token leaves the
    lane off, as the step's own stop scan would."""
    slot = ints[0]
    stop = state["stopt"][slot]
    out = dict(state)
    out["positions"] = state["positions"].at[slot].set(ints[1])
    out["last"] = state["last"].at[slot].set(first)
    out["active"] = state["active"].at[slot].set(
        ~((stop >= 0) & (first == stop)))
    out["outc"] = state["outc"].at[slot].set(ints[2])
    return out


_SET_SLOT = jax.jit(_set_slot_impl, donate_argnums=(0,))
_SET_TABLE = jax.jit(_set_table_impl, donate_argnums=(0,))
_ARM_SLOT = jax.jit(_arm_slot_impl, donate_argnums=(0,))


# --------------------------------------------------------------- requests

@dataclass
class SamplingParams:
    """Per-request decode controls. ``temperature <= 0`` is greedy;
    ``top_k <= 0`` disables the top-k filter."""
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    stop_token: Optional[int] = None


_req_ids = itertools.count(1)

QUEUED, RUNNING, FINISHED, FAILED = "QUEUED", "RUNNING", "FINISHED", "FAILED"


@dataclass
class GenRequest:
    """One generation request. Tokens stream into ``tokens_out`` (a
    Queue terminated by ``None``); ``done`` fires at completion."""
    prompt: List[int]
    sampling: SamplingParams
    id: int = field(default_factory=lambda: next(_req_ids))
    state: str = QUEUED
    out_tokens: List[int] = field(default_factory=list)
    tokens_out: "queue.Queue" = field(default_factory=queue.Queue)
    done: threading.Event = field(default_factory=threading.Event)
    error: Optional[str] = None
    submitted_at: float = field(default_factory=time.monotonic)
    first_token_at: Optional[float] = None
    # the TTFT timeline (time.monotonic, each set once — a request
    # preempted before its first token keeps its first stamps):
    # submitted → admitted (a lane and pages) → first chunk (just before
    # the step call that carries it) → first token
    admitted_at: Optional[float] = None
    first_chunk_at: Optional[float] = None
    # auth identity for door QoS: the fair admission queue orders
    # pending requests by the tenant's decayed usage share
    tenant: str = ""
    preemptions: int = 0
    prefix_tokens_reused: int = 0     # cached tokens mapped at admission
    # trace context of the request's door span: engine-side spans
    # (admit/preempt/first-token) run on the scheduler thread where no
    # contextvar survives, so the context rides the request itself
    trace_ctx: Optional[Any] = None
    # engine-private placement
    _slot: Optional[int] = None
    _proposer: Optional[Any] = None   # n-gram draft index (speculation)
    _blocks: List[int] = field(default_factory=list)
    _shared_blocks: int = 0           # leading blocks mapped from cache
    _ctx: List[int] = field(default_factory=list)
    _prefill_pos: Optional[int] = None  # next position to prefill
    _admit_seq: int = 0

    def _deliver(self, token: int) -> None:
        if self.first_token_at is None:
            self.first_token_at = time.monotonic()
        self.out_tokens.append(token)
        self.tokens_out.put(token)

    def _finish(self, state: str = FINISHED, error: str = None) -> None:
        self.state = state
        self.error = error
        self.tokens_out.put(None)
        self.done.set()

    def wait(self, timeout: Optional[float] = None) -> List[int]:
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.id} not done")
        if self.state == FAILED:
            raise RuntimeError(self.error or "generation failed")
        return list(self.out_tokens)


@dataclass
class _Flight:
    """A step that is on the device and not read back yet, with what
    the host needs to deliver it later: a row of the bundle belongs to
    the request that held the slot WHEN THE STEP WAS DISPATCHED (the
    slot may have been released, and placed again, since)."""
    packed: Any                     # the [B, G+3+] bundle, on the device
    c_first: Any                    # the chunk's sample (fused shape)
    rows: List[tuple]               # (slot, request) of the lanes it ran
    pre: Optional[GenRequest]       # whose prompt chunk rode along
    n_valid: int                    # tokens of that chunk
    last_chunk: bool                # ... and it was the prompt's last
    proposed: int                   # draft tokens it verifies
    t0: float                       # when its device time began


# ----------------------------------------------------------------- engine
# (_norm and head_matrix come from models.decoder — the engine must
# apply EXACTLY the trained model's norm/head rules or served logits
# silently diverge from training)

# what every step counts of itself on the device: ServingMetrics counters
# fed by the first columns of the packed read-back past the verdict, in
# order, before the family's own
_STEP_COUNTERS = ("steps_argmax_only", "steps_topk")


def _mask_and_scale(logits, temps, topks):
    """The exact top-k mask + temperature transform ``_sample`` draws
    from, rank-polymorphic over leading axes — the speculation
    verifier shares it so the acceptance distribution can never drift
    from the sampler's.

    A row with ``topks > 0`` keeps its ``topks`` largest logits and
    whatever ties the last of them; the threshold is found without a
    sort (``ops/topk.kth_largest``: the same value ``sort(logits)[V -
    k]`` holds, so the same entries stay), and only when some row of the
    call asks for one: the other rows of such a call pay one compare."""
    bits = sortable(logits)
    want = jnp.clip(topks, 1, logits.shape[-1]).astype(jnp.int32)
    # 0 lies under every float's bits: a call with no top-k masks nothing
    kth = jax.lax.cond(jnp.any(topks > 0),
                       lambda: kth_largest(bits, want),
                       lambda: jnp.zeros(topks.shape, jnp.uint32))
    below = (topks > 0)[..., None] & (bits < kth[..., None])
    masked = jnp.where(below, _NEG_INF, logits)
    return masked / jnp.maximum(temps, 1e-6)[..., None]


def _sample(logits, temps, topks, key):
    """logits [T, V] float32; per-row temperature/top-k; greedy when
    temperature <= 0 (the fused decode+sampling step of arxiv
    2502.17728 — sampling stays inside the compiled program so no
    [T, V] logits tensor crosses to the host).

    The work follows what the rows ask for: when no row has a
    temperature the call is an arg-max and nothing else — no mask, no
    scale, no random bits. The step hands a row that nobody reads (a
    free lane, whose sampling parameters stay in the carried state until
    the slot is placed again) a temperature of 0, and a top-k only to a
    row that samples. A row's draw comes from the categorical over ALL
    the call's rows, so it depends on the shape of the call: the fused
    step (``B*G + 1`` rows) and the decode-only step (``B*G``) give a
    sampled lane the same distribution under two streams of bits."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw():
        scaled = _mask_and_scale(logits, temps, topks)
        sampled = jax.random.categorical(key, scaled, axis=-1).astype(
            jnp.int32)
        return jnp.where(temps <= 0, greedy, sampled)

    return jax.lax.cond(jnp.any(temps > 0), draw, lambda: greedy)


class DecodeEngine:
    """Continuous-batching decode over a fixed slot batch and a paged KV
    pool, with prefix reuse and step-fused chunked prefill. Drive it
    either with the background scheduler thread
    (``start``/``submit``/``stop`` — the serving replica) or by calling
    ``step()`` directly (tests, offline bench)."""

    def __init__(self, params, cfg: ModelConfig, *,
                 max_batch: Optional[int] = None, block_size: int = 8,
                 num_blocks: Optional[int] = None,
                 max_context: Optional[int] = None,
                 prefill_chunk: int = 16,
                 prefix_cache: bool = True,
                 kv_host_bytes: int = 0,
                 kv_store_fs=None, kv_store_dir: str = "/kvcache",
                 kv_dfs_min_refs: int = 1, kv_codec: str = "raw",
                 kv_fetch_window: int = 4,
                 speculate_k: int = 0, speculate_ngram: int = 3,
                 admission_queue=None, drain_persist: bool = True,
                 hbm_bytes: int = 0, max_lanes: int = 16,
                 quantize_seconds: float = 0.0,
                 moe_capacity_factor: float = 0.0, moe_shards: int = 0,
                 moe_a2a_codec: str = "int8",
                 plan=None, metrics=None, tracer=None):
        self.cfg = cfg
        # ---- the weight plane: MEASURED resident bytes decide the KV
        # budget. serving.parity=relaxed loads int8 weights + per-group
        # scales (serving/weightplane.py); the freed HBM converts into
        # more decode lanes x context below, at the same hbm_bytes.
        self._relaxed_weights = is_quantized_tree(params)
        # ---- the model family. The planes it is not built for refuse
        # here, by conf key — never a silent wrong layout.
        self._family = families.family_for(cfg, {
            families.RELAXED: self._relaxed_weights,
            families.TP_PLAN: plan is not None,
            "serving.kv.host.bytes": kv_host_bytes,
            "serving.kv.dfs.enable": kv_store_fs is not None,
            "serving.speculate.k": speculate_k,
            "serving.moe.shards": int(moe_shards) > 1},
            moe_capacity_factor=moe_capacity_factor,
            moe_shards=moe_shards, moe_a2a_codec=moe_a2a_codec)
        self.block_size = block_size
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.max_context = min(max_context or cfg.max_seq, cfg.max_seq)
        self.blocks_per_seq = -(-self.max_context // block_size)
        self.s_max = self.blocks_per_seq * block_size
        if self.s_max > cfg.max_seq:
            # never round past the rope/pos-embed tables: positions
            # beyond max_seq would silently clamp (wrong logits)
            self.blocks_per_seq = cfg.max_seq // block_size
            if self.blocks_per_seq == 0:
                raise ValueError(f"block_size {block_size} exceeds the "
                                 f"model's max_seq {cfg.max_seq}")
            self.s_max = self.blocks_per_seq * block_size
        self._q_embed = is_qtensor(params.get("embed"))
        self._q_head = is_qtensor(params["embed"]) if cfg.tie_embeddings \
            else is_qtensor(params.get("lm_head"))
        # cached once: the params tree never changes after construction,
        # and /v1/health scrapes weight_plane() every autoscaler poll
        self._weight_desc = describe_tree(params)
        self.weight_bytes = self._weight_desc["weight_bytes"]
        self.quantize_seconds = quantize_seconds
        # expert stacks: measured resident bytes (the moe_experts HBM
        # component, beside the dense remainder) and their shard count
        # over the replica's chips. Beside stacks split over local chips
        # (or weights over a tp mesh, below) a step hands pools and lane
        # state back replicated over that mesh; _carry_sharding places
        # them so from the first call, or a second one traces again
        self.expert_bytes = expert_weight_bytes(params, cfg)
        self.expert_shards = self._family.expert_shards
        params, self._carry_sharding = self._family.place_experts(params)
        self.hbm_bytes = int(hbm_bytes or 0)
        # a page is what every pool holds of it, in the layers the pool
        # spans
        pools = self._family.pools(block_size)
        itemsize = jnp.dtype(cfg.jax_dtype).itemsize
        self._pool_desc = [
            {"layers": layers, "page": list(page),
             "page_bytes": layers * int(np.prod(page)) * itemsize}
            for layers, page in pools]
        self.block_nbytes = sum(p["page_bytes"] for p in self._pool_desc)
        if self.hbm_bytes:
            # capacity = budget minus what the weights measurably
            # occupy; lanes sized so each can hold a full context
            kv_budget = self.hbm_bytes - self.weight_bytes
            min_blocks = self.blocks_per_seq + 2  # one lane + scratch
            if kv_budget < min_blocks * self.block_nbytes:
                raise ValueError(
                    f"serving.kv.hbm.bytes={self.hbm_bytes} leaves "
                    f"{kv_budget} bytes of KV after {self.weight_bytes} "
                    f"bytes of resident weights — below one "
                    f"{self.s_max}-token lane "
                    f"({min_blocks * self.block_nbytes} bytes)")
            budget_blocks = kv_budget // self.block_nbytes
            if num_blocks is None:
                num_blocks = int(budget_blocks)
            if max_batch is None:
                max_batch = max(1, min(int(max_lanes),
                                       (num_blocks - 1)
                                       // self.blocks_per_seq))
        if max_batch is None:
            max_batch = 4
        self.max_batch = max_batch
        if num_blocks is None:
            num_blocks = max_batch * self.blocks_per_seq + 1
        self.pool = BlockPool(num_blocks, block_size)
        self.metrics = metrics
        if metrics:
            metrics.weight_bytes.set(self.weight_bytes)
        self.tracer = tracer or global_tracer()
        # the tier manager owns the radix index and the cold tiers;
        # the engine stays the device owner (extract/inject below)
        self.kvstore = TieredKVCache(
            self.pool, layers=self._family.page_slots,
            kv_heads=self._family.salt_layout[0],
            head_dim=self._family.salt_layout[1], dtype=cfg.jax_dtype,
            enabled=prefix_cache, host_bytes=kv_host_bytes,
            fs=kv_store_fs, dfs_dir=kv_store_dir,
            dfs_min_refs=kv_dfs_min_refs, codec=kv_codec,
            fetch_window=kv_fetch_window,
            metrics=metrics, tracer=self.tracer,
            extract=self._extract_block)
        self.prefix_cache = self.kvstore.radix

        # under a byte budget the tree is the engine's: the pool was
        # sized as if the weights were resident ONCE, so a leaf the
        # family replaces is freed before the pools are made, and a
        # caller that goes on using its tree hands over a copy
        params = self._family.place_weights(params,
                                            owned=bool(self.hbm_bytes))
        self._mesh = None
        if plan is not None:
            from hadoop_tpu.parallel.mesh import (make_mesh, param_specs,
                                                  shard_params)
            if plan.pp != 1 or plan.sp != 1 or plan.ep != 1:
                raise ValueError("serving shards over tp (and dp) only; "
                                 f"got plan={plan}")
            self._mesh = make_mesh(plan)
            params = shard_params(params, self._mesh, param_specs(cfg, plan))
        self.params = params

        self._pool_shapes = [(layers, num_blocks) + tuple(page)
                             for layers, page in pools]
        self._kv_sharding = None
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._kv_sharding = NamedSharding(self._mesh,
                                              self._family.pool_spec)
            self._carry_sharding = NamedSharding(self._mesh, P())
        self._pools = self._fresh_kv_pools()

        # live HBM ledger (obs/hbm.py): this engine's resident bytes —
        # measured weights + the K/V pool it sized against them —
        # published as htpu_hbm_bytes{component=...} beside the trainer
        # and longctx components; torn down in stop()
        # trailing separator: unregister_prefix("engine@123") must not
        # also match a coexisting "engine@1234..." owner
        self._hbm_owner = f"engine@{id(self)}."
        kv_pool_bytes = num_blocks * self.block_nbytes
        led = hbm_ledger()
        led.register(f"{self._hbm_owner}weights", "weights",
                     lambda: self.weight_bytes - self.expert_bytes)
        if cfg.is_moe:
            # expert stacks get their own component so the autoscaler
            # sees where an MoE replica's HBM actually went
            led.register(f"{self._hbm_owner}experts", "moe_experts",
                         lambda: self.expert_bytes)
        led.register(f"{self._hbm_owner}kv", "kv_pool",
                     lambda: kv_pool_bytes)

        # speculation lane: k draft tokens per decode lane, verified by
        # the same fused step (0 = off; every lane is then one row,
        # exactly the pre-speculation layout)
        self.spec_k = max(0, int(speculate_k))
        self.spec_ngram = max(1, int(speculate_ngram))
        self.spec_proposed = 0
        self.spec_accepted = 0

        # host MIRRORS of the slot state (management reads: page
        # allocation, occupancy, tests). The device copy below is the
        # one the compiled step consumes and advances.
        self._tables = np.zeros((max_batch, self.blocks_per_seq), np.int32)
        self._seq_lens = np.zeros((max_batch,), np.int32)
        self._active = np.zeros((max_batch,), bool)
        self._slots: List[Optional[GenRequest]] = [None] * max_batch
        # device-resident step state: carried (donated) through every
        # step, mutated from the host ONLY by slot lifecycle events via
        # _SET_SLOT/_SET_TABLE/_ARM_SLOT. "seed" replaces the per-step host
        # PRNGKey upload — the key is derived in-graph.
        self._dispatched = 0        # steps ever dispatched: the seed
        # what the family's layers keep of a lane beside its pages (None:
        # nothing), carried in the step state under "lane"
        self._lane_shapes = self._family.lane_state(max_batch)
        self._dstate = self._fresh_dstate()
        # per-step draft proposals (host-filled when speculating); the
        # device-resident zero twins are dispatched on steps with no
        # proposals so an idle speculation lane uploads nothing
        self._draft_tokens = np.zeros((max_batch, self.spec_k), np.int32)
        self._draft_lens = np.zeros((max_batch,), np.int32)
        self._dz_drafts = jnp.zeros((max_batch, self.spec_k), jnp.int32)
        self._dz_lens = jnp.zeros((max_batch,), jnp.int32)
        # the step in flight (dispatched, not read back) and, per lane,
        # the steps it has there: the mirrors above are as of the last
        # step DELIVERED, and a lane's next write lands at its mirror
        # position plus these. The serving thread keeps one step ahead
        # of its own read-back unless the host computes an input of
        # step n+1 from an output of step n, as the draft proposer does.
        self._flight: Optional[_Flight] = None
        self._in_flight = np.zeros((max_batch,), np.int32)
        self._runs_ahead = self.spec_k == 0
        self.steps_run_ahead = 0    # dispatched with the last unread
        self.steps_argmax_only = 0  # no live row sampled (device's count)
        self.steps_topk = 0         # a threshold search ran (device's)

        # the admission seam: a deque by default, or any deque-shaped
        # queue (append/appendleft/popleft/len/[0]) — the door's QoS
        # layer installs a per-tenant weighted-round-robin queue here
        self._pending = admission_queue if admission_queue is not None \
            else deque()                # guarded-by: _cond
        self.drain_persist = drain_persist
        self._admit_counter = itertools.count()
        self._cond = threading.Condition()
        self._sched_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.steps = 0
        self.tokens_generated = 0
        self.occupancy_log: List[int] = []      # active slots per step
        # the loop's own time (PHASES): cumulative seconds per phase
        # (monotone)
        self.phase_s: Dict[str, float] = {}
        self._phase_published: Dict[str, float] = {}    # to the counters
        # (start, engine.wait so far, compiles so far) of the last step()
        # that ran a device step: _log_iteration
        self._iter_prev = None
        self._fused_compiles = 0                # [B + chunk]-row traces
        self._decode_only_compiles = 0          # [B]-row traces
        self._chunk_fill = 0                    # chunk rows used last step
        # prefix-cache lifetime stats (cold-start zeros)
        self.prefix_tokens_seen = 0
        self.prefix_tokens_matched = 0
        self.prefix_evictions = 0
        self.prefix_inserted_blocks = 0
        # the long-context plane (serving/longctx): attached after
        # construction (it reads this engine's kvstore) and ONLY under
        # serving.parity=relaxed — the CP softmax reassociation is not
        # bitwise, so the bitwise default must keep it unreachable
        self._relaxed_longctx = None
        n_pools = len(self._pool_shapes)
        self._step_fn = jax.jit(
            self._step_impl, donate_argnums=tuple(range(1, n_pools + 2)))
        self._start_lane_fn = jax.jit(self._start_lane_impl,
                                      donate_argnums=(0,))

    # the first two pools by the names they had while every family had
    # two (tests and the benchmark's compiled-program check read them)
    @property
    def _kp(self):
        return self._pools[0]

    @_kp.setter
    def _kp(self, pool) -> None:
        self._pools[0] = pool

    @property
    def _vp(self):
        return self._pools[1]

    @_vp.setter
    def _vp(self, pool) -> None:
        self._pools[1] = pool

    def attach_longctx(self, plane) -> None:
        """Wire the long-context serving plane (``serving/longctx``):
        prompts at least ``plane.min_tokens`` long route to it from
        ``submit`` instead of the fused-step path. Caller is the
        relaxed-tier gate (``longctx_plane_from_conf`` re-validates)."""
        self._family.refuse({"serving.longctx.enable": True})
        self._relaxed_longctx = plane

        def wake() -> None:
            # a drain parked on `idle` in stop() waits on the scheduler
            # condition; without this, a completion on the plane's own
            # worker thread would only be seen at the drain deadline
            with self._cond:
                self._cond.notify_all()

        plane.on_done = wake

    @property
    def decode_compiles(self) -> int:
        """Traces of the decode-only shape of the step ([B] rows —
        dispatched when nothing is prefilling, so pure decode never
        pays for idle chunk rows). At most 1 or shapes are retracing."""
        return self._decode_only_compiles

    @property
    def prefill_compiles(self) -> int:
        """Traces of the fused shape of the step ([B + chunk] rows —
        dispatched when a prompt chunk rides along). At most 1."""
        return self._fused_compiles

    # ------------------------------------------------- tier page movers

    def _extract_block(self, blk: int):
        """One page's (K, V) payload to host numpy — the demotion /
        persistence copy (the cold tiers move two pools: a family with
        another set of them refuses the tiers). Fixed-shape jit, compiled
        once per layout. Drains first (scheduler lock held, as for every
        tier move)."""
        self._drain()
        k, v = _EXTRACT(self._kp, self._vp, jnp.int32(blk))
        return np.asarray(k), np.asarray(v)

    def _inject_block(self, blk: int, k, v) -> None:
        """Scatter a cold-tier payload into pool page ``blk`` (donated
        buffers — no pool-sized copy, no new compile). Drains first."""
        self._drain()
        self._pools = list(_INJECT(
            self._kp, self._vp, jnp.int32(blk),
            jnp.asarray(k, self._kp.dtype),
            jnp.asarray(v, self._vp.dtype)))

    # ----------------------------------------------------- compiled body

    def _start_lane_impl(self, state, pools, ints):
        """A lane starts: its family state set from the pools. ``ints`` =
        [slot, the last page it maps from the prefix cache (0: none)]."""
        return {**state, "lane": self._family.start_lane(
            state["lane"], pools, ints[0], ints[1])}

    def _step_impl(self, params, *rest):
        """The ONE compiled function: every row is one token at one
        position. The first ``max_batch * (spec_k + 1)`` rows are the
        decode lanes — each lane a GROUP of ``spec_k + 1`` rows (its
        last accepted token plus up to ``spec_k`` draft tokens at
        consecutive positions, sharing the lane's block table row);
        when ``chunk`` rides along, the last ``prefill_chunk`` rows are
        consecutive positions of one request's prompt chunk.
        Scatter-all-then-attend makes earlier rows' entries visible to
        later positions within the same step; each row's length
        ``position + 1`` (0 for an inactive row) is its causal mask.

        ``rest`` is ``*pools, state, drafts, draft_lens, chunk``: the
        family's donated pools ``[layers, blocks, *page]`` (two of them
        for most families: ``kp, vp``) come back as the same buffers.
        The layers are the family's (``serving/families``: ``run_layers``
        over the rows built here; its scans carry the pools whole and
        address a layer's pages at ``l * blocks + page``, so no slab is
        sliced out, stacked back or copied); embedding, head, sampling,
        speculation's verify and the stop scan are here.

        All lane state arrives in (and leaves through) the donated
        ``state`` dict (the family's own under ``"lane"``, handed to
        ``run_layers`` and taken back from it): positions advance by the
        accepted length, the
        stop-condition scan retires lanes in-graph, and the PRNG key
        derives from the carried seed — the host uploads nothing per
        steady-state decode step and reads back one packed ``[B, spec_k
        + 4]`` bundle (tokens | emit_count | finished | accept_len), a
        column wider for each of ``_STEP_COUNTERS`` and for each counter
        the family's layers feed.

        The head and the sampler do what the step's live rows ask for
        (``head_sample``): logits are made for the decode rows and the
        ONE chunk row that is read, and ``_sample`` is an arg-max unless
        a live lane — or the lane whose chunk rides along — has a
        temperature. Greedy tokens do not depend on any of it. A sampled
        lane's draw comes from the categorical over the call's rows, so
        the first token of a sampled prompt (drawn in a fused step, among
        ``B*G + 1`` rows) and a decode token drawn beside a chunk come
        from another stream of bits than in a decode-only step: the same
        distribution, as between the two shapes before.

        Compiled at exactly TWO shapes for the replica's lifetime
        (decode-only, and with a prompt chunk riding along): any
        further trace is a retracing bug the counters expose."""
        *pools, state, drafts, draft_lens, chunk = rest
        cfg = self.cfg
        B, S = self.max_batch, self.spec_k
        G = S + 1
        # python side effect at trace time only: shape-family counters
        if chunk is None:
            self._decode_only_compiles += 1
        else:
            self._fused_compiles += 1
        tables_s = state["tables"]
        positions_s = state["positions"]
        active_s = state["active"]
        temps_s, topks_s = state["temps"], state["topks"]
        outc, maxn, stopt = state["outc"], state["maxn"], state["stopt"]
        drafts = drafts.astype(jnp.int32)
        gj = jnp.arange(G)

        # ---- build the decode rows from the carried state
        if S:
            row_tok = jnp.concatenate([state["last"][:, None], drafts],
                                      axis=1)
        else:
            row_tok = state["last"][:, None]
        row_pos = positions_s[:, None] + gj[None, :]
        row_act = active_s[:, None] & (gj[None, :] <=
                                       draft_lens[:, None])
        bps = tables_s.shape[1]
        tokens = row_tok.reshape(B * G)
        positions = row_pos.reshape(B * G)
        active = row_act.reshape(B * G)
        tables = jnp.broadcast_to(tables_s[:, None, :],
                                  (B, G, bps)).reshape(B * G, bps)
        if chunk is not None:
            # chunk rows: tokens uploaded, everything else derived from
            # the prefilling slot's carried state (table row, sampling
            # params) — ints = [slot, start, n_valid]
            c_tok, c_ints = chunk
            c_slot, c_start, c_n = c_ints[0], c_ints[1], c_ints[2]
            C = self.prefill_chunk
            cj = jnp.arange(C)
            tokens = jnp.concatenate([tokens, c_tok.astype(jnp.int32)])
            positions = jnp.concatenate([positions, c_start + cj])
            active = jnp.concatenate([active, cj < c_n])
            tables = jnp.concatenate(
                [tables, jnp.broadcast_to(tables_s[c_slot][None, :],
                                          (C, bps))], axis=0)
        # inactive draft rows can sit past the end of the table/rope
        # range; clip (identity for every live row) and let the active
        # mask discard their output
        pos = jnp.minimum(positions, self.s_max - 1)

        cos, sin = self._family.rope_tables()
        with jax.named_scope("embed"):
            if self._relaxed_weights and self._q_embed:
                # quantized embedding gather (policy-selectable; norms
                # and pos_embed never quantize)
                h = qrows(params["embed"], tokens, cfg.jax_dtype)
            else:
                h = params["embed"][tokens]
            if not cfg.use_rope:
                h = h + params["pos_embed"][
                    jnp.clip(pos, 0, cfg.max_seq - 1)]
        blk = jnp.take_along_axis(
            tables, (pos // self.block_size)[:, None], axis=1)[:, 0]
        blk = jnp.where(active, blk, BlockPool.SCRATCH)
        off = pos % self.block_size
        # causal by length: a live row attends to positions <= its own;
        # an inactive row attends to nothing and gets zeros
        lens = jnp.where(active, pos + 1, 0)
        h, pools, lane, stats = self._family.run_layers(
            params, h, tuple(pools), state["lane"], {
                "pos": pos, "blk": blk, "off": off, "active": active,
                "lens": lens, "tables": tables, "tables_s": tables_s,
                "B": B, "G": G, "block": self.block_size,
                "cos": cos, "sin": sin,
                "chunk_slot": None if chunk is None else c_slot,
                "chunk_n": None if chunk is None else c_n})
        with jax.named_scope("head_sample"):
            # ---- the rows that are read: every decode row, and of the
            # chunk's rows the ONE at the chunk's tip (its sample is the
            # prompt's first token when the chunk is the prompt's last)
            live = active[:B * G]
            temps = jnp.broadcast_to(temps_s[:, None], (B, G)).reshape(B * G)
            topks = jnp.broadcast_to(topks_s[:, None], (B, G)).reshape(B * G)
            if chunk is not None:
                h = jnp.concatenate(
                    [h[:B * G], jax.lax.dynamic_slice_in_dim(
                        h, B * G + c_n - 1, 1)])
                live = jnp.concatenate([live, (c_n > 0)[None]])
                temps = jnp.concatenate([temps, temps_s[c_slot][None]])
                topks = jnp.concatenate([topks, topks_s[c_slot][None]])
            # ... and what they ask of the sampler: a free lane keeps its
            # parameters in the carried state until the slot is placed
            # again, and asks for nothing; a greedy row has no use for a
            # top-k
            temps = jnp.where(live, temps, 0.0)
            topks = jnp.where(temps > 0, topks, 0)
            h = _norm(h, params["final_norm_w"], params.get("final_norm_b"),
                      cfg)
            if self._relaxed_weights and self._q_head:
                logits = qhead(params, h, cfg).astype(jnp.float32)
            else:
                logits = (h @ head_matrix(params, cfg, h.dtype)).astype(
                    jnp.float32)

            # ---- sample + verify (the key derives from the carried seed:
            # identical to the old host-side PRNGKey(step_counter))
            key = jax.random.PRNGKey(state["seed"])
            c_first = None
            if S == 0:
                # no speculation: one sample per row. A decode-only step
                # is bitwise the pre-speculation engine (same _sample over
                # the same rows with the same key); a fused step draws a
                # sampled row from the categorical of its B + 1 rows
                sampled = _sample(logits, temps, topks, key)
                out = sampled[:B][:, None]                      # [B, 1]
                accept = jnp.zeros((B,), jnp.int32)
                if chunk is not None:
                    c_first = sampled[B * G]
            else:
                ku, kr_, kc_ = jax.random.split(key, 3)
                dec_logits = logits[:B * G].reshape(B, G, -1)
                V = dec_logits.shape[-1]
                greedy_tok = jnp.argmax(dec_logits, axis=-1).astype(
                    jnp.int32)                                  # [B, G]
                drafted = jnp.arange(S)[None, :] < draft_lens[:, None]
                agree = drafted & (drafts == greedy_tok[:, :S])

                def accepted(ok):                               # [B] 0..S
                    return jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                                   axis=1)

                def greedy_at(a):
                    return jnp.take_along_axis(greedy_tok, a[:, None],
                                               axis=1)[:, 0]

                def verify_greedy():
                    # greedy lanes accept by argmax equality and take the
                    # argmax at group index `accept` as their bonus token
                    a = accepted(agree)
                    return a, greedy_at(a)

                def verify_sampled():
                    # target distribution per row: the exact _sample
                    # transform (top-k mask, temperature) in probability
                    # space
                    scaled = _mask_and_scale(
                        dec_logits, temps[:B * G].reshape(B, G),
                        topks[:B * G].reshape(B, G))
                    probs = jax.nn.softmax(scaled, axis=-1)     # [B, G, V]
                    # sampled lanes accept by rejection sampling — the
                    # n-gram draft is a point mass, so accept iff
                    # u < p_target(draft)
                    u = jax.random.uniform(ku, (B, S))
                    p_draft = jnp.take_along_axis(
                        probs[:, :S], drafts[..., None], axis=2)[..., 0]
                    greedy_lane = temps_s <= 0
                    a = accepted(jnp.where(greedy_lane[:, None], agree,
                                           drafted & (u < p_draft)))
                    # the bonus token at group index `accept`: sampled
                    # lanes draw from the target with a rejected draft
                    # token removed and renormalized (exact speculative
                    # sampling — all-accepted lanes sample the unmodified
                    # target)
                    p_a = jnp.take_along_axis(
                        probs, jnp.broadcast_to(a[:, None, None],
                                                (B, 1, V)), axis=1)[:, 0]
                    rejected = a < draft_lens
                    d_a = jnp.take_along_axis(
                        drafts, jnp.minimum(a, S - 1)[:, None],
                        axis=1)[:, 0]
                    adj = jnp.where(rejected[:, None] &
                                    (jnp.arange(V)[None, :] == d_a[:, None]),
                                    0.0, p_a)
                    adj = adj / jnp.maximum(adj.sum(-1, keepdims=True),
                                            1e-30)
                    samp_a = jax.random.categorical(
                        kr_, jnp.log(jnp.maximum(adj, 1e-38)),
                        axis=-1).astype(jnp.int32)
                    return a, jnp.where(greedy_lane, greedy_at(a), samp_a)

                # the softmax and the rejection run only when a live lane
                # samples (the same question _sample asks of its rows)
                accept, final = jax.lax.cond(
                    jnp.any(temps[:B * G] > 0), verify_sampled,
                    verify_greedy)
                draft_pad = jnp.concatenate(
                    [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)
                out = jnp.where(gj[None, :] < accept[:, None],
                                draft_pad, final[:, None])      # [B, G]
                if chunk is not None:
                    c_first = _sample(logits[B * G:], temps[B * G:],
                                      topks[B * G:], kc_)[0]
            # what the sampler was asked for, as the device saw it: the
            # step took the arg-max-only arm; a threshold search ran
            asked = jnp.stack([~jnp.any(temps > 0),
                               jnp.any(topks > 0)]).astype(jnp.int32)

            # ---- in-graph stop-condition scan: budget clamp, stop_token
            # cut, lane retirement — the host reads the verdict, it does
            # not compute it
            remaining = jnp.maximum(maxn - outc, 0)
            n_emit = jnp.minimum(accept + 1, remaining)
            has_stop = stopt >= 0
            stop_hits = (out == stopt[:, None]) & has_stop[:, None]
            first_stop = jnp.min(
                jnp.where(stop_hits, gj[None, :], G + 1), axis=1)
            n_emit = jnp.minimum(n_emit, first_stop + 1)
            n_emit = jnp.where(active_s, n_emit, 0)
            stop_hit = first_stop < n_emit
            finished = active_s & ((outc + n_emit >= maxn) | stop_hit)
            last_idx = jnp.maximum(n_emit - 1, 0)
            new_last = jnp.where(
                active_s,
                jnp.take_along_axis(out, last_idx[:, None], axis=1)[:, 0],
                state["last"])
            new_state = {
                "tables": tables_s,
                "positions": positions_s + n_emit,
                "last": new_last,
                "active": active_s & ~finished,
                "temps": temps_s,
                "topks": topks_s,
                "outc": outc + n_emit,
                "maxn": maxn,
                "stopt": stopt,
                "seed": state["seed"] + 1,
                "lane": lane,
            }
            packed = jnp.concatenate(
                [out, n_emit[:, None], finished.astype(jnp.int32)[:, None],
                 accept[:, None]],
                axis=1)                                         # [B, G + 3]
            # the step's own counts and the layers': a column each, in
            # row 0 (``_STEP_COUNTERS`` then the family's ``counters``)
            counts = jnp.concatenate([asked, stats]) \
                if self._family.counters else asked
            packed = jnp.concatenate(
                [packed, jnp.zeros((B, counts.shape[0]),
                                   jnp.int32).at[0].set(counts)], axis=1)
        if chunk is None:
            return (*pools, new_state, packed)
        return (*pools, new_state, packed, c_first)

    # -------------------------------------------------------- public face

    def submit(self, prompt: List[int],
               sampling: Optional[SamplingParams] = None,
               trace_ctx=None, tenant: str = "") -> GenRequest:
        sampling = sampling or SamplingParams()
        if not prompt:
            raise ValueError("empty prompt")
        if sampling.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (prefill "
                             "always emits the first token)")
        if self._relaxed_longctx is not None and \
                len(prompt) >= self._relaxed_longctx.min_tokens:
            # the long-context lane: CP prefill across the mesh, KV
            # streamed into the cold tiers, working-set decode — the
            # prompt never has to fit this engine's pool or s_max
            return self._relaxed_longctx.longctx_submit(
                prompt, sampling,
                trace_ctx=trace_ctx or current_context(), tenant=tenant)
        if len(prompt) + sampling.max_new_tokens > self.s_max:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({sampling.max_new_tokens})"
                f" exceeds engine max_context {self.s_max}")
        # fail fast on requests the pool can NEVER satisfy — parking
        # them in the admission queue would wedge the queue forever
        # (prefix hits could shrink the footprint, but cache contents
        # are transient and must not admit what can't run cold)
        pages = -(-(len(prompt) + sampling.max_new_tokens)
                  // self.block_size)
        if pages > self.pool.num_usable:
            raise ValueError(
                f"request needs {pages} KV pages but the pool holds only "
                f"{self.pool.num_usable} — it could never run alone")
        req = GenRequest(prompt=list(prompt), sampling=sampling,
                         trace_ctx=trace_ctx or current_context(),
                         tenant=tenant)
        with self._cond:
            self._pending.append(req)
            depth = len(self._pending)
            self._cond.notify_all()
        if self.metrics:
            self.metrics.requests.incr()
            self.metrics.queue_depth.set(depth)
        return req

    @property
    def num_active(self) -> int:
        return int(self._active.sum())

    @property
    def num_prefilling(self) -> int:
        return sum(1 for r in self._slots
                   if r is not None and r._prefill_pos is not None)

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._pending)

    @property
    def prefill_backlog(self) -> int:
        """Prompt tokens still awaiting prefill across admitted
        requests — the stall gauge the autoscaler sizes prefill
        capacity against. Read lock-free from the health thread: each
        slot's fields are snapshotted once, so a prefill completing
        mid-scan reads as 0, never as a TypeError."""
        total = 0
        for r in list(self._slots):
            if r is None:
                continue
            pos = r._prefill_pos
            if pos is not None:
                total += max(0, len(r._ctx) - pos)
        return total

    @property
    def _local_idle(self) -> bool:
        """No fused-step work: the RUN LOOP's wait predicate. It must
        NOT consult the longctx plane — the plane serves on its own
        worker thread, and parking the scheduler on its busyness would
        hot-spin no-op step() calls against the very CP prefill it is
        waiting for."""
        with self._cond:
            has_pending = bool(self._pending)
        return not has_pending and all(r is None for r in self._slots)

    @property
    def idle(self) -> bool:
        """Nothing in flight ANYWHERE (fused step + longctx plane) —
        the drain/stop predicate."""
        lc = self._relaxed_longctx
        return self._local_idle and (lc is None or lc.idle)

    def longctx_stats(self) -> Dict[str, Any]:
        """The long-context plane's observability face (health, bench):
        ``{"enabled": False}`` when no plane is attached."""
        lc = self._relaxed_longctx
        return lc.stats() if lc is not None else {"enabled": False}

    def weight_plane(self) -> Dict[str, Any]:
        """The resident-weight policy and the capacity it bought —
        /v1/health, the registry record and the bench all read this:
        dtype, MEASURED weight bytes, quantize-at-load seconds, and the
        lanes x context the KV budget admits at those bytes."""
        desc = self._weight_desc
        return {
            "parity": "relaxed" if self._relaxed_weights else "bitwise",
            "dtype": desc["dtype"],
            "weight_bytes": self.weight_bytes,
            "quantize_seconds": self.quantize_seconds,
            "quantized_leaves": desc["int8_leaves"],
            "hbm_bytes": self.hbm_bytes,
            "lanes": self.max_batch,
            "max_context": self.s_max,
            "kv_capacity_tokens": self.pool.num_usable * self.block_size,
            "lanes_x_context": self.max_batch * self.s_max,
            # expert placement, beside weight_dtype for the autoscaler
            # and the registry record (0s on a dense checkpoint)
            "experts": self.cfg.n_experts,
            "expert_shards": self.expert_shards,
            "expert_bytes": self.expert_bytes,
            **self._family.describe_experts(
                self.max_batch * (self.spec_k + 1))}

    def cache_stats(self) -> Dict[str, Any]:
        """Prefix-cache + chunked-prefill observability (health, bench)."""
        seen = self.prefix_tokens_seen
        return {
            "enabled": self.prefix_cache is not None,
            "cached_blocks": len(self.prefix_cache)
                             if self.prefix_cache is not None else 0,
            "tokens_seen": seen,
            "tokens_matched": self.prefix_tokens_matched,
            "hit_rate": (self.prefix_tokens_matched / seen) if seen
                        else 0.0,
            "evictions": self.prefix_evictions,
            "inserted_blocks": self.prefix_inserted_blocks,
            "prefill_chunk": self.prefill_chunk,
            # what a page is made of: every pool's layers, page shape
            # and bytes a page (they add up to ``block_nbytes``)
            "pools": self._pool_desc,
            "block_nbytes": self.block_nbytes,
            # per-tier traffic: HBM radix hits vs host-ring and DFS
            # recoveries, demotions/promotions/persists
            "tiers": self.kvstore.stats(),
            # speculation lane: draft tokens proposed vs accepted
            # (engine-local — bench A-B runs must not bleed into each
            # other through the process-global metrics source)
            "speculate": {
                "k": self.spec_k,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "accept_rate": (self.spec_accepted / self.spec_proposed)
                               if self.spec_proposed else 0.0,
            },
        }

    # ------------------------------------------------------ the scheduler

    def step(self) -> int:
        """One scheduler iteration, whole: admit waiting requests into
        free slots (mapping any cached prefix), propose draft tokens for
        the speculation lane, ensure every decoding request has pages
        for this step's tokens, run the fused decode+prefill-chunk
        step, read it back, retire finished requests. Nothing is in
        flight on return, and the tokens emitted are returned: tests,
        ``generate()`` and the offline bench drive this; the serving
        thread (``_run_loop``) runs the same iteration one step ahead
        of its read-back."""
        with self._sched_lock:
            return self._iterate(ahead=False)

    def _iterate(self, ahead: bool) -> int:
        """admit → (propose) → pages → dispatch step n+1 → read back
        and deliver step n → publish; scheduler lock held. With
        ``ahead`` the new step stays in flight, so the device holds its
        next step while the host delivers, admits and dispatches;
        without, it is read back and delivered here too."""
        began = (time.monotonic(),
                 self.phase_s.get("engine.wait", 0.0),
                 self._decode_only_compiles + self._fused_compiles)
        try:
            with self._phase("engine.admit"):
                self._admit()
            if self.spec_k:
                with self._phase("engine.propose"):
                    self._propose_drafts()
            with self._phase("engine.pages"):
                self._ensure_blocks()
            with self._phase("engine.dispatch"):
                flight = self._dispatch()
            before, self._flight = self._flight, flight
            emitted = self._deliver(before) if before is not None else 0
            if flight is not None:
                self._log_iteration(began)
                if not ahead or all(r is None for r in self._slots):
                    # (nobody is left to run: what flies is stale rows)
                    emitted += self._drain()
        except BaseException:
            # a failed step takes what was in flight with it
            self._flight = None
            self._in_flight[:] = 0
            raise
        with self._phase("engine.publish"):
            self._publish_metrics()
        return emitted

    def _drain(self) -> int:
        """Read back and deliver the step in flight, if there is one;
        scheduler lock held. Everything that is not the steady loop
        calls this first — a preemption on a dry pool, the tier page
        movers, ``persist_cache``, ``prefill_to_store``, ``stop`` — so
        that the mirrors, the pool's counts and the radix index are
        those of a device with nothing pending."""
        flight, self._flight = self._flight, None
        return self._deliver(flight) if flight is not None else 0

    def _phase(self, name: str) -> phase:
        return phase(name, self.phase_s)

    def _log_iteration(self, began) -> None:
        """``began`` opens an iteration that ran a device step and closes
        the one before it: the seconds from that one's start to this
        one's, less ``engine.wait`` in between (an idle engine is not
        stalled; a hole between two steps of a busy one is in here,
        where ``decode_step`` cannot see it). An iteration in which a
        step shape compiled is left out: the compile counters hold it."""
        prev, self._iter_prev = self._iter_prev, began
        if prev is None or not self.metrics:
            return
        (t0, waited0, compiled0), (t1, waited1, compiled1) = prev, began
        if compiled0 == compiled1:
            self.metrics.iteration_hist.add(t1 - t0 - (waited1 - waited0))

    def _propose_drafts(self) -> None:
        """Fill the per-lane draft buffers from each running request's
        n-gram index, clamped so speculation can never out-emit the
        request's remaining token budget (each step emits at most
        draft_len + 1 tokens; the last budgeted token must come from a
        verified sample, so a lane with 1 token left proposes none)."""
        if self.spec_k == 0:
            return
        self._draft_lens[:] = 0
        for slot, req in enumerate(self._slots):
            if req is None or req._prefill_pos is not None or \
                    not self._active[slot]:
                continue
            budget = min(self.spec_k,
                         req.sampling.max_new_tokens
                         - len(req.out_tokens) - 1)
            if budget <= 0:
                continue
            toks = req._proposer.propose(budget)
            if toks:
                self._draft_tokens[slot, :len(toks)] = toks
                self._draft_lens[slot] = len(toks)

    def _admit(self) -> None:
        while True:
            with self._cond:
                if not self._pending:
                    return
                req = self._pending[0]
            slot = next((i for i, r in enumerate(self._slots)
                         if r is None), None)
            if slot is None:
                return
            # prompt plus already-generated tokens (preempted requests
            # resume by recompute — often warm, off their own cached
            # prompt blocks); the first decode step after prefill needs
            # one more page slot for its token
            ctx = req.prompt + req.out_tokens
            shared: List[int] = []
            nodes = []
            cold = []
            limit = 0
            if self.prefix_cache is not None:
                # cap the match below the full context: the last token
                # must always be prefilled so its logits exist to
                # sample the first output token from
                limit = (len(ctx) - 1) // self.block_size
                nodes = self.prefix_cache.match_nodes(ctx)[:limit]
                if nodes:
                    shared = [n.block for n in nodes]
                    # pin before any eviction this admission might do
                    self.pool.incref(shared)
            need = -(-(len(ctx) + 1) // self.block_size) - len(shared)
            private = self._try_alloc(need)
            if private is None:
                # running requests outrank waiting ones (preemption only
                # keeps the running set going, never feeds admission) —
                # wait for retirements to return pages. The cold-tier
                # walk hasn't run yet, so a saturated pool never burns
                # DataNode reads on an admission it can't complete
                if shared:
                    # unpin; zero-ref pages stay resident in the index
                    self.pool.decref(shared)
                return
            if self.prefix_cache is not None:
                # a radix miss consults host RAM, then the DFS store,
                # for the next chunks of the chain — only the still-
                # uncached tail falls back to prefill. The matched
                # node's chain digest seeds the walk, so nothing is
                # rehashed from the root
                cold = self.kvstore.fetch_cold(
                    ctx, len(nodes), limit, parent_ctx=req.trace_ctx,
                    start_digest=nodes[-1].digest if nodes else None)
            with self._cond:
                self._pending.popleft()
            if cold:
                # cold payloads land in the first of the freshly
                # allocated pages (ref 1, owned by this request) and
                # re-register in the radix so siblings share them from
                # HBM; a mid-admission eviction above could only have
                # taken OTHER zero-ref pages — the shared span is
                # pinned and these pages are already allocated
                cold_pages = private[:len(cold)]
                for page, hit in zip(cold_pages, cold):
                    self._inject_block(page, hit.k, hit.v)
                span = shared + cold_pages
                self.prefix_cache.insert(
                    ctx[:len(span) * self.block_size], span)
                self.kvstore.mark_promoted(cold, cold_pages)
            self.kvstore.note_match(nodes, parent_ctx=req.trace_ctx,
                                    count=req.preemptions == 0)
            reused = (len(shared) + len(cold)) * self.block_size
            req.prefix_tokens_reused = reused
            if req.preemptions == 0:
                # hit-rate counts cross-request reuse only: a preempted
                # request re-matching its OWN surviving blocks is warm
                # resume, and counting it would inflate the gauge
                # exactly when the pool is thrashing
                self.prefix_tokens_seen += len(ctx)
                self.prefix_tokens_matched += reused
                if self.metrics and reused:
                    self.metrics.prefix_tokens_reused.incr(reused)
            self._place(req, slot, shared + private, ctx,
                        len(shared) + len(cold))

    def _try_alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages, evicting LRU zero-ref cached blocks to
        make room before giving up (cold cache yields to live work).
        Victims demote to the host-RAM ring on their way out (the
        ``on_evict`` hook copies the payload while the page is still
        valid), so "evicted" means "one memcpy away", not "gone"."""
        if n <= 0:
            return []
        got = self.pool.alloc(n)
        if got is not None or self.prefix_cache is None:
            return got
        if self.kvstore.host is not None:
            # a victim's demotion reads its page: not under the eviction
            # walk, where a delivery would change the index it walks
            self._drain()
        evicted = self.prefix_cache.evict(n - self.pool.num_free,
                                          self.pool.refcount,
                                          on_evict=self.kvstore.demote)
        if not evicted:
            return None
        self.pool.free(evicted)
        self.prefix_evictions += len(evicted)
        if self.metrics:
            self.metrics.prefix_cache_evictions.incr(len(evicted))
        return self.pool.alloc(n)

    def _place(self, req: GenRequest, slot: int, blocks: List[int],
               ctx: List[int], shared_blocks: int) -> None:
        req.state = RUNNING
        req._slot = slot
        req._blocks = blocks
        req._shared_blocks = shared_blocks
        req._ctx = ctx
        req._prefill_pos = shared_blocks * self.block_size
        req._admit_seq = next(self._admit_counter)
        if self.spec_k:
            req._proposer = NgramProposer(ctx, max_n=self.spec_ngram)
        self._slots[slot] = req
        row = np.zeros((self.blocks_per_seq,), np.int32)
        row[:len(blocks)] = blocks
        self._tables[slot] = row
        self._seq_lens[slot] = 0
        self._active[slot] = False
        # the admission-event scatter: the slot's whole lane state
        # (table row, sampling params, budget, stop token) lands on
        # device ONCE here; the compiled step carries it from now on
        self._push_slot(slot, req)
        if self._lane_shapes is not None:
            # the family's lane state starts where the request does:
            # after the last page it maps (a prefix hit, or a resume
            # after preemption), or from nothing
            self._dstate = self._start_lane_fn(
                self._dstate, tuple(self._pools), np.asarray(
                    [slot, blocks[shared_blocks - 1] if shared_blocks
                     else BlockPool.SCRATCH], np.int32))
            if self.metrics:
                (self.metrics.recurrent_state_restores if shared_blocks
                 else self.metrics.recurrent_state_cold_starts).incr()
        if req.admitted_at is None:
            req.admitted_at = time.monotonic()
        sp = self.tracer.span("serving.admit", parent=req.trace_ctx)
        sp.add_kv("request", str(req.id))
        sp.add_kv("queue_wait_s",
                  f"{req.admitted_at - req.submitted_at:.6f}")
        sp.add_kv("prompt_tokens", str(len(ctx)))
        sp.add_kv("prefix_tokens_reused", str(req.prefix_tokens_reused))
        if self._lane_shapes is not None:
            sp.add_kv("state_restored_pages", str(shared_blocks))
        sp.finish()

    def _ensure_blocks(self) -> None:
        """Every decoding slot must own the page its next token lands
        in; allocate at block boundaries (evicting cold cache first),
        preempting the youngest request when everything is dry. Draft
        rows scatter K/V too, so a speculating lane best-effort
        allocates through its furthest draft position — and on a dry
        pool the drafts are CLAMPED to the owned pages rather than
        preempting anyone: speculation degrades before it evicts."""
        for slot, req in enumerate(self._slots):
            if req is None or req._prefill_pos is not None:
                continue     # prefilling slots pre-allocated at admit
            # this step scatters K/V at the lane's position: its mirror
            # plus the step in flight; that page must be owned or the
            # write would land in scratch and silently corrupt the
            # request's context. (A lane the device retires in flight
            # for its stop token emits nothing; the page asked for it
            # here is released with the slot.)
            while req._slot is not None and self._runs_next(slot, req) \
                    and len(req._blocks) * self.block_size <= \
                    int(self._seq_lens[slot] + self._in_flight[slot]):
                got = self._try_alloc(1)
                if got is not None:
                    if req._slot is None:
                        # retired by a delivery inside the allocation
                        self.pool.free(got)
                    else:
                        self._append_block(slot, req, got[0])
                    continue
                if self._flight is not None:
                    # what finished in flight gives its pages back
                    self._drain()
                    continue
                # pool and cache dry: evict the youngest running
                # request — which may be this one (then its slot
                # empties and the loop ends; it resumes by recompute
                # once pages free up). Preempting a sharer only drops
                # its refs — pages still mapped by a sibling survive.
                victim = max((r for r in self._slots if r is not None),
                             key=lambda r: r._admit_seq)
                self._preempt(victim)
            lens = int(self._draft_lens[slot]) if self.spec_k else 0
            if req._slot is None or not lens:
                continue
            want = (int(self._seq_lens[slot]) + lens) \
                // self.block_size + 1
            while len(req._blocks) < want:
                # pool.alloc, NOT _try_alloc: a possibly-rejected
                # draft page must never evict a cached prefix either —
                # the clamp below degrades speculation instead
                got = self.pool.alloc(1)
                if got is None:
                    break
                self._append_block(slot, req, got[0])
            self._draft_lens[slot] = min(
                lens, len(req._blocks) * self.block_size
                - int(self._seq_lens[slot]) - 1)

    def _runs_next(self, slot: int, req: GenRequest) -> bool:
        """Whether the lane decodes in the next step to be dispatched:
        armed, and not at the end of its budget with the step in flight
        (the device retires it there; the host can count that far
        without reading)."""
        return bool(self._active[slot]) and \
            len(req.out_tokens) + int(self._in_flight[slot]) \
            < req.sampling.max_new_tokens

    def _append_block(self, slot: int, req: GenRequest,
                      block: int) -> None:
        """One new page for a decoding slot: host mirror + the
        device-side table scatter (a page-growth event — once per
        block_size tokens per lane, never per step)."""
        idx = len(req._blocks)
        self._tables[slot][idx] = block
        req._blocks.append(block)
        self._dstate = _SET_TABLE(
            self._dstate, np.asarray([slot, idx, block], np.int32))

    def _preempt(self, victim: GenRequest) -> None:
        """vLLM-style recompute preemption: drop the request's page
        refs and requeue it at the front; re-admission prefills prompt
        + tokens generated so far (warm when its prompt blocks survive
        in the prefix index)."""
        self._release_slot(victim)
        victim.state = QUEUED
        victim.preemptions += 1
        with self._cond:
            self._pending.appendleft(victim)
        if self.metrics:
            self.metrics.preemptions.incr()
        psp = self.tracer.span("serving.preempt", parent=victim.trace_ctx)
        psp.add_kv("request", str(victim.id))
        psp.finish()

    def _fresh_kv_pools(self):
        """Zeroed paged pools, sharded when the engine owns a mesh —
        construction and the failed-step recovery path share it."""
        pools = [jnp.zeros(shape, self.cfg.jax_dtype)
                 for shape in self._pool_shapes]
        sharding = self._kv_sharding or self._carry_sharding
        return pools if sharding is None \
            else jax.device_put(pools, sharding)

    def _fresh_dstate(self) -> dict:
        """Zeroed device-resident step state, every lane cleared. Used
        at construction and to REPLACE a state dict whose buffers a
        failed (donated) step call consumed — the seed resumes at the
        count of steps dispatched so the sampled-lane key stream never
        replays."""
        mb = self.max_batch
        state = {
            "tables": jnp.zeros((mb, self.blocks_per_seq), jnp.int32),
            "positions": jnp.zeros((mb,), jnp.int32),
            "last": jnp.zeros((mb,), jnp.int32),
            "active": jnp.zeros((mb,), bool),
            "temps": jnp.zeros((mb,), jnp.float32),
            "topks": jnp.zeros((mb,), jnp.int32),
            "outc": jnp.zeros((mb,), jnp.int32),
            "maxn": jnp.zeros((mb,), jnp.int32),
            "stopt": jnp.full((mb,), -1, jnp.int32),
            "seed": jnp.int32(self._dispatched),
            "lane": jax.tree_util.tree_map(
                lambda shape: jnp.zeros(shape, self.cfg.jax_dtype),
                self._lane_shapes, is_leaf=lambda x: isinstance(x, tuple)),
        }
        if self._carry_sharding is not None:
            state = jax.device_put(state, self._carry_sharding)
        return state

    def _push_slot(self, slot: int, req: Optional[GenRequest]) -> None:
        """One event scatter carrying a slot's whole lane state to the
        device copy (``req=None`` clears the lane)."""
        if req is None:
            ints = np.zeros((8,), np.int32)
            ints[0] = slot
            ints[7] = -1
            row = np.zeros((self.blocks_per_seq,), np.int32)
            temp = np.float32(0.0)
        else:
            sp = req.sampling
            stop = -1 if sp.stop_token is None else int(sp.stop_token)
            ints = np.asarray(
                # (last token 0: a lane's last token is the device's
                # own from the step that arms it — ``_ARM_SLOT``)
                [slot, int(self._seq_lens[slot]), 0,
                 int(self._active[slot]), sp.top_k,
                 len(req.out_tokens), sp.max_new_tokens, stop],
                np.int32)
            row = self._tables[slot]
            temp = np.float32(sp.temperature)
        self._dstate = _SET_SLOT(self._dstate, ints, row, temp)

    def _finish_request(self, req: GenRequest, state: str = FINISHED,
                        error: str = None) -> None:
        """Complete a request and wake anyone waiting on the scheduler
        condition (``stop(drain=True)`` parks there)."""
        req._finish(state, error)
        with self._cond:
            self._cond.notify_all()

    def _release_slot(self, req: GenRequest) -> None:
        slot = req._slot
        if slot is None:
            return
        released = self.pool.decref(req._blocks)
        if self.prefix_cache is not None:
            # zero-ref pages registered in the radix index stay
            # resident as reusable cache; the rest return to the pool
            drop = [b for b in released
                    if not self.prefix_cache.contains_block(b)]
        else:
            drop = released
        self.pool.free(drop)
        req._blocks = []
        req._shared_blocks = 0
        req._ctx = []
        req._prefill_pos = None
        req._slot = None
        self._slots[slot] = None
        self._active[slot] = False
        self._seq_lens[slot] = 0
        self._tables[slot] = 0
        self._in_flight[slot] = 0
        self._draft_lens[slot] = 0     # stale drafts must not dispatch
        self._push_slot(slot, None)    # release event: clear the lane

    def _dispatch(self) -> Optional[_Flight]:
        """Put the next step on the device and return its record; reads
        nothing back. What the host decides here it decides from its own
        numbers: which lanes run (``_runs_next``), the chunk's span
        (``_prefill_pos`` advances HERE, so the step after carries the
        next chunk), and whether the chunk is the prompt's last — then
        the lane is armed from the step's ``c_first`` on the device."""
        # oldest still-prefilling request gets this step's chunk budget
        pre: Optional[GenRequest] = None
        for r in self._slots:
            if r is not None and r._prefill_pos is not None:
                if pre is None or r._admit_seq < pre._admit_seq:
                    pre = r
        rows = [(slot, r) for slot, r in enumerate(self._slots)
                if r is not None and self._runs_next(slot, r)]
        if pre is None and not rows:
            return None
        proposed = int(self._draft_lens.sum()) if self.spec_k else 0
        if proposed:
            drafts_in, lens_in = self._draft_tokens, self._draft_lens
        else:
            # nothing proposed this step: dispatch the device-resident
            # zero twins so an idle speculation lane uploads nothing
            drafts_in, lens_in = self._dz_drafts, self._dz_lens
        n_valid = 0
        if pre is not None:
            start = pre._prefill_pos
            n_valid = min(self.prefill_chunk, len(pre._ctx) - start)
        if self.metrics:
            self._count_attn_pages(rows, pre, n_valid)
        t0 = time.monotonic()
        last_chunk = False
        if pre is None:
            # decode-only shape: no idle chunk rows to pay for — and
            # with the state device-resident, NOTHING crosses
            # host→device on this path (the steady-state contract the
            # transfer-guard test pins)
            *self._pools, self._dstate, packed = self._step_fn(
                self.params, *self._pools, self._dstate,
                drafts_in, lens_in, None)
            c_first = None
        else:
            c = self.prefill_chunk
            c_tokens = np.zeros((c,), np.int32)
            c_tokens[:n_valid] = pre._ctx[start:start + n_valid]
            c_ints = np.asarray([pre._slot, start, n_valid], np.int32)
            if pre.first_chunk_at is None:
                pre.first_chunk_at = time.monotonic()
            *self._pools, self._dstate, packed, c_first = \
                self._step_fn(self.params, *self._pools,
                              self._dstate, drafts_in, lens_in,
                              (c_tokens, c_ints))
            pre._prefill_pos = start + n_valid
            last_chunk = pre._prefill_pos >= len(pre._ctx)
            if last_chunk:
                self._arm(pre, c_first)
                c_first.copy_to_host_async()
        # the bundle starts for the host the moment the step ends
        packed.copy_to_host_async()
        self._dispatched += 1
        for slot, _ in rows:
            self._in_flight[slot] += 1
        if self._flight is not None:
            self.steps_run_ahead += 1
            if self.metrics:
                self.metrics.steps_run_ahead.incr()
        return _Flight(packed, c_first, rows, pre, n_valid, last_chunk,
                       proposed, t0)

    def _arm(self, req: GenRequest, c_first) -> None:
        """The prompt's last chunk is on the device: the slot is a decode
        lane from the next step on, at the context's tip, its last token
        the chunk's sample — which stays on the device (``_ARM_SLOT``);
        the host delivers it when it reads the step (``_finish_prefill``).
        A request at the end of its budget with that token is not armed."""
        slot = req._slot
        req._prefill_pos = None
        self._seq_lens[slot] = len(req._ctx)
        if self.prefix_cache is not None:
            # the fully-filled prompt blocks enter the prefix index HERE,
            # not at the read: the device runs its steps in order, so
            # whoever maps these pages from the next admission on reads
            # them after this step has written them
            full = len(req._ctx) // self.block_size
            if full:
                self.prefix_inserted_blocks += self.prefix_cache.insert(
                    req._ctx[:full * self.block_size], req._blocks[:full])
        count = len(req.out_tokens) + 1
        if count >= req.sampling.max_new_tokens:
            return
        self._active[slot] = True
        self._dstate = _ARM_SLOT(
            self._dstate,
            np.asarray([slot, len(req._ctx), count], np.int32), c_first)

    def _deliver(self, flight: _Flight) -> int:
        """The host's half of a step: read its bundle, hand the tokens
        out. With the next step already dispatched the read returns as
        soon as this step ends, and the device is not waiting on it."""
        with self._phase("engine.readback"):
            # the ONE device→host read of the step: [B, G+3] =
            # tokens | emit_count | finished | accept_len, then the
            # device's counts in row 0
            packed = np.asarray(flight.packed)
        if self._flight is not None:
            # the step behind this one starts on the device about now
            self._flight.t0 = time.monotonic()
        with self._phase("engine.deliver"):
            return self._deliver_step(packed, flight)

    def _count_attn_pages(self, rows, pre: Optional[GenRequest],
                          n_valid: int) -> None:
        """The live-page share of this step's attention, from the host's
        mirrors: pages its live rows attend to (a row at position ``p``
        reads the pages of ``p + 1`` tokens) against every row's whole
        table — table pages, each as deep as the family's pools — the
        pool's fill, then what only the family counts."""
        bs = self.block_size
        j = np.arange(self.spec_k + 1)
        lanes = np.asarray([slot for slot, _ in rows], np.intp)
        at = self._seq_lens[lanes] + self._in_flight[lanes]
        lens = at[:, None] + 1 + j
        lens = lens[j <= self._draft_lens[lanes, None]]
        n_rows = self.max_batch * j.size
        chunk_lens = np.zeros((0,), np.int64)
        if pre is not None:
            cj = np.arange(self.prefill_chunk)
            chunk_lens = np.where(cj < n_valid, pre._prefill_pos + 1 + cj, 0)
            lens = np.concatenate([lens, chunk_lens[:n_valid]])
            n_rows += self.prefill_chunk
        self.metrics.attn_pages_read.incr(int(np.sum(-(-lens // bs))))
        self.metrics.attn_pages_dense.incr(n_rows * self.blocks_per_seq)
        # the pool's fill, step by step: pages held (by a lane or by the
        # prefix cache) against the pages there are
        self.metrics.kv_pages_live_steps.incr(
            self.pool.num_usable - self.pool.num_free)
        self.metrics.kv_pages_pool_steps.incr(self.pool.num_usable)
        self._family.count_step(self.metrics, lens,
                                self._chains(rows, at, pre, n_valid),
                                chunk_lens, (bs, self.blocks_per_seq))

    def _chains(self, rows, at, pre: Optional[GenRequest], n_valid: int):
        """(first page, pages held) per live request; lazy."""
        bs = self.block_size
        for (_, req), pos in zip(rows, at):
            yield req._blocks[0], -(-(int(pos) + 1) // bs)
        if pre is not None and n_valid:
            yield pre._blocks[0], -(-(pre._prefill_pos + n_valid) // bs)

    def _deliver_step(self, packed, flight: _Flight) -> int:
        """What the host does with a step's read-back bundle: advance
        the mirrors, deliver each lane's tokens, retire what finished,
        complete the prefill whose last chunk rode along. A row is its
        request's only while that request still holds the slot."""
        G = self.spec_k + 1
        self.steps += 1
        self._chunk_fill = flight.n_valid
        self.steps_argmax_only += int(packed[0, G + 3])
        self.steps_topk += int(packed[0, G + 4])
        if self.metrics:
            for j, name in enumerate(_STEP_COUNTERS
                                     + self._family.counters):
                getattr(self.metrics, name).incr(int(packed[0, G + 3 + j]))
        emitted = 0
        self.occupancy_log.append(len(flight.rows))
        if len(self.occupancy_log) > 100_000:
            del self.occupancy_log[:50_000]
        accepted = 0
        spec_parent = None
        step_exemplar = None   # any sampled request names this step
        for slot, req in flight.rows:
            if self._slots[slot] is not req:
                continue       # released since the step was dispatched
            self._in_flight[slot] -= 1
            if step_exemplar is None and req.trace_ctx is not None \
                    and req.trace_ctx.sampled:
                step_exemplar = req.trace_ctx.trace_id
            n = int(packed[slot, G])
            if n <= 0:
                continue
            toks = packed[slot, :n]
            if self.spec_k:
                # the VERIFIER's accept count, not the delivered n-1:
                # a stop-token or budget clamp truncates the burst but
                # must not read as the proposer guessing wrong
                acc = int(packed[slot, G + 2])
                accepted += acc
                if self._draft_lens[slot]:
                    if self.metrics:
                        self.metrics.spec_accept_len.add(acc)
                    if spec_parent is None:
                        spec_parent = req.trace_ctx
            # mirrors advance with the device state (the device already
            # committed these positions)
            self._seq_lens[slot] += n
            emitted += self._deliver_burst(req, toks)
            if packed[slot, G + 1] or self._exhausted(req):
                self._release_slot(req)
                self._finish_request(req, FINISHED)
        proposed = flight.proposed
        if self.spec_k and proposed:
            self.spec_proposed += proposed
            self.spec_accepted += accepted
            if self.metrics:
                self.metrics.spec_proposed.incr(proposed)
                if accepted:
                    self.metrics.spec_accepted.incr(accepted)
            # join a speculating request's trace (root spans at
            # decode-step rate would flood the bounded collector ring
            # with single-span traces and evict real request traces)
            ssp = self.tracer.span("serving.speculate",
                                   parent=spec_parent)
            ssp.add_kv("proposed", str(proposed))
            ssp.add_kv("accepted", str(accepted))
            ssp.finish()
        pre = flight.pre
        if flight.last_chunk and pre._slot is not None:
            # the chunk's last valid row sat at the final context
            # position — its sample is the first output token
            self._finish_prefill(pre, int(flight.c_first))
            emitted += 1
        self.tokens_generated += emitted
        if self.metrics:
            self.metrics.tokens_out.incr(emitted)
            step_s = time.monotonic() - flight.t0
            self.metrics.decode_step.add(step_s)
            # exemplar: a slow decode_step bucket on /prom names a
            # trace riding this step, resolvable at the fleet doctor
            self.metrics.decode_step_hist.add(
                step_s, exemplar_trace=step_exemplar)
        return emitted

    def _deliver_burst(self, req: GenRequest, toks) -> int:
        """Deliver a step's accepted tokens in order, guarded against
        multi-token overshoot: never past ``max_new_tokens``, nothing
        past a ``stop_token`` hit mid-burst. The compiled step already
        truncates — this is the host-side belt to its braces."""
        sp = req.sampling
        n = 0
        for t in toks:
            if len(req.out_tokens) >= sp.max_new_tokens:
                break
            tok = int(t)
            req._deliver(tok)
            if req._proposer is not None:
                req._proposer.append(tok)
            n += 1
            if sp.stop_token is not None and tok == sp.stop_token:
                break
        return n

    @staticmethod
    def _exhausted(req: GenRequest) -> bool:
        sp = req.sampling
        return len(req.out_tokens) >= sp.max_new_tokens or \
            (sp.stop_token is not None and req.out_tokens and
             req.out_tokens[-1] == sp.stop_token)

    def _finish_prefill(self, req: GenRequest, tok: int) -> None:
        """The step that carried the prompt's last chunk has been read:
        deliver the first token. The lane was armed, and its prompt's
        blocks indexed, at dispatch (``_arm``); a first token that ends
        the request (its budget, or the stop token — which the device
        saw too and left the lane off) releases the slot here."""
        first = req.first_token_at is None
        req._deliver(tok)
        if req._proposer is not None:
            req._proposer.append(tok)
        if first:
            ttft = req.first_token_at - req.submitted_at
            # the three stages sum to ttft, request by request
            stages = {
                "queue": req.admitted_at - req.submitted_at,
                "prefill_wait": req.first_chunk_at - req.admitted_at,
                "prefill": req.first_token_at - req.first_chunk_at}
            if self.metrics:
                # a slow TTFT bucket's exemplar IS this request's trace
                exemplar = req.trace_ctx.trace_id \
                    if req.trace_ctx is not None and \
                    req.trace_ctx.sampled else None
                self.metrics.ttft.add(ttft)
                self.metrics.ttft_hist.add(ttft, exemplar_trace=exemplar)
                for stage, secs in stages.items():
                    self.metrics.ttft_stage_hist[stage].add(
                        secs, exemplar_trace=exemplar)
            fsp = self.tracer.span("serving.first_token",
                                   parent=req.trace_ctx)
            fsp.add_kv("request", str(req.id))
            fsp.add_kv("ttft_s", f"{ttft:.6f}")
            fsp.add_kv("prefill_wait_s", f"{stages['prefill_wait']:.6f}")
            fsp.add_kv("prefill_service_s", f"{stages['prefill']:.6f}")
            fsp.finish()
        if self._exhausted(req):
            self._release_slot(req)
            self._finish_request(req, FINISHED)

    def _publish_metrics(self) -> None:
        if not self.metrics:
            return
        m = self.metrics
        with self._cond:
            depth = len(self._pending)
        m.queue_depth.set(depth)
        m.batch_occupancy.set(self.num_active)
        used = self.pool.num_usable - self.pool.num_free
        m.kv_blocks_in_use.set(used)
        m.kv_block_utilization.set(used / max(1, self.pool.num_usable))
        stats = self.cache_stats()
        m.prefix_cache_hit_rate.set(round(stats["hit_rate"], 4))
        m.prefix_cached_blocks.set(stats["cached_blocks"])
        m.chunk_occupancy.set(self._chunk_fill / self.prefill_chunk)
        m.prefill_backlog.set(self.prefill_backlog)
        for name, secs in self.phase_s.items():
            m.phase_seconds[name].incr(
                secs - self._phase_published.get(name, 0.0))
        self._phase_published = dict(self.phase_s)

    # --------------------------------------------------- replica lifecycle

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self._run_loop,
                                        name="decode-engine", daemon=True)
        self._thread.start()
        # the process's stall witness (one a process, shared): it reads
        # this thread's schedstat and ``phase_s``; the loop tells it
        # nothing
        PauseMonitor.watch_process(
            self, self._thread, self.phase_s, self.metrics,
            threshold_s=STALL_THRESHOLD_S, interval_s=STALL_TICK_S)

    def stop(self, drain: bool = False, timeout: float = 30.0) -> None:
        """``drain=True``: keep decoding until every queued and running
        request completes (graceful replica shutdown), then stop. The
        wait parks on the scheduler condition — request completions
        notify it — instead of a sleep-poll, so the drain turns around
        the moment the last request finishes."""
        if drain and self._thread is not None:
            deadline = time.monotonic() + timeout
            with self._cond:
                # self.idle re-enters _cond (Condition() wraps an
                # RLock); completions and submits both notify
                while not self.idle:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
            if self.drain_persist and self.kvstore.dfs_enabled:
                # affinity-aware drain: ship every resident cached
                # prefix to the DFS tier BEFORE the pools die with this
                # process, so a surviving replica maps the departed
                # replica's hot prefixes back instead of re-prefilling
                # — scale-in must never torch the fleet's cache
                self.persist_cache(
                    timeout=max(1.0, deadline - time.monotonic()))
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        # a stopped engine's pool must not haunt the HBM ledger
        hbm_ledger().unregister_prefix(self._hbm_owner)
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None
        PauseMonitor.unwatch_process(self)
        # only touch slot/pool state under the scheduler lock — a step
        # still stuck in compilation past the join timeout must not race
        # a double-free of its KV pages; if the lock can't be had the
        # pages stay allocated (the process is going down anyway)
        locked = self._sched_lock.acquire(timeout=5.0)
        try:
            if locked:
                try:
                    # what the device already made is delivered
                    self._drain()
                except Exception as e:  # noqa: BLE001 — going down
                    log.warning("step in flight lost at stop: %s", e)
            for req in [r for r in self._slots if r]:
                if not req.done.is_set():
                    if locked:
                        self._release_slot(req)
                    self._finish_request(req, FAILED, "engine stopped")
            # drain, don't snapshot-and-clear: a submit() racing this
            # shutdown must fail its request, not vanish from the queue
            while True:
                with self._cond:
                    if not self._pending:
                        break
                    req = self._pending.popleft()
                if not req.done.is_set():
                    self._finish_request(req, FAILED, "engine stopped")
        finally:
            if locked:
                self._sched_lock.release()
        if self._relaxed_longctx is not None:
            # the drain above already waited for the plane through
            # `idle`; this stops its worker and fails anything queued
            self._relaxed_longctx.stop(drain=drain, timeout=timeout)
        self.kvstore.close()

    def persist_cache(self, timeout: float = 30.0) -> int:
        """Force-persist every resident cached block (HBM radix + host
        ring) to the DFS tier and wait for durability — the drain half
        of affinity-aware scale-in. Returns the number of blocks
        enqueued; best-effort on timeout (whatever went durable is
        durable, the rest is recomputable by definition)."""
        if not self.kvstore.dfs_enabled:
            return 0
        with self._sched_lock:
            self._drain()
            n = self.kvstore.persist_resident()
            watermark = self.kvstore.persists_enqueued
        if n and not self.kvstore.flush(timeout, up_to=watermark):
            log.warning("drain persist did not finish in %.1fs "
                        "(%d blocks enqueued)", timeout, n)
        return n

    # ------------------------------------------------ disaggregation face

    def prefill_to_store(self, prompt: List[int],
                         timeout: float = 60.0) -> int:
        """Prefill ``prompt`` and force-persist its full-block KV span
        to the DFS tier — the prefill half of prefill/decode
        disaggregation. The KV ships over the DataTransferProtocol via
        the DFS write pipeline; the decode replica's admission maps it
        back with hedged reads and prefills only the tail. Returns the
        number of tokens actually durable on return — re-verified
        against the radix after the flush, so a DataNode refusal can
        never be reported as a persisted handoff. Raises when nothing
        went durable (the router's signal to decode cold)."""
        if not self.kvstore.dfs_enabled:
            raise ValueError("DFS KV tier disabled (set "
                             "serving.kv.dfs.enable for prefill-role "
                             "replicas)")
        if self._relaxed_longctx is not None and \
                len(prompt) >= self._relaxed_longctx.min_tokens:
            # monster handoff: CP prefill + streamed tier ingest — the
            # radix never sees these blocks, so the radix-walking
            # persist below would report 0 durable tokens for a chain
            # that IS durable
            return self._relaxed_longctx.prefill_to_store(prompt,
                                                          timeout)
        req = self.submit(prompt, SamplingParams(max_new_tokens=1))
        if self._thread is None:
            # offline/test mode: no scheduler thread, drive it here
            deadline = time.monotonic() + timeout
            while not req.done.is_set():
                if time.monotonic() > deadline:
                    raise TimeoutError(f"prefill {req.id} not done")
                self.step()
        req.wait(timeout)
        with self._sched_lock:
            self._drain()
            blocks = self.kvstore.persist_prefix(prompt,
                                                 parent_ctx=req.trace_ctx)
            # flush to THIS handoff's watermark, not the global queue
            # tail — other requests' min-refs persists keep arriving
            watermark = self.kvstore.persists_enqueued
        if not self.kvstore.flush(timeout, up_to=watermark):
            raise TimeoutError("DFS KV persist did not drain in "
                               f"{timeout}s")
        with self._sched_lock:
            durable = self.kvstore.persisted_span(prompt)
        if blocks and not durable:
            raise RuntimeError(
                f"handoff persist failed: 0/{blocks} blocks durable "
                "(DataNodes refusing writes?)")
        return durable * self.block_size

    def _run_loop(self) -> None:
        """The serving thread: ``_iterate`` for as long as there is
        work, one step ahead of its own read-back (``_runs_ahead``) —
        step n+1 is on the device before step n's tokens are read, so
        the host's part of an iteration runs under the device's. An
        engine that speculates keeps in step with its read-back: its
        drafts for step n+1 come from the tokens of step n."""
        while not self._stop.is_set():
            with self._cond:
                # _local_idle, not idle: a busy longctx plane must not
                # flip this predicate — step() would return 0 in a
                # tight no-sleep loop for the whole monster request
                with self._phase("engine.wait"):
                    while self._local_idle and not self._stop.is_set():
                        self._cond.wait(0.05)
            if self._stop.is_set():
                return
            try:
                with self._sched_lock:
                    self._iterate(ahead=self._runs_ahead)
            except Exception as e:  # noqa: BLE001 — fail requests, not
                # the thread: a poisoned request must not wedge the
                # replica with clients blocked on .done forever
                self._recover(e)

    def _recover(self, e: Exception) -> None:
        """A step failed — at its dispatch, or at the read of the step
        in flight, where a device failure surfaces (``_iterate`` has
        dropped what was in flight). Slot state only moves under the
        scheduler lock (a racing stop() must not double-release the
        same pages), and the queue drains via popleft — a submit()
        racing this handler is left pending for the next loop
        iteration, never silently dropped."""
        with self._sched_lock:
            # the failed step call consumed ALL the donated device
            # buffers (KV pools + step state), of the step in flight
            # and of the one behind it alike — rebuild them once,
            # BEFORE the release path scatters lane-clear events into
            # the state, or the recovery itself raises on deleted
            # buffers and wedges the replica
            self._dstate = self._fresh_dstate()
            self._pools = self._fresh_kv_pools()
            for req in [r for r in self._slots if r]:
                self._release_slot(req)
                self._finish_request(req, FAILED, f"decode failed: {e}")
            # the HBM radix indexed pages that died with the
            # pools: purge it (no demotion — the bytes are
            # gone; host/DFS tier copies are digest-keyed and
            # survive) so no future admission maps a zeroed
            # page as a cached prefix
            if self.prefix_cache is not None:
                self.pool.free(self.prefix_cache.evict(
                    len(self.prefix_cache), self.pool.refcount))
            while True:
                with self._cond:
                    if not self._pending:
                        break
                    req = self._pending.popleft()
                self._finish_request(req, FAILED, f"decode failed: {e}")

    # ------------------------------------------------------------- offline

    def generate(self, prompts: List[List[int]],
                 sampling: Optional[SamplingParams] = None,
                 ) -> List[List[int]]:
        """Offline batch API: submit everything, step until done."""
        reqs = [self.submit(p, sampling) for p in prompts]
        while not all(r.done.is_set() for r in reqs):
            self.step()
        return [r.wait(0) for r in reqs]
