"""Serving plane: continuous-batching decode engine + the full replica.

Engine tests pin the three properties that make the engine a real
serving core: paged-KV decode is EXACT (greedy tokens match a full
recompute through ``models.decoder.forward``), the two compiled
functions trace exactly once across an arbitrary workload, and the
paged pool admits/evicts under pressure without corrupting any stream.

The end-to-end test is the acceptance path of the subsystem: trainer
checkpoint → miniDFS → ``load_serving_params`` → replica HTTP door with
auth, streaming, mid-decode admission observable in the occupancy
metric, and graceful drain.
"""

import json
import http.client
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.conf import Configuration
from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.decoder import forward, init_params
from hadoop_tpu.serving import engine as engine_mod
from hadoop_tpu.serving.engine import (BlockPool, DecodeEngine,
                                       PrefixCache, SamplingParams)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("tiny")
    return init_params(jax.random.PRNGKey(0), cfg), cfg


_REF_P = 48
_ref_fwd_cache = {}


def _reference_greedy(params, cfg, prompt, max_new):
    """Full forward recompute each step — the engine's ground truth.
    Sequences are padded to one fixed length so the reference forward
    compiles once per config (causal attention: the padded tail cannot
    influence logits at earlier positions)."""
    fwd = _ref_fwd_cache.get(id(cfg))
    if fwd is None:
        fwd = jax.jit(lambda p, t: forward(p, t, cfg))
        _ref_fwd_cache[id(cfg)] = fwd
    seq = list(prompt)
    for _ in range(max_new):
        padded = seq + [0] * (_REF_P - len(seq))
        logits = fwd(params, jnp.asarray([padded]))
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[len(prompt):]


# -------------------------------------------------------------- block pool

def test_block_pool_alloc_free():
    pool = BlockPool(num_blocks=8, block_size=4)
    assert pool.num_usable == 7          # block 0 is scratch
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert a is not None and b is not None
    assert BlockPool.SCRATCH not in a + b
    assert len(set(a + b)) == 7          # no page handed out twice
    assert pool.alloc(1) is None         # all-or-nothing exhaustion
    pool.free(a)
    assert pool.num_free == 3
    c = pool.alloc(3)
    assert sorted(c) == sorted(a)        # freed pages recycle
    with pytest.raises(ValueError):
        pool.free([BlockPool.SCRATCH])


def test_block_pool_refcounts_protect_shared_pages():
    pool = BlockPool(num_blocks=6, block_size=4)
    blocks = pool.alloc(2)
    assert all(pool.refcount(b) == 1 for b in blocks)
    pool.incref(blocks)                  # a second request maps them
    with pytest.raises(ValueError):      # still shared: free must refuse
        pool.free(blocks)
    assert pool.decref(blocks) == []     # first unmap: nothing hits zero
    zeros = pool.decref(blocks)          # second unmap: both unreferenced
    assert sorted(zeros) == sorted(blocks)
    pool.free(zeros)                     # only now may they recycle
    assert pool.num_free == 5
    with pytest.raises(ValueError):      # double-decref is a bug
        pool.decref(blocks)
    with pytest.raises(ValueError):
        pool.incref([BlockPool.SCRATCH])


def test_prefix_cache_radix_match_insert_evict():
    """Block-granular trie: longest full-block prefix match, first
    writer wins on insert, LRU zero-ref leaves evict first (a parent
    can only go after its children)."""
    cache = PrefixCache(block_size=2)
    ref = {10: 0, 11: 0, 12: 0, 13: 0}
    assert cache.match([1, 2, 3, 4]) == []
    assert cache.insert([1, 2, 3, 4], [10, 11]) == 2
    assert cache.match([1, 2, 3, 4, 5]) == [10, 11]   # partial tail cut
    assert cache.match([1, 2, 9, 9]) == [10]          # diverges mid-way
    assert cache.match([9, 2, 3, 4]) == []            # prefix is the key:
    # same block tokens under a different head must NOT match
    assert cache.insert([1, 2, 3, 4], [12, 13]) == 0  # dedup: first wins
    assert cache.match([1, 2, 3, 4]) == [10, 11]
    assert cache.insert([1, 2, 7, 8], [10, 12]) == 1  # sibling branch
    assert len(cache) == 3
    # 11 is the least-recently-touched leaf (12 was just inserted)
    assert cache.evict(1, ref.get) == [11]
    ref[12] = 1                                       # a request maps 12
    assert cache.evict(2, ref.get) == []   # leaf pinned, parent has kids
    ref[12] = 0
    assert cache.evict(2, ref.get) == [12, 10]        # leaf, then parent
    assert len(cache) == 0


# ------------------------------------------------------------------ engine

@pytest.mark.parametrize("preset", ["tiny", "tiny-gpt2"])
def test_paged_decode_matches_reference_forward(preset):
    """Greedy decode through the paged KV cache must produce exactly
    the tokens a full-context recompute produces — for both the
    rope/rmsnorm/swiglu and learned-pos/layernorm/gelu families."""
    cfg = get_config(preset)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = [3, 17, 42, 99, 5]
    ref = _reference_greedy(params, cfg, prompt, 8)
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=32)
    got = eng.generate([prompt], SamplingParams(max_new_tokens=8))[0]
    assert got == ref


def test_batched_requests_decode_independently(tiny_model):
    """Different-length requests in one batch each match their solo
    greedy reference — lanes must not bleed into each other."""
    params, cfg = tiny_model
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [200]]
    refs = [_reference_greedy(params, cfg, p, 6) for p in prompts]
    eng = DecodeEngine(params, cfg, max_batch=4, block_size=4,
                       max_context=32)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
    assert outs == refs


def test_mid_decode_admission_is_continuous(tiny_model):
    """A request admitted while another is mid-decode joins the running
    batch at a step boundary (occupancy 1 → 2) and neither stream is
    perturbed."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=4, block_size=4,
                       max_context=48)
    ref_a = _reference_greedy(params, cfg, [7, 8, 9], 10)
    ref_b = _reference_greedy(params, cfg, [42, 43], 5)
    a = eng.submit([7, 8, 9], SamplingParams(max_new_tokens=10))
    eng.step()                   # prefill A + first decode
    eng.step()
    assert eng.occupancy_log[-1] == 1
    b = eng.submit([42, 43], SamplingParams(max_new_tokens=5))
    while not (a.done.is_set() and b.done.is_set()):
        eng.step()
    assert max(eng.occupancy_log) == 2, "B never joined the batch"
    assert a.wait(0) == ref_a
    assert b.wait(0) == ref_b


def test_decode_compiles_exactly_once(tiny_model):
    """Any mix of prompt lengths, sampling params and admission orders
    rides two fixed-shape executables — no per-request retracing."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=3, block_size=4,
                       max_context=32)
    eng.generate([[1], [2, 3, 4, 5]], SamplingParams(max_new_tokens=3))
    eng.generate([[9, 8, 7]], SamplingParams(max_new_tokens=7,
                                             temperature=0.9, top_k=5))
    eng.generate([[4, 4], [5], [6, 6, 6]],
                 SamplingParams(max_new_tokens=2))
    assert eng.decode_compiles == 1
    assert eng.prefill_compiles == 1


def test_kv_pool_pressure_preempts_youngest_and_recovers(tiny_model):
    """When the pool runs dry the youngest request is evicted (pages
    freed, request requeued) and later resumes by recompute — both
    streams still match their solo greedy references."""
    params, cfg = tiny_model
    # usable pages: 7. A alone peaks at 6 pages, B at 5 — running
    # together they outgrow the pool and the younger (B) must yield.
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=32, num_blocks=8,
                       metrics=_metrics())
    ref_a = _reference_greedy(params, cfg, [1, 2, 3, 4], 20)
    ref_b = _reference_greedy(params, cfg, [9, 9, 9, 9], 16)
    a = eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=20))
    b = eng.submit([9, 9, 9, 9], SamplingParams(max_new_tokens=16))
    while not (a.done.is_set() and b.done.is_set()):
        eng.step()
    assert b.preemptions >= 1, "pool pressure never evicted the youngest"
    assert eng.metrics.preemptions.value() >= 1
    assert a.wait(0) == ref_a
    assert b.wait(0) == ref_b
    # every page is either free or resident ref-zero prefix cache —
    # nothing is still mapped by a finished request
    cached = len(eng.prefix_cache)
    assert eng.pool.num_free + cached == eng.pool.num_usable
    assert all(eng.pool.refcount(b) == 0
               for b in range(1, eng.pool.num_blocks))


def test_submit_rejects_impossible_requests(tiny_model):
    """A request the pool can NEVER satisfy must fail fast at submit —
    parking it in the admission queue would wedge the queue forever."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=16, num_blocks=3)
    with pytest.raises(ValueError):
        eng.submit(list(range(20)), SamplingParams(max_new_tokens=1))
    with pytest.raises(ValueError):     # pool can never hold it
        eng.submit([1, 2], SamplingParams(max_new_tokens=12))
    assert eng.queue_depth == 0         # rejected, not parked
    with pytest.raises(ValueError):
        eng.submit([], SamplingParams())
    with pytest.raises(ValueError):     # prefill always emits one token
        eng.submit([1], SamplingParams(max_new_tokens=0))
    # the bound is pool capacity, not current availability: resident
    # prefix-cache blocks are evictable, so a feasible request must
    # still be accepted when the pool is momentarily full of cache
    eng2 = DecodeEngine(params, cfg, max_batch=1, block_size=4,
                        max_context=16, num_blocks=4)   # 3 usable pages
    eng2.generate([[1, 2, 3, 4, 5, 6]], SamplingParams(max_new_tokens=2))
    assert len(eng2.prefix_cache) > 0   # cache resident, pages not free
    with pytest.raises(ValueError):     # 13 tokens = 4 pages > 3 ever
        eng2.submit(list(range(9)), SamplingParams(max_new_tokens=4))
    out = eng2.generate([[9, 9, 9, 9, 9, 9, 9, 9]],
                        SamplingParams(max_new_tokens=4))
    assert len(out[0]) == 4             # feasible: cache evicted to fit


def test_engine_context_never_exceeds_model_max_seq(tiny_model):
    """Block-size rounding must never admit positions past the model's
    rope/pos-embed tables (silent clamping = wrong logits)."""
    params, cfg = tiny_model                   # cfg.max_seq == 128
    eng = DecodeEngine(params, cfg, max_batch=1, block_size=48)
    assert eng.s_max <= cfg.max_seq
    with pytest.raises(ValueError):
        DecodeEngine(params, cfg, max_batch=1, block_size=256)


def test_per_request_sampling_params(tiny_model):
    """top_k=1 at any temperature is argmax; free sampling stays in
    vocab range. Both ride the same compiled step as greedy lanes."""
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=3, block_size=4,
                       max_context=32)
    ref = _reference_greedy(params, cfg, [11, 12, 13], 6)
    greedy = eng.submit([11, 12, 13], SamplingParams(max_new_tokens=6))
    topk1 = eng.submit([11, 12, 13],
                       SamplingParams(max_new_tokens=6,
                                      temperature=1.0, top_k=1))
    free = eng.submit([50, 51], SamplingParams(max_new_tokens=6,
                                               temperature=1.2))
    while not all(r.done.is_set() for r in (greedy, topk1, free)):
        eng.step()
    assert greedy.wait(0) == ref
    assert topk1.wait(0) == ref
    assert all(0 <= t < cfg.vocab_size for t in free.wait(0))


# ---------------------------------- the head and the sampler, by what is asked

@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("shape", ["decode", "fused"])
def test_step_holds_no_sort_and_a_head_of_the_rows_it_reads(
        tiny_model, jaxpr_eqns, shape, spec_k):
    """Neither shape of the step sorts the vocabulary, and the fused
    shape's head matmul has ``B*G + 1`` rows — the lanes' and the ONE
    chunk row whose sample is read — not ``B*G + C``."""
    params, cfg = tiny_model
    b, c, g = 3, 8, spec_k + 1
    eng = DecodeEngine(params, cfg, max_batch=b, block_size=4,
                       max_context=32, prefill_chunk=c, speculate_k=spec_k)
    chunk = None if shape == "decode" else (
        jnp.zeros((c,), jnp.int32), jnp.asarray([0, 0, c], jnp.int32))
    closed = jax.make_jaxpr(eng._step_impl)(
        eng.params, *eng._pools, eng._dstate, eng._dz_drafts, eng._dz_lens,
        chunk)
    prims = jaxpr_eqns(closed.jaxpr)
    assert "sort" not in {name for name, _ in prims}
    heads = {s[0] for name, s in prims
             if name == "dot_general" and s[-1:] == (cfg.vocab_size,)}
    assert heads == {b * g + (shape == "fused")}


def _stepped(eng, *requests):
    """Step until the requests are done; per step: (which of them were
    still live when it began, steps_argmax_only's gain, steps_topk's)."""
    log = []
    while not all(r.done.is_set() for r in requests):
        live = tuple(not r.done.is_set() for r in requests)
        before = eng.steps, eng.steps_argmax_only, eng.steps_topk
        eng.step()
        if eng.steps > before[0]:
            log.append((live, eng.steps_argmax_only - before[1],
                        eng.steps_topk - before[2]))
    return log


def test_steps_count_what_the_sampler_was_asked_for(tiny_model):
    """``steps_argmax_only`` / ``steps_topk`` are the device's own record
    of the arm each step took: every step of an all-greedy run; none
    while a sampled lane is live or its prompt chunk rides; again once
    that lane has finished; never for a slot that is not live, whatever
    parameters it still carries; the search only for a live sampled
    top-k."""
    params, cfg = tiny_model
    m = _metrics()
    eng = DecodeEngine(params, cfg, max_batch=3, block_size=4,
                       max_context=48, prefill_chunk=4, metrics=m)
    prompt = [11, 12, 13, 14, 15, 16, 17, 18, 19, 20]      # three chunks
    ref = _reference_greedy(params, cfg, prompt, 14)

    # (a) all greedy — a greedy lane with a top-k asks for no search
    a = eng.submit(prompt, SamplingParams(max_new_tokens=5))
    b = eng.submit([5, 6], SamplingParams(max_new_tokens=3, top_k=4))
    log = _stepped(eng, a, b)
    assert len(log) >= 6 and all(x[1:] == (1, 0) for x in log)
    assert a.wait(0) == ref[:5]

    # (b) a sampled lane beside a greedy one, and the steps after it
    # (the sampled prompt first: its chunk rides the first step)
    s = eng.submit([7, 8, 9], SamplingParams(max_new_tokens=3,
                                             temperature=0.9))
    g = eng.submit(prompt, SamplingParams(max_new_tokens=14))
    log = _stepped(eng, g, s)
    while_s = [x for x in log if x[0][1]]
    after_s = [x for x in log if not x[0][1]]
    assert len(while_s) >= 3 and all(x[1:] == (0, 0) for x in while_s)
    assert len(after_s) >= 4 and all(x[1:] == (1, 0) for x in after_s)
    assert g.wait(0) == ref                 # greedy beside it: unmoved

    # (c) a sampled prompt alone: its chunk (the tail the prefix cache
    # does not hold) switches the arm on before any lane decodes, and
    # its top-k the search
    t = eng.submit(prompt, SamplingParams(max_new_tokens=4,
                                          temperature=0.7, top_k=3))
    log = _stepped(eng, t)
    assert len(log) >= 4 and all(x[1:] == (0, 1) for x in log)
    assert all(0 <= tok < cfg.vocab_size for tok in t.wait(0))

    # (d) top_k=1 at a temperature is the arg-max, by the search
    k1 = eng.submit(prompt, SamplingParams(max_new_tokens=5,
                                           temperature=1.0, top_k=1))
    log = _stepped(eng, k1)
    assert all(x[1:] == (0, 1) for x in log) and k1.wait(0) == ref[:5]

    # (e) a lane the device has retired keeps its parameters until the
    # host's release event lands (one step later, when the loop runs
    # ahead): such a slot asks for nothing
    stale = np.asarray([2, 0, 0, 0, 5, 0, 0, -1], np.int32)
    eng._dstate = engine_mod._SET_SLOT(
        eng._dstate, stale, np.zeros((eng.blocks_per_seq,), np.int32),
        np.float32(0.9))
    e = eng.submit(prompt, SamplingParams(max_new_tokens=5))
    log = _stepped(eng, e)
    assert all(x[1:] == (1, 0) for x in log) and e.wait(0) == ref[:5]
    temps, topks, active = jax.device_get(
        [eng._dstate[k] for k in ("temps", "topks", "active")])
    assert temps[2] > 0 and topks[2] == 5 and not active[2]

    snap = m.snapshot()
    assert snap["steps_argmax_only"] == eng.steps_argmax_only > 0
    assert snap["steps_topk"] == eng.steps_topk > 0
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1


def test_warm_prefix_cache_stays_exact_match(tiny_model):
    """The tentpole correctness pin: decode through REUSED KV blocks
    must produce exactly the tokens a cold full recompute produces —
    for a shared-head sibling and for an identical resubmit."""
    params, cfg = tiny_model
    head = [5, 9, 2, 7, 1, 8, 3, 6, 4, 2, 9, 1, 7, 3, 8, 5]   # 4 blocks
    pa, pb = head + [11, 12], head + [13]
    ref_a = _reference_greedy(params, cfg, pa, 8)
    ref_b = _reference_greedy(params, cfg, pb, 8)
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=48, prefill_chunk=4,
                       metrics=_metrics())
    a = eng.submit(pa, SamplingParams(max_new_tokens=8))
    while not a.done.is_set():
        eng.step()
    assert a.wait(0) == ref_a                   # cold
    assert a.prefix_tokens_reused == 0
    assert len(eng.prefix_cache) >= 4           # head blocks resident
    b = eng.submit(pb, SamplingParams(max_new_tokens=8))
    while not b.done.is_set():
        eng.step()
    assert b.wait(0) == ref_b                   # warm sibling: exact
    assert b.prefix_tokens_reused == 16         # the whole head
    a2 = eng.submit(pa, SamplingParams(max_new_tokens=8))
    while not a2.done.is_set():
        eng.step()
    assert a2.wait(0) == ref_a                  # identical resubmit:
    # matched to the last full block, never the final prompt token
    # (its logits must be recomputed to sample the first output)
    assert a2.prefix_tokens_reused == 16
    stats = eng.cache_stats()
    assert stats["hit_rate"] > 0
    # engine-local counter, not the process-global metrics source
    # (other tests in this process share that counter object)
    assert eng.prefix_tokens_matched == 32
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1


def test_chunked_prefill_does_not_stall_running_decodes(tiny_model):
    """A long prompt prefills prefill_chunk tokens per step INSIDE the
    decode step: the running request keeps emitting one token every
    step of the newcomer's multi-chunk prefill (the head-of-line block
    the monolithic prefill used to cause), and both streams stay
    exact."""
    params, cfg = tiny_model
    long_prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3,
                   2, 3, 8, 4]                                  # 5 chunks
    ref_a = _reference_greedy(params, cfg, [7, 8, 9], 16)
    ref_b = _reference_greedy(params, cfg, long_prompt, 6)
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=48, prefill_chunk=4)
    a = eng.submit([7, 8, 9], SamplingParams(max_new_tokens=16))
    eng.step()
    eng.step()
    a_before = len(a.out_tokens)
    b = eng.submit(long_prompt, SamplingParams(max_new_tokens=6))
    b_first_step = None
    for i in range(1, 30):
        eng.step()
        if b.out_tokens and b_first_step is None:
            b_first_step = i
            a_during = len(a.out_tokens) - a_before
            break
    assert b_first_step >= 5, "20-token prompt at chunk=4 must take " \
                              ">= 5 steps to its first token"
    # every prefill-chunk step also advanced A by one decode token
    assert a_during >= b_first_step - 1
    while not (a.done.is_set() and b.done.is_set()):
        eng.step()
    assert a.wait(0) == ref_a
    assert b.wait(0) == ref_b
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1


def test_preempting_a_sharer_never_frees_sibling_blocks(tiny_model):
    """Preemption x chunked prefill x prefix sharing: B maps A's cached
    head blocks; pool pressure then preempts B (the youngest). The
    shared pages must survive for A (its stream stays exact), and B's
    warm resubmit-by-recompute stays exact too."""
    params, cfg = tiny_model
    head = [5, 9, 2, 7, 1, 8, 3, 6]                   # 2 full blocks
    pa, pb = head + [1, 2], head + [3, 4]
    ref_a = _reference_greedy(params, cfg, pa, 14)
    ref_b = _reference_greedy(params, cfg, pb, 10)
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=32, num_blocks=8, prefill_chunk=4,
                       metrics=_metrics())
    a = eng.submit(pa, SamplingParams(max_new_tokens=14))
    while a._prefill_pos is not None or not a.out_tokens:
        eng.step()                      # A's head is now cached
    b = eng.submit(pb, SamplingParams(max_new_tokens=10))
    while not (a.done.is_set() and b.done.is_set()):
        eng.step()
    assert b.prefix_tokens_reused >= 8, "B never mapped the shared head"
    assert b.preemptions >= 1, "pool pressure never evicted the youngest"
    assert a.wait(0) == ref_a           # sibling pages survived
    assert b.wait(0) == ref_b           # warm recompute resume: exact
    # every page is free or resident zero-ref cache; nothing leaked
    assert eng.pool.num_free + len(eng.prefix_cache) == \
        eng.pool.num_usable
    assert all(eng.pool.refcount(blk) == 0
               for blk in range(1, eng.pool.num_blocks))


def test_engine_shards_over_tp_mesh(tiny_model):
    """The same engine code runs with weights and KV heads sharded over
    a tp=2 mesh (virtual CPU devices) — greedy output is unchanged."""
    from hadoop_tpu.parallel.mesh import MeshPlan
    params, cfg = tiny_model
    ref = _reference_greedy(params, cfg, [5, 6, 7], 6)
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=32, plan=MeshPlan(tp=2))
    got = eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=6))[0]
    assert got == ref


def _metrics():
    from hadoop_tpu.serving.metrics import ServingMetrics
    return ServingMetrics()


# ------------------------------------------------------------------ loader

def test_loader_reads_wrapped_and_bare_trees(tmp_path, tiny_model):
    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    from hadoop_tpu.serving.loader import load_serving_params
    params, cfg = tiny_model
    fs = LocalFileSystem()
    # the trainer's layout ({"params":..., "opt":...}) and a bare tree
    save_checkpoint(fs, f"{tmp_path}/wrapped", 3,
                    {"params": params, "opt": {"step": jnp.zeros(())}})
    save_checkpoint(fs, f"{tmp_path}/bare", 5, params)
    # sequential and concurrent shard fetch must load identical trees
    for io_workers in (1, 4):
        for base in ("wrapped", "bare"):
            got, step = load_serving_params(fs, f"{tmp_path}/{base}",
                                            cfg, io_workers=io_workers)
            assert step == (3 if base == "wrapped" else 5)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(params)):
                assert jnp.allclose(a, b)


# ----------------------------------------------------------- http replica

def _post_json(port, path, payload):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("POST", path, body=json.dumps(payload).encode())
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, (json.loads(body) if body else {})


def test_end_to_end_dfs_checkpoint_to_streaming_http(tmp_path,
                                                     tiny_model):
    """The acceptance path: checkpoint written to miniDFS is loaded by
    the replica; three concurrent different-length requests decode
    correctly with at least one admitted mid-decode (batch-occupancy
    observable); /v1/generate streams tokens and enforces auth; drain
    refuses new work and finishes what it holds."""
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    from hadoop_tpu.serving.loader import load_serving_params
    from hadoop_tpu.serving.server import ServingServer
    from hadoop_tpu.testing.minicluster import MiniDFSCluster, fast_conf
    params, cfg = tiny_model
    conf = fast_conf()
    conf.set("dfs.replication", "1")
    with MiniDFSCluster(num_datanodes=1, conf=conf,
                        base_dir=str(tmp_path)) as cluster:
        cluster.wait_active()
        fs = cluster.get_filesystem()
        save_checkpoint(fs, "/models/tiny", 7,
                        {"params": params, "opt": {"s": jnp.zeros(())}})
        loaded, step = load_serving_params(fs, "/models/tiny", cfg)
        assert step == 7

        conf.set("serving.http.auth.secret", "s3cr3t")
        eng = DecodeEngine(loaded, cfg, max_batch=4, block_size=4,
                           max_context=48, metrics=_metrics())
        srv = ServingServer(eng, conf)
        eng.start()
        srv.start()
        try:
            # auth enforced: no credential -> 401
            status, body = _post_json(srv.port, "/v1/generate",
                                      {"tokens": [1, 2]})
            assert status == 401
            assert "AuthenticationException" in str(body)

            prompts = [[7, 8, 9], [42, 43], [1, 2, 3, 4, 5, 6]]
            refs = [_reference_greedy(params, cfg, p, n)
                    for p, n in zip(prompts, (40, 8, 8))]
            results = {}

            def ask(i, prompt, max_new):
                status, body = _post_json(
                    srv.port, "/v1/generate?user.name=alice",
                    {"tokens": prompt, "max_new_tokens": max_new})
                results[i] = (status, body)

            # long request first; the others join while it decodes
            t0 = threading.Thread(target=ask, args=(0, prompts[0], 40))
            t0.start()
            deadline = time.monotonic() + 60
            while eng.num_active < 1 and time.monotonic() < deadline:
                time.sleep(0.005)
            ts = [threading.Thread(target=ask, args=(i, prompts[i], 8))
                  for i in (1, 2)]
            for t in ts:
                t.start()
            for t in [t0] + ts:
                t.join(timeout=120)
            for i in range(3):
                status, body = results[i]
                assert status == 200, body
                assert body["tokens"] == refs[i]
            # continuous batching observable: the occupancy metric saw
            # more than one request in the batch at once
            assert max(eng.occupancy_log) >= 2
            assert eng.metrics.ttft.snapshot()[
                "time_to_first_token_count"] == 3
            # cache observability rides the health door
            status, health = _post_json(srv.port, "/v1/health", {})
            assert status == 200
            assert health["prefix_cache"]["enabled"] is True
            assert health["prefix_cache"]["prefill_chunk"] >= 1

            # streaming: chunked JSON lines, one per token
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=60)
            conn.request("POST", "/v1/generate?user.name=alice",
                         body=json.dumps({"tokens": [7, 8, 9],
                                          "max_new_tokens": 4,
                                          "stream": True}).encode())
            resp = conn.getresponse()
            assert resp.status == 200
            lines = [json.loads(l) for l in resp.read().splitlines() if l]
            conn.close()
            assert [l["token"] for l in lines[:-1]] == refs[0][:4]
            assert lines[-1]["done"] is True

            # drain: in-flight work finishes, new work is refused
            srv.drain(timeout=30)
            status, body = _post_json(srv.port,
                                      "/v1/generate?user.name=alice",
                                      {"tokens": [1]})
            assert status == 503
            status, health = _post_json(srv.port, "/v1/health", {})
            assert health["status"] == "draining"
        finally:
            srv.stop()


def test_generate_timeout_returns_408_not_retriable(tiny_model):
    """A generation outliving the client timeout returns 408 (a 4xx the
    router fails fast on) instead of a 500 the router would replay on
    every replica — retry amplification under load."""
    from hadoop_tpu.serving.server import ServingServer
    params, cfg = tiny_model
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=32)
    srv = ServingServer(eng, Configuration(load_defaults=False))
    srv.start()          # engine scheduler NOT started: request parks
    try:
        status, body = _post_json(
            srv.port, "/v1/generate",
            {"tokens": [1, 2], "max_new_tokens": 4, "timeout": 0.2})
        assert status == 408
        assert "RequestTimedOutException" in str(body)
        status, body = _post_json(
            srv.port, "/v1/generate",
            {"tokens": [1, 2], "timeout": "abc"})
        assert status == 400         # malformed timeout is a 400 like
        assert "IllegalArgument" in str(body)   # every other bad field
    finally:
        srv.stop()


def test_router_power_of_two_and_drain(tiny_model):
    """Router resolves replicas from the registry, balances, retries
    past a draining replica, and sees drained replicas leave the
    candidate set."""
    from hadoop_tpu.registry import (RegistryClient, RegistryServer,
                                     ServiceRecord)
    from hadoop_tpu.serving.router import ServingRouter, replica_path
    from hadoop_tpu.serving.server import ServingServer
    params, cfg = tiny_model
    conf = Configuration(load_defaults=False)
    reg_srv = RegistryServer(conf)
    reg_srv.init(conf)
    reg_srv.start()
    engines, servers = [], []
    try:
        for _ in range(2):
            eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                               max_context=32)
            srv = ServingServer(eng, Configuration(load_defaults=False))
            eng.start()
            srv.start()
            engines.append(eng)
            servers.append(srv)
        reg_addr = ("127.0.0.1", reg_srv.port)
        rc = RegistryClient(reg_addr, conf)
        for i, srv in enumerate(servers):
            rc.register(ServiceRecord(
                replica_path("demo", f"r{i}"),
                {"http": f"127.0.0.1:{srv.port}"},
                {"state": "serving"}), ttl_s=30.0, auto_renew=False)
        # and one dead endpoint the retry policy must route around
        rc.register(ServiceRecord(replica_path("demo", "dead"),
                                  {"http": "127.0.0.1:1"},
                                  {"state": "serving"}),
                    ttl_s=30.0, auto_renew=False)
        router = ServingRouter(reg_addr, "demo", conf, cache_ttl_s=0.0)
        ref = _reference_greedy(params, cfg, [3, 4, 5], 4)
        for _ in range(6):
            out = router.generate({"tokens": [3, 4, 5],
                                   "max_new_tokens": 4})
            assert out["tokens"] == ref
        # drain replica 0: record flips, router keeps succeeding via 1
        servers[0].drain(timeout=10)
        rc.register(ServiceRecord(replica_path("demo", "r0"),
                                  {"http":
                                   f"127.0.0.1:{servers[0].port}"},
                                  {"state": "draining"}),
                    ttl_s=30.0, auto_renew=False)
        for _ in range(4):
            out = router.generate({"tokens": [3, 4, 5],
                                   "max_new_tokens": 4})
            assert out["tokens"] == ref
        live = router.replicas(refresh=True)
        assert {r.path for r in live} == {replica_path("demo", "r1"),
                                          replica_path("demo", "dead")}
        # deterministic 400s fail fast — no cross-replica retry storm
        from hadoop_tpu.serving.router import ReplicaRequestError
        with pytest.raises(ReplicaRequestError):
            router.generate({"tokens": []})
        # registry outage: the stale replica cache keeps serving
        router.replicas(refresh=True)
        reg_srv.stop()
        out = router.generate({"tokens": [3, 4, 5],
                               "max_new_tokens": 4})
        assert out["tokens"] == ref
        router.close()
        rc.close()
    finally:
        for srv in servers:
            srv.stop()
        reg_srv.stop()


def test_router_prefix_affinity_pins_shared_prefixes(tiny_model):
    """Requests sharing a prompt prefix rendezvous onto ONE replica
    (its prefix cache keeps earning hits across the fleet) and fail
    over when that replica drains."""
    from hadoop_tpu.registry import (RegistryClient, RegistryServer,
                                     ServiceRecord)
    from hadoop_tpu.serving.router import ServingRouter, replica_path
    from hadoop_tpu.serving.server import ServingServer
    params, cfg = tiny_model
    conf = Configuration(load_defaults=False)
    reg_srv = RegistryServer(conf)
    reg_srv.init(conf)
    reg_srv.start()
    engines, servers = [], []
    try:
        for _ in range(2):
            eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                               max_context=32)
            srv = ServingServer(eng, Configuration(load_defaults=False))
            eng.start()
            srv.start()
            engines.append(eng)
            servers.append(srv)
        reg_addr = ("127.0.0.1", reg_srv.port)
        rc = RegistryClient(reg_addr, conf)
        for i, srv in enumerate(servers):
            rc.register(ServiceRecord(
                replica_path("affine", f"r{i}"),
                {"http": f"127.0.0.1:{srv.port}"},
                {"state": "serving"}), ttl_s=30.0, auto_renew=False)
        router = ServingRouter(reg_addr, "affine", conf, cache_ttl_s=0.0)
        ref = _reference_greedy(params, cfg, [3, 4, 5], 4)
        for _ in range(6):
            out = router.generate({"tokens": [3, 4, 5],
                                   "max_new_tokens": 4})
            assert out["tokens"] == ref
        assert router.affinity_routed == 6
        # all six shared-prefix requests landed on one replica
        served = [e for e in engines if e.tokens_generated > 0]
        assert len(served) == 1
        # drain the pinned replica: affinity must fail over, not wedge
        pinned = engines.index(served[0])
        servers[pinned].drain(timeout=10)
        rc.register(ServiceRecord(
            replica_path("affine", f"r{pinned}"),
            {"http": f"127.0.0.1:{servers[pinned].port}"},
            {"state": "draining"}), ttl_s=30.0, auto_renew=False)
        out = router.generate({"tokens": [3, 4, 5],
                               "max_new_tokens": 4})
        assert out["tokens"] == ref
        assert engines[1 - pinned].tokens_generated > 0
        router.close()
        rc.close()
    finally:
        for srv in servers:
            srv.stop()
        reg_srv.stop()


def test_replica_lifecycle_with_registry(tmp_path, tiny_model):
    """ServingReplica end-to-end without YARN: file:// checkpoint,
    registry registration, router-routed generate, drain-and-stop
    leaves the registry clean. (The YARN service spec launches exactly
    this entry point per container.)"""
    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    from hadoop_tpu.registry import RegistryServer
    from hadoop_tpu.serving.router import ServingRouter
    from hadoop_tpu.serving.service import ServingReplica
    params, cfg = tiny_model
    save_checkpoint(LocalFileSystem(), f"{tmp_path}/ckpt", 2,
                    {"params": params, "opt": {}})
    conf = Configuration(load_defaults=False)
    reg_srv = RegistryServer(conf)
    reg_srv.init(conf)
    reg_srv.start()
    try:
        replica = ServingReplica(
            conf, name="lifecycle", checkpoint=f"file://{tmp_path}/ckpt",
            preset="tiny", registry_addr=("127.0.0.1", reg_srv.port),
            instance="i0")
        replica.start()
        router = ServingRouter(("127.0.0.1", reg_srv.port), "lifecycle",
                               conf)
        ref = _reference_greedy(params, cfg, [1, 2], 3)
        out = router.generate({"tokens": [1, 2], "max_new_tokens": 3})
        assert out["tokens"] == ref
        replica.drain_and_stop(timeout=15)
        assert router.replicas(refresh=True) == []
        router.close()
    finally:
        reg_srv.stop()


def test_serving_service_spec_packaging():
    """The YARN packaging: one replica component, restart ALWAYS, the
    replica entry point in the launch command, JSON-roundtrippable."""
    from hadoop_tpu.serving.service import serving_service_spec
    from hadoop_tpu.yarn.services import ServiceSpec
    spec = serving_service_spec(
        "llm", checkpoint="htpu://nn:8020/models/llm", preset="tiny",
        replicas=3, registry_addr="127.0.0.1:7777")
    rt = ServiceSpec.from_json(spec.to_json())
    assert rt.name == "llm"
    comp = rt.components[0]
    assert comp.number_of_containers == 3
    assert comp.restart_policy == "ALWAYS"
    assert "hadoop_tpu.serving.service" in comp.launch_command
    assert "--checkpoint" in comp.launch_command
