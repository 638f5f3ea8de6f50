"""The serving thread runs one step ahead of its own read-back
(``DecodeEngine._run_loop`` → ``_iterate(ahead=True)``): step n+1 is
dispatched before step n's bundle is read, so what the host knows at
dispatch it knows from its own numbers — pages, the chunk's span,
whether the chunk is the prompt's last (the lane is then armed on the
device, from the step's own ``c_first``), and whose row a row is.

Held here, for the dense, the Mixtral and the latent family alike:
the thread serves the tokens a ``step()``-driven engine serves; stop
tokens and a budget of one; a slot placed again inside one flight;
everything that is not the steady loop (a dry pool, a drain, a tier
move, a failed step) leaves no request hanging and no page behind; the
two step shapes still compile once each; the decode-only path still
uploads nothing; the share of steps that ran ahead; the TTFT stages.
"""

import threading
import time

import jax
import pytest

from hadoop_tpu.fs import LocalFileSystem
from hadoop_tpu.models import decoder, deepseek
from hadoop_tpu.models.config import get_config
from hadoop_tpu.serving.engine import (FAILED, FINISHED, DecodeEngine,
                                       SamplingParams)
from hadoop_tpu.serving.metrics import ServingMetrics

PROMPTS = ([7, 3, 9, 4, 1, 8, 2],
           [5, 6, 7],
           [9, 8, 7, 6, 5, 4, 3, 2, 1, 2, 3, 4, 5, 6, 7, 8, 9, 1, 2])
BUDGETS = (24, 9, 17)


@pytest.fixture(scope="module", params=["tiny", "tiny-moe", "tiny-dsv32"])
def model(request):
    cfg = get_config(request.param)
    init = deepseek.init_params if request.param == "tiny-dsv32" \
        else decoder.init_params
    return request.param, init(jax.random.PRNGKey(0), cfg), cfg


def _engine(model, **kw):
    _, params, cfg = model
    kw.setdefault("max_batch", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 96)
    kw.setdefault("prefill_chunk", 8)
    # Mixtral's capacity-padded routing lets co-batched rows take an
    # expert's room (ROADMAP R2); with room for every row an answer
    # does not depend on who else is in the step
    kw.setdefault("moe_capacity_factor", 4.0)
    return DecodeEngine(params, cfg, **kw)


def _stepped(eng, reqs):
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    return [list(r.out_tokens) for r in reqs]


def _served(eng, reqs, timeout=120.0):
    """On the serving thread; the engine is stopped on the way out."""
    eng.start()
    try:
        return [r.wait(timeout) for r in reqs]
    finally:
        eng.stop()


def _no_page_held(eng):
    """Every page is free or zero-ref cache: the pool counts back."""
    cached = len(eng.prefix_cache) if eng.prefix_cache is not None else 0
    return eng.pool.num_free + cached == eng.pool.num_usable and all(
        eng.pool.refcount(b) == 0 for b in range(1, eng.pool.num_blocks))


@pytest.fixture(scope="module")
def greedy(model):
    """Each prompt's own greedy answer, 40 tokens of it."""
    eng = _engine(model)
    return _stepped(eng, [eng.submit(p, SamplingParams(max_new_tokens=40))
                          for p in PROMPTS])


# ------------------------------------------------------------ the same work

@pytest.mark.parametrize("sampling", [
    pytest.param({}, id="greedy"),
    pytest.param({"temperature": 0.9, "top_k": 8}, id="seeded")])
def test_the_thread_serves_a_stepped_engines_tokens(model, sampling):
    """Token for token: the schedule is the same (every request placed
    in the first iteration, chunks in admission order), so sampled
    lanes draw from the same keys — the seed is carried on the device,
    one increment a step — and the step counts are equal: a lane at
    the end of its budget is not run again, which the host can count
    without reading."""
    def submit(eng):
        return [eng.submit(p, SamplingParams(max_new_tokens=n, **sampling))
                for p, n in zip(PROMPTS, BUDGETS)]
    ref = _engine(model)
    want = _stepped(ref, submit(ref))
    assert [len(t) for t in want] == list(BUDGETS)
    assert ref.steps_run_ahead == 0 and ref._flight is None
    eng = _engine(model)
    assert _served(eng, submit(eng)) == want
    assert eng.steps == ref.steps
    assert eng.steps_run_ahead >= eng.steps - 2
    # the device's record of its sampler's arm, read a step late: every
    # step an arg-max when nobody samples, every step a search otherwise
    arms = (0, eng.steps) if sampling else (eng.steps, 0)
    assert (eng.steps_argmax_only, eng.steps_topk) == arms == (
        ref.steps_argmax_only, ref.steps_topk)
    # still two compiles an engine
    assert (eng.decode_compiles, eng.prefill_compiles) == (1, 1)
    assert (ref.decode_compiles, ref.prefill_compiles) == (1, 1)
    assert _no_page_held(eng) and not eng._in_flight.any()


def test_stop_tokens_and_a_budget_of_one(model, greedy):
    """A stop token met in flight (the device retires the lane; the host
    learns it a step late and the row of the step between is nobody's),
    a stop token that is the first token (the device leaves the lane
    off when it arms it), and ``max_new_tokens`` 1 (never armed) — each
    beside lanes that run on."""
    a, b, c = greedy
    mid = a[6]
    cases = [
        (PROMPTS[0], SamplingParams(max_new_tokens=30, stop_token=mid),
         a[:a.index(mid) + 1]),
        (PROMPTS[1], SamplingParams(max_new_tokens=30, stop_token=b[0]),
         b[:1]),
        (PROMPTS[2], SamplingParams(max_new_tokens=1), c[:1]),
        (PROMPTS[1], SamplingParams(max_new_tokens=30), b[:30]),
    ]
    eng = _engine(model, max_batch=4)
    reqs = [eng.submit(p, sp) for p, sp, _ in cases]
    got = _served(eng, reqs)
    assert got == [want for _, _, want in cases]
    assert all(r.state == FINISHED for r in reqs)
    assert _no_page_held(eng) and not eng._active.any()


def test_a_slot_placed_again_inside_one_flight(model, greedy):
    """Two lanes, three requests: the first ends on a stop token, so the
    step dispatched behind that one still lists it; by the time that
    step is read the slot holds the request that waited (the other lane
    keeps the loop going). A row belongs to the request that held the
    slot when the step was dispatched."""
    a, b, c = greedy
    stop = a[4]
    eng = _engine(model, max_batch=2)
    stale = []
    deliver = eng._deliver_step

    def watching(packed, flight):
        stale.extend((req.id, eng._slots[slot].id)
                     for slot, req in flight.rows
                     if eng._slots[slot] is not None
                     and eng._slots[slot] is not req)
        return deliver(packed, flight)

    eng._deliver_step = watching
    reqs = [eng.submit(PROMPTS[0],
                       SamplingParams(max_new_tokens=30, stop_token=stop)),
            eng.submit(PROMPTS[1], SamplingParams(max_new_tokens=40)),
            eng.submit(PROMPTS[2], SamplingParams(max_new_tokens=7))]
    got = _served(eng, reqs)
    assert got == [a[:a.index(stop) + 1], b[:40], c[:7]]
    # it happened: a step was read whose row's slot had moved on
    assert (reqs[0].id, reqs[2].id) in stale
    assert _no_page_held(eng)


def test_a_prompt_is_indexed_when_its_last_chunk_is_dispatched(model, greedy):
    """The admission that follows the dispatch of a prompt's last chunk
    maps its blocks, as it did when the step was read first: the device
    runs its steps in order, so the pages are written before they are
    read."""
    eng = _engine(model, max_batch=2)
    first = eng.submit(PROMPTS[2], SamplingParams(max_new_tokens=12))
    with eng._sched_lock:
        for _ in range(3):              # 19 tokens, 8 a chunk
            eng._iterate(ahead=True)
        assert first._prefill_pos is None and not first.out_tokens
        assert eng._flight is not None and eng._flight.last_chunk
    second = eng.submit(PROMPTS[2], SamplingParams(max_new_tokens=12))
    assert _stepped(eng, [first, second]) == [greedy[2][:12]] * 2
    assert second.prefix_tokens_reused == 16    # 4 whole blocks of 4
    assert _no_page_held(eng)


# ------------------------------------------------ what is not the steady loop

def test_a_dry_pool_preempts_after_a_drain(model, greedy):
    """Three lanes over a pool that holds two of them to the end: the
    youngest is preempted — after the step in flight is delivered, so
    no token of it is lost or given twice — and resumes by recompute."""
    m = ServingMetrics(f"serving.test.run-ahead.dry-{model[0]}")
    eng = _engine(model, num_blocks=16, prefix_cache=False, metrics=m)
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=24))
            for p in PROMPTS]
    got = _served(eng, reqs)
    assert got == [t[:24] for t in greedy]
    assert sum(r.preemptions for r in reqs) >= 1
    assert eng.pool.num_free == eng.pool.num_usable
    assert eng.steps_run_ahead > 0


def test_a_drain_stop_delivers_everything(model, greedy):
    eng = _engine(model, max_batch=2)
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=16))
            for p in PROMPTS]          # the third waits for a lane
    eng.start()
    eng.stop(drain=True, timeout=120.0)
    assert all(r.state == FINISHED for r in reqs)
    assert [r.out_tokens for r in reqs] == [t[:16] for t in greedy]
    assert eng._flight is None and _no_page_held(eng)


def test_a_stop_without_a_drain_leaves_nobody_waiting(model):
    eng = _engine(model)
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=60))
            for p in PROMPTS]
    eng.start()
    while eng.steps < 6:
        time.sleep(0.005)
    eng.stop()
    assert all(r.done.is_set() for r in reqs)
    assert {r.state for r in reqs} <= {FAILED, FINISHED}
    assert eng._flight is None and _no_page_held(eng)


def test_a_step_that_raises_with_one_in_flight(model, greedy):
    """The failure surfaces with a sound step still unread: both are
    dropped, the donated buffers rebuilt once, the running and the
    queued requests failed — and the thread serves on."""
    eng = _engine(model, max_batch=2)
    real, seen = eng._step_fn, []

    def flaky(*args):
        if len(seen) == 5:
            seen.append(eng._flight is not None)
            raise RuntimeError("injected device failure")
        seen.append(None)
        return real(*args)

    eng._step_fn = flaky
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=20))
            for p in PROMPTS]
    eng.start()
    try:
        for r in reqs:
            with pytest.raises(RuntimeError, match="decode failed"):
                r.wait(60.0)
        assert seen[5] is True          # one was in flight when it raised
        assert eng.pool.num_free == eng.pool.num_usable
        assert eng._flight is None and not eng._in_flight.any()
        fresh = eng.submit(PROMPTS[0], SamplingParams(max_new_tokens=8))
        assert fresh.wait(60.0) == greedy[0][:8]
    finally:
        eng.stop()
    assert _no_page_held(eng)


def test_tier_moves_drain_first(model, greedy, tmp_path):
    """``persist_cache`` from another thread while the loop runs ahead,
    and a host ring under a pool so small that victims are demoted
    between steps: each reads pages, so each waits for the step in
    flight — the answers are the plain ones and the pool counts back."""
    name = model[0]
    if name == "tiny-dsv32":
        with pytest.raises(NotImplementedError, match="serving.kv.host"):
            _engine(model, kv_host_bytes=1 << 20)
        return
    eng = _engine(model, num_blocks=20, kv_host_bytes=1 << 20,
                  kv_store_fs=LocalFileSystem(),
                  kv_store_dir=f"{tmp_path}/kv", kv_dfs_min_refs=1)
    rounds = [[eng.submit(p, SamplingParams(max_new_tokens=20))
               for p in PROMPTS]]
    eng.start()
    try:
        persisted = 0
        for _ in range(3):
            while not all(r.first_token_at for r in rounds[-1]):
                time.sleep(0.002)
            persisted += eng.persist_cache(timeout=60.0)
            for r in rounds[-1]:
                r.wait(120.0)
            rounds.append([eng.submit([11 + len(rounds)] + p,
                                      SamplingParams(max_new_tokens=6))
                           for p in PROMPTS])
        for r in rounds[-1]:
            r.wait(120.0)
    finally:
        eng.stop()
    assert [r.out_tokens for r in rounds[0]] == [t[:20] for t in greedy]
    assert persisted > 0 and eng.kvstore.stats()["demotions"] > 0
    assert _no_page_held(eng)


# ------------------------------------------------------------- the contract

def test_the_decode_only_path_uploads_nothing_while_ahead(model):
    """The steady state of the loop that runs ahead: dispatch n+1, read
    n — and nothing crosses host→device (the lane state, the seed and
    the pools are the device's; ``_in_flight`` is the host's own)."""
    eng = _engine(model, max_batch=2, block_size=16, max_context=64)
    req = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=40))
    for _ in range(4):       # prefill, arm, compile both shapes
        eng.step()
    assert eng._active[0] and eng._flight is None
    before = len(req.out_tokens)
    with jax.transfer_guard_host_to_device("disallow"):
        with eng._sched_lock:
            for _ in range(8):   # no admission/finish/page event in here
                eng._iterate(ahead=True)
            assert eng._flight is not None and eng._in_flight[0] == 1
            eng._drain()
    assert len(req.out_tokens) == before + 8
    assert eng.steps_run_ahead == 7
    # step() keeps its contract with the loop's leftovers too
    assert eng.step() == 1 and eng._flight is None


def test_the_share_of_steps_that_ran_ahead(model):
    """A step is not run ahead only when the loop starts from
    ``engine.wait`` or after a drain; an engine that speculates drafts
    step n+1 from the tokens of step n and stays in step."""
    name = model[0]
    m = ServingMetrics(f"serving.test.run-ahead.share-{name}")
    eng = _engine(model, metrics=m)
    assert eng._runs_ahead
    out = _served(eng, [eng.submit(PROMPTS[0],
                                   SamplingParams(max_new_tokens=64))])
    assert len(out[0]) == 64
    ahead = m.snapshot()["steps_run_ahead"]
    assert ahead == eng.steps_run_ahead
    assert ahead / eng.steps >= 0.9
    if name == "tiny-dsv32":
        return          # refuses serving.speculate.k by name
    m2 = ServingMetrics(f"serving.test.run-ahead.spec-{name}")
    spec = _engine(model, speculate_k=2, metrics=m2)
    assert not spec._runs_ahead
    motif = [4, 9, 2, 7] * 5
    out = _served(spec, [spec.submit(motif,
                                     SamplingParams(max_new_tokens=64))])
    assert len(out[0]) == 64 and spec.steps > 0
    assert spec.steps_run_ahead == 0
    assert m2.snapshot()["steps_run_ahead"] == 0


def test_ttft_stages_still_sum_to_ttft(model):
    """``first_chunk_at`` at the first dispatch, ``first_token_at`` at
    delivery, a step later than the device made it at most."""
    m = ServingMetrics(f"serving.test.run-ahead.ttft-{model[0]}")
    eng = _engine(model, max_batch=2, metrics=m)
    eng.start()
    try:
        reqs = []
        for i in range(5):
            reqs.append(eng.submit([1 + i] + PROMPTS[i % 3],
                                   SamplingParams(max_new_tokens=6)))
            time.sleep(0.01)
        for r in reqs:
            r.wait(120.0)
    finally:
        eng.stop()
    total = 0.0
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.first_chunk_at \
            <= r.first_token_at
        stages = (r.admitted_at - r.submitted_at,
                  r.first_chunk_at - r.admitted_at,
                  r.first_token_at - r.first_chunk_at)
        assert sum(stages) == pytest.approx(
            r.first_token_at - r.submitted_at, abs=1e-9)
        total += sum(stages)
    sums = [m.ttft_stage_hist[s].buckets()[1:]
            for s in ("queue", "prefill_wait", "prefill")]
    assert all(n == 5 for _, n in sums)
    assert sum(secs for secs, _ in sums) == pytest.approx(total, abs=1e-6)


def test_a_foreign_thread_may_step_beside_the_loop(model, greedy):
    """``step()`` takes the scheduler lock, delivers what the loop left
    in flight and its own step: nothing is delivered twice or lost."""
    eng = _engine(model)
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=30))
            for p in PROMPTS]
    eng.start()
    stop = threading.Event()

    def poke():
        while not stop.is_set():
            eng.step()
            time.sleep(0.002)

    t = threading.Thread(target=poke, daemon=True)
    t.start()
    try:
        got = [r.wait(120.0) for r in reqs]
    finally:
        stop.set()
        t.join(10.0)
        eng.stop()
    assert got == [t_[:30] for t_ in greedy]
    assert _no_page_held(eng)
