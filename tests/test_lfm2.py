"""``family="lfm2_moe"`` on the serving path, at test size on the CPU: the
engine THROUGH THE CACHE (pages of K/V in the attention layers, pages'
state tails in the convolution layers, the lanes' own state carried with
the step) against the plain reference in ``chipbench/families/lfm2_moe.py``
on LOGITS — cold chunked prefill, lanes started from 1, 2 and 3 cached
pages, a request preempted mid-decode and resumed, lanes decoding while
another prefills, the serving thread one step ahead against a stepped
engine; the router against its rule; four shares of the experts against
the whole layer; every plane the family refuses, by its key; the
validator.
"""

import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from chipbench import weights as W
from chipbench.families import lfm2_moe as F
from hadoop_tpu.models import lfm2
from hadoop_tpu.models.config import ModelConfig, get_config
from hadoop_tpu.models.moe import moe_share, route_grouped
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams
from hadoop_tpu.serving.metrics import ServingMetrics

SEED = 11
S_REF = 128          # every reference pass is padded to this length
TOL = 2e-4           # float32 on both sides (tests/test_deepseek_v32.py's)
# the configuration file's scalars, at test size
MODEL = {
    "model_type": "lfm2_moe", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 4, "num_dense_layers": 1, "conv_L_cache": 3,
    "num_hidden_layers": 7, "vocab_size": 256, "norm_eps": 1e-5,
    "routed_scaling_factor": 1, "router_norm_eps": 1e-6,
    "rope_theta": 10000, "tie_word_embeddings": True,
    "torch_dtype": "float32",
    "layer_kinds": "conv,full_attention,conv,conv,full_attention,conv,conv"}
BLOCK, CHUNK, LANES = 4, 8, 3


def make_params(model=MODEL, seed=SEED):
    """bfloat16 values (what the reference regenerates) held in float32."""
    tree = jax.jit(lambda k: F.make_params(model, k, jnp.bfloat16))(
        W.seed_key(seed))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def make_engine(**kw):
    cfg = F.model_config(MODEL, {"context": 256})
    kw.setdefault("metrics", ServingMetrics("serving.test.lfm2"))
    return DecodeEngine(make_params(), cfg, max_batch=LANES,
                        block_size=BLOCK, max_context=256,
                        prefill_chunk=CHUNK, **kw)


@pytest.fixture(scope="module")
def served(tap_logits):
    """One engine serves the logit cases; every row's logits are tapped
    where the layers hand their rows back (``conftest.tap_logits``)."""
    taps = []
    eng = make_engine()
    tap_logits(eng, taps)
    step_fn, eng.chunks_seen = eng._step_fn, []

    def spy(params, *rest):
        chunk = rest[-1]
        eng.chunks_seen.append(
            None if chunk is None else [int(v) for v in chunk[1]])
        return step_fn(params, *rest)

    eng._step_fn = spy
    return eng, taps


def reference_logits(seq):
    tokens = np.zeros((1, S_REF), np.int32)
    tokens[0, :len(seq)] = seq
    x = F.hidden_states(MODEL, SEED, tokens)[0, :len(seq)]
    top = F._top(W.seed_key(SEED), W.freeze(MODEL))
    h = reference.rms_norm(jnp.asarray(x), top["final_norm_w"],
                           MODEL["norm_eps"])
    return np.asarray(reference.mm(h, top["embed"].T))


def drive(eng, taps, requests, until=None, got=None):
    """Step the engine until every request is done (or ``until()``);
    every tapped row of logits goes to (request, position of the token it
    was computed from): a lane's row by the lane's length before the
    step, a chunk's rows by the ``[slot, start, n_valid]`` the engine
    itself handed the compiled step."""
    until = until or (lambda: all(r.done.is_set() for r in requests))
    got = {} if got is None else got
    b = eng.max_batch
    steps = 0
    while not until():
        lanes = [(s, eng._slots[s], int(eng._seq_lens[s]))
                 for s in range(b) if eng._active[s]]
        del taps[:], eng.chunks_seen[:]
        eng.step()
        jax.effects_barrier()
        steps += 1
        assert steps < 2000
        if not taps:
            continue        # an iteration that ran no device step
        logits = taps[-1]
        for slot, req, pos in lanes:
            got.setdefault(id(req), {})[pos] = logits[slot]
        if eng.chunks_seen[-1] is not None:
            slot, start, n_valid = eng.chunks_seen[-1]
            rows = got.setdefault(id(eng._slots[slot]), {})
            for j in range(n_valid):
                rows[start + j] = logits[b + j]
    return got


def check_against_reference(req, rows, tol=TOL):
    seq = req.prompt + req.out_tokens
    ref = reference_logits(seq)
    assert rows, "no logits were tapped for the request"
    worst = max(float(np.abs(rows[p] - ref[p]).max()) for p in rows)
    assert worst < tol, worst
    # and the served tokens are the reference's choices, token by token
    p = len(req.prompt)
    assert req.out_tokens == [int(np.argmax(ref[p - 1 + j]))
                              for j in range(len(req.out_tokens))]
    return len(rows)


def submit(eng, prompt, max_new):
    return eng.submit(prompt, SamplingParams(max_new_tokens=max_new))


def tokens(seed, n):
    return np.random.RandomState(seed).randint(0, 256, n).tolist()


# ---------------------------------------- (a) cold prefill, then decoding

@pytest.mark.parametrize("n_prompt,n_new", [(21, 24), (3, 9), (37, 6)])
def test_cold_prefill_then_decode_matches_the_reference(served, n_prompt,
                                                        n_new):
    """Prompts that are no multiple of the chunk (8) or of the page (4):
    the chunk's rows read their predecessors from the rows before them
    and then from the lane's state, across chunks and into decode."""
    eng, taps = served
    cold = eng.metrics.recurrent_state_cold_starts.value()
    req = submit(eng, tokens(n_prompt, n_prompt), n_new)
    rows = drive(eng, taps, [req])[id(req)]
    assert check_against_reference(req, rows) >= n_prompt + n_new - 1
    assert eng.metrics.recurrent_state_cold_starts.value() == cold + 1


# ------------------------------------- (b) a lane started from a page tail

@pytest.mark.parametrize("pages", [1, 2, 3])
def test_a_prefix_hit_starts_its_lane_from_the_pages_tail(served, pages):
    eng, taps = served
    head = tokens(100 + pages, pages * BLOCK)
    first = submit(eng, head + tokens(200 + pages, 7), 5)
    got = drive(eng, taps, [first])
    matched = eng.prefix_tokens_matched
    restores = eng.metrics.recurrent_state_restores.value()
    second = submit(eng, head + tokens(300 + pages, 6), 12)
    got = drive(eng, taps, [second], got=got)
    assert eng.prefix_tokens_matched - matched == pages * BLOCK
    assert eng.metrics.recurrent_state_restores.value() == restores + 1
    check_against_reference(first, got[id(first)])
    # its own tail of the prompt and every decode row: none of the rows
    # it shares was computed again
    rows = got[id(second)]
    assert min(rows) == pages * BLOCK
    assert check_against_reference(second, rows) >= 6 + 12 - 1


def test_a_lane_started_with_zero_state_is_caught(served):
    """The planted fault of the proofs: were a prefix hit to start its
    lane from nothing, the first rows after the shared pages are wrong by
    far more than the tolerance."""
    eng, taps = served
    head = tokens(41, 2 * BLOCK)
    first = submit(eng, head + tokens(42, 5), 2)
    drive(eng, taps, [first])
    zero = jax.jit(lambda state, pools, ints: {
        **state, "lane": state["lane"].at[:, ints[0]].set(0)},
        donate_argnums=(0,))
    with mock.patch.object(eng, "_start_lane_fn", zero):
        second = submit(eng, head + tokens(43, 6), 3)
        rows = drive(eng, taps, [second])[id(second)]
    ref = reference_logits(second.prompt + second.out_tokens)
    assert float(np.abs(rows[2 * BLOCK] - ref[2 * BLOCK]).max()) > 100 * TOL


# --------------------------- (c) preempted mid-decode, resumed by recompute

def test_a_preempted_request_resumes_from_its_cached_pages(served):
    eng, taps = served
    req = submit(eng, tokens(7, 19), 30)
    got = drive(eng, taps, [req], until=lambda: len(req.out_tokens) >= 9)
    restores = eng.metrics.recurrent_state_restores.value()
    with eng._sched_lock:
        eng._preempt(req)
    assert req.preemptions == 1 and req._slot is None
    got = drive(eng, taps, [req], got=got)
    # re-admitted over its own prompt's 4 whole pages: the lane started
    # from the fourth page's tail and prefilled the rest again
    assert eng.metrics.recurrent_state_restores.value() == restores + 1
    assert len(req.out_tokens) == 30
    assert check_against_reference(req, got[id(req)]) >= 19 + 30 - 1


# ------------- (e) two lanes decode while a third prefills in the same step

def test_lanes_decode_while_another_prefills(served):
    eng, taps = served
    a, b = submit(eng, tokens(1, 6), 40), submit(eng, tokens(2, 11), 40)
    got = drive(eng, taps, [a, b],
                until=lambda: a.out_tokens and b.out_tokens)
    c = submit(eng, tokens(3, 29), 10)          # four chunks of 8
    fused = []
    real = eng._step_fn

    def counting(params, *rest):
        if rest[-1] is not None:
            fused.append(int(eng._active.sum()))
        return real(params, *rest)

    with mock.patch.object(eng, "_step_fn", counting):
        got = drive(eng, taps, [a, b, c], got=got)
    assert len(fused) == 4 and min(fused) == 2     # both decoded beside it
    for req in (a, b, c):
        check_against_reference(req, got[id(req)])


def test_compile_once_counters_and_pools(served):
    eng, _ = served
    eng.generate([tokens(5, 13)], SamplingParams(max_new_tokens=4))
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1
    cfg = eng.cfg
    assert [p.shape[0] for p in eng._pools] == [2, 2, 5]
    assert eng._pools[2].shape[2:] == (2, 64)
    assert eng._dstate["lane"].shape == (5, LANES, 2, 64)
    assert eng.block_nbytes == 4 * (2 * 2 * BLOCK * 2 * 16 + 5 * 2 * 64)
    stats = eng.cache_stats()
    assert sum(p["page_bytes"] for p in stats["pools"]) == eng.block_nbytes
    snap = eng.metrics.snapshot()
    # every expert is resident: no assignment falls elsewhere
    assert snap["moe_assignments_local"] == snap["moe_assignments"] > 0
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert snap["moe_local_experts_hit"] <= 16 * n_moe * eng.steps
    assert snap["moe_assignments_local"] / 16 \
        <= snap["moe_expert_rows_max"] <= snap["moe_assignments_local"]
    plane = eng.weight_plane()
    assert plane["experts"] == 16 and plane["experts_routed"] == 16
    assert plane["expert_bytes"] == 6 * 16 * 3 * 64 * 32 * 4


# --------------------------------------------- (d) run-ahead on and off

def test_the_thread_one_step_ahead_serves_the_stepped_tokens():
    head = tokens(60, 2 * BLOCK)
    prompts = [head + tokens(61, 9), tokens(62, 5), head + tokens(63, 3),
               tokens(64, 26), head + tokens(65, 1)]
    stepped = make_engine(metrics=None)
    want = stepped.generate(prompts, SamplingParams(max_new_tokens=14))
    stepped.stop()
    eng = make_engine(metrics=None)
    eng.start()
    try:
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=14))
                for p in prompts]
        got = [r.wait(120.0) for r in reqs]
    finally:
        eng.stop()
    assert got == want
    assert eng.steps_run_ahead > 0
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1


# ------------------------------------------------------------ (f) the router

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_bias_moves_the_choice_and_never_the_weights(seed):
    cfg = F.model_config(MODEL, {"context": 64})
    rng = np.random.RandomState(seed)
    x = rng.randn(24, 64).astype(np.float32)
    w = (rng.randn(64, 16) / 8).astype(np.float32)
    bias = (0.3 * rng.randn(16)).astype(np.float32)
    scores = 1.0 / (1.0 + np.exp(-(x @ w)))
    idx, wts = (np.asarray(a) for a in route_grouped(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias), cfg))
    idx0, _ = route_grouped(jnp.asarray(x), jnp.asarray(w), jnp.zeros(16),
                            cfg)
    assert (np.sort(np.asarray(idx0), 1) != np.sort(idx, 1)).any()
    for t in range(24):
        # the choice: top 4 of score + bias
        assert sorted(idx[t].tolist()) == sorted(
            np.argsort(-(scores[t] + bias), kind="stable")[:4].tolist())
        # the weights: the chosen experts' UNBIASED scores over their sum
        # plus 1e-6
        total = scores[t][idx[t]].sum()
        np.testing.assert_allclose(wts[t], scores[t][idx[t]]
                                   / (total + 1e-6), rtol=1e-5)
        assert 0 < 1.0 - wts[t].sum() < 1e-5
    # the reference's router is the same rule
    lp = {"router": jnp.asarray(w), "router_bias": jnp.asarray(bias)}
    chosen, rw = F.route(jnp.asarray(x), lp, MODEL, None)
    assert (np.sort(np.asarray(chosen), 1) == np.sort(idx, 1)).all()
    np.testing.assert_allclose(np.sort(np.asarray(rw), 1),
                               np.sort(wts, 1), rtol=1e-5)


# ------------------------------------------------------------- (g) the share

@pytest.mark.parametrize("seed", [0, 1])
def test_four_shares_of_the_experts_add_up_to_the_whole_layer(seed):
    """16 experts over 4 ranks of 4 (the cell's 64 over 4 of 16): each
    share routes over the whole router and computes what its own experts
    give; with no shared expert the four sum to the uncut layer, and no
    assignment is lost at any row count."""
    lp = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: W.one_layer(F.stack_leaves(MODEL, "moe_layers"),
                                      k, 1, jnp.bfloat16))(
            W.seed_key(seed)))
    cfg = F.model_config(MODEL, {"context": 64})
    for rows in (1, 24, 97):
        x = jax.random.normal(jax.random.PRNGKey(seed + rows), (rows, 64),
                              jnp.float32)
        uncut = np.asarray(F.expert_layer(x, lp, MODEL, None))
        whole, stats = moe_share(x, lp, cfg, busiest=True)
        np.testing.assert_allclose(np.asarray(whole), uncut, atol=2e-5)
        assert int(stats[0]) == rows * 4
        assert rows * 4 / 16 <= int(stats[2]) <= rows
        total, local = np.zeros_like(uncut), 0
        for rank in range(4):
            share = dataclasses.replace(cfg, n_experts=4,
                                        experts_from=4 * rank)
            held = dict(lp, **{k: lp[k][4 * rank:4 * rank + 4]
                               for k in ("w_gate", "w_up", "w_down")})
            y, st = moe_share(x, held, share)
            total += np.asarray(y)
            local += int(st[0])
        assert local == rows * 4
        np.testing.assert_allclose(total, uncut, atol=2e-5)


def test_a_popular_expert_overflows_no_room_in_the_reference():
    """A bias that sends every row to expert 0: the reference's gathered
    room (8 x a uniform share) is too small and it must run the expert
    over all rows — still equal to the program's layer."""
    lp = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: W.one_layer(F.stack_leaves(MODEL, "moe_layers"),
                                      k, 0, jnp.bfloat16))(W.seed_key(3)))
    lp["router_bias"] = lp["router_bias"].at[0].set(50.0)
    x = jax.random.normal(jax.random.PRNGKey(9), (512, 64), jnp.float32)
    cfg = F.model_config(MODEL, {"context": 64})
    y, stats = moe_share(x, lp, cfg, busiest=True)
    assert int(stats[2]) == 512
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(F.expert_layer(x, lp, MODEL, None)),
        atol=2e-5)


# ---------------------------------------------------- (h) what it refuses

def _engine(**kw):
    cfg = get_config("tiny-lfm2")
    params = kw.pop("params", None) or lfm2.init_params(
        jax.random.PRNGKey(0), cfg)
    return DecodeEngine(params, cfg, max_batch=2, block_size=4,
                        max_context=64, **kw)


def _relaxed():
    cfg = get_config("tiny-lfm2")
    params = lfm2.init_params(jax.random.PRNGKey(0), cfg)
    # a quantized leaf, as serving.parity=relaxed would hand the engine
    params["embed"] = {"q": jnp.zeros((256, 4, 16), jnp.int8),
                       "s": jnp.ones((256, 4), jnp.float32)}
    return _engine(params=params)


def _plan():
    from hadoop_tpu.parallel.mesh import MeshPlan
    return _engine(plan=MeshPlan(tp=2))


def _longctx():
    _engine().attach_longctx(object())


def _train():
    from hadoop_tpu.parallel.train import make_train_step
    make_train_step(get_config("tiny-lfm2"), None, None)


def _decoder():
    from hadoop_tpu.models import decoder
    decoder.forward({}, jnp.zeros((1, 4), jnp.int32),
                    get_config("tiny-lfm2"))


REFUSED = {
    "serving.parity=relaxed": _relaxed,
    "tp plan": _plan,
    "serving.kv.host.bytes": lambda: _engine(kv_host_bytes=1 << 20),
    "serving.kv.dfs.enable": lambda: _engine(kv_store_fs=object()),
    "serving.speculate.k": lambda: _engine(speculate_k=2),
    "serving.moe.shards": lambda: _engine(moe_shards=2),
    "serving.longctx.enable": _longctx,
    "make_train_step": _train,
    "models.decoder": _decoder,
}


@pytest.mark.parametrize("key", sorted(REFUSED))
def test_each_plane_not_built_refuses_by_name(key):
    with pytest.raises(NotImplementedError) as e:
        REFUSED[key]()
    assert key in str(e.value) and "lfm2_moe" in str(e.value)


def test_a_page_shorter_than_the_state_is_refused():
    cfg = get_config("tiny-lfm2")
    with pytest.raises(ValueError, match="serving.kv.block.size"):
        DecodeEngine(lfm2.init_params(jax.random.PRNGKey(0), cfg), cfg,
                     max_batch=2, block_size=1, max_context=16)


# ------------------------------------------------------ (i) the validator

@pytest.mark.parametrize("field,value,says", [
    ("layer_types", ("conv", "full_attention"), "layer_types names 2"),
    ("layer_types", ("conv",) * 6 + ("window",), "an operator is one of"),
    ("n_dense_layers", 8, "n_dense_layers"),
    ("n_heads", 3, "must divide"),
    ("n_kv_heads", 3, "must divide"),
    ("conv_kernel", 1, "conv_kernel"),
    ("experts_from", 4, "experts_from"),
    ("n_shared_experts", 1, "no shared expert"),
    ("tie_embeddings", False, "tied head"),
])
def test_config_is_validated_at_construction(field, value, says):
    with pytest.raises(ValueError) as e:
        dataclasses.replace(get_config("tiny-lfm2"), **{field: value})
    assert says in str(e.value) and "lfm2_moe" in str(e.value)
    assert isinstance(get_config("tiny-lfm2"), ModelConfig)


def test_runs_pair_an_operator_stack_with_an_ffn_stack():
    cfg = F.model_config(MODEL, {"context": 64})
    assert lfm2.runs(cfg) == [
        ("conv", "dense", 0, 0, 1), ("full_attention", "moe", 0, 0, 1),
        ("conv", "moe", 1, 1, 2), ("full_attention", "moe", 1, 3, 1),
        ("conv", "moe", 3, 4, 2)]
    assert [(op, oi, ffn, fi) for op, oi, ffn, fi in F.places(MODEL)] == [
        ("conv_ops", 0, "dense_layers", 0), ("attn_ops", 0, "moe_layers", 0),
        ("conv_ops", 1, "moe_layers", 1), ("conv_ops", 2, "moe_layers", 2),
        ("attn_ops", 1, "moe_layers", 3), ("conv_ops", 3, "moe_layers", 4),
        ("conv_ops", 4, "moe_layers", 5)]
    # the program's tree and the benchmark's have the same leaves
    mine = jax.eval_shape(lambda k: lfm2.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: F.make_params(MODEL, k, jnp.float32), W.seed_key(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, mine) == \
        jax.tree_util.tree_map(lambda a: a.shape, theirs)
