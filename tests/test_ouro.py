"""``family="ouro"`` on the serving path, at test size on the CPU: the
engine THROUGH THE CACHE (pools ``passes x layers`` slots deep, the pass
loop around the dense family's one layer body) against the plain
reference in ``chipbench/families/ouro.py`` on LOGITS — whole-prompt
prefill then decode, chunked prefill beside decoding lanes, a lane started
from a prefix hit, a lane preempted and resumed, the serving thread one
step ahead against a stepped engine; the slot (a pass that reads or
writes another pass's slot fails by orders of magnitude); the pools'
depth and a page's bytes; one layer body in the step program whatever the
passes; every plane the family refuses, by its key; the validator.
"""

import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from chipbench import weights as W
from chipbench.families import ouro as F
from hadoop_tpu.models import ouro
from hadoop_tpu.models.config import ModelConfig, get_config
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams
from hadoop_tpu.serving.families import looped
from hadoop_tpu.serving.metrics import ServingMetrics

SEED = 13
S_REF = 128          # every reference pass is padded to this length
# float32 on both sides: the engine and the reference differ in the order
# of their sums alone (paged attention's online softmax, XLA's matmul
# against ``highest``), and nine layer applications with a norm after
# every sub-layer keep that to ~1e-5 of logits of order 1
# (tests/test_lfm2.py's and tests/test_deepseek_v32.py's tolerance)
TOL = 2e-4
# the configuration file's scalars, at test size: 3 layers, 3 passes
MODEL = {
    "model_type": "ouro", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
    "num_hidden_layers": 3, "total_ut_steps": 3, "early_exit_threshold": 1,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "sliding_window": None, "rope_scaling": None}
BLOCK, CHUNK, LANES = 4, 8, 3


def make_params(model=MODEL, seed=SEED):
    """bfloat16 values (what the reference regenerates) held in float32."""
    tree = jax.jit(lambda k: F.make_params(model, k, jnp.bfloat16))(
        W.seed_key(seed))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def make_engine(**kw):
    cfg = F.model_config(MODEL, {"context": 256})
    kw.setdefault("metrics", ServingMetrics("serving.test.ouro"))
    kw.setdefault("prefill_chunk", CHUNK)
    return DecodeEngine(make_params(), cfg, max_batch=LANES,
                        block_size=BLOCK, max_context=256, **kw)


def spy_chunks(eng):
    """Record the ``[slot, start, n_valid]`` of every step's chunk."""
    step_fn, eng.chunks_seen = eng._step_fn, []

    def spy(params, *rest):
        chunk = rest[-1]
        eng.chunks_seen.append(
            None if chunk is None else [int(v) for v in chunk[1]])
        return step_fn(params, *rest)

    eng._step_fn = spy


@pytest.fixture(scope="module")
def served(tap_logits):
    """One engine serves the logit cases; every row's logits are tapped
    where the layers hand their rows back (``conftest.tap_logits``)."""
    taps = []
    eng = make_engine()
    tap_logits(eng, taps)
    spy_chunks(eng)
    return eng, taps


def reference_logits(seq):
    tokens = np.zeros((1, S_REF), np.int32)
    tokens[0, :len(seq)] = seq
    x = F.hidden_states(MODEL, SEED, tokens)[0, :len(seq)]
    top = F._top(W.seed_key(SEED), W.freeze(MODEL))
    h = reference.rms_norm(x, top["final_norm_w"], MODEL["rms_norm_eps"])
    return np.asarray(reference.mm(h, top["lm_head"]))


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


# ------------------------------------------ (a) prefill, then decoding

def test_whole_prompt_prefill_then_decode_matches_the_reference(tap_logits):
    """The prompt in ONE fused step (a chunk as long as the prompt), then
    decode-only steps: every row of both against the reference."""
    taps = []
    eng = make_engine(prefill_chunk=32)
    tap_logits(eng, taps)
    spy_chunks(eng)
    req = submit(eng, tokens(5, 27), 14)
    rows = drive(eng, taps, [req])[id(req)]
    assert eng.steps == 14          # the prompt's step, then 13 decodes
    assert check_against_reference(req, rows) == 27 + 14 - 1
    eng.stop()


@pytest.mark.parametrize("n_prompt,n_new", [(21, 24), (3, 9), (37, 6)])
def test_chunked_prefill_then_decode_matches_the_reference(served, n_prompt,
                                                           n_new):
    """Prompts that are no multiple of the chunk (8) or of the page (4)."""
    eng, taps = served
    req = submit(eng, tokens(n_prompt, n_prompt), n_new)
    rows = drive(eng, taps, [req])[id(req)]
    assert check_against_reference(req, rows) >= n_prompt + n_new - 1


# ----------------------------------------- (b) a lane from a prefix hit

@pytest.mark.parametrize("pages", [1, 3])
def test_a_prefix_hit_reads_every_pass_of_the_shared_pages(served, pages):
    eng, taps = served
    head = tokens(100 + pages, pages * BLOCK)
    first = submit(eng, head + tokens(200 + pages, 7), 5)
    got = drive(eng, taps, [first])
    matched = eng.prefix_tokens_matched
    second = submit(eng, head + tokens(300 + pages, 6), 12)
    got = drive(eng, taps, [second], got=got)
    assert eng.prefix_tokens_matched - matched == pages * BLOCK
    check_against_reference(first, got[id(first)])
    # its own tail of the prompt and every decode row: none of the rows
    # it shares was computed again, in any pass
    rows = got[id(second)]
    assert min(rows) == pages * BLOCK
    assert check_against_reference(second, rows) >= 6 + 12 - 1


# --------------------------- (c) preempted mid-decode, resumed by recompute

def test_a_preempted_request_resumes_from_its_cached_pages(served):
    eng, taps = served
    req = submit(eng, tokens(7, 19), 30)
    got = drive(eng, taps, [req], until=lambda: len(req.out_tokens) >= 9)
    with eng._sched_lock:
        eng._preempt(req)
    assert req.preemptions == 1 and req._slot is None
    got = drive(eng, taps, [req], got=got)
    assert len(req.out_tokens) == 30
    assert check_against_reference(req, got[id(req)]) >= 19 + 30 - 1


# ------------- (d) two lanes decode while a third prefills in the same step

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


# ------------------------------------------------ (e) the slots, the counts

def test_pools_are_passes_times_layers_deep_and_the_step_counts_its_passes(
        served):
    eng, _ = served
    steps, passes = eng.steps, eng.metrics.loop_passes.value()
    live, pool = (eng.metrics.kv_pages_live_steps.value(),
                  eng.metrics.kv_pages_pool_steps.value())
    eng.generate([tokens(5, 13)], SamplingParams(max_new_tokens=4))
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1
    cfg = eng.cfg
    slots = cfg.n_passes * cfg.n_layers
    assert slots == 9 and eng._family.page_slots == 9
    assert [p.shape[0] for p in eng._pools] == [9, 9]
    assert eng._kp.shape[2:] == (BLOCK, 4, 16)
    # a K and a V for every (pass, layer): float32 here
    assert eng.block_nbytes == 9 * 2 * BLOCK * 4 * 16 * 4
    stats = eng.cache_stats()
    assert [p["layers"] for p in stats["pools"]] == [9, 9]
    assert sum(p["page_bytes"] for p in stats["pools"]) == eng.block_nbytes
    assert eng.kvstore.block_shape == (9, BLOCK, 4, 16)
    # every step ran every pass, by the device's own count
    ran = eng.steps - steps
    assert ran > 0
    assert eng.metrics.loop_passes.value() - passes == cfg.n_passes * ran
    # and the pool's fill was counted once a step, against the pool
    assert eng.metrics.kv_pages_pool_steps.value() - pool \
        == ran * eng.pool.num_usable
    assert 0 < eng.metrics.kv_pages_live_steps.value() - live \
        <= ran * eng.pool.num_usable


def test_block_nbytes_at_the_published_shapes():
    """192 slots x 8,192 B x 16 tokens, from the family alone (no
    weights are made)."""
    cfg = ModelConfig(family="ouro", vocab_size=49152, d_model=2048,
                      n_layers=48, n_heads=16, n_kv_heads=16, d_ff=5632,
                      max_seq=2048, rope_theta=1e6, norm_eps=1e-6,
                      n_passes=4, sandwich_norm=True)
    fam = looped.LoopedKVFamily(cfg, {})
    pools = fam.pools(16)
    assert pools == [(192, (16, 16, 128))] * 2
    nbytes = sum(n * int(np.prod(page)) * 2 for n, page in pools)
    assert nbytes == 192 * 8192 * 16 == 25_165_824
    assert fam.salt_layout == (16, 128) and fam.counters == ("loop_passes",)


@pytest.mark.parametrize("fault", ["first-pass-slot", "last-two-share"])
def test_a_pass_in_another_pass_slot_is_caught(tap_logits, fault):
    """The planted fault of the proofs (every pass uses pass 1's slots),
    and one that two passes could not show (the last two share a slot):
    a step scatters before it attends, so within a step a row finds its
    own pass's K and V, but the first decoded row reads what a LATER pass
    left of the earlier tokens and is wrong by orders of magnitude more
    than the tolerance."""
    wrong = {"first-pass-slot": lambda t, layers, n_blocks: 0 * t,
             "last-two-share": lambda t, layers, n_blocks:
             jnp.minimum(t, 1) * (layers * n_blocks)}[fault]
    taps = []
    with mock.patch.object(looped, "pass_offset", wrong):
        eng = make_engine()
        tap_logits(eng, taps)
        spy_chunks(eng)
        req = submit(eng, tokens(9, 11), 4)
        rows = drive(eng, taps, [req])[id(req)]
    ref = reference_logits(req.prompt + req.out_tokens)
    first_decoded = len(req.prompt)
    assert float(np.abs(rows[first_decoded] - ref[first_decoded]).max()) \
        > 100 * TOL
    eng.stop()


def test_the_step_holds_one_layer_body_whatever_the_passes(jaxpr_eqns):
    """Four passes are a loop, not four copies: the step's program has
    the same equations at 1, 3 and 5 passes but for the loop's bounds
    (as many matmuls, as many attention calls), and one more loop than a
    plain stack's."""
    def eqns(passes):
        cfg = dataclasses.replace(get_config("tiny-ouro"), n_passes=passes)
        eng = DecodeEngine(ouro.init_params(jax.random.PRNGKey(0), cfg), cfg,
                           max_batch=2, block_size=4, num_blocks=9,
                           max_context=32, prefill_chunk=8)
        jaxpr = jax.make_jaxpr(eng._step_impl)(
            eng.params, *eng._pools, eng._dstate, eng._dz_drafts,
            eng._dz_lens, None)
        eng.stop()
        names = [name for name, _ in jaxpr_eqns(jaxpr.jaxpr)]
        return {n: names.count(n) for n in ("dot_general", "scan", "while")}
    one, three, five = eqns(1), eqns(3), eqns(5)
    assert one == three == five
    assert three["scan"] >= 2       # the pass loop around the layer scan


# --------------------------------------------- (f) run-ahead on and off

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


# ---------------------------------------------------- (g) what it refuses

def _engine(**kw):
    cfg = get_config("tiny-ouro")
    params = kw.pop("params", None) or ouro.init_params(
        jax.random.PRNGKey(0), cfg)
    return DecodeEngine(params, cfg, max_batch=2, block_size=4,
                        max_context=64, **kw)


def _relaxed():
    cfg = get_config("tiny-ouro")
    params = ouro.init_params(jax.random.PRNGKey(0), cfg)
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
    make_train_step(get_config("tiny-ouro"), None, None)


def _decoder():
    from hadoop_tpu.models import decoder
    decoder.forward({}, jnp.zeros((1, 4), jnp.int32),
                    get_config("tiny-ouro"))


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
    assert key in str(e.value) and "ouro" in str(e.value)


def test_an_early_exit_threshold_below_one_refuses_by_name():
    with pytest.raises(NotImplementedError) as e:
        dataclasses.replace(get_config("tiny-ouro"),
                            early_exit_threshold=0.5)
    assert "early_exit_threshold=0.5" in str(e.value)
    # the benchmark's configuration file reaches the same refusal
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        F.model_config({**MODEL, "early_exit_threshold": 0.5},
                       {"context": 64})


# ------------------------------------------------------ (h) the validator

@pytest.mark.parametrize("field,value,says", [
    ("n_passes", 0, "n_passes=0"),
    ("sandwich_norm", False, "sandwich_norm"),
    ("n_heads", 3, "must divide"),
    ("n_kv_heads", 3, "must divide"),
    ("tie_embeddings", True, "untied head"),
    ("n_experts", 4, "dense SwiGLU"),
])
def test_config_is_validated_at_construction(field, value, says):
    with pytest.raises(ValueError) as e:
        dataclasses.replace(get_config("tiny-ouro"), **{field: value})
    assert says in str(e.value) and "ouro" in str(e.value)
    assert isinstance(get_config("tiny-ouro"), ModelConfig)


@pytest.mark.parametrize("field,value", [("n_passes", 2),
                                         ("sandwich_norm", True)])
def test_the_loop_and_the_sandwich_are_no_other_familys(field, value):
    with pytest.raises(ValueError, match="family 'ouro'"):
        dataclasses.replace(get_config("tiny"), **{field: value})


def test_the_tree_is_the_dense_one_plus_two_norms_and_the_gate():
    cfg = get_config("tiny-ouro")
    assert cfg.n_passes >= 3 and cfg.n_layers >= 3
    params = ouro.init_params(jax.random.PRNGKey(0), cfg)
    tree = jax.jit(lambda k: F.make_params(MODEL, k, jnp.float32))(
        W.seed_key(1))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(params) == shapes(tree)
    assert set(params["layers"]) - {
        "attn_norm_w", "wq", "wk", "wv", "wo", "mlp_norm_w", "w_gate",
        "w_up", "w_down"} == {"attn_post_norm_w", "mlp_post_norm_w"}
    assert params["exit_gate_w"].shape == (64, 1)
    assert params["exit_gate_b"].shape == (1,)


@pytest.mark.parametrize("preset", ["tiny-ouro", "tiny-lfm2", "tiny-dsv32"])
def test_a_checkpoint_of_a_serving_only_family_loads_for_serving(tmp_path,
                                                                 preset):
    """``bin/hadoop-tpu serve`` loads a checkpoint against the FAMILY's
    tree (``models.init_params_for``), not the decoder's, which refuses
    these families."""
    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.models import init_params_for
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    from hadoop_tpu.serving.loader import load_serving_params
    cfg = get_config(preset)
    params = init_params_for(cfg)(jax.random.PRNGKey(3), cfg)
    fs = LocalFileSystem()
    save_checkpoint(fs, f"{tmp_path}/m", 2, {"params": params})
    got, step = load_serving_params(fs, f"{tmp_path}/m", cfg)
    assert step == 2
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
