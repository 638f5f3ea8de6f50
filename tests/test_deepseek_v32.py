"""``family="deepseek_v32"`` on the serving path, at test size on the CPU:
the engine (chunked prefill through the latent and index-key pages, then
decode, a shared cached prefix) against the plain reference in
``chipbench/families/deepseek_v32.py`` on LOGITS; one replica's share of
the expert layer against the uncut layer; the router against a brute
force; the exact top-k against a sort with planted ties; YaRN against its
formula; compile-once; and every plane the family refuses, by its key.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from chipbench import weights as W
from chipbench.families import deepseek_v32 as F
from hadoop_tpu.models import deepseek
from hadoop_tpu.models.config import ModelConfig, get_config
from hadoop_tpu.models.moe import moe_share, route_grouped
from hadoop_tpu.ops.rope import yarn_inv_frequencies, yarn_mscale
from hadoop_tpu.ops.sparse_mla import exact_topk
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams
from hadoop_tpu.serving.metrics import ServingMetrics

SEED = 7
S_REF = 128          # every reference pass is padded to this length
# the configuration file's scalars, at test size (index_topk 16)
MODEL = {
    "model_type": "deepseek_v32", "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "n_routed_experts": 8, "router_width": 32,
    "ep_rank": 0, "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "vocab_size": 256,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "yarn_factor": 40, "yarn_original_max_position_embeddings": 32,
    "yarn_beta_fast": 32, "yarn_beta_slow": 1, "yarn_mscale": 1}


def make_params(model=MODEL, seed=SEED):
    """bfloat16 values (what the reference regenerates) held in float32."""
    tree = jax.jit(lambda k: F.make_params(model, k, jnp.bfloat16))(
        W.seed_key(seed))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def served(tap_logits):
    """One engine serves every case; every row's logits are tapped where
    the layers hand their rows back (``conftest.tap_logits``), so the comparison
    is on logits."""
    cfg = F.model_config(MODEL, {"context": 256})
    taps = []
    eng = DecodeEngine(make_params(), cfg, max_batch=2, block_size=4,
                       max_context=256, prefill_chunk=8,
                       metrics=ServingMetrics("serving.test.dsv32"))
    tap_logits(eng, taps)
    step_fn, eng.chunks_seen = eng._step_fn, []

    def spy(params, kp, vp, state, drafts, lens, chunk):
        eng.chunks_seen.append(
            None if chunk is None else [int(v) for v in chunk[1]])
        return step_fn(params, kp, vp, state, drafts, lens, chunk)

    eng._step_fn = spy
    return eng, taps


def reference_logits(seq):
    tokens = np.zeros((1, S_REF), np.int32)
    tokens[0, :len(seq)] = seq
    x = F.hidden_states(MODEL, SEED, tokens)
    x = F.final_states(MODEL, SEED, x, np.arange(len(seq))[None, :])[0]
    top = F._top(W.seed_key(SEED), W.freeze(MODEL))
    h = reference.rms_norm(jnp.asarray(x), top["final_norm_w"],
                           MODEL["rms_norm_eps"])
    return np.asarray(reference.mm(h, top["lm_head"]))


def drive(eng, taps, requests, until=None):
    """Step the engine until every request is done (or ``until()``);
    every tapped row of logits goes to (request, position of the token
    it was computed from): a lane's row by the lane's length before the
    step, a chunk's rows by the ``[slot, start, n_valid]`` the engine
    itself handed the compiled step."""
    until = until or (lambda: all(r.done.is_set() for r in requests))
    got = {id(r): {} for r in requests}
    b = eng.max_batch
    while not until():
        lanes = [(s, eng._slots[s], int(eng._seq_lens[s]))
                 for s in range(b) if eng._active[s]]
        del taps[:], eng.chunks_seen[:]
        eng.step()
        jax.effects_barrier()
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


def check_against_reference(req, rows, tol=2e-4):
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


CASES = {
    # context stays under index_topk = 16: every row attends to all of it
    "below_topk": (5, 8),
    # the context crosses index_topk while decoding
    "across_topk": (10, 70),
    # prefill in several chunks, already above index_topk, 64+ decode steps
    "above_topk": (37, 66),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_match_the_plain_reference(served, case):
    eng, taps = served
    n_prompt, n_new = CASES[case]
    rng = np.random.RandomState(len(case))
    req = submit(eng, rng.randint(0, 256, n_prompt).tolist(),
                           n_new)
    rows = drive(eng, taps, [req])[id(req)]
    compared = check_against_reference(req, rows)
    # the fresh prompt's rows past nothing cached, and every decode row
    assert compared >= n_new - 1


def test_two_lanes_share_a_cached_prefix(served):
    """The second request maps the first one's latent AND index-key pages
    by the same blocks; both decode side by side, 64 steps, on logits."""
    eng, taps = served
    rng = np.random.RandomState(101)
    head = rng.randint(0, 256, 24).tolist()
    first = submit(eng, head + rng.randint(0, 256, 9).tolist(), 70)
    rows = drive(eng, taps, [first], until=lambda: bool(first.out_tokens))
    matched = eng.prefix_tokens_matched
    second = submit(eng, head + rng.randint(0, 256, 5).tolist(), 64)
    got = drive(eng, taps, [first, second])
    assert eng.prefix_tokens_matched - matched == 24
    rows[id(first)].update(got[id(first)])
    assert check_against_reference(first, rows[id(first)]) >= 33 + 70 - 1
    # its own tail of the prompt and every decode row
    assert check_against_reference(second, got[id(second)]) >= 5 + 64 - 1


def test_compile_once_and_counters(served):
    """Exactly two compiled shapes after every case above, and the
    counters of the selection and of the held share."""
    eng, _ = served
    # (a request of its own, so that the test stands alone too)
    rng = np.random.RandomState(5)
    eng.generate([rng.randint(0, 256, 40).tolist()],
                 SamplingParams(max_new_tokens=12))
    assert eng.decode_compiles == 1
    assert eng.prefill_compiles == 1
    snap = eng.metrics.snapshot()
    assert 0 < snap["attn_entries_selected"] < snap["attn_entries_live"]
    assert 0 < snap["moe_assignments_local"] < snap["moe_assignments"]
    assert 0 < snap["moe_local_experts_hit"]
    assert snap["attn_pages_distinct"] > 0
    plane = eng.weight_plane()
    assert plane["experts"] == 8 and plane["experts_routed"] == 32
    assert eng.block_nbytes == 3 * 4 * 4 * (128 + 16)


# ------------------------------------------------------------- the share

@pytest.mark.parametrize("seed", [0, 1])
def test_shares_of_all_ranks_add_up_to_the_uncut_layer(seed):
    """32 experts over 4 ranks: the four partial outputs, with the shared
    expert counted once, are the uncut layer — the program's shares
    against the reference's layer with every expert held."""
    whole = dict(MODEL, n_routed_experts=32, ep_rank=0)
    lp = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: F.layer_params(whole, k, 1, jnp.bfloat16, "moe"))(
            W.seed_key(seed)))
    x = jax.random.normal(jax.random.PRNGKey(seed), (24, 64), jnp.float32)
    uncut = np.asarray(F.expert_layer(x, lp, whole, None))
    shared = np.asarray(F.swiglu_mlp(x, lp["ws_gate"], lp["ws_up"],
                                     lp["ws_down"], None))
    total, local = np.zeros_like(uncut), 0
    for rank in range(4):
        cfg = F.model_config(dict(MODEL, ep_rank=rank), {"context": 64})
        held = dict(lp, **{k: lp[k][8 * rank:8 * rank + 8]
                           for k in ("w_gate", "w_up", "w_down")})
        y, stats = moe_share(x, held, cfg)
        total += np.asarray(y) - shared
        local += int(stats[0])
    assert local == 24 * MODEL["num_experts_per_tok"]
    np.testing.assert_allclose(total + shared, uncut, atol=2e-5)


# ------------------------------------------------------------ the router

def brute_force_route(scores, bias, n_group, topk_group, top_k, scale):
    chosen, weights = [], []
    for s in scores:
        sb = s + bias
        groups = sb.reshape(n_group, -1)
        group_score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        keep = np.argsort(-group_score, kind="stable")[:topk_group]
        allowed = np.zeros_like(sb, bool).reshape(n_group, -1)
        allowed[keep] = True
        order = np.argsort(-np.where(allowed.ravel(), sb, -np.inf),
                           kind="stable")[:top_k]
        chosen.append(sorted(order.tolist()))
        w = s[order]
        weights.append(dict(zip(order.tolist(), w / w.sum() * scale)))
    return chosen, weights


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_against_a_brute_force(seed):
    cfg = F.model_config(MODEL, {"context": 64})
    rng = np.random.RandomState(seed)
    x = rng.randn(16, 64).astype(np.float32)
    w = (rng.randn(64, 32) / 8).astype(np.float32)
    bias = (0.2 * rng.randn(32)).astype(np.float32)
    idx, wts = route_grouped(jnp.asarray(x), jnp.asarray(w),
                             jnp.asarray(bias), cfg)
    scores = 1.0 / (1.0 + np.exp(-(x @ w)))
    chosen, weights = brute_force_route(scores, bias, 4, 2, 4, 2.5)
    idx, wts = np.asarray(idx), np.asarray(wts)
    for t in range(16):
        assert sorted(idx[t].tolist()) == chosen[t]
        for e, got in zip(idx[t], wts[t]):
            assert got == pytest.approx(weights[t][int(e)], rel=1e-5)
    # the correction bias changes the choice, never the weights
    idx0, wts0 = route_grouped(jnp.asarray(x), jnp.asarray(w),
                               jnp.zeros(32), cfg)
    assert (np.sort(np.asarray(idx0), 1) != np.sort(idx, 1)).any()
    for t in range(16):
        w0 = dict(zip(np.asarray(idx0)[t].tolist(), np.asarray(wts0)[t]))
        s0 = sum(scores[t][e] for e in w0)
        for e, got in w0.items():
            assert got == pytest.approx(scores[t][e] / s0 * 2.5, rel=1e-5)


# -------------------------------------------------------- the exact top-k

@pytest.mark.parametrize("n,s,k", [(5, 700, 16), (3, 1024, 300),
                                   (4, 256, 16), (2, 4096, 2048)])
def test_exact_topk_against_a_sort_with_planted_ties(n, s, k):
    rng = np.random.RandomState(s)
    x = np.round(rng.randn(n, s) * 4).astype(np.float32) / 4    # many ties
    x[0, :50] = 0.0
    x[0, 10] = -0.0                                 # equal to 0.0, not below
    lens = np.array([s, s - 3, 7, 0, k][:n], np.int32)
    idx, valid = jax.jit(exact_topk, static_argnums=2)(
        jnp.asarray(x), jnp.asarray(lens), k)
    idx, valid = np.asarray(idx), np.asarray(valid)
    for i in range(n):
        want = min(k, int(lens[i]))
        # a stable sort of the negated scores: ties to the lower position
        ref = np.argsort(-x[i, :lens[i]], kind="stable")[:want]
        assert int(valid[i].sum()) == want
        assert idx[i][valid[i]].tolist() == sorted(ref.tolist())
        # and against jnp.sort: the kept values are the top ``want``
        top = np.asarray(jnp.sort(jnp.asarray(x[i, :lens[i]])))[::-1][:want]
        np.testing.assert_array_equal(
            np.sort(x[i][idx[i][valid[i]]])[::-1], top)


# ------------------------------------------------------------------- YaRN

@pytest.mark.parametrize("dim,theta,factor,orig", [
    (64, 10000.0, 40.0, 4096), (8, 10000.0, 40.0, 32),
    (64, 10000.0, 1.0, 4096)])
def test_yarn_tables_against_the_formula(dim, theta, factor, orig):
    def corr(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo, hi = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    i = np.arange(dim // 2)
    f = theta ** (-2.0 * i / dim)
    r = np.clip((i - lo) / (hi - lo), 0, 1)
    want = f / factor * r + f * (1 - r)
    got = np.asarray(yarn_inv_frequencies(dim, theta, factor, orig))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(F.yarn_inv_freq({
        "qk_rope_head_dim": dim, "rope_theta": theta, "yarn_factor": factor,
        "yarn_original_max_position_embeddings": orig,
        "yarn_beta_fast": 32, "yarn_beta_slow": 1})), want, rtol=1e-6)
    if factor == 40.0:
        assert yarn_mscale(factor) == pytest.approx(1.3689, abs=1e-4)
        assert got[0] == pytest.approx(1.0) and \
            got[-1] == pytest.approx(f[-1] / 40.0)
    else:
        assert yarn_mscale(factor) == 1.0
        np.testing.assert_allclose(got, f, rtol=1e-6)


# ---------------------------------------------------------- what it refuses

def _engine(**kw):
    cfg = get_config("tiny-dsv32")
    params = kw.pop("params", None) or deepseek.init_params(
        jax.random.PRNGKey(0), cfg)
    return DecodeEngine(params, cfg, max_batch=2, block_size=4,
                        max_context=64, **kw)


def _relaxed():
    cfg = get_config("tiny-dsv32")
    params = deepseek.init_params(jax.random.PRNGKey(0), cfg)
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
    make_train_step(get_config("tiny-dsv32"), None, None)


def _decoder():
    from hadoop_tpu.models import decoder
    decoder.forward({}, jnp.zeros((1, 4), jnp.int32),
                    get_config("tiny-dsv32"))


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
    assert key in str(e.value) and "deepseek_v32" in str(e.value)


@pytest.mark.parametrize("field,value,says", [
    ("kv_lora_rank", 0, "kv_lora_rank"),
    ("experts_from", 30, "experts_from"),
    ("topk_group", 9, "topk_group"),
    ("n_dense_layers", 7, "n_dense_layers"),
    ("tie_embeddings", True, "untied head"),
])
def test_config_is_validated_at_construction(field, value, says):
    with pytest.raises(ValueError) as e:
        dataclasses.replace(get_config("tiny-dsv32"), **{field: value})
    assert says in str(e.value)
    assert isinstance(get_config("tiny-dsv32"), ModelConfig)


# ------------------------------------------- the chip's compiler, no chip

@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: its compiler shows what a
    CPU run cannot — the layouts it gives the pools, what it copies."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tables,rows", [(32, 1), (1, 256)])
def test_the_op_compiles_for_v5e_at_the_cells_widths(one_chip, tables, rows):
    """32 decode lanes by their own tables, and a 256-row prefill chunk
    by one: 128 heads over 512 + 64 latents in 640-wide rows, 64 index
    heads of 128, top 2048 of a 36,864-token context, the cell's pools of
    5 x 13,312 pages. Neither pool is copied or converted: a step moves
    what it selected (at 576-wide rows the compiler relaid all 1.2 GB of
    the latent pool on the way in and out of every step)."""
    from jax.experimental.compilation_cache import compilation_cache
    from hadoop_tpu.ops.sparse_mla import sparse_mla_attention
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pages = 5 * 13312
    fn = jax.jit(lambda *a: sparse_mla_attention(*a, topk=2048,
                                                 scale=0.1353))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = fn.lower(
            sds((tables, rows, 128, 512), bf16),
            sds((tables, rows, 128, 64), bf16),
            sds((tables, rows, 64, 128), bf16),
            sds((tables, rows, 64), jnp.float32),
            sds((pages, 16, 640), bf16), sds((pages, 16, 128), bf16),
            sds((tables, 2304), jnp.int32),
            sds((tables, rows), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    # the latent pool alone is 1.36 GB: nothing of that size is made
    assert compiled.memory_analysis().temp_size_in_bytes < 400 << 20
