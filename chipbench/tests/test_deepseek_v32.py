"""The ``deepseek_v32`` family module, its configuration and its cell, as
far as a CPU can show them: the rehearsal of ``deepseek-v3.2.serve-longdoc``
(``rehearsal-dsv32/``: the same driver, family, readers and metric files at
toy widths) with its float8 control coming out not correct; the weights'
layout against the program's; the configuration file against the published
widths; the work counted; the roofline readers on a parent without the
counters."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, kinds
from chipbench import weights as W
from chipbench.families import Served
from chipbench.families import deepseek_v32 as F

REHEARSAL = "chipbench/tests/rehearsal-dsv32/BENCHMARK.json"
CELL = "tiny.serve-longdoc"
REAL = "chipbench/configs/deepseek-v3.2.serve-ep16-1chip.json"


@pytest.fixture(autouse=True)
def from_the_root(monkeypatch):
    monkeypatch.chdir(harness.ROOT)


def tiny_model():
    return harness.load_cell(CELL, REHEARSAL).model


def real_config():
    with open(os.path.join(harness.ROOT, REAL)) as f:
        return json.load(f)


# ---------------------------------------------------------------- the cell

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_not_correct(seed):
    import time
    cell = harness.load_cell(CELL, REHEARSAL)
    run = kinds.driver(cell.traffic["kind"])
    out = run(cell, jax.devices()[:1], seed=seed, seconds=1.0, traced=False,
              t_start=time.monotonic(), control="fp8")
    assert out.correct, out.checks
    limits = cell.harness["limits"]
    control = out.obs["control"]["fp8"]
    assert [k for k in limits if not control[k] <= limits[k]], control


def test_the_rehearsed_cell_prints_its_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", REHEARSAL,
         "--workload", CELL, "--seed", str(2 ** 31 + 7), "--seconds", "1",
         "--trace", "1"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # a CPU speaks only under the names of program counters
    assert 0 < got["engine.dsa_selected_share.longdoc"] < 100
    assert 0 < got["engine.moe_local_share.longdoc"] < 100
    assert got["compile.in_window.longdoc"] == 0
    assert got["kv.prefix_hit_share.longdoc"] > 50
    assert not any("roofline" in k or "device" in k for k in got)


def test_a_program_without_the_family_ends_the_run_by_name(monkeypatch):
    from hadoop_tpu.models import config

    def older(**kw):
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'q_lora_rank'")
    monkeypatch.setattr(config, "ModelConfig", older)
    with pytest.raises(SystemExit) as e:
        F.model_config(tiny_model(), {"context": 64})
    assert "deepseek_v32" in str(e.value)


# ------------------------------------------------------------- the weights

def test_leaves_follow_the_programs_layout_bit_for_bit():
    model = tiny_model()
    key = W.seed_key(5)
    tree = jax.jit(lambda k: F.make_params(model, k, jnp.bfloat16))(key)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    paths = [tuple(p.key for p in path) for path, _ in flat]
    assert paths == [tuple(p) for p in F.leaf_paths(model)]
    for path, leaf in zip(paths, (v for _, v in flat)):
        alone = jax.jit(lambda k, path=path: F.make_leaf(
            model, k, path, jnp.bfloat16))(key)
        np.testing.assert_array_equal(np.asarray(alone, np.float32),
                                      np.asarray(leaf, np.float32))
    # the reference's layer at a time is the stack's slice, numbered by
    # the layer's place in the model (dense layers first)
    one = jax.jit(lambda k: F.layer_params(model, k, 2, jnp.bfloat16))(key)
    for name, leaf in one.items():
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32),
            np.asarray(tree["moe_layers"][name][1], np.float32))
    # and the program's own description of the tree agrees on every shape
    from hadoop_tpu.models import deepseek
    cfg = F.model_config(model, {"context": 64})
    for name, kind, _, n in F.runs(model):
        shapes = deepseek.layer_shapes(cfg, kind)
        assert {k: (n,) + s for k, (s, _) in shapes.items()} == \
            {k: v.shape for k, v in tree[name].items()}


# ------------------------------------------------------- the configuration

def test_the_configuration_holds_the_published_widths_uncut():
    c = real_config()
    widths = {"hidden_size": 7168, "num_attention_heads": 128,
              "q_lora_rank": 1536, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "index_n_heads": 64, "index_head_dim": 128,
              "index_topk": 2048, "router_width": 256,
              "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
              "routed_scaling_factor": 2.5, "moe_intermediate_size": 2048,
              "intermediate_size": 18432, "n_shared_experts": 1,
              "rms_norm_eps": 1e-06, "rope_theta": 10000}
    assert {k: c[k] for k in widths} == widths
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # YaRN's numbers repeated as scalars are the group's
    rs = c["rope_scaling"]
    assert (c["yarn_factor"], c["yarn_original_max_position_embeddings"],
            c["yarn_beta_fast"], c["yarn_beta_slow"], c["yarn_mscale"]) == \
        (rs["factor"], rs["original_max_position_embeddings"],
         rs["beta_fast"], rs["beta_slow"], rs["mscale"])
    assert sorted(c["reduced"]) == sorted(c["published"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"])
    assert c["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280,
        "num_nextn_predict_layers": 1}
    # the floors: a whole period and four layers after the dense one, at
    # least 8 routed experts, at least an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    assert sorted(c["harness"]["conf"]) == [
        "serving.kv.hbm.bytes", "serving.max.batch", "serving.max.context",
        "serving.prefill.chunk"]


def test_the_cut_is_reckoned_from_the_shapes():
    """4.636 B parameters = 9.27 GB bfloat16, as PERF.md's table has it."""
    model = {k: v for k, v in real_config().items()
             if not isinstance(v, (dict, list))}
    shapes = jax.eval_shape(
        lambda k: F.make_params(model, k, jnp.bfloat16), W.seed_key(1))
    count = lambda t: sum(int(np.prod(x.shape))        # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes["dense_layers"]) == 597_442_816
    assert count(shapes["moe_layers"]) == 4 * 951_599_616
    assert count(shapes) == 4_635_518_208
    conf = real_config()["harness"]["conf"]
    pages = conf["serving.kv.hbm.bytes"] - 2 * count(shapes)
    assert pages == 13312 * 5 * 16 * (640 + 128) * 2


# ------------------------------------------------------------------ the work

def test_the_work_counted_for_a_request():
    model = tiny_model()
    k = model["index_topk"]
    one = F.serve_work(model, [Served(100, 0.64, [0, 1, 2])])["flops"]
    tokens = 36 + 2
    live = (100 * 101 - 64 * 65) / 2 + 101 + 102
    kept = 36 * k + 2 * k
    want = F.token_matmul_flops(model) * tokens + F.index_flops(model, live) \
        + F.attention_flops(model, kept) + 3 * F.head_flops(model)
    assert one == pytest.approx(want)
    # a context under index_topk is attended to whole
    short = F.serve_work(model, [Served(10, 0.0, [0])])["flops"]
    assert short == pytest.approx(
        F.token_matmul_flops(model) * 10 + F.index_flops(model, 55)
        + F.attention_flops(model, 55) + F.head_flops(model))
    with pytest.raises(F.NotBuilt):
        F.follow(model, 1, [])
    with pytest.raises(F.NotBuilt):
        F.train_flops_per_token(model, 4096)


def test_an_expert_more_popular_than_its_room_is_still_computed(monkeypatch):
    """A correction bias that sends every row to one held expert: the
    reference runs that expert over all rows instead of the gathered few
    (it once poisoned such a layer with NaN, and a chip run of the cell
    read not correct for it), and gives the brute force's sum."""
    model = tiny_model()
    lp = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: F.layer_params(model, k, 1, jnp.bfloat16, "moe"))(
            W.seed_key(3)))
    lp["router_bias"] = lp["router_bias"].at[0].set(50.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 64), jnp.float32)
    monkeypatch.setattr(F, "ROOM", 1)           # room for 24 of 128 rows
    got = np.asarray(F.expert_layer(x, lp, model, None))
    chosen, w = F.route(x, lp, model, None)
    want = np.asarray(F.swiglu_mlp(x, lp["ws_gate"], lp["ws_up"],
                                   lp["ws_down"], None)).copy()
    for t in range(128):
        for e, g in zip(np.asarray(chosen[t]), np.asarray(w[t])):
            if e < model["n_routed_experts"]:
                want[t] += g * np.asarray(F.swiglu_mlp(
                    x[t:t + 1], lp["w_gate"][e], lp["w_up"][e],
                    lp["w_down"][e], None))[0]
    assert (np.asarray(chosen) == 0).any(axis=1).all()
    np.testing.assert_allclose(got, want, atol=2e-5)


def _outcome(obs, scopes):
    dev = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    return types.SimpleNamespace(
        obs=obs, devices=[dev],
        trace={"window_s": 10.0, "busy_s": 8.0,
               "scopes": {"scopes": scopes}} if scopes is not None else None)


@pytest.mark.parametrize("reader", sorted(F.READERS))
def test_roofline_readers(reader):
    cell = harness.load_cell("deepseek-v3.2.serve-longdoc")
    read = F.READERS[reader]
    scopes = {"dsa_index": 0.8, "dsa_select": 1.5, "attn": 0.3, "moe": 2.0}
    # a parent without the counters, a run without a trace: nothing read
    assert read({}, _outcome({"window_s": 50.0, "steps": 900}, scopes),
                cell) is None
    obs = {"window_s": 50.0, "steps": 900,
           "counter.attn_entries_live": 900 * 20 * 33000,
           "counter.attn_entries_selected": 900 * 20 * 2048,
           "counter.attn_pages_distinct": 900 * 4 * 2100,
           "counter.moe_assignments": 900 * 20 * 8 * 4,
           "counter.moe_assignments_local": 900 * 20 * 2,
           "counter.moe_local_experts_hit": 900 * 4 * 10}
    assert read({}, _outcome(obs, None), cell) is None
    share = read({}, _outcome(obs, scopes), cell)
    assert 0 < share < 100
