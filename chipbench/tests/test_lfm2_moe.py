"""The ``lfm2_moe`` family module, its configuration and its cell, as far as
a CPU can show them: the rehearsal of ``lfm2-24b-a2b.serve-agent``
(``rehearsal-lfm2/``: the same driver, family, readers and metric files at
toy widths) correct, with its float8 control and the planted zero-state
fault each coming out not correct; the weights' layout against the
program's; the configuration file against the published widths; the cut's
parameter counts as numbers; the work counted; the readers on a parent
without the counters."""

import json
import os
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, kinds, proof_faults
from chipbench import weights as W
from chipbench.families import Served
from chipbench.families import lfm2_moe as F

REHEARSAL = "chipbench/tests/rehearsal-lfm2/BENCHMARK.json"
CELL = "tiny.serve-agent"
REAL = "chipbench/configs/lfm2-24b-a2b.serve-1chip.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def from_the_root(monkeypatch):
    monkeypatch.chdir(harness.ROOT)


def tiny_model():
    return harness.load_cell(CELL, REHEARSAL).model


def real_config():
    with open(os.path.join(harness.ROOT, REAL)) as f:
        return json.load(f)


def real_model():
    return {k: v for k, v in real_config().items()
            if not isinstance(v, (dict, list))}


def rehearse(seed, **kw):
    cell = harness.load_cell(CELL, REHEARSAL)
    run = kinds.driver(cell.traffic["kind"])
    return cell, run(cell, jax.devices()[:1], seed=seed, seconds=1.0,
                     traced=False, t_start=time.monotonic(), **kw)


# ---------------------------------------------------------------- the cell

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_not_correct(seed):
    cell, out = rehearse(seed, control="fp8")
    assert out.correct, out.checks
    limits = cell.harness["limits"]
    control = out.obs["control"]["fp8"]
    assert [k for k in limits if not control[k] <= limits[k]], control


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_lane_started_from_zero_state_comes_out_not_correct(seed):
    """The planted fault: every request of the mix starts its lane from
    the tail of a cached page, and its answer begins within the
    convolution's reach of it."""
    with proof_faults.zero_state():
        cell, out = rehearse(seed)
    assert out.failed == 0 and not out.correct, out.checks
    value, limit = out.checks["served_logit_gap"]
    assert value > limit


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
    assert got["kv.state_restore_share.agent"] == 100.0
    assert got["engine.moe_load_imbalance.agent"] >= 1.0
    assert got["engine.moe_experts_hit_share.agent"] > 0
    assert got["compile.in_window.agent"] == 0
    assert got["kv.prefix_hit_share.agent"] > 50
    assert not any("roofline" in k or "device" in k for k in got)


def test_a_program_without_the_family_ends_the_run_by_name(monkeypatch):
    from hadoop_tpu.models import config

    def older(**kw):
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'layer_types'")
    monkeypatch.setattr(config, "ModelConfig", older)
    with pytest.raises(SystemExit) as e:
        F.model_config(tiny_model(), {"context": 64})
    assert "lfm2_moe" in str(e.value)


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
    # the reference's layer at a time is a slice of the operator's stack
    # and a slice of the FFN's: layer 3 is conv operator 2 + expert FFN 2
    one = jax.jit(lambda k: F.layer_params(model, k, 3, jnp.bfloat16))(key)
    for name, leaf in one.items():
        stack = "conv_ops" if name in tree["conv_ops"] else "moe_layers"
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32),
            np.asarray(tree[stack][name][2], np.float32))
    # and the program's own description of the tree agrees on every shape
    from hadoop_tpu.models import lfm2
    cfg = F.model_config(model, {"context": 64})
    for stack, n in lfm2.stack_sizes(cfg).items():
        shapes = lfm2.stack_shapes(cfg, stack)
        assert {k: (n,) + s for k, (s, _) in shapes.items()} == \
            {k: v.shape for k, v in tree[stack].items()}


# ------------------------------------------------------- the configuration

def test_the_configuration_holds_the_published_widths_uncut():
    c = real_config()
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 8, "intermediate_size": 11776,
              "moe_intermediate_size": 1536, "num_experts": 64,
              "num_experts_per_tok": 4, "conv_L_cache": 3,
              "conv_bias": False, "vocab_size": 65536, "norm_eps": 1e-05,
              "norm_topk_prob": True, "use_expert_bias": True,
              "routed_scaling_factor": 1,
              "max_position_embeddings": 128000}
    assert {k: c[k] for k in widths} == widths
    assert c["rope_parameters"] == {"rope_theta": 1000000,
                                    "rope_type": "default"}
    # what is repeated as a scalar is the group's
    assert c["rope_theta"] == c["rope_parameters"]["rope_theta"]
    assert c["layer_kinds"].split(",") == c["layer_types"]
    assert sorted(c["reduced"]) == sorted(c["published"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types"])
    assert c["published"]["num_hidden_layers"] == 40
    assert c["published"]["num_dense_layers"] == 2
    pub = c["published"]["layer_types"]
    assert len(pub) == 40 and pub.count("full_attention") == 10
    # the cut: one leading dense layer, then two whole periods of the
    # pattern that follows the published model's two
    assert c["layer_types"] == pub[1:2] + pub[2:10]
    assert c["num_hidden_layers"] == 9 and c["num_dense_layers"] == 1
    # the floors: a whole period and four layers after the dense one,
    # at least 8 experts (all 64 here), at least an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["num_dense_layers"] >= 4
    assert sorted(c["harness"]["conf"]) == [
        "serving.kv.hbm.bytes", "serving.max.batch", "serving.max.context",
        "serving.prefill.chunk"]
    # the widest gap is left out: one flipped expert choice moves a
    # single token's logits by whole units whatever the precision, and
    # its readings leave no room for a limit (PERF.md section 6, PR 33)
    assert sorted(c["harness"]["limits"]) == ["served_gap_p90"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalog_row_is_in_the_file():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    c = real_config()
    assert c["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in c["reduced"]:
            assert c["published"][k] == v
        else:
            assert c[k] == v, k


def test_the_cut_is_reckoned_from_the_shapes():
    """5.178 B parameters = 10.36 GB bfloat16, as PERF.md's table has it."""
    model = real_model()
    shapes = jax.eval_shape(
        lambda k: F.make_params(model, k, jnp.bfloat16), W.seed_key(1))
    count = lambda t: sum(int(np.prod(x.shape))        # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes["conv_ops"]) == 7 * 16_785_408
    assert count(shapes["attn_ops"]) == 2 * 10_487_936
    assert count(shapes["dense_layers"]) == 72_353_792
    assert count(shapes["moe_layers"]) == 8 * 604_112_960
    assert count(shapes["embed"]) == 134_217_728
    assert count(shapes) == 5_177_950_976
    parts = F.parameter_counts(model)
    assert parts == {
        "conv_operator": 16_785_408, "attention_operator": 10_487_936,
        "expert_ffn": 604_112_960, "dense_ffn": 72_353_792,
        "embedding": 134_217_728, "final_norm": 2048,
        "total": count(shapes)}
    conf = real_config()["harness"]["conf"]
    pages = conf["serving.kv.hbm.bytes"] - 2 * count(shapes)
    # 10,240 pages: K and V in 2 layers, a state tail in 7
    assert pages == 10240 * 2 * (2 * 2 * 16 * 8 * 64 + 7 * 2 * 2048)
    # and the engine sizes a page the same way
    from hadoop_tpu.serving.families import family_for
    cfg = F.model_config(model, real_config()["harness"])
    pools = family_for(cfg, {}).pools(16)
    assert pools == [(2, (16, 512)), (2, (16, 512)), (7, (2, 2048))]
    assert 2 * sum(n * int(np.prod(p)) for n, p in pools) * 10240 == pages


# ------------------------------------------------------------------ the work

def test_the_work_counted_for_a_request():
    model = tiny_model()
    m = F.dims(model)
    one = F.serve_work(model, [Served(100, 0.64, [0, 1, 2])])
    tokens = 36 + 2
    live = (100 * 101 - 64 * 65) / 2 + 101 + 102
    want = F.token_matmul_flops(model) * tokens \
        + F.attention_flops(model, live) \
        + 3 * 2 * m["D"] * m["V"]
    assert one["flops"] == pytest.approx(want)
    # bytes: the cache those tokens read and write, and the weights once
    # for each of the three steps the request cannot do without
    item = 2
    cache = item * (m["La"] * 2 * m["Hkv"] * m["dh"] * (live + tokens)
                    + m["Lc"] * 2 * 2 * m["D"] * tokens)
    assert one["bytes"] > cache
    dense = F.parameter_counts(model)["total"] \
        - (m["L"] - m["Ld"]) * m["E"] * 3 * m["D"] * m["Fe"]
    assert one["bytes"] - cache >= 3 * item * dense
    assert F.serve_work(model, [])["bytes"] == 0
    with pytest.raises(F.NotBuilt):
        F.follow(model, 1, [])
    with pytest.raises(F.NotBuilt):
        F.train_flops_per_token(model, 4096)


def _outcome(obs, scopes):
    dev = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    return types.SimpleNamespace(
        obs=obs, devices=[dev],
        trace={"window_s": 10.0, "busy_s": 8.0,
               "scopes": {"scopes": scopes}} if scopes is not None else None)


@pytest.mark.parametrize("reader", ["moe-roofline", "conv-roofline"])
def test_roofline_readers(reader):
    cell = harness.load_cell("lfm2-24b-a2b.serve-agent")
    read = F.READERS[reader]
    scopes = {"moe": 5.0, "conv": 1.0}
    # a parent without the counters, a run without a trace: nothing read
    assert read({}, _outcome({"window_s": 50.0, "steps": 2000}, scopes),
                cell) is None
    rows = 2000 * 40
    obs = {"window_s": 50.0, "steps": 2000,
           "counter.moe_assignments": rows * 4 * 8,
           "counter.moe_assignments_local": rows * 4 * 8,
           "counter.moe_local_experts_hit": 2000 * 8 * 58,
           "counter.moe_expert_rows_max": 2000 * 8 * 7}
    assert read({}, _outcome(obs, None), cell) is None
    share = read({}, _outcome(obs, scopes), cell)
    assert 0 < share < 100


def test_the_restore_share_reads_both_counters():
    cell = harness.load_cell("lfm2-24b-a2b.serve-agent")
    read = F.READERS["state-restore-share"]
    assert read({}, _outcome({}, None), cell) is None
    obs = {"counter.recurrent_state_restores": 30,
           "counter.recurrent_state_cold_starts": 10}
    assert read({}, _outcome(obs, None), cell) == 75.0
