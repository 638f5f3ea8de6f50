"""The ``ouro`` family module, its configuration and its cell, as far as a
CPU can show them: the rehearsal of ``ouro-2.6b.serve-reason``
(``rehearsal-ouro/``: the same driver, family, readers and metric files at
toy widths) correct, with its float8 control and the planted wrong-slot
fault each coming out not correct; the weights' layout against the
program's; the configuration file against the published sizes, nothing
cut; the parameter counts and the bytes a token keeps as numbers; the work
counted; the readers on a parent without the counters."""

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

from chipbench import harness, kinds, proof_fault_slots
from chipbench import weights as W
from chipbench.families import Served
from chipbench.families import ouro as F

REHEARSAL = "chipbench/tests/rehearsal-ouro/BENCHMARK.json"
CELL = "tiny.serve-reason"
REAL_CELL = "ouro-2.6b.serve-reason"
REAL = "chipbench/configs/ouro-2.6b.serve-1chip.json"
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
    return harness.load_cell(REAL_CELL).model


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
def test_every_pass_in_the_first_pass_slots_comes_out_not_correct(seed):
    """The planted fault: most rows of the mix are decode rows, which read
    of the earlier tokens what the LAST pass left in the one slot."""
    with proof_fault_slots.first_pass_slot():
        cell, out = rehearse(seed)
    assert out.failed == 0 and not out.correct, out.checks
    value, limit = out.checks["served_logit_gap"]
    assert value > 10 * limit


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
    assert got["engine.loop_passes_per_step.reason"] == 3.0
    assert 0 < got["kv.pool_fill_share.reason"] < 100
    assert got["engine.preemptions.reason"] == 0
    assert got["compile.in_window.reason"] == 0
    assert got["kv.prefix_hit_share.reason"] > 50
    assert not any("roofline" in k or "device" in k for k in got)


def test_a_program_without_the_family_ends_the_run_by_name(monkeypatch):
    from hadoop_tpu.models import config

    def older(**kw):
        raise TypeError("__init__() got an unexpected keyword argument "
                        "'n_passes'")
    monkeypatch.setattr(config, "ModelConfig", older)
    with pytest.raises(SystemExit) as e:
        F.model_config(tiny_model(), {"context": 64})
    assert "no family 'ouro'" in str(e.value)


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
    # the reference's layer at a time is a slice of the stack
    one = jax.jit(lambda k: F.layer_params(model, k, 2, jnp.bfloat16))(key)
    for name, leaf in one.items():
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32),
            np.asarray(tree["layers"][name][2], np.float32))
    # and the program's own tree agrees on every leaf and shape
    from hadoop_tpu.models import ouro
    cfg = F.model_config(model, {"context": 64})
    shapes = lambda t: jax.tree_util.tree_map(          # noqa: E731
        lambda a: a.shape, t)
    assert shapes(jax.eval_shape(
        lambda k: ouro.init_params(k, cfg), jax.random.PRNGKey(0))) \
        == shapes(tree)


# ------------------------------------------------------- the configuration

def test_the_configuration_holds_the_published_sizes_uncut():
    c = real_config()
    sizes = {"num_hidden_layers": 48, "total_ut_steps": 4,
             "hidden_size": 2048, "num_attention_heads": 16,
             "num_key_value_heads": 16, "head_dim": 128,
             "intermediate_size": 5632, "vocab_size": 49152,
             "rope_theta": 1000000, "rms_norm_eps": 1e-06,
             "early_exit_threshold": 1, "tie_word_embeddings": False,
             "sliding_window": None, "rope_scaling": None,
             "max_position_embeddings": 65536, "hidden_act": "silu",
             "model_type": "ouro"}
    assert {k: c[k] for k in sizes} == sizes
    assert c["layer_types"] == ["full_attention"] * 48
    # nothing cut: the first configuration here whose ``reduced`` is empty
    assert c["reduced"] == [] and c["published"] == {}
    assert sorted(c["assumed"]) == sorted([
        "sandwich_norm", "closing_norm", "kv_slot", "exit_gate", "rope",
        "weights", "dtype", "context"])
    for key in ("sandwich_norm", "closing_norm", "kv_slot", "exit_gate"):
        assert "modeling_ouro.py" in c["assumed"][key]
        assert "arXiv:2510.25741" in c["assumed"][key]
    conf = c["harness"]["conf"]
    assert sorted(conf) == ["serving.kv.hbm.bytes", "serving.max.batch",
                            "serving.max.context", "serving.prefill.chunk"]
    assert conf["serving.max.batch"] == 16
    assert conf["serving.max.context"] == c["harness"]["context"] == 2048
    # both limits lie between the sound readings and the float8
    # control's, at their geometric middles (PERF.md section 6, PR 35:
    # sound <= 0.838 / 1.465 over 25 seeds, control >= 2.335 / 4.715)
    assert c["harness"]["limits"] == {"served_gap_p90": 1.4,
                                      "served_logit_gap": 2.6}
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    entry = next(e for e in bench["configs"]
                 if e["name"] == "ouro-2.6b.serve-1chip")
    assert entry["reduced"] == [] and entry["source"] == c["source"]


def test_the_rate_is_six_tenths_of_the_knee_the_sweep_found():
    tr = harness.load_cell(REAL_CELL).traffic
    assert tr["kind"] == "open-loop"
    assert tr["rate_per_s"] == pytest.approx(0.6 * tr["knee_per_s"])
    assert "counter.preemptions" in tr["knee_found"]
    assert tr["shared_prompts"] == {"count": 2, "tokens": 256, "zipf": 1.0}
    assert tr["unique_tokens"] == {"median": 64, "sigma": 0.8, "lo": 16,
                                   "hi": 256}
    assert tr["output_tokens"] == {"median": 256, "sigma": 0.7, "lo": 32,
                                   "hi": 1024}
    # the longest request fits a lane's context
    from chipbench import traffic
    assert traffic.max_request_tokens(tr) <= 2048


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalog_row_is_in_the_file():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    c = real_config()
    assert c["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert c[k] == v, k


def test_the_model_is_reckoned_from_the_shapes():
    """2,667,974,657 parameters = 5.336 GB bfloat16; 1,572,864 B of K/V a
    token, 24 MiB a 16-token page: as PERF.md's table has them."""
    model = real_model()
    shapes = jax.eval_shape(
        lambda k: F.make_params(model, k, jnp.bfloat16), W.seed_key(1))
    count = lambda t: sum(int(np.prod(x.shape))        # noqa: E731
                          for x in jax.tree_util.tree_leaves(t))
    assert count(shapes["layers"]) == 48 * 51_388_416 == 2_466_643_968
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 201_326_592
    assert count(shapes) == 2_667_974_657
    parts = F.parameter_counts(model)
    assert parts == {
        "attention": 4 * 2048 ** 2, "mlp": 3 * 2048 * 5632,
        "layer_norms": 4 * 2048, "layer": 51_388_416,
        "layers": 2_466_643_968, "embedding": 100_663_296,
        "head": 100_663_296, "final_norm": 2048, "exit_gate": 2049,
        "total": count(shapes)}
    assert F.kv_bytes_per_token(model) == 192 * 8192 == 1_572_864
    # the budget is the weights and whole pages
    conf = real_config()["harness"]["conf"]
    pool = conf["serving.kv.hbm.bytes"] - 2 * count(shapes)
    page = 16 * F.kv_bytes_per_token(model)
    assert page == 25_165_824 and pool % page == 0 and pool // page >= 321
    # and the engine sizes a page the same way: 192 slots, from the family
    from hadoop_tpu.serving.families import family_for
    cfg = F.model_config(model, real_config()["harness"])
    family = family_for(cfg, {})
    assert family.pools(16) == [(192, (16, 16, 128))] * 2
    assert family.page_slots == 192
    assert 2 * sum(n * int(np.prod(p)) for n, p in family.pools(16)) == page


# ------------------------------------------------------------------ the work

def test_the_work_counted_for_a_request():
    model = tiny_model()
    m = F.dims(model)
    counts = F.parameter_counts(model)
    one = F.serve_work(model, [Served(100, 0.64, [0, 1, 2])])
    tokens = 36 + 2
    live = (100 * 101 - 64 * 65) / 2 + 101 + 102
    # every computed token runs every layer P times and attends in P x L
    # slots; the head where a token is sampled
    want = m["P"] * m["L"] * 2 * (counts["attention"] + counts["mlp"]) \
        * tokens + 4 * m["P"] * m["L"] * m["H"] * m["dh"] * live \
        + 3 * 2 * m["D"] * m["V"]
    assert one["flops"] == pytest.approx(want)
    # bytes: the slots those tokens read and write, and for each of the
    # three steps the request cannot do without the layers' weights once
    # A PASS and the head once
    cache = F.kv_bytes_per_token(model) * (live + tokens)
    assert one["bytes"] == pytest.approx(
        cache + 3 * 2 * (m["P"] * counts["layers"] + counts["head"]))
    assert F.serve_work(model, [])["bytes"] == 0
    with pytest.raises(F.NotBuilt):
        F.follow(model, 1, [])
    assert F.train_flops_per_token(model, 64) > 3 * m["P"] * m["L"] \
        * F.layer_matmul_flops(model)


def _outcome(obs, scopes):
    dev = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    return types.SimpleNamespace(
        obs=obs, devices=[dev],
        trace={"window_s": 10.0, "busy_s": 9.9, "scopes": {
            "scopes": scopes,
            "modules": {"_step_impl": {"count": 178.0, "seconds": 9.9}}}}
        if scopes is not None else None)


def test_roofline_readers():
    cell = harness.load_cell(REAL_CELL)
    loop, attn = F.READERS["loop-roofline"], F.READERS["attn-kv-roofline"]
    # the scopes' seconds of a traced slice of 178 whole steps (my chip
    # run, PR 35, seed 3000003503)
    scopes = {"attn_proj": 0.845, "mlp": 3.340, "loop_norm": 0.0004,
              "scan_carry": 1.291, "attn": 3.583}
    # a parent without the counters, a run without a trace: nothing read
    bare = {"window_s": 50.0, "steps": 1011, "prompt_tokens_seen": 8400,
            "prompt_tokens_matched": 6400, "tokens_out": 6910,
            "first_tokens": 26}
    assert loop({}, _outcome(bare, scopes), cell) is None
    assert attn({}, _outcome(bare, scopes), cell) is None
    obs = {**bare, "counter.loop_passes": 4044,
           "counter.attn_pages_read": 269644}
    assert loop({}, _outcome(obs, None), cell) is None
    assert attn({}, _outcome(obs, None), cell) is None
    # 178 steps in the slice, each 4 x 4.93 GB at 819 GB/s = 24.1 ms, of
    # 30.8 ms a step in the four scopes
    share = loop({}, _outcome(obs, scopes), cell)
    assert share == pytest.approx(
        100 * 178 * 4 * 2 * 2_466_643_968 / 819e9 / 5.4764, rel=1e-6)
    assert 70 < share < 85
    # 266.7 table pages a step, each 25,165,824 B deep, once a step
    share = attn({}, _outcome(obs, scopes), cell)
    assert share == pytest.approx(
        100 * 178 * (269644 / 1011) * 25_165_824 / 819e9 / 3.583, rel=1e-6)
    assert 0 < share < 100
    # the slice's own steps, not the window's scaled: a window whose
    # steps are slower at its end reads no higher for it
    late = {**obs, "window_s": 25.0}
    assert loop({}, _outcome(late, scopes), cell) \
        == loop({}, _outcome(obs, scopes), cell)
