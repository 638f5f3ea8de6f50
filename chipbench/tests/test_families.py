"""The contract of ``chipbench/families`` and of the counters: a later PR
adds an architecture, a metric over one of the engine's counters and a
metric over a scope of the trace as NEW FILES and entries, and no file the
benchmark already has changes. Shown on a copy of the tree: the toy family
below has key names of its own (``width``, ``depth`` ...), so a driver that
took a weight, a layer equation or a FLOP count from anywhere but the module
named by ``model_type`` would fail on its configuration."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REHEARSAL = os.path.join(ROOT, "chipbench", "tests", "rehearsal")

TOY_FAMILY = '''
"""A toy architecture: Mistral's equations under key names of its own."""
from chipbench.families import mistral

RENAMED = {"width": "hidden_size", "ffn": "intermediate_size",
           "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
           "depth": "num_hidden_layers", "vocab": "vocab_size",
           "theta": "rope_theta", "eps": "rms_norm_eps",
           "tied": "tie_word_embeddings", "dtype": "torch_dtype"}


def _m(model):
    return {RENAMED[k]: v for k, v in model.items() if k in RENAMED}


def model_config(model, harness):
    return mistral.model_config(_m(model), harness)


def make_params(model, key, dtype):
    return mistral.make_params(_m(model), key, dtype)


def layer_params(model, key, layer, dtype):
    return mistral.layer_params(_m(model), key, layer, dtype)


def make_leaf(model, key, path, dtype):
    return mistral.make_leaf(_m(model), key, path, dtype)


def leaf_paths(model):
    return mistral.leaf_paths(_m(model))


def hidden_states(model, seed, tokens, quant=None):
    return mistral.hidden_states(_m(model), seed, tokens, quant)


def score(model, seed, x, positions, tokens_at, quant=None):
    return mistral.score(_m(model), seed, x, positions, tokens_at, quant)


def follow(model, seed, batches, quant=None, keep=1.0):
    return mistral.follow(_m(model), seed, batches, quant, keep)


def train_flops_per_token(model, seq):
    return mistral.train_flops_per_token(_m(model), seq)


def serve_work(model, requests):
    # the contexts themselves reach a family: this one's attention reads
    # at most 16 entries
    ctx = sum(min(16, r.prompt_len + j) for r in requests for j in r.outputs)
    return {"flops": 4.0 * model["width"] * ctx, "bytes": None}


def _contexts(spec, out, cell):
    return out.obs["model_flops"] / (4.0 * cell.model["width"])


READERS = {"toy-contexts": _contexts}
'''

TOY_CONFIG = {
    "model_type": "toy", "width": 64, "ffn": 128, "heads": 4, "kv_heads": 2,
    "depth": 2, "vocab": 256, "theta": 10000.0, "eps": 1e-05, "tied": False,
    "dtype": "bfloat16",
    "harness": {"platform": "cpu", "context": 256,
                "conf": {"serving.max.context": 256, "serving.max.batch": 4,
                         "serving.kv.num.blocks": 160,
                         "serving.kv.block.size": 8,
                         "serving.prefill.chunk": 8},
                "limits": {"served_logit_gap": 0.1}}}

TOY_METRICS = {
    # the window's gain of a counter of the engine's registry, by its name
    "toy.attn_pages_dense": {"reader": "value",
                             "of": "counter.attn_pages_dense"},
    # the family's own reader
    "toy.contexts": {"reader": "toy-contexts"},
    # a scope the trace holds and no metric read; a name no list knows
    "toy.device_share.embed": {"reader": "trace-scope-share",
                               "scope": "embed"},
    "toy.device_share.train_step": {"reader": "trace-scope-share",
                                    "scope": "train_step"},
}


def _entry(name, source):
    return {"name": name, "unit": "count", "better": "lower",
            "source": source, "layer": "toy", "moves": "itl_p90_ms",
            "workloads": ["toy.serve-chat"]}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the toy's files added beside it."""
    top = tmp_path_factory.mktemp("added")
    copy = top / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (copy / "families" / "toy.py").write_text(TOY_FAMILY)
    conf = copy / "tests" / "rehearsal" / "configs" / "toy.serve.json"
    conf.write_text(json.dumps(TOY_CONFIG))
    for name, spec in TOY_METRICS.items():
        (copy / "metrics" / (name + ".json")).write_text(json.dumps(spec))
    with open(os.path.join(REHEARSAL, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy.serve", "source": "none: a stand-in",
                         "file": "chipbench/tests/rehearsal/configs/"
                                 "toy.serve.json",
                         "reduced": [], "why": "a family added by files"}]
    bench["workloads"] = [{"name": "toy.serve-chat", "config": "toy.serve",
                           "traffic": "chat-tiny", "chips": 1,
                           "why": "the toy family under the rehearsal's chat"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.serve-chat" in m.get("workloads", []):
            m["workloads"] = ["toy.serve-chat"]
    bench["per_layer"] += [
        _entry("toy.attn_pages_dense", "program_counter"),
        _entry("toy.contexts", "program_counter"),
        _entry("toy.device_share.embed", "device_trace"),
        _entry("toy.device_share.train_step", "device_trace")]
    (top / "TOY.json").write_text(json.dumps(bench))
    return top


def _python(tree, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tree / ".jax_cache"))
    return subprocess.run([sys.executable, *args], cwd=str(tree), env=env,
                          capture_output=True, text=True, timeout=600)


def _differences(cmp, at=""):
    found = [at + n for n in cmp.left_only + cmp.diff_files + cmp.funny_files
             if n != "__pycache__"]
    for name, sub in cmp.subdirs.items():
        if name != "__pycache__":
            found += _differences(sub, at + name + "/")
    return found


def test_a_family_and_a_counter_metric_added_by_files_alone(tree):
    p = _python(tree, "-m", "chipbench.run", "--workload", "toy.serve-chat",
                "--benchmark", "TOY.json", "--seed", "3000000013",
                "--seconds", "2", "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["check"]["served_logit_gap"]["value"] \
        <= line["check"]["served_logit_gap"]["limit"]
    got = line["metrics"]
    assert got["toy.attn_pages_dense"]["value"] > 0
    # the family's own reader, over the family's own count of the work:
    # every context it was handed, cut to the 16 entries it reads
    assert got["toy.contexts"]["value"] > 0
    assert got["toy.contexts"]["value"] % 16 == 0
    # the engine's metrics read this family's run like any other
    assert got["engine.attn_live_page_share"]["value"] > 0
    assert "engine.queue_wait_ms" in got
    # nothing the benchmark already had was touched to get there
    cmp = filecmp.dircmp(os.path.join(ROOT, "chipbench"),
                         str(tree / "chipbench"))
    assert _differences(cmp) == []


def test_a_scope_share_read_from_the_recorded_trace_by_its_file_alone(tree):
    """A reduction made as ``harness.Tracer.reduce`` makes it, of the
    recorded v5e trace; the metric is its file and nothing else."""
    p = _python(tree, "-c", """
import json
from chipbench import harness, readers, scopes, trace
with open("chipbench/tests/scopes_small.json") as f:
    events = json.load(f)["events"]
red = trace.reduce(events)
red["scopes"] = scopes.reduce(events, scopes.named_in_metrics())
out = harness.Outcome({}, {}, 0, 0, trace=red)
cell = harness.load_cell("toy.serve-chat", "TOY.json")
print(json.dumps({m["name"]: readers.read(harness.metric_spec(m["name"]),
                                          out, cell)
                  for m in cell.per_layer if m["source"] == "device_trace"}))
""")
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert 0 < got["toy.device_share.embed"] < 100
    # a name that no list in the code holds: known because a file reads it
    assert 0 < got["toy.device_share.train_step"] < 100
    assert got["device.idle_share"] is not None


def test_a_model_type_without_a_module_names_the_families(tmp_path):
    (tmp_path / "configs").mkdir()
    shutil.copytree(os.path.join(REHEARSAL, "traffic"), tmp_path / "traffic")
    conf = dict(TOY_CONFIG, model_type="deepseek_v32", hidden_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=128, vocab_size=256)
    (tmp_path / "configs" / "new.json").write_text(json.dumps(conf))
    with open(os.path.join(REHEARSAL, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [dict(bench["configs"][1], name="new",
                             file=str(tmp_path / "configs" / "new.json"))]
    bench["workloads"] = [dict(bench["workloads"][1], name="new.serve-chat",
                               config="new")]
    (tmp_path / "B.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "new.serve-chat", "--benchmark", str(tmp_path / "B.json"),
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    last = p.stderr.strip().splitlines()[-1]
    assert "deepseek_v32" in last and "mistral" in last and "mixtral" in last
