"""Every cell's stand-in end to end on the CPU (the rehearsal), and what a
run must refuse: a real cell without its chip, and a checkout that holds
the benchmark alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REHEARSAL = "chipbench/tests/rehearsal/BENCHMARK.json"
# read from the engine's own record: window deltas of its phases, its TTFT
# stages, its iteration histogram and two counters of its registry
ENGINE = {"engine.queue_wait_ms", "engine.prefill_wait_ms",
          "engine.prefill_service_ms", "engine.step_busy_ms",
          "engine.host_ms_per_step", "engine.iter_max_ms",
          "engine.attn_live_page_share"}


def run_cell(workload, *more, cwd=ROOT, seed=3000000007):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload,
         "--seed", str(seed), "--seconds", "2", *more],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", ["tiny.train", "tiny.serve-chat",
                                      "tiny.serve-batch"])
def test_rehearsal_runs_end_to_end_and_names_the_cpu(workload, trace):
    p = run_cell(workload, "--benchmark", REHEARSAL, "--trace", str(trace))
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    with open(os.path.join(ROOT, REHEARSAL)) as f:
        bench = json.load(f)
    counted = {m["name"] for m in bench["per_layer"]
               if m["source"] == "program_counter"}
    # a CPU never speaks under the name of a time, a rate or a share of
    # the device
    assert set(line["metrics"]) <= counted
    assert not any(k.startswith(("hbm.", "device.")) for k in line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    if trace:
        assert line["metrics"]
        assert all(m["value"] == 0 for k, m in line["metrics"].items()
                   if k.startswith("compile.in_window"))
    if trace and workload != "tiny.train":
        got = {k: m["value"] for k, m in line["metrics"].items()}
        assert ENGINE <= set(got)
        assert all(got[k] >= 0 for k in ENGINE)
        # host time is part of busy time; the longest iteration reads as
        # the upper bound of its bucket; live pages are some of all pages
        assert got["engine.host_ms_per_step"] \
            <= got["engine.step_busy_ms"]
        assert got["engine.iter_max_ms"] in [
            0.25 * 2 ** i for i in range(20)]
        assert 0 < got["engine.attn_live_page_share"] <= 100
    for name, c in line["check"].items():
        assert c["value"] <= c["limit"], name
    assert p.stderr.strip().splitlines()[-1].startswith("correct=True")


def test_a_real_cell_without_its_chip_prints_no_result():
    p = run_cell("mistral-7b.train-4k", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr


def test_the_benchmark_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cell("mistral-7b.train-4k", "--trace", "0", cwd=str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
