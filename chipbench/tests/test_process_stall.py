"""``process.stalled_ms`` through the rehearsal: the serving stand-ins run
end to end on the CPU against ``rehearsal-stall/BENCHMARK.json`` — the
accepted rehearsal's two serving cells over its own configurations and
traffic files, with the one entry more — and a traced run prints the
metric, read from the program's own counter with no code of the
benchmark's."""

import json
import os

import pytest

from chipbench.tests.test_cells import ENGINE, ROOT, run_cell

STALL = "chipbench/tests/rehearsal-stall/BENCHMARK.json"
NAME = "process.stalled_ms"
# the every-tick counters that the chip's machines keep (PERF.md, §3)
TICKS = ("process.gc_ms", "process.major_faults")


def _bench(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", ["tiny.serve-chat", "tiny.serve-batch"])
def test_a_traced_rehearsal_prints_the_stalled_milliseconds(workload):
    p = run_cell(workload, "--benchmark", STALL, "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    got = line["metrics"][NAME]
    assert got["unit"] == "ms" and got["value"] >= 0
    # a stall inside the window was also said on stderr, one line a stall
    # (a sound run says nothing; a loaded CPU may stall in set-up too)
    said = [ln for ln in p.stderr.splitlines() if "Detected pause of" in ln]
    assert bool(said) or got["value"] == 0
    # every other engine metric of the accepted rehearsal is still read,
    # and the monitor's every-tick counters beside it
    assert ENGINE | set(TICKS) <= set(line["metrics"])


def test_an_untraced_run_prints_no_per_layer_metric():
    p = run_cell("tiny.serve-chat", "--benchmark", STALL, "--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert NAME not in line["metrics"] and line["correct"] is True


def test_the_entry_lists_the_four_serving_cells_and_has_its_file():
    bench = _bench("BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    serving = [w["name"] for w in bench["workloads"]
               if w["name"] != "mistral-7b.train-4k"]
    assert entries[NAME] == {"name": NAME, "unit": "ms", "better": "lower",
                             "source": "program_counter",
                             "layer": "engine scheduler",
                             "moves": "ttft_mean_ms", "workloads": serving}
    for name in TICKS + ("loadgen.late_ms_p90",):
        assert entries[name]["workloads"] == serving
    assert _bench(f"chipbench/metrics/{NAME}.json") == {
        "reader": "value", "of": "counter.process_stalled_seconds",
        "scale": 1000.0}


def test_the_stall_rehearsal_is_the_accepted_one_and_one_entry_more():
    old = _bench("chipbench/tests/rehearsal/BENCHMARK.json")
    new = _bench(STALL)
    cells = {w["name"] for w in new["workloads"]}
    assert cells == {"tiny.serve-chat", "tiny.serve-batch"}
    assert [w for w in old["workloads"] if w["name"] in cells] \
        == new["workloads"]
    assert all(c in old["configs"] for c in new["configs"])
    assert new["run_seconds"] == old["run_seconds"]
    mine = new["per_layer"][-1]
    assert mine["name"] == NAME and set(mine["workloads"]) == cells
    kept = {m["name"] for m in new["per_layer"][:-1]}
    assert kept == {m["name"] for m in old["per_layer"]
                    if cells & set(m.get("workloads", cells))}
