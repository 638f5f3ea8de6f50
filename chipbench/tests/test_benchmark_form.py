"""The form of ``BENCHMARK.json``'s per-layer list and of the rehearsals'
copies of it: one entry a quantity, named once, with its file and a list
of cells that exist and report what it moves; one file a metric, with an
entry. A copy of a quantity under a second name (a tag a cell) would read
the same file for the same end-to-end metric, which the last test refuses,
so that a later cell appends itself to the entry's list instead."""

import glob
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = os.path.join(ROOT, "chipbench", "metrics")
BENCHES = ["BENCHMARK.json"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(
        os.path.join(ROOT, "chipbench", "tests", "rehearsal*",
                     "BENCHMARK.json")))


def _load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _spec(name):
    return _load(os.path.join(METRICS, name + ".json"))


@pytest.mark.parametrize("bench", BENCHES)
def test_every_per_layer_name_occurs_once(bench):
    names = [m["name"] for m in _load(bench)["per_layer"]]
    assert sorted(n for n in set(names) if names.count(n) > 1) == []
    assert len(names) <= 128


@pytest.mark.parametrize("bench", BENCHES)
def test_every_entry_has_its_file(bench):
    b = _load(bench)
    missing = [m["name"] for m in b["per_layer"] + b["end_to_end"]
               if not os.path.isfile(os.path.join(METRICS,
                                                  m["name"] + ".json"))]
    assert missing == []


def test_every_metric_file_has_an_entry_or_is_an_end_to_end_metric():
    b = _load("BENCHMARK.json")
    named = {m["name"] for m in b["per_layer"] + b["end_to_end"]}
    files = {f[:-len(".json")] for f in os.listdir(METRICS)
             if f.endswith(".json")}
    assert sorted(files - named) == []


@pytest.mark.parametrize("bench", BENCHES)
def test_every_cell_an_entry_lists_is_a_cell(bench):
    b = _load(bench)
    cells = [w["name"] for w in b["workloads"]]
    for m in b["per_layer"] + b["end_to_end"]:
        listed = m.get("workloads", cells)
        assert set(listed) <= set(cells), m["name"]
        assert listed == sorted(listed, key=cells.index), m["name"]


def test_every_cell_an_entry_lists_reports_what_it_moves():
    b = _load("BENCHMARK.json")
    cells = [w["name"] for w in b["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]


@pytest.mark.parametrize("bench", BENCHES)
def test_no_two_entries_share_file_content_and_moves(bench):
    seen = {}
    for m in _load(bench)["per_layer"]:
        key = (json.dumps(_spec(m["name"]), sort_keys=True), m["moves"])
        assert key not in seen, (m["name"], seen.get(key))
        seen[key] = m["name"]
