"""``chipbench.inside``: the metrics that read the program's own record,
on the CPU rehearsal and on hand-made records."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import inside

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REHEARSAL = "chipbench/tests/rehearsal/BENCHMARK.json"
STAGE_1 = set(inside.SERVE[:6])


@pytest.mark.parametrize("workload", ["tiny.serve-chat", "tiny.serve-batch"])
def test_the_serving_rehearsal_prints_the_six_engine_metrics(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.inside", "--workload", workload,
         "--seed", "3000000011", "--seconds", "2", "--benchmark", REHEARSAL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    ordinary, last = [json.loads(ln) for ln in
                      p.stdout.strip().splitlines()[-2:]]
    assert ordinary["correct"] is True and ordinary["rehearsal"] is True
    assert last["rehearsal"] is True and "itl_p90_ms" in last["end_to_end"]
    got = last["inside"]
    assert set(got) == STAGE_1
    assert all(v >= 0 for v in got.values())
    # host time is part of busy time, the phases tile the window
    assert got["engine.host_ms_per_step.chat"] \
        <= got["engine.step_busy_ms.chat"]
    assert got["engine.step_busy_ms.chat"] * last["steps"] / 1e3 <= 2.0
    # the longest iteration reads as the upper bound of its bucket
    assert got["engine.iter_max_ms.chat"] in [
        0.25 * 2 ** i for i in range(20)]
    assert 0.8 * 2.0 < sum(last["phases"].values()) <= 2.0 + 0.1
    # a CPU reads no trace: no device time by module or scope
    assert "scopes" not in last and "modules" not in last


def test_the_training_rehearsal_reads_nothing_on_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.inside", "--workload",
         "tiny.train", "--seed", "3000000011", "--seconds", "2",
         "--benchmark", REHEARSAL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["inside"] == {} and last["rehearsal"] is True
    assert set(last["end_to_end"]) == {"train_tokens_per_s_per_chip",
                                       "setup_s"}


class _Hist:
    def __init__(self, total=0.0, counts=()):
        self.total, self.counts = total, list(counts)

    def buckets(self):
        bounds = [0.128, 0.256, 0.512, 1.024, 2.048, 4.096, float("inf")]
        cum = [sum(self.counts[:i + 1]) for i in range(len(bounds))]
        return list(zip(bounds, cum)), self.total, sum(self.counts)


class _Metrics:
    def __init__(self):
        self.ttft_stage_hist = {s: _Hist() for s in inside.STAGES}
        self.iteration_hist = _Hist(counts=[0] * 7)


class _Engine:
    def __init__(self, **kw):
        self.__dict__.update(kw)


class _Out:
    def __init__(self, obs, trace=None):
        self.obs, self.trace = obs, trace


def _read(marks, obs, trace=None):
    from chipbench import harness, readers, scopes
    inside.observe(marks, obs)
    known = dict(readers.READERS, **scopes.READERS)
    out = _Out(obs, trace)
    got = {}
    for name in inside.SERVE + inside.TRAIN:
        spec = harness.metric_spec(name)
        got[name] = known[spec["reader"]](spec, out, None)
    return got


def _stage(m, stage, total, n):
    m.ttft_stage_hist[stage].total = total
    m.ttft_stage_hist[stage].counts = [n]


def test_window_deltas_of_a_hand_made_record():
    m = _Metrics()
    eng = _Engine(metrics=m,
                  phase_s={"engine.wait": 4.0, "engine.dispatch": 1.0})
    for stage, total in (("queue", 1.0), ("prefill_wait", 2.0),
                         ("prefill", 3.0)):
        _stage(m, stage, total, 2)
    m.iteration_hist.counts = [5, 1, 0, 0, 0, 1, 0]  # a stall before it
    a = inside.engine_record(eng)
    for stage, total in (("queue", 1.5), ("prefill_wait", 4.0),
                         ("prefill", 9.0)):
        _stage(m, stage, total, 6)
    eng.phase_s.update({"engine.wait": 6.0, "engine.dispatch": 3.0,
                        "engine.readback": 40.0, "engine.deliver": 1.0})
    m.iteration_hist.counts = [90, 14, 0, 0, 1, 1, 0]
    b = inside.engine_record(eng)
    m.iteration_hist.counts[6] += 1         # after the window closed
    obs = {"steps": 100}
    got = _read([a, b], obs)
    assert {k: v for k, v in got.items() if v is not None} == pytest.approx({
        "engine.queue_wait_ms.chat": 125.0,
        "engine.prefill_wait_ms.chat": 500.0,
        "engine.prefill_service_ms.chat": 1500.0,
        "engine.step_busy_ms.chat": 430.0,
        "engine.host_ms_per_step.chat": 30.0,
        "engine.iter_max_ms.chat": 2048.0})
    assert obs["phase_window_s"]["engine.wait"] == 2.0


def test_the_trace_metrics_read_the_reduction_under_scopes():
    by_name = {"modules": {"_step_impl": {"count": 4.0, "seconds": 0.4}},
               "scopes": {"attn": 0.1, "scan_carry": 0.05, "unscoped": 0.02}}
    got = _read([], {}, {"busy_s": 0.5, "scopes": by_name})
    assert {k: v for k, v in got.items() if v is not None} == pytest.approx({
        "engine.step_device_ms.chat": 100.0,
        "engine.device_share.attn.chat": 20.0,
        "engine.device_share.scan_carry.chat": 10.0,
        "engine.device_share.unscoped.chat": 4.0,
        "train.device_share.attn": 20.0,
        "train.device_share.unscoped": 4.0})


def test_a_program_without_the_record_reads_nothing():
    old = _Engine(steps=3)                  # an engine before ISSUE 26
    assert inside.engine_record(old) is None
    assert inside.engine_record(_Engine(phase_s={}, metrics=None)) is None
    assert set(_read([None, None], {"steps": 5}).values()) == {None}
    assert set(_read([], {"steps": 5}).values()) == {None}
    assert set(_read([], {}, {"busy_s": 1.0, "scopes": None}).values()) \
        == {None}
    # no first token and no step in the window: no mean, not a 0
    eng = _Engine(metrics=_Metrics(), phase_s={"engine.wait": 1.0})
    rec = inside.engine_record(eng)
    assert set(_read([rec, rec], {"steps": 0}).values()) == {None}
