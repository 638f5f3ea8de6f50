"""``serve_cell._counters`` and ``window_deltas``: the engine's own record
(phases, TTFT stages, iteration histogram, every number of its metrics
registry) becomes window deltas that the metric files read, and a program
without the record reads nothing."""

import pytest

from chipbench import harness, readers
from chipbench.serve_cell import STAGES, _counters, window_deltas

ENGINE = ["engine.queue_wait_ms.chat", "engine.prefill_wait_ms.chat",
          "engine.prefill_service_ms.chat", "engine.step_busy_ms.chat",
          "engine.host_ms_per_step.chat", "engine.iter_max_ms.chat",
          "engine.attn_live_page_share.chat"]


class _Hist:
    def __init__(self, total=0.0, counts=()):
        self.total, self.counts = total, list(counts)

    def buckets(self):
        bounds = [0.128, 0.256, 0.512, 1.024, 2.048, 4.096, float("inf")]
        cum = [sum(self.counts[:i + 1]) for i in range(len(bounds))]
        return list(zip(bounds, cum)), self.total, sum(self.counts)


class _Metrics:
    def __init__(self):
        self.ttft_stage_hist = {s: _Hist() for s in STAGES}
        self.iteration_hist = _Hist(counts=[0] * 7)
        self.numbers = {}

    def snapshot(self):
        return dict(self.numbers)


class _Engine:
    steps = prefix_tokens_seen = prefix_tokens_matched = 0
    queue_depth = num_prefilling = decode_compiles = prefill_compiles = 0
    occupancy_log = ()

    def __init__(self, **kw):
        self.__dict__.update(kw)


class _Out:
    def __init__(self, obs):
        self.obs, self.trace = obs, None


def _read(obs):
    out = _Out(obs)
    got = {n: readers.read(harness.metric_spec(n), out, None)
           for n in ENGINE}
    return {k: v for k, v in got.items() if v is not None}


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
    m.numbers = {"attn_pages_read": 100, "attn_pages_dense": 1000,
                 "kv_block_utilization": 0.5, "gone_later": 1,
                 "a_flag": True, "a_label": "x", "nothing": None}
    a = _counters(eng)
    for stage, total in (("queue", 1.5), ("prefill_wait", 4.0),
                         ("prefill", 9.0)):
        _stage(m, stage, total, 6)
    eng.phase_s.update({"engine.wait": 6.0, "engine.dispatch": 3.0,
                        "engine.readback": 40.0, "engine.deliver": 1.0})
    m.iteration_hist.counts = [90, 14, 0, 0, 1, 1, 0]
    m.numbers = {"attn_pages_read": 180, "attn_pages_dense": 2000,
                 "kv_block_utilization": 0.25, "new_later": 7,
                 "a_flag": False, "a_label": "y", "nothing": None}
    b = _counters(eng)
    m.iteration_hist.counts[6] += 1         # after the window closed
    obs = dict(window_deltas(a, b), steps=100)
    assert _read(obs) == pytest.approx({
        "engine.queue_wait_ms.chat": 125.0,
        "engine.prefill_wait_ms.chat": 500.0,
        "engine.prefill_service_ms.chat": 1500.0,
        "engine.step_busy_ms.chat": 430.0,
        "engine.host_ms_per_step.chat": 30.0,
        "engine.iter_max_ms.chat": 2048.0,
        "engine.attn_live_page_share.chat": 8.0})
    assert obs["phase_window_s"]["engine.wait"] == 2.0
    # every number of the registry that is one at both ends, by its name;
    # nothing for a flag, a label, or a name one end lacks
    assert {k for k in obs if k.startswith("counter.")} == {
        "counter.attn_pages_read", "counter.attn_pages_dense",
        "counter.kv_block_utilization"}
    assert obs["counter.kv_block_utilization"] == -0.25


def test_a_program_without_the_record_reads_nothing():
    old = _Engine(steps=3)                  # an engine before ISSUE 26
    c = _counters(old)
    assert set(c) == {"steps", "seen", "matched", "occ", "backlog",
                      "compiles"}
    assert window_deltas(c, c) == {}
    assert _read({"steps": 5}) == {}
    bare = _Engine(phase_s={}, metrics=None)
    assert _read(dict(window_deltas(_counters(bare), _counters(bare)),
                      steps=5)) == {}
    # no first token and no step in the window: no mean, not a 0
    eng = _Engine(metrics=_Metrics(), phase_s={"engine.wait": 1.0})
    rec = _counters(eng)
    assert _read(dict(window_deltas(rec, rec), steps=0)) == {}
