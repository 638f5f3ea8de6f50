"""The engine loop's own record: the phases that tile one scheduler
iteration (``tracing.tracer.phase`` → ``engine.phase_s``, the profiler's
host plane), the histogram of iterations in which a stall between two
steps shows (a stall of the loop alone, as here, or of the whole process:
the second kind has its own witness, ``tests/test_pause_monitor.py``),
and the request's TTFT timeline (submitted → admitted → first chunk →
first token).

Timing assertions are loose on purpose: a CPU timing must not make the
suite unsteady. What is exact is arithmetic: the three TTFT stages sum
to the TTFT the engine already records, request by request.
"""

import glob
import os
import time

import jax
import pytest

from hadoop_tpu.metrics import metrics_system
from hadoop_tpu.metrics.prom import render_prom
from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.decoder import init_params
from hadoop_tpu.serving.engine import PHASES, DecodeEngine, SamplingParams
from hadoop_tpu.serving.metrics import ServingMetrics
from hadoop_tpu.tracing.tracer import Tracer, phase


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("tiny")
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _engine(tiny_model, **kw):
    params, cfg = tiny_model
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 64)
    return DecodeEngine(params, cfg, **kw)


def _drive(eng, reqs):
    while not all(r.done.is_set() for r in reqs):
        eng.step()


def _stages(req):
    return (req.admitted_at - req.submitted_at,
            req.first_chunk_at - req.admitted_at,
            req.first_token_at - req.first_chunk_at)


def _sum_and_count(hist):
    _, total, n = hist.buckets()
    return total, n


def _over(hist, secs):
    """How many observations lie above the bucket bound ``secs``."""
    buckets, _, n = hist.buckets()
    return n - dict(buckets)[secs]


def test_phase_helper_adds_up():
    sink = {}
    for secs in (0.01, 0.03):
        with phase("p", sink):
            time.sleep(secs)
    with pytest.raises(KeyError):
        with phase("q", sink):
            raise KeyError("the phase is closed on the way out")
    assert set(sink) == {"p", "q"}
    assert 0.04 <= sink["p"] < 1.0 and 0.0 <= sink["q"] < 0.04


def test_ttft_stages_sum_to_ttft_for_every_request_of_a_burst(tiny_model):
    """Open loop on the scheduler thread: six requests arrive at a
    two-lane engine, so some wait for a lane and some for the one
    prefill lane."""
    m = ServingMetrics("serving.engine.phases-burst")
    tracer = Tracer("t")
    eng = _engine(tiny_model, metrics=m, tracer=tracer)
    eng.start()
    try:
        reqs = []
        for i in range(6):
            reqs.append(eng.submit([1 + i, 2, 3, 4, 5, 6, 7] * (1 + i % 3),
                                   SamplingParams(max_new_tokens=6)))
            time.sleep(0.01)
        for r in reqs:
            r.wait(120)
    finally:
        eng.stop()
    total = 0.0
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.first_chunk_at \
            <= r.first_token_at
        ttft = r.first_token_at - r.submitted_at
        assert sum(_stages(r)) == pytest.approx(ttft, abs=1e-9)
        total += ttft
    # the histograms' sums are the window sums a benchmark reads
    sums = {stage: _sum_and_count(m.ttft_stage_hist[stage])
            for stage in ("queue", "prefill_wait", "prefill")}
    assert all(n == 6 for _, n in sums.values())
    assert sum(secs for secs, _ in sums.values()) \
        == pytest.approx(total, abs=1e-6)
    assert _sum_and_count(m.ttft_hist) == (pytest.approx(total), 6)
    # lanes were scarce: someone queued
    assert sums["queue"][0] > 0 and sums["prefill"][0] > 0
    # the marker spans carry the request's own intervals
    admits = [s for s in tracer.finished if s.name == "serving.admit"]
    firsts = [s for s in tracer.finished if s.name == "serving.first_token"]
    assert len(admits) == len(firsts) == 6
    by_id = {str(r.id): r for r in reqs}
    for s in admits:
        r = by_id[s.kv["request"]]
        assert float(s.kv["queue_wait_s"]) == pytest.approx(
            _stages(r)[0], abs=1e-5)
    for s in firsts:
        r = by_id[s.kv["request"]]
        assert float(s.kv["prefill_wait_s"]) == pytest.approx(
            _stages(r)[1], abs=1e-5)
        assert float(s.kv["prefill_service_s"]) == pytest.approx(
            _stages(r)[2], abs=1e-5)
        assert float(s.kv["ttft_s"]) == pytest.approx(
            sum(_stages(r)), abs=1e-5)


def test_a_request_preempted_before_its_first_token_keeps_its_stamps(
        tiny_model):
    m = ServingMetrics("serving.engine.phases-preempt")
    eng = _engine(tiny_model, prefill_chunk=4, metrics=m)
    req = eng.submit(list(range(1, 14)), SamplingParams(max_new_tokens=4))
    eng.step()                      # admitted, first of four chunks
    assert req.first_token_at is None and req._prefill_pos == 4
    stamps = (req.admitted_at, req.first_chunk_at)
    assert None not in stamps
    eng._preempt(req)
    _drive(eng, [req])
    assert req.preemptions == 1 and len(req.out_tokens) == 4
    assert (req.admitted_at, req.first_chunk_at) == stamps
    assert sum(_stages(req)) == pytest.approx(
        req.first_token_at - req.submitted_at, abs=1e-9)
    # the time lost to the preemption is inside the prefill stage
    assert _sum_and_count(m.ttft_stage_hist["prefill"]) == (
        pytest.approx(_stages(req)[2]), 1)


@pytest.mark.parametrize("spec_k", [0, 2])
def test_phases_tile_the_loop_under_exactly_their_names(tiny_model, spec_k):
    """On the scheduler thread every phase shows, ``engine.propose``
    only when speculating; totals never fall."""
    eng = _engine(tiny_model, speculate_k=spec_k)
    eng.start()
    try:
        seen = []
        for i in range(3):
            eng.submit([1, 2, 3, 1, 2, 3, 1, 2 + i],
                       SamplingParams(max_new_tokens=8)).wait(120)
            seen.append(dict(eng.phase_s))
            time.sleep(0.06)        # the loop parks in engine.wait
    finally:
        eng.stop()
    seen.append(dict(eng.phase_s))
    want = set(PHASES) if spec_k else set(PHASES) - {"engine.propose"}
    assert set(eng.phase_s) == want
    for a, b in zip(seen, seen[1:]):
        assert all(b[k] >= v for k, v in a.items())
    assert eng.phase_s["engine.wait"] >= 0.1


def test_phases_account_for_a_generate_call(tiny_model):
    eng = _engine(tiny_model)
    eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=2))  # compiles
    before = sum(eng.phase_s.values())
    t0 = time.monotonic()
    eng.generate([[1, 2, 3, 4, 5, 6, 7], [3, 4, 5], [9, 8, 7, 6]],
                 SamplingParams(max_new_tokens=24))
    wall = time.monotonic() - t0
    spent = sum(eng.phase_s.values()) - before
    assert "engine.wait" not in eng.phase_s     # nobody parked: no thread
    assert 0.5 * wall < spent <= wall


def test_a_stall_between_two_steps_shows_with_its_phase(tiny_model,
                                                        monkeypatch):
    m = ServingMetrics("serving.engine.phases-stall")
    eng = _engine(tiny_model, metrics=m)
    eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=3))
    # warm: both shapes and the event scatters are compiled. An iteration
    # in which a step shape compiled is not in the histogram at all
    total, logged = _sum_and_count(m.iteration_hist)
    assert logged < eng.steps - 1
    steps, before = eng.steps, dict(eng.phase_s)
    slow = [_over(h, 0.256) for h in (m.iteration_hist, m.decode_step_hist)]
    calls = []
    publish = eng._publish_metrics

    def stalling():
        calls.append(eng.steps)
        if len(calls) == 5:
            time.sleep(0.3)
        publish()

    monkeypatch.setattr(eng, "_publish_metrics", stalling)
    eng.generate([[1, 2, 3, 4]], SamplingParams(max_new_tokens=12))
    total1, logged1 = _sum_and_count(m.iteration_hist)
    assert logged1 - logged == eng.steps - steps
    assert total1 - total >= 0.3
    # one iteration lies above the 0.256 s bound, and the phase whose
    # seconds jumped with it names where
    assert _over(m.iteration_hist, 0.256) - slow[0] == 1
    spent = {k: v - before[k] for k, v in eng.phase_s.items()}
    assert max(spent, key=spent.get) == "engine.publish"
    assert spent["engine.publish"] >= 0.3
    # decode_step's own interval (dispatch → delivery) cannot see it.
    # Neither does the process's stall witness, and rightly: the loop's
    # thread slept and the process ran on. A freeze of the WHOLE process
    # shows in iteration_seconds as this did, and since PR 37 also, in
    # exact seconds and with what froze it, in process_stalled_seconds
    # (util.misc.PauseMonitor; tests/test_pause_monitor.py)
    assert _over(m.decode_step_hist, 0.256) - slow[1] == 0


def test_waiting_for_work_is_not_a_stall(tiny_model):
    m = ServingMetrics("serving.engine.phases-idle")
    eng = _engine(tiny_model, metrics=m)
    eng.start()
    try:
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4)).wait(120)
        eng.submit([3, 2, 1], SamplingParams(max_new_tokens=4)).wait(120)
        slow = _over(m.iteration_hist, 0.256)   # a loaded CPU's own
        time.sleep(0.6)
        eng.submit([4, 5, 6], SamplingParams(max_new_tokens=4)).wait(120)
    finally:
        eng.stop()
    assert eng.phase_s["engine.wait"] >= 0.6
    assert _sum_and_count(m.iteration_hist)[1] >= 4
    assert _over(m.iteration_hist, 0.256) == slow


def test_phases_and_the_timeline_reach_prom(tiny_model):
    m = ServingMetrics("serving.engine.phases-prom")
    eng = _engine(tiny_model, metrics=m)
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_new_tokens=6))
    text = render_prom(metrics_system())
    mine = [ln for ln in text.splitlines() if "phases-prom" in ln]
    phases = {ln.split('phase="')[1].split('"')[0]: float(ln.split()[-1])
              for ln in mine
              if ln.startswith("htpu_serving_engine_phase_seconds_total")}
    assert set(phases) == set(PHASES)
    assert phases["engine.dispatch"] > 0 and phases["engine.wait"] == 0
    # the counters are fed from phase_s (publish lags by its own phase)
    assert phases["engine.dispatch"] == pytest.approx(
        eng.phase_s["engine.dispatch"])
    assert text.count(
        "# TYPE htpu_serving_engine_phase_seconds_total counter") == 1
    assert text.count(
        "# TYPE htpu_serving_engine_ttft_stage_seconds histogram") == 1
    for stage in ("queue", "prefill_wait", "prefill"):
        assert any(ln.startswith(
            "htpu_serving_engine_ttft_stage_seconds_count")
            and f'stage="{stage}"' in ln and ln.endswith(" 1")
            for ln in mine)
    count, = [ln for ln in mine
              if ln.startswith("htpu_iteration_seconds_count")]
    assert int(count.split()[-1]) == m.iteration_hist.buckets()[2] >= 1


def test_under_a_profiler_session_the_phases_are_host_events(tiny_model,
                                                             tmp_path):
    """Stage 2: the phases are on the profiler's clock, on the thread
    that feeds the device; request-lifetime spans never are (they would
    cover every idle gap whole and every gap would read alike)."""
    from jax.profiler import ProfileData
    tracer = Tracer("t")
    eng = _engine(tiny_model, tracer=tracer)
    eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=2))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracer.span("serving.request") as door:
            req = eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=5),
                             trace_ctx=door.context())
            _drive(eng, [req])
    finally:
        jax.profiler.stop_trace()
    assert any(s.name == "serving.request" for s in tracer.finished)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    by_line = {}
    for line in host.lines:
        for ev in line.events:
            by_line.setdefault(ev.name, set()).add(line.name)
    steps = eng.steps
    assert steps >= 5
    for name in ("engine.admit", "engine.pages", "engine.dispatch",
                 "engine.readback", "engine.deliver", "engine.publish"):
        assert name in by_line, sorted(k for k in by_line
                                       if k.startswith("engine"))
    # one thread feeds the device: all phases on one line
    assert len(set.union(*(by_line[n] for n in by_line
                           if n.startswith("engine.")))) == 1
    assert not any(n.startswith("serving.") for n in by_line)
