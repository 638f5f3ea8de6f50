"""The trace -> metrics reduction on a small trace recorded on the v5e
(``record_trace.py`` says how)."""

import json
import os

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return json.load(f)


def test_recorded_trace_is_from_a_tpu(recorded):
    assert recorded["device"].startswith("TPU")
    assert ["/device:TPU:0", "XLA Ops"] in recorded["lines_seen"]


def test_busy_union_and_window(recorded):
    ops = [e for e in recorded["events"]
           if e["plane"] == "/device:TPU:0"]
    red = trace.reduce(recorded["events"])
    start = min(e["start_ns"] for e in ops)
    end = max(e["start_ns"] + e["dur_ns"] for e in ops)
    assert red["window_s"] == pytest.approx((end - start) / 1e9)
    # the union is no longer than the window and no longer than the sum
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] <= sum(e["dur_ns"] for e in ops) / 1e9
    # a `while` spans its body on the same line: the union must not
    # count it twice, so busy is well under the plain sum
    assert any(e["name"].startswith("%while") for e in ops)
    assert red["busy_s"] < 0.75 * sum(e["dur_ns"] for e in ops) / 1e9


def test_top_ops_are_leaves_with_short_names(recorded):
    red = trace.reduce(recorded["events"])
    names = [n for n, _ in red["device_ops"]]
    assert names and len(names) <= trace.TOP
    assert not any(n.startswith("while") for n in names)
    assert all(" = " not in n and len(n) <= trace.NAME_MAX for n in names)
    assert any(n.startswith("fusion") and "bf16[1024,1024]" in n
               for n in names)
    secs = [s for _, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_idle_gaps_name_what_the_host_did(recorded):
    red = trace.reduce(recorded["events"])
    assert red["idle_gaps"] and len(red["idle_gaps"]) <= trace.TOP
    assert all(name.startswith("chip_0: ") and " after " in name
               for name, _ in red["idle_gaps"])
    idle = red["window_s"] - red["busy_s"]
    assert sum(s for _, s in red["idle_gaps"]) <= idle + 1e-9
    # the recording syncs with the host after every step
    assert any("no host span" not in n for n, _ in red["idle_gaps"])


def test_union_of_hand_made_intervals():
    def ev(s, d, name="%op.1 = f32[2]{0} add(f32[2] a)"):
        return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
                "start_ns": s, "dur_ns": d}
    events = [ev(0, 10), ev(10, 15), ev(30, 10),
              ev(30, 4, "%inner = f32[2]{0} mul(f32[2] a)"),
              {"plane": "/host:CPU", "line": "python3", "name": "sync",
               "start_ns": 16, "dur_ns": 12}]
    red = trace.reduce(events)
    assert red["busy_s"] == pytest.approx(35e-9)
    assert red["window_s"] == pytest.approx(40e-9)
    assert red["idle_gaps"] == [["chip_0: sync after op.1 f32[2]", 5e-9]]
    # op.1 at 30 holds `inner`, so only the other two op.1 and inner count
    assert dict(red["device_ops"]) == pytest.approx(
        {"op.1 f32[2]": 25e-9, "inner f32[2]": 4e-9})


def test_no_tpu_plane_reads_nothing_not_the_hosts_threads():
    assert trace.reduce([]) is None
    host = [{"plane": "/host:CPU", "line": "tf_XLAPjRtCpuClient/1",
             "name": "dot", "start_ns": 0, "dur_ns": 50},
            {"plane": "/host:CPU", "line": "python3", "name": "sync",
             "start_ns": 10, "dur_ns": 5}]
    assert trace.reduce(host) is None


class _Dev:
    def __init__(self, platform):
        self.platform = platform


def _tracer_with(events, monkeypatch, tmp_path):
    from chipbench import harness
    from chipbench import scopes
    monkeypatch.setattr(trace, "load_events", lambda d: events)
    monkeypatch.setattr(scopes, "load_dir", lambda d: events)
    t = harness.Tracer(True)
    t.dir = str(tmp_path / "trace")
    return t


@pytest.mark.parametrize("planes,chips", [(0, 1), (1, 4)])
def test_a_tpu_run_whose_trace_lacks_a_chip_prints_no_result(
        planes, chips, monkeypatch, tmp_path):
    events = [{"plane": f"/device:TPU:{i}", "line": "XLA Ops",
               "name": "%op = f32[2]{0} add(f32[2] a)", "start_ns": 0,
               "dur_ns": 10} for i in range(planes)]
    t = _tracer_with(events, monkeypatch, tmp_path)
    with pytest.raises(SystemExit):
        t.reduce([_Dev("tpu")] * chips)


def test_the_reduction_keeps_modules_and_scopes_for_the_readers(
        monkeypatch, tmp_path):
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "scopes_small.json")) as f:
        events = json.load(f)["events"]
    red = _tracer_with(events, monkeypatch, tmp_path).reduce([_Dev("tpu")])
    assert red["busy_s"] > 0 and red["device_ops"]
    assert {"train_step", "_step_impl"} <= set(red["scopes"]["modules"])
    assert red["scopes"]["scopes"]["attn"] > 0


def test_the_cpu_rehearsal_reads_no_trace_numbers(monkeypatch, tmp_path):
    host = [{"plane": "/host:CPU", "line": "tf_XLAPjRtCpuClient/1",
             "name": "dot", "start_ns": 0, "dur_ns": 50}]
    t = _tracer_with(host, monkeypatch, tmp_path)
    assert t.reduce([_Dev("cpu")]) is None


def test_the_profiler_covers_the_windows_last_seconds_only(monkeypatch,
                                                           tmp_path):
    import jax

    from chipbench import harness
    started = []
    monkeypatch.setattr(harness.tempfile, "mkdtemp",
                        lambda prefix: str(tmp_path / prefix))
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: started.append(d))
    t = harness.Tracer(True)
    t.due(50.0)
    t.due(harness.TRACE_SECONDS + 0.5)
    assert not started and t.dir is None
    t.due(harness.TRACE_SECONDS)
    t.due(3.0)
    assert len(started) == 1
    off = harness.Tracer(False)
    off.due(1.0)
    assert len(started) == 1 and off.dir is None
