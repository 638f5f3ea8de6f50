"""The process's stall witness (``util.misc.PauseMonitor``): what a stall
is called from its record alone (``stall_cause``, case by case), who held
the interpreter (``stall_holder``), what each source's text parses to,
and the monitor at work — a thread holding the GIL beside a running
engine, a child process frozen by ``SIGSTOP``, an idle engine, two engines
sharing the one monitor a process has, and the counters' way into the
benchmark's observations.

A loaded CPU stalls on its own: what is asserted about stalls bounds them
from below. Only the idle case bounds from above, and only by what its
own idleness could add.
"""

import ctypes
import gc
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import pytest

from hadoop_tpu.metrics import metrics_system
from hadoop_tpu.metrics.prom import render_prom
from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.decoder import init_params
from hadoop_tpu.serving import engine as engine_mod
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams
from hadoop_tpu.serving.metrics import ServingMetrics
from hadoop_tpu.tracing.tracer import global_tracer
from hadoop_tpu.util import misc
from hadoop_tpu.util.misc import (CAUSES, PauseMonitor, stall_cause,
                                  stall_holder)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------- cause, by table

def _rec(seconds=2.0, **gains):
    """A stall of the engine's monitor (tick 0.1 s; a waiter for the GIL
    asks for it every 5 ms: 400 times in 2 s) in which nothing moved but
    ``gains``, on a host whose CPUs sat idle."""
    quiet = {"cpu_s": 0.0, "gc_s": 0.0, "suspended_s": 0.0,
             "throttled_s": 0.0, "monitor_run_delay_s": 0.0,
             "engine_run_delay_s": 0.0, "steal_s": 0.0,
             "cpu_some_s": 0.0, "cpu_full_s": 0.0, "memory_some_s": 0.0,
             "memory_full_s": 0.0, "io_some_s": 0.0, "io_full_s": 0.0,
             "major_faults": 0, "blkio_s": 0.0, "monitor_switches": 0,
             "monitor_preemptions": 0, "host_idle_s": seconds,
             "host_iowait_s": 0.0, "host_user_s": 0.0, "host_system_s": 0.0}
    return {"start": 100.0, "seconds": seconds, "interval_s": 0.1,
            "switch_interval_s": 0.005, **quiet, **gains}


@pytest.mark.parametrize("cause, gains", [
    ("suspended", {"suspended_s": 1.9}),
    ("gc", {"gc_s": 1.2, "cpu_s": 2.0, "monitor_switches": 390}),
    ("throttled", {"throttled_s": 1.5, "monitor_run_delay_s": 1.5}),
    ("cpu_starved", {"monitor_run_delay_s": 1.1}),
    ("cpu_starved", {"engine_run_delay_s": 1.0}),
    ("cpu_starved", {"steal_s": 1.4}),
    ("cpu_starved", {"host_idle_s": 0.1, "host_system_s": 1.9}),
    # every CPU busy, but the monitor asked all along: it was not starved
    ("gil", {"host_idle_s": 0.0, "host_user_s": 2.0, "cpu_s": 20.0,
             "monitor_switches": 380}),
    ("memory", {"memory_full_s": 1.0}),
    ("memory", {"memory_some_s": 1.6, "major_faults": 12}),
    ("io", {"io_full_s": 1.3}),
    ("io", {"io_some_s": 1.3, "blkio_s": 0.4}),
    ("gil", {"monitor_switches": 380}),
    ("gil", {"cpu_s": 1.95, "monitor_switches": 30}),
    ("frozen", {"monitor_switches": 1}),
    ("frozen", {"monitor_switches": 0, "cpu_s": 0.05}),
    ("unknown", {"monitor_switches": 50, "cpu_s": 0.3}),
    ("unknown", {"monitor_switches": 0, "memory_some_s": 1.6, "cpu_s": 0.2}),
    ("unknown", {"io_some_s": 1.9, "cpu_some_s": 1.9,
                 "monitor_run_delay_s": 0.3}),
])
def test_cause_is_the_first_rule_that_holds(cause, gains):
    assert cause in CAUSES
    assert stall_cause(_rec(**gains)) == cause


def test_every_cause_has_a_case_and_its_counter():
    m = ServingMetrics("serving.test.pause-causes")
    assert set(m.process_stalled_by_cause) == set(CAUSES)
    # the rules are tried in CAUSES' order: an earlier cause's evidence
    # wins over that of every later one
    evidence = [("suspended", "suspended_s"), ("gc", "gc_s"),
                ("throttled", "throttled_s"), ("cpu_starved", "steal_s"),
                ("memory", "memory_full_s"), ("io", "io_full_s"),
                ("gil", "cpu_s")]
    assert [c for c, _ in evidence] == list(CAUSES[:7])
    for i, (cause, _) in enumerate(evidence):
        assert stall_cause(_rec(**{k: 2.0 for _, k in evidence[i:]})) \
            == cause


@pytest.mark.parametrize("polls, cause", [
    (0, "frozen"),          # of 50 that a waiter makes in 0.25 s: it never
    (2, "frozen"),          # woke
    (5, "unknown"),
    (12, "unknown"),
    (13, "gil"),            # a quarter of them: it asked all along
    (50, "gil"),
])
def test_the_monitors_own_polling_tells_held_from_frozen(polls, cause):
    assert stall_cause(_rec(seconds=0.25, monitor_switches=polls)) == cause
    # at another switch interval the same polls mean something else
    slow = _rec(seconds=0.25, monitor_switches=polls,
                switch_interval_s=0.05)     # 5 expected
    assert stall_cause(slow) == ("gil" if polls >= 2 else "frozen")


def test_half_of_the_stall_is_most_of_it():
    assert stall_cause(_rec(gc_s=1.0)) == "gc"
    assert stall_cause(_rec(gc_s=0.99, monitor_switches=300)) == "gil"


@pytest.mark.parametrize("absent, cause", [
    # no per-thread rusage: nothing says frozen, CPU still says gil
    (("monitor_switches", "monitor_preemptions"), "unknown"),
    # no schedstat: frozen rests on the process's CPU time alone
    (("monitor_run_delay_s", "engine_run_delay_s"), "frozen"),
    # a host with no cgroup file, no /proc/pressure, no /proc/stat
    (("throttled_s", "steal_s", "memory_some_s", "memory_full_s",
      "io_some_s", "io_full_s", "cpu_some_s", "cpu_full_s", "host_idle_s",
      "host_iowait_s", "host_user_s", "host_system_s"), "frozen"),
    (("suspended_s", "gc_s", "major_faults", "blkio_s"), "frozen"),
])
def test_an_absent_source_supports_no_rule_and_breaks_none(absent, cause):
    rec = _rec(monitor_switches=1)
    for k in absent:
        del rec[k]
    assert stall_cause(rec) == cause
    ran = dict(rec, cpu_s=1.5)
    assert stall_cause(ran) == "gil"


def test_cause_reads_the_record_and_nothing_else():
    rec = _rec(monitor_switches=350, cpu_s=0.01)
    before = json.dumps(rec, sort_keys=True)
    assert stall_cause(rec) == stall_cause(json.loads(before)) == "gil"
    assert json.dumps(rec, sort_keys=True) == before


# ------------------------------------------------------ holder, from dumps

_STACKS = '''Thread 0x00007f0000000111 [helper] (most recent call first):
  File "{here}", line {line} in hold_the_gil
  File "/usr/lib/python3.12/threading.py", line 1012 in run

Thread 0x00007f0000000222 [pause-monitor] (most recent call first):
  File "/repo/hadoop_tpu/util/misc.py", line 444 in _run
  File "/usr/lib/python3.12/threading.py", line 1012 in run

Thread 0x00007f0000000333 [MainThread] (most recent call first):
  File "{here}", line {nap} in _nap
  File "/repo/main.py", line 3 in <module>

Thread 0x00007f0000000444 [http] (most recent call first):
  File "/usr/lib/python3.12/selectors.py", line 415 in select
  File "/usr/lib/python3.12/socketserver.py", line 235 in serve_forever

Thread 0x00007f0000000555 [decode-engine] (most recent call first):
  File "/repo/hadoop_tpu/serving/engine.py", line 1740 in _deliver
'''


def _nap():
    time.sleep(0.0)     # the line stall_holder reads for thread …333


def _stacks():
    nap = _nap.__code__.co_firstlineno + 1
    return _STACKS.format(here=__file__, line=1, nap=nap)


def test_holder_is_the_thread_the_stacks_do_not_show_waiting():
    # the monitor's own thread is known; the main thread is in time.sleep
    # (by its source line), one waits in selectors.py; the engine's thread
    # is known to have polled for the interpreter
    here = os.path.basename(__file__)
    assert stall_holder(_stacks(), waiting=[0x7f0000000222,
                                            0x7f0000000555]) \
        == f"hold_the_gil ({here}:1)"
    # not known: it stands beside the holder
    assert stall_holder(_stacks(), waiting=[0x7f0000000222]) == (
        f"hold_the_gil ({here}:1) | _deliver (engine.py:1740)")


def test_holder_of_no_stack_is_none():
    assert stall_holder("") is None
    assert stall_holder("Thread 0x1 [x] (most recent call first):\n\n") \
        is None


def test_stacks_are_written_as_the_holder_reads_them():
    seen = threading.Event()
    done = threading.Event()

    def parked_here():
        seen.set()
        done.wait(30)
    t = threading.Thread(target=parked_here, name="a-parked-thread")
    t.start()
    seen.wait(30)
    try:
        text = misc.format_stacks(sys._current_frames())
    finally:
        done.set()
        t.join()
    assert f"Thread 0x{t.ident:016x} [a-parked-thread] (most recent call " \
        "first):" in text
    assert "in parked_here" in text and "in wait" in text
    # this thread runs; the other waits in threading.py and is struck out
    assert stall_holder(text) is not None
    assert "parked_here" not in stall_holder(text)
    assert "test_stacks_are_written" in stall_holder(text)


# ------------------------------------------------ what each source parses to

@pytest.mark.parametrize("parse, raw, want", [
    (misc._schedstat("engine"), b"614951 57355 12\n",
     {"engine_cpu_s": 614951e-9, "engine_run_delay_s": 57355e-9,
      "engine_slices": 12}),
    (misc._cpu_stat, b"usage_usec 10\nuser_usec 6\nnr_periods 4\n"
     b"nr_throttled 2\nthrottled_usec 2500000\n", {"throttled_s": 2.5}),
    (misc._cpu_stat, b"nr_periods 4\nnr_throttled 2\n"
     b"throttled_time 1500000000\n", {"throttled_s": 1.5}),
    (misc._pressure("io"),
     b"some avg10=0.00 avg60=0.18 avg300=0.89 total=6547965\n"
     b"full avg10=0.00 avg60=0.15 avg300=0.88 total=6416514\n",
     {"io_some_s": 6.547965, "io_full_s": 6.416514}),
    (misc._pressure("cpu"), b"some avg10=0.63 avg60=0.86 avg300=0.80 "
     b"total=5527081\n", {"cpu_some_s": 5.527081}),
    (misc._self_stat, b"4242 (a (b) c) S " + b" ".join(
        str(n).encode() for n in range(4, 53)) + b"\n",
     {"major_faults": 12, "blkio_s": 42 / os.sysconf("SC_CLK_TCK")}),
])
def test_a_source_parses_to_seconds_under_the_records_names(parse, raw,
                                                            want):
    got = parse(raw)
    assert got == pytest.approx(want) and set(got) == set(want)


def test_the_hosts_cpu_seconds_are_the_mean_of_one_cpu():
    n = os.cpu_count() or 1
    tck = os.sysconf("SC_CLK_TCK")
    # cpu user nice system idle iowait irq softirq steal guest guest_nice
    raw = ("cpu  " + " ".join(str(v * n) for v in (
        100, 20, 30, 4000, 50, 6, 4, 300, 0, 0)) + "\ncpu0 1 2 3\n").encode()
    assert misc._host_stat(raw) == pytest.approx({
        "host_user_s": 120 / tck, "host_system_s": 40 / tck,
        "host_idle_s": 4000 / tck, "host_iowait_s": 50 / tck,
        "steal_s": 300 / tck})


def test_a_source_that_reads_and_never_moves_is_an_absent_source(monkeypatch):
    """The chip's sandbox (PERF.md §6, PR 37): ``/proc/stat`` reads all
    zeros and no thread is shown a context switch. Neither may say "every
    CPU was busy" or "the monitor never woke"."""
    dead = {"host_user_s": 0.0, "host_system_s": 0.0, "host_idle_s": 0.0,
            "host_iowait_s": 0.0, "steal_s": 0.0, "cpu_s": 0.01}
    misc._drop_dead_host(dead, 3.4)
    assert dead == {"cpu_s": 0.01}
    live = {"host_user_s": 0.2, "host_system_s": 0.1, "host_idle_s": 3.0,
            "host_iowait_s": 0.0, "steal_s": 0.05, "cpu_s": 0.01}
    kept = dict(live)
    misc._drop_dead_host(kept, 3.4)
    assert kept == live
    mon = PauseMonitor()
    assert mon._read()["monitor_switches"] >= 1     # this kernel counts
    monkeypatch.setattr(misc.resource, "getrusage",
                        lambda who: type("R", (), {"ru_nvcsw": 0,
                                                   "ru_nivcsw": 0}))
    assert not {"monitor_switches", "monitor_preemptions"} & set(mon._read())
    # and with both gone a process that burned nothing reads unknown
    rec = _rec(seconds=3.4)
    for k in ("monitor_switches", "monitor_preemptions", *misc._HOST_KEYS):
        del rec[k]
    assert stall_cause(rec) == "unknown"


def test_a_root_cgroup_without_throttling_is_an_absent_source(tmp_path):
    f = tmp_path / "cpu.stat"
    f.write_bytes(b"usage_usec 10\nuser_usec 6\nsystem_usec 4\n")
    assert misc._open(str(f), misc._cpu_stat) is None
    assert misc._open(str(tmp_path / "nowhere"), misc._cpu_stat) is None
    f.write_bytes(b"throttled_usec 7\n")
    src = misc._open(str(f), misc._cpu_stat)
    assert src.read() == {"throttled_s": 7e-6}
    f.write_bytes(b"throttled_usec 9\n")        # read anew each tick
    assert src.read() == {"throttled_s": 9e-6}
    src.close()


# ------------------------------------------------------ the monitor at work

@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("tiny")
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _engine(tiny_model, **kw):
    params, cfg = tiny_model
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 128)
    return DecodeEngine(params, cfg, **kw)


def _stall_spans():
    return [s for s in global_tracer().finished if s.name == "process.stall"]


def _wait_for(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.02)


HOLD_S = 1.0        # three periods of the engine's watchdog and a bit


def _hold_the_gil():
    # a C call that burns no CPU and releases nothing: PyDLL keeps the GIL
    ctypes.PyDLL(None).usleep(int(HOLD_S * 1e6))


def test_a_gil_holder_beside_a_running_engine_is_named(tiny_model, caplog):
    m = ServingMetrics("serving.test.pause-gil")
    eng = _engine(tiny_model, metrics=m)
    eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=3))  # compiled
    spans = len(_stall_spans())
    eng.start()

    def named(r):
        return r["seconds"] >= 0.8 * HOLD_S and r["cause"] == "gil" \
            and "_hold_the_gil" in (r.get("holder") or "")
    try:
        mon = PauseMonitor._process
        assert mon is not None and mon._thread.is_alive()
        assert mon.interval_s == engine_mod.STALL_TICK_S
        # (the loop one step ahead of its read-back compiles nothing new,
        # but the first request through it is not the one to stall)
        eng.submit([9, 8, 7], SamplingParams(max_new_tokens=4)).wait(120)
        snap = m.snapshot()
        _, iterated, _ = m.iteration_hist.buckets()
        held = 0
        with caplog.at_level(logging.WARNING, logger=misc.log.name):
            # the stacks are read at the thaw, under the GIL: the holder
            # stands where it held only if the monitor woke before it ran
            # on, about two times in three — so up to six holds
            while not any(map(named, list(mon.pauses))) and held < 6:
                req = eng.submit([1, 2, 3, 4],
                                 SamplingParams(max_new_tokens=110))
                _wait_for(lambda: len(req.out_tokens) >= 3)
                assert not req.done.is_set()
                helper = threading.Thread(target=_hold_the_gil,
                                          name="helper")
                helper.start()
                helper.join()
                held += 1
                req.wait(120)
                _wait_for(lambda: sum(
                    r["seconds"] >= 0.8 * HOLD_S and r["cause"] == "gil"
                    for r in list(mon.pauses)) >= held)
        stalls = [r for r in list(mon.pauses)
                  if r["seconds"] >= 0.8 * HOLD_S and r["cause"] == "gil"]
        rec = next(filter(named, stalls))
    finally:
        eng.stop()
    # every hold was seen, measured and called by its cause
    assert len(stalls) >= held
    for r in stalls:
        assert r["seconds"] < HOLD_S + 1.0
        assert r["cpu_s"] < 0.5 * r["seconds"]      # nothing burned: held
        # the monitor asked for the interpreter all through it
        assert r["monitor_switches"] >= 0.25 * r["seconds"] \
            / r["switch_interval_s"]
    # the record: who held it, where the loop was, every thread's stack
    assert "_hold_the_gil (test_pause_monitor.py:" in rec["holder"]
    assert "in _hold_the_gil" in rec["stacks"] \
        and "[decode-engine]" in rec["stacks"]
    assert len(rec["stacks"]) <= PauseMonitor.STACK_BYTES
    # (a thread that lost the interpreter BETWEEN two phases was in none)
    where = rec.get("phase")
    assert where is None or where in engine_mod.PHASES
    # the counters, against the engine's own histogram of iterations: the
    # iterations that the stalls fell in are at least as long, to a tick
    now = m.snapshot()
    total = sum(r["seconds"] for r in stalls)
    stalled = now["process_stalled_seconds"] - snap["process_stalled_seconds"]
    assert stalled >= total
    assert now["process_stalls"] - snap["process_stalls"] >= held
    assert now["process_stalled_seconds_gil"] \
        - snap["process_stalled_seconds_gil"] >= total
    _, iterated1, _ = m.iteration_hist.buckets()
    assert iterated1 - iterated >= total - held * engine_mod.STALL_TICK_S
    assert now["process_tick_oversleep_seconds_count"] \
        > snap["process_tick_oversleep_seconds_count"]
    # one span a stall in the tracer's ring, one warning in the log
    mine = [s for s in _stall_spans()[spans:]
            if s.kv["start"] == f"{rec['start']:.6f}"]
    assert len(mine) == 1 and mine[0].parent_id is None
    assert mine[0].kv["cause"] == "gil" \
        and "_hold_the_gil" in mine[0].kv["holder"]
    assert mine[0].end - mine[0].start == pytest.approx(rec["seconds"])
    assert float(mine[0].kv["seconds"]) == pytest.approx(rec["seconds"])
    assert mine[0].kv.get("phase") == where and "stacks" not in mine[0].kv
    lines = [r.getMessage() for r in caplog.records
             if "Detected pause" in r.getMessage()]
    assert sum("cause=gil" in ln for ln in lines) >= held
    lines = [ln for ln in lines if "_hold_the_gil" in ln]
    assert len(lines) == 1
    assert (f" in {where}" if where else "s") \
        + ": cause=gil holder=_hold_the_gil" in lines[0]
    assert f"polls={rec['monitor_switches']}" in lines[0]
    # and on /prom, as one family by cause
    text = render_prom(metrics_system())
    assert ('htpu_serving_engine_process_stalled_seconds_total{source='
            '"serving.test.pause-gil",cause="gil"}') in text
    assert 'htpu_process_tick_oversleep_seconds_bucket{source=' \
        '"serving.test.pause-gil"' in text


_CHILD = r'''
import json, sys, time
from hadoop_tpu.util.misc import PauseMonitor
mon = PauseMonitor(threshold_s=0.2, interval_s=0.1)
mon.start()
time.sleep(0.3)
print("ready", flush=True)
sys.stdin.readline()
time.sleep(0.3)         # the tick after the stall publishes it
mon.stop()
print(json.dumps(list(mon.pauses)), flush=True)
'''


def test_a_process_stopped_by_a_signal_reads_frozen():
    frozen_s, times = 1.5, 3
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD], cwd=ROOT, text=True,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        env=dict(os.environ, PYTHONPATH=ROOT))
    try:
        assert child.stdout.readline().strip() == "ready"
        for _ in range(times):
            os.kill(child.pid, signal.SIGSTOP)
            time.sleep(frozen_s)
            os.kill(child.pid, signal.SIGCONT)
            time.sleep(0.5)
        out, _ = child.communicate("go\n", timeout=60)
    finally:
        child.kill()
    pauses = [r for r in json.loads(out.strip().splitlines()[-1])
              if r["seconds"] >= 0.8 * frozen_s]
    brief = [{k: v for k, v in r.items() if k != "stacks"}
             for r in pauses]
    assert len(pauses) == times, brief
    # on a loaded CPU a thawed thread may wait for a core (run-delay over
    # 5% of the stall reads "unknown" or "cpu_starved"): one of three
    frozen = [r for r in pauses if r["cause"] == "frozen"]
    assert frozen, brief
    for rec in frozen:
        # the monitor's thread did not wake once to ask for anything
        assert rec["monitor_switches"] < 0.05 * rec["seconds"] \
            / rec["switch_interval_s"]
        assert rec["cpu_s"] < 0.05 * rec["seconds"]
        assert "holder" not in rec and "phase" not in rec
    assert all(r["cause"] != "gil" and r["cpu_s"] < 0.5 * r["seconds"]
               for r in pauses), brief


_DEEP = r'''
import sys, threading, time
from hadoop_tpu.util.misc import PauseMonitor
sys.setrecursionlimit(5000)
def rec(n):
    return 1 if n == 0 else rec(n - 1) + 1
stop = []
def deep():
    n = 50
    while not stop:
        rec(n)
        n = 50 + (n * 7) % 850
threads = [threading.Thread(target=deep) for _ in range(2)]
[t.start() for t in threads]
mon = PauseMonitor(threshold_s=0.0005, interval_s=0.002)   # late at once
mon.start()
time.sleep(2.5)
mon.stop()
stop.append(1)
[t.join() for t in threads]
print("stalls", len(mon.pauses), flush=True)
'''


def test_late_ticks_beside_threads_deep_in_python_do_not_kill_the_process():
    """What the first design of this monitor did on the chip (PERF.md §6,
    PR 37): ``faulthandler``'s watchdog read, without the GIL, the frames
    of threads that were popping them, and the process died of SIGSEGV in
    14 of 15 runs. The stacks are read under the GIL now, whatever the
    other threads do and however often the tick is late."""
    p = subprocess.run([sys.executable, "-c", _DEEP], cwd=ROOT, text=True,
                       capture_output=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=ROOT))
    assert p.returncode == 0, (p.returncode, p.stderr[-500:])
    assert int(p.stdout.split()[-1]) >= 1


def test_an_idle_engine_is_not_a_stalled_process(tiny_model):
    m = ServingMetrics("serving.test.pause-idle")
    eng = _engine(tiny_model, metrics=m)
    eng.start()
    try:
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4)).wait(120)
        snap = m.snapshot()
        waited = eng.phase_s.get("engine.wait", 0.0)
        time.sleep(0.6)
        eng.submit([4, 5, 6], SamplingParams(max_new_tokens=4)).wait(120)
        now = m.snapshot()
    finally:
        eng.stop()
    assert eng.phase_s["engine.wait"] - waited >= 0.5
    # the monitor ticked through the idleness and called none of it a
    # stall (what a loaded CPU adds on its own is far from 0.6 s)
    assert now["process_tick_oversleep_seconds_count"] \
        - snap["process_tick_oversleep_seconds_count"] >= 3
    assert now["process_stalled_seconds"] \
        - snap["process_stalled_seconds"] < 0.3


def test_two_engines_share_one_monitor_and_the_last_stop_ends_it(tiny_model):
    m = ServingMetrics("serving.test.pause-shared")
    a, b = _engine(tiny_model, metrics=m), _engine(tiny_model, metrics=m)
    a.start()
    mon = PauseMonitor._process
    thread = mon._thread
    b.start()
    try:
        assert PauseMonitor._process is mon and mon._thread is thread
        assert len(mon._watched) >= 2
        assert sum(t.name == "pause-monitor"
                   for t in threading.enumerate()) == 1
        ticks = m.snapshot()["process_tick_oversleep_seconds_count"]
        _wait_for(lambda: m.snapshot()[
            "process_tick_oversleep_seconds_count"] >= ticks + 5)
        t0 = time.monotonic()
        n0 = m.snapshot()["process_tick_oversleep_seconds_count"]
        time.sleep(0.5)
        n1 = m.snapshot()["process_tick_oversleep_seconds_count"]
        # two sinks over one registry are fed as one: a tick counts once
        assert n1 - n0 <= (time.monotonic() - t0) / mon.interval_s + 1
        a.stop()
        assert PauseMonitor._process is mon and thread.is_alive()
        assert id(a) not in mon._watched and id(b) in mon._watched
    finally:
        a.stop()
        b.stop()
    if not mon._watched:        # no other test's engine is watched
        assert PauseMonitor._process is None
        assert not thread.is_alive()
        assert mon._on_gc not in gc.callbacks


def test_under_a_profiler_session_a_stall_is_a_marker_on_the_host_plane(
        tmp_path):
    """One zero-length event at detection, on the monitor's own line, with
    the cause and the seconds; no event spans a tick (it would cover
    every idle gap of the device whole)."""
    import glob

    from jax.profiler import ProfileData
    mon = PauseMonitor(threshold_s=0.2, interval_s=0.1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        mon.start()
        time.sleep(0.35)        # on-time ticks: no marker
        helper = threading.Thread(target=_hold_the_gil)
        helper.start()
        helper.join()
        _wait_for(lambda: any(r["seconds"] >= 0.8 * HOLD_S
                              for r in list(mon.pauses)))
    finally:
        mon.stop()
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    marks = [(line.name, ev) for line in host.lines for ev in line.events
             if ev.name == "process.stall"]
    stalls = [r for r in mon.pauses]
    assert 1 <= len(marks) == len(stalls)
    assert len({name for name, _ in marks}) == 1        # one thread's line
    stats = [dict(ev.stats) for _, ev in marks]
    assert any(st["cause"] == "gil" and float(st["seconds"]) >= 0.8 * HOLD_S
               for st in stats)
    assert all(ev.duration_ns < 1e6 for _, ev in marks)     # a marker


def test_the_ring_of_records_is_bounded():
    mon = PauseMonitor(threshold_s=0.2, interval_s=0.1, ring=4)
    for i in range(10):
        mon._publish({"start": float(i), "seconds": 0.3, "cause": "unknown"},
                     {}, [])
    assert len(mon.pauses) == 4
    assert [r["start"] for r in mon.pauses] == [6.0, 7.0, 8.0, 9.0]


def test_a_tick_feeds_the_counters_a_slow_run_would_show_in():
    m = ServingMetrics("serving.test.pause-tick")
    before = m.snapshot()
    m.process_tick(0.004, {"gc_s": 0.25, "engine_run_delay_s": 0.5,
                           "throttled_s": 0.125, "major_faults": 3,
                           "steal_s": 0.0625, "cpu_some_s": 1.0,
                           "memory_some_s": 2.0, "io_some_s": 4.0,
                           "cpu_s": 9.0, "monitor_slices": 7})
    m.process_tick(-0.0001, {})     # a source that is absent adds nothing
    now = m.snapshot()
    gained = {k: now[k] - before[k] for k in now
              if isinstance(now[k], (int, float)) and now[k] != before[k]}
    assert gained == pytest.approx({
        "process_gc_seconds": 0.25, "engine_thread_run_delay_seconds": 0.5,
        "process_cpu_throttled_seconds": 0.125, "process_major_faults": 3,
        "host_steal_seconds": 0.0625, "process_pressure_seconds_cpu": 1.0,
        "process_pressure_seconds_memory": 2.0,
        "process_pressure_seconds_io": 4.0,
        "process_tick_oversleep_seconds_count": 2,
        "process_tick_oversleep_seconds_sum": 0.004,
        "process_tick_oversleep_seconds_mean": 0.002})
    text = render_prom(metrics_system())
    assert ('htpu_serving_engine_process_pressure_seconds_total{source='
            '"serving.test.pause-tick",resource="io"} 4.0') in text


def test_window_deltas_carry_the_stalled_seconds_to_the_benchmark():
    from chipbench.serve_cell import window_deltas
    m = ServingMetrics("serving.test.pause-window")
    m.process_stall({"seconds": 9.0, "cause": "gc"})    # before the window

    def numbers():
        return {"snapshot": {k: v for k, v in m.snapshot().items()
                             if isinstance(v, (int, float))
                             and not isinstance(v, bool)}}
    opened = numbers()
    m.process_stall({"seconds": 2.125, "cause": "gil"})
    m.process_stall({"seconds": 0.25, "cause": "unknown"})
    obs = window_deltas(opened, numbers())
    assert obs["counter.process_stalled_seconds"] == pytest.approx(2.375)
    assert obs["counter.process_stalls"] == 2
    assert obs["counter.process_stalled_seconds_gil"] == pytest.approx(2.125)
    assert obs["counter.process_stalled_seconds_gc"] == 0
    # the metric file's reading of it: exact milliseconds
    with open(os.path.join(ROOT, "chipbench", "metrics",
                           "process.stalled_ms.json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "value",
                    "of": "counter.process_stalled_seconds", "scale": 1000.0}
    assert obs[spec["of"]] * spec["scale"] == pytest.approx(2375.0)
    sound = window_deltas(numbers(), numbers())
    assert sound[spec["of"]] * spec["scale"] == 0
