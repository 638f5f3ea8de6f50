"""Small shared utilities.

Ref analogs: util/Daemon.java (daemon threads), util/StopWatch.java,
util/JvmPauseMonitor.java:47 (here: the process's stall witness — wall-clock
drift of a sleeper thread, and what the OS, the collector and the thread's own
scheduler saw of each stall), NetUtils (ephemeral port helpers).
"""

from __future__ import annotations

import collections
import gc
import linecache
import logging
import os
import re
import resource
import socket
import sys
import threading
import time
from typing import Callable, Deque, Dict, Iterable, List, Optional

from hadoop_tpu.tracing.tracer import global_tracer

log = logging.getLogger(__name__)


class Daemon(threading.Thread):
    """Named daemon thread. Ref: util/Daemon.java."""

    def __init__(self, target: Callable, name: str, args=(), kwargs=None):
        super().__init__(target=target, name=name, args=args,
                         kwargs=kwargs or {}, daemon=True)


def parse_addr_list(spec):
    """Parse a comma-separated ``host:port`` list into [(host, port)].
    Raises on a missing/non-numeric port instead of silently mis-splitting
    (ref: NetUtils.createSocketAddr's strict parsing)."""
    out = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        host, sep, port = part.rpartition(":")
        if not sep or not port.isdigit():
            raise ValueError(f"invalid host:port {part!r} in {spec!r}")
        out.append((host or "127.0.0.1", int(port)))
    return out


def free_port(host: str = "127.0.0.1") -> int:
    """Ephemeral port for minicluster daemons (ref: MiniDFSCluster port=0 use)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


class StopWatch:
    def __init__(self, start: bool = True):
        self._t0 = time.monotonic() if start else None
        self._elapsed = 0.0

    def start(self) -> "StopWatch":
        self._t0 = time.monotonic()
        return self

    def stop(self) -> float:
        if self._t0 is not None:
            self._elapsed += time.monotonic() - self._t0
            self._t0 = None
        return self._elapsed

    def elapsed(self) -> float:
        if self._t0 is not None:
            return self._elapsed + (time.monotonic() - self._t0)
        return self._elapsed


# ------------------------------------------------------------ the witness
#
# What a stall of the process leaves behind. Every source is a cumulative
# number (seconds, or a count) that PauseMonitor reads each tick; a source
# this machine lacks is left out of the sample and of the record, never 0.

# the causes a stall can be given, in the order ``stall_cause`` tries them
CAUSES = ("suspended", "gc", "throttled", "cpu_starved", "memory", "io",
          "gil", "frozen", "unknown")

_CLK_TCK = os.sysconf("SC_CLK_TCK")


class _Source:
    """One small /proc or cgroup file, opened once and read anew from its
    start each tick (procfs makes the text at the read)."""

    __slots__ = ("fd", "parse")

    def __init__(self, path: str, parse: Callable[[bytes], Dict[str, float]]):
        self.fd = os.open(path, os.O_RDONLY)
        self.parse = parse
        try:
            self.read()
        except (OSError, ValueError, IndexError) as e:
            os.close(self.fd)
            raise OSError(f"{path} does not read as expected: {e}") from e

    def read(self) -> Dict[str, float]:
        return self.parse(os.pread(self.fd, 4096, 0))

    def close(self) -> None:
        os.close(self.fd)


def _schedstat(who: str) -> Callable[[bytes], Dict[str, float]]:
    def parse(raw: bytes) -> Dict[str, float]:
        # ns on a CPU, ns runnable with no CPU, times it was given one
        cpu, delay, slices = raw.split()[:3]
        return {who + "_cpu_s": int(cpu) / 1e9,
                who + "_run_delay_s": int(delay) / 1e9,
                who + "_slices": int(slices)}
    return parse


def _cpu_stat(raw: bytes) -> Dict[str, float]:
    for line in raw.splitlines():
        key, _, val = line.partition(b" ")
        if key == b"throttled_usec":        # cgroup v2
            return {"throttled_s": int(val) / 1e6}
        if key == b"throttled_time":        # cgroup v1: ns
            return {"throttled_s": int(val) / 1e9}
    raise ValueError("no throttled time")


def _pressure(resource: str) -> Callable[[bytes], Dict[str, float]]:
    def parse(raw: bytes) -> Dict[str, float]:
        # "some avg10=… total=<us>" and, but for old kernels' cpu, "full …"
        return {f"{resource}_{line.split(b' ', 1)[0].decode()}_s":
                int(line.rsplit(b"total=", 1)[1]) / 1e6
                for line in raw.splitlines()}
    return parse


def _self_stat(raw: bytes) -> Dict[str, float]:
    # after "pid (comm) ", index n - 3 holds field n of proc(5)
    f = raw[raw.rindex(b")") + 2:].split()
    return {"major_faults": int(f[9]), "blkio_s": int(f[39]) / _CLK_TCK}


def _host_stat(raw: bytes) -> Dict[str, float]:
    # "cpu user nice system idle iowait irq softirq steal …" in ticks,
    # summed over the host's CPUs: kept as the mean of one CPU
    f = [int(x) / _CLK_TCK / (os.cpu_count() or 1)
         for x in raw.split(b"\n", 1)[0].split()[1:9]]
    return {"host_user_s": f[0] + f[1], "host_system_s": f[2] + f[5] + f[6],
            "host_idle_s": f[3], "host_iowait_s": f[4], "steal_s": f[7]}


_HOST_KEYS = ("host_user_s", "host_system_s", "host_idle_s", "host_iowait_s",
              "steal_s")


def _drop_dead_host(gains: Dict[str, float], elapsed_s: float) -> None:
    """A ``/proc/stat`` that does not account for the time that passed (a
    sandbox's stand-in reads all zeros) is an absent source, not an idle
    or a busy host: its keys leave ``gains``."""
    if sum(gains.get(k, 0.0) for k in _HOST_KEYS) < 0.5 * elapsed_s:
        for k in _HOST_KEYS:
            gains.pop(k, None)


def _cpu_stat_paths() -> List[str]:
    """Where this process's CPU controller keeps ``cpu.stat``: its own
    group (a host with no cgroup namespace), then the mount's root (where
    a container sees its own group); cgroup v2, then v1."""
    own = {}
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                _, ctrl, path = line.rstrip("\n").split(":", 2)
                own[ctrl] = path.strip("/")
    except (OSError, ValueError):
        pass
    base = "/sys/fs/cgroup"
    paths = []
    for ctrl, mount in (("", base), ("cpu", f"{base}/cpu"),
                        ("cpu,cpuacct", f"{base}/cpu,cpuacct")):
        if ctrl in own:
            paths += [f"{mount}/{own[ctrl]}/cpu.stat", f"{mount}/cpu.stat"]
    return paths


def _open(path: str, parse) -> Optional[_Source]:
    try:
        return _Source(path, parse)
    except OSError:
        return None


def stall_cause(rec: Dict) -> str:
    """What froze the process, from a stall's record alone; the first rule
    that holds, "most" being half of the stall's seconds or more. A key
    the record lacks (a source the machine lacks) supports no rule."""
    seconds = rec["seconds"]

    def most(*keys: str) -> bool:
        return any(rec.get(k) is not None and rec[k] >= 0.5 * seconds
                   for k in keys)

    def under(share: float, *keys: str) -> bool:
        return all(rec.get(k, 0.0) < share * seconds for k in keys)

    # a thread that waits for the interpreter wakes once a switch
    # interval to ask for it: the monitor's own voluntary switches say
    # whether it polled through the stall or never woke in it
    polls = rec.get("monitor_switches")
    expected = seconds / rec["switch_interval_s"]
    polled = polls is not None and polls >= 0.25 * expected
    if most("suspended_s"):
        return "suspended"      # the boot-time clock ran ahead
    if most("gc_s"):
        return "gc"             # inside full collections
    if most("throttled_s"):
        return "throttled"      # the cgroup's CPU quota
    if most("monitor_run_delay_s", "engine_run_delay_s", "steal_s") or \
            ("host_idle_s" in rec and not polled
             and under(0.1, "host_idle_s", "host_iowait_s")):
        return "cpu_starved"    # runnable and given no CPU: the kernel
        # says so, or no CPU of the host sat idle and the monitor did
        # not get to ask for the interpreter either
    if most("memory_full_s") or \
            (most("memory_some_s") and rec.get("major_faults")):
        return "memory"
    if most("io_full_s") or (most("io_some_s") and rec.get("blkio_s")):
        return "io"
    if polled or most("cpu_s"):
        return "gil"            # the process ran; the interpreter was held
    if polls is not None and polls < 0.05 * expected and "cpu_s" in rec \
            and under(0.05, "cpu_s", "monitor_run_delay_s",
                      "engine_run_delay_s"):
        return "frozen"         # nothing of the process ran or asked to
    return "unknown"


# Where a thread that waits is found: (file, function) of its innermost
# Python frame. A thread whose blocking call is C (``time.sleep``, a
# socket's ``recv``) shows its caller's frame and is told from a holder
# by the source line, where that calls ``sleep(``.
_PARKED = {("threading.py", "wait"), ("threading.py", "acquire"),
           ("threading.py", "join"), ("threading.py", "_wait_for_tstate_lock"),
           ("selectors.py", "select"), ("socket.py", "accept"),
           ("socket.py", "readinto"), ("queue.py", "get"),
           ("queue.py", "put"), ("thread.py", "_worker"),
           ("subprocess.py", "_try_wait"), ("connection.py", "_recv"),
           ("connection.py", "poll"), ("ssl.py", "read")}
_SLEEPS = re.compile(r"(?<![\w])sleep\(")
_STACK_THREAD = re.compile(
    r"^Thread (0x[0-9a-f]+)[^\n]*\n"
    r"(?:  File \"([^\"]*)\", line (\d+) in ([^\n]*))?", re.M)


def format_stacks(frames: Dict[int, object], depth: int = 12) -> str:
    """Every thread's stack, innermost frame first, as ``faulthandler``
    writes them; ``frames`` is ``sys._current_frames()``."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in frames.items():
        out.append(f"Thread 0x{ident:016x} [{names.get(ident, '?')}] "
                   "(most recent call first):")
        for _ in range(depth):
            if frame is None:
                break
            out.append(f'  File "{frame.f_code.co_filename}", line '
                       f"{frame.f_lineno} in {frame.f_code.co_name}")
            frame = frame.f_back
        out.append("")
    return "\n".join(out)


def stall_holder(stacks: str, waiting: Iterable[int] = ()) -> Optional[str]:
    """The innermost frame of each thread that ``stacks``
    (``format_stacks``) does not show waiting, as ``function
    (file:line)``, joined by " | " (at most three). ``waiting`` are
    idents of threads known to have waited (the monitor's own; a watched
    thread that the scheduler saw polling for the interpreter). The
    stacks are taken at the thaw, under the GIL — reading another
    thread's frames without it is not safe (PERF.md §6, PR 37) — so the
    holder stands where it was only if the monitor woke before it ran
    on; and a thread parked in a C call nobody listed here may stand
    beside it, which is why the record keeps the stacks' text."""
    waiting = set(waiting)
    found = []
    for ident, path, line, func in _STACK_THREAD.findall(stacks):
        if int(ident, 16) in waiting or not path:
            continue
        base = os.path.basename(path)
        if (base, func) in _PARKED or \
                _SLEEPS.search(linecache.getline(path, int(line))):
            continue
        found.append(f"{func} ({base}:{line})")
    return " | ".join(found[:3]) or None


class _Watched:
    """A thread that feeds the device, as the monitor sees it."""

    __slots__ = ("thread", "phases", "then", "sink", "sched", "now",
                 "gains")

    def __init__(self, thread, phases, sink):
        self.thread = thread
        self.phases = phases if phases is not None else {}
        self.then = dict(self.phases)       # as of the last tick
        self.sink = sink
        self.sched = None if thread is None or thread.native_id is None \
            else _open(f"/proc/self/task/{thread.native_id}/schedstat",
                       _schedstat("engine"))
        self.now = self.read()
        self.gains: Dict[str, float] = {}

    def read(self) -> Dict[str, float]:
        try:
            return self.sched.read() if self.sched else {}
        except (OSError, ValueError):
            return {}       # the thread has ended


class PauseMonitor:
    """The process's stall witness. Ref: util/JvmPauseMonitor.java:47 —
    the same detection (a thread sleeps ``interval_s`` and measures how
    late it wakes), and beside it what the reference's daemons get from
    the JVM's collector beans: what the OS, the collector and the
    scheduler saw of the stall.

    Every tick the monitor reads, each only where the machine has it:
    process CPU time; ``CLOCK_BOOTTIME`` against ``monotonic`` (a
    suspended machine); its own thread's context switches
    (``getrusage(RUSAGE_THREAD)``: a thread waiting for the GIL wakes
    once a switch interval to ask for it, a frozen one never wakes —
    this tells an interpreter that was held from a process that was
    stopped); ``schedstat`` of its own thread and of each watched
    thread (run-delay: runnable with no CPU — a thread waiting for the
    GIL is blocked, not runnable); the cgroup's ``throttled`` time;
    ``/proc/pressure``; major faults and block-I/O delay; the host's
    CPU seconds by kind and its steal; seconds inside full collections
    (one ``gc.callbacks`` hook that returns at once for the young
    generations).

    An oversleep over ``threshold_s`` is a stall. Its record (a dict, the
    newest ``ring`` of them in ``pauses``) holds ``start`` and
    ``seconds`` on ``time.monotonic()``, each source's gain across it,
    ``stacks`` (every Python thread's stack the moment the monitor woke,
    cut at 8 KB), ``phase`` (of a watched loop's ``phases`` dict, read
    either side), ``holder`` (``stall_holder``) and ``cause``
    (``stall_cause``). It goes out as one log line, one finished root
    span ``process.stall`` in ``global_tracer()``, one zero-length
    ``process.stall`` annotation on the profiler's host plane (where
    ``jax`` is loaded; no annotation spans a tick, it would cover every
    idle gap whole), and to the sinks.

    No watchdog reads the threads' stacks DURING a stall:
    ``faulthandler.dump_traceback_later`` does it without the GIL, and
    on a thread that runs (a tick is late in set-up, while the loop's
    thread traces a program) it reads frames that are being popped and
    their memory unmapped — it killed 14 of 15 runs of the chat cell on
    the chip (PERF.md §6, PR 37).

    A sink is what a watcher hands ``watch``: ``process_tick(oversleep_s,
    gains)`` every tick and ``process_stall(record)`` a stall, with a
    ``registry`` — two sinks over one registry are fed as one.
    ``watch_process`` / ``unwatch_process`` share ONE monitor among a
    process's watchers: the first starts it, the last stops it."""

    STACK_BYTES = 8192

    def __init__(self, threshold_s: float = 1.0, interval_s: float = 0.5,
                 ring: int = 64):
        self.threshold_s = threshold_s
        self.interval_s = interval_s
        self.pauses: Deque[Dict] = collections.deque(maxlen=ring)
        self._watched: Dict[int, _Watched] = {}     # guarded-by: _lock
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._sources: List[_Source] = []
        # (seconds inside full collections so far, start of the one that
        # runs): one tuple, so that a reader sees both or neither
        self._gc: tuple = (0.0, None)

    # ------------------------------------------------- one a process

    _process: Optional["PauseMonitor"] = None
    _process_lock = threading.Lock()

    @classmethod
    def watch_process(cls, owner, thread, phases, sink, *,
                      threshold_s: float, interval_s: float) -> None:
        """``owner``'s loop thread joins the process's monitor, which is
        started (with these settings) if there is none."""
        with cls._process_lock:
            if cls._process is None:
                cls._process = cls(threshold_s, interval_s)
                cls._process.start()
            cls._process.watch(owner, thread, phases, sink)

    @classmethod
    def unwatch_process(cls, owner) -> None:
        with cls._process_lock:
            mon = cls._process
            if mon is not None and mon.unwatch(owner) == 0:
                cls._process = None
                mon.stop()

    def watch(self, owner, thread=None, phases=None, sink=None) -> None:
        w = _Watched(thread, phases, sink)
        self.unwatch(owner)
        with self._lock:
            self._watched[id(owner)] = w

    def unwatch(self, owner) -> int:
        """Returns how many are still watched."""
        # the file is closed under the lock, as the tick reads under it
        with self._lock:
            w = self._watched.pop(id(owner), None)
            if w is not None and w.sched:
                w.sched.close()
            return len(self._watched)

    # ----------------------------------------------------- the thread

    def start(self) -> None:
        self._stop.clear()
        self._thread = Daemon(self._run, "pause-monitor")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=2 * self.interval_s + 1)
            self._thread = None

    def _on_gc(self, phase: str, info: Dict) -> None:
        # on whichever thread collects, at every collection: the young
        # generations return here
        if info["generation"] != 2:
            return
        total, began = self._gc
        if phase == "start":
            self._gc = (total, time.monotonic())
        elif began is not None:
            self._gc = (total + time.monotonic() - began, None)

    def _read(self) -> Dict[str, float]:
        # a collection's "stop" hook hands the GIL over before it has
        # added anything: what runs is counted up to now
        total, began = self._gc
        now = {"cpu_s": time.process_time(), "gc_s": total if began is None
               else total + time.monotonic() - began}
        own = resource.getrusage(resource.RUSAGE_THREAD)   # this thread's
        if own.ru_nvcsw:
            # (a thread that sleeps every tick has switched; a kernel that
            # shows none keeps no count — the chip's sandbox does not)
            now["monitor_switches"] = own.ru_nvcsw
            now["monitor_preemptions"] = own.ru_nivcsw
        try:
            now["suspended_s"] = time.clock_gettime(time.CLOCK_BOOTTIME) \
                - time.monotonic()
        except (AttributeError, OSError):
            pass
        for src in self._sources:
            try:
                now.update(src.read())
            except (OSError, ValueError, IndexError):
                pass
        return now

    def _run(self) -> None:
        # (thread-self: opened here, by the thread it is to mean)
        found = [_open("/proc/thread-self/schedstat", _schedstat("monitor")),
                 _open("/proc/self/stat", _self_stat),
                 _open("/proc/stat", _host_stat),
                 *(_open(f"/proc/pressure/{r}", _pressure(r))
                   for r in ("cpu", "memory", "io")),
                 next(filter(None, (_open(p, _cpu_stat)
                                    for p in _cpu_stat_paths())), None)]
        self._sources = [src for src in found if src is not None]
        gc.callbacks.append(self._on_gc)
        try:
            prev, pending = self._read(), None
            while True:
                t0 = time.monotonic()
                stopped = self._stop.wait(self.interval_s)
                over = time.monotonic() - t0 - self.interval_s
                # first of all, before another thread runs on: where
                # every thread stands at the thaw
                stacks = format_stacks(sys._current_frames()) \
                    if over > self.threshold_s else ""
                now = self._read()
                with self._lock:
                    watched = list(self._watched.values())
                    for w in watched:
                        then, w.now = w.now, w.read()
                        w.gains = {k: v - then[k] for k, v in w.now.items()
                                   if k in then}
                if pending is not None:
                    self._publish(*pending, watched)
                    pending = None
                if stopped:
                    return
                gains = {k: v - prev[k] for k, v in now.items() if k in prev}
                _drop_dead_host(gains, self.interval_s + over)
                self._feed(over, gains, watched)
                if over > self.threshold_s:
                    rec = self._record(t0 + self.interval_s, over, gains,
                                       watched, stacks)
                    self._mark(rec)
                    # published a tick later, with the phase it fell in
                    pending = (rec, {id(w): w.then for w in watched})
                for w in watched:
                    w.then = dict(w.phases)
                prev = now
        finally:
            gc.callbacks.remove(self._on_gc)
            for src in self._sources:
                src.close()
            self._sources = []

    def _feed(self, over: float, gains: Dict[str, float],
              watched: List[_Watched]) -> None:
        groups: Dict[int, list] = {}
        for w in watched:
            if w.sink is not None:
                g = groups.setdefault(id(w.sink.registry), [w.sink, {}])
                for k, v in w.gains.items():
                    g[1][k] = g[1].get(k, 0.0) + v
        for sink, threads in groups.values():
            sink.process_tick(over, {**gains, **threads})

    def _record(self, start: float, seconds: float, gains: Dict[str, float],
                watched: List[_Watched], stacks: str) -> Dict:
        rec = {"start": start, "seconds": seconds,
               "interval_s": self.interval_s,
               "switch_interval_s": sys.getswitchinterval(), **gains}
        for w in watched:       # of several loops' threads, the most
            for k, v in w.gains.items():
                rec[k] = max(rec.get(k, v), v)
        rec["stacks"] = stacks[:self.STACK_BYTES]
        rec["cause"] = stall_cause(rec)
        if rec["cause"] == "gil":
            # a watched thread the scheduler saw poll for the interpreter
            # waited for it (a waiter wakes every switch interval, 5 ms:
            # 200 times a second; half of that is asked for)
            polled = [w.thread.ident for w in watched
                      if w.thread is not None
                      and w.gains.get("engine_slices", 0) >= 100 * seconds
                      and w.gains.get("engine_cpu_s", 0.0) < 0.5 * seconds]
            rec["holder"] = stall_holder(
                stacks, [threading.get_ident(), *polled])
        return rec

    @staticmethod
    def _mark(rec: Dict) -> None:
        """The stall on the profiler's host plane, at the end of the idle
        gap it made (a no-op outside a session; a process that has not
        loaded jax is not made to)."""
        if "jax" in sys.modules:
            from jax.profiler import TraceAnnotation
            with TraceAnnotation("process.stall", cause=rec["cause"],
                                 seconds=rec["seconds"]):
                pass

    def _publish(self, rec: Dict, phases: Dict[int, Dict[str, float]],
                 watched: List[_Watched]) -> None:
        """One tick after the stall was seen: by now the phase it fell in
        has ended and added its seconds to the loop's own record."""
        seconds = rec["seconds"]
        best = 0.5 * seconds
        for w in watched:
            then = phases.get(id(w))
            if then is None:
                continue        # watched since: nothing read before it
            for name, total in dict(w.phases).items():
                gain = total - then.get(name, 0.0)
                if gain >= best:
                    rec["phase"], best = name, gain
        self.pauses.append(rec)

        def s(key: str) -> str:
            return f"{rec[key]:.2f}s" if key in rec else "n/a"
        log.warning(
            "Detected pause of ~%.2fs%s: cause=%s holder=%s cpu=%s "
            "run_delay=%s gc=%s throttled=%s polls=%s", seconds,
            f" in {rec['phase']}" if "phase" in rec else "", rec["cause"],
            rec.get("holder"), s("cpu_s"),
            s("engine_run_delay_s" if "engine_run_delay_s" in rec
              else "monitor_run_delay_s"),
            s("gc_s"), s("throttled_s"), rec.get("monitor_switches", "n/a"))
        with global_tracer().span("process.stall") as span:
            # written after the fact, over the stall's own interval
            span.start = time.time() - (time.monotonic() - rec["start"])
            for k, v in rec.items():
                if k != "stacks" and v is not None:
                    span.add_kv(k, f"{v:.6f}" if isinstance(v, float)
                                else str(v))
            span.finish(end=span.start + seconds)
        for sink in {id(w.sink.registry): w.sink for w in watched
                     if w.sink is not None}.values():
            sink.process_stall(rec)


# Shared retry randomness: one process-wide generator so tests can seed
# it (misc.RETRY_RNG.seed(0)) and get deterministic delay sequences
# without monkeypatching every retry site.
import random as _random  # noqa: E402 — grouped with its consumer

RETRY_RNG = _random.Random()


def backoff_delay(base_s: float, attempt: int, max_s: float = 30.0,
                  rng=None) -> float:
    """Exponential backoff with full-range jitter (ref:
    io/retry/RetryPolicies.exponentialBackoffRetry — delay doubles per
    attempt, then is scaled by a random factor in [0.5, 1.5) so a fleet
    of clients never retries in lockstep)."""
    rng = RETRY_RNG if rng is None else rng
    return min(max_s, base_s * (2 ** attempt)) * (0.5 + rng.random())


class RetryOnException:
    """Bounded retry helper for idempotent host-side calls; delays grow
    exponentially with jitter (util.misc.backoff_delay)."""

    def __init__(self, attempts: int = 3, delay_s: float = 0.1, backoff: float = 2.0,
                 retryable=(OSError, ConnectionError), max_delay_s: float = 30.0):
        self.attempts = attempts
        self.delay_s = delay_s
        self.backoff = backoff
        self.retryable = retryable
        self.max_delay_s = max_delay_s

    def call(self, fn: Callable, *args, **kwargs):
        for i in range(self.attempts):
            try:
                return fn(*args, **kwargs)
            except self.retryable:
                if i == self.attempts - 1:
                    raise
                # honor the caller's growth factor (backoff=1.0 means
                # constant-with-jitter) — same jitter law as backoff_delay
                delay = min(self.max_delay_s,
                            self.delay_s * (self.backoff ** i))
                time.sleep(delay * (0.5 + RETRY_RNG.random()))


def local_host_names() -> set:
    """Names/addresses that mean "this host" — shared by the short-circuit
    read lane and the local shuffle fetch lane (ref: the reference's
    DomainSocketFactory.getPathInfo locality check)."""
    import socket as _socket
    names = {"127.0.0.1", "localhost", "::1"}
    try:
        hn = _socket.gethostname()
        names.add(hn)
        names.add(_socket.gethostbyname(hn))
    except OSError:
        pass
    return names


def check_dir(path: str, min_free_bytes: int = 0) -> None:
    """Health-check a storage directory: exists (created if needed),
    writable, readable, and above the free-space floor — raising
    DiskErrorException-style OSError otherwise (ref: util/DiskChecker
    .java checkDir + the DN's startup/failed-volume policy)."""
    import os
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        raise OSError(f"cannot create storage dir {path}: {e}") from e
    if not os.access(path, os.W_OK):
        raise OSError(f"storage dir {path} is not writable")
    if not os.access(path, os.R_OK):
        raise OSError(f"storage dir {path} is not readable")
    probe = os.path.join(path, ".disk-check")
    try:
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
    except OSError as e:
        raise OSError(f"storage dir {path} failed write probe: {e}") from e
    if min_free_bytes:
        st = os.statvfs(path)
        free = st.f_bavail * st.f_frsize
        if free < min_free_bytes:
            raise OSError(f"storage dir {path} below free-space floor: "
                          f"{free} < {min_free_bytes}")
