"""Distributed tracing: spans created client-side, propagated in RPC headers,
resumed server-side around handler execution.

Capability parity with the reference's HTrace-4 integration (ref:
hadoop-common/pom.xml:286-287; span creation hdfs/DFSClient.java:1563;
propagation ipc/Server.java:121-123 SpanId in RPC headers; runtime-configurable
receivers tracing/TracerConfigurationManager.java, TraceAdmin.java).

A Span carries (trace_id, span_id, parent_id, sampled); the active span lives
in a contextvar so nested ``with tracer.span(...)`` calls parent correctly
across threads spawned with the span-aware helpers below (``carry_context``
wraps a callable so the spawning thread's active span survives into the new
thread — the seam the async checkpoint writer and hedged-read pool ride).

Sampling is decided ONCE, at root-span creation, and the verdict travels in
``SpanContext`` across every wire hop — children (local or remote) inherit
it, so a trace is delivered all-or-nothing. (The seed flipped a coin per
*finished* span in ``_deliver``, which shredded every trace at
sample_rate < 1.0: each span of one trace was kept or dropped
independently.)

Receivers are callables fed finished spans; the in-memory list backs tests
and ``tracing.collector.SpanCollector`` is the production receiver behind
``/ws/v1/traces``.
"""

from __future__ import annotations

import contextvars
import logging
import random
import threading
import time
from typing import Callable, Dict, List, Optional

log = logging.getLogger(__name__)

_active: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "htpu_active_span", default=None)


class SpanContext:
    """Wire form of a span: what travels in RPC / data-transfer / HTTP
    headers. ``sampled`` is the root's sampling verdict — every hop
    honors it instead of re-rolling."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: int, span_id: int, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def to_wire(self) -> Dict[str, int]:
        return {"t": self.trace_id, "s": self.span_id,
                "sm": 1 if self.sampled else 0}

    @classmethod
    def from_wire(cls, d: Optional[Dict[str, int]]) -> Optional["SpanContext"]:
        if not d:
            return None
        # pre-sampled-bit peers omit "sm": treat as sampled (the old
        # behavior for a delivered context)
        return cls(d["t"], d["s"], bool(d.get("sm", 1)))

    def to_header(self) -> str:
        """Compact HTTP-header form (``X-Htpu-Trace``)."""
        return f"{self.trace_id:x}:{self.span_id:x}:{int(self.sampled)}"

    @classmethod
    def from_header(cls, h: Optional[str]) -> Optional["SpanContext"]:
        if not h:
            return None
        try:
            t, s, sm = h.split(":")
            return cls(int(t, 16), int(s, 16), sm != "0")
        except (ValueError, AttributeError):
            return None


class Span:
    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 parent_id: Optional[int], sampled: bool = True):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = random.getrandbits(63)
        self.parent_id = parent_id
        self.sampled = sampled
        self.start = time.time()
        self.end: Optional[float] = None
        self.annotations: List[str] = []
        self.kv: Dict[str, str] = {}
        self._token = None

    def annotate(self, msg: str) -> None:
        self.annotations.append(msg)

    def add_kv(self, k: str, v: str) -> None:
        self.kv[k] = v

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id, self.sampled)

    def duration_ms(self) -> float:
        return ((self.end if self.end is not None else time.time())
                - self.start) * 1e3

    def __enter__(self) -> "Span":
        self._token = _active.set(self)
        return self

    def __exit__(self, *exc) -> bool:
        self.finish()
        return False

    def finish(self, end: Optional[float] = None) -> None:
        """``end``: for a span written after the fact (a stall is known
        only once it is over), its end on ``time.time()``'s clock."""
        if self.end is None:
            self.end = time.time() if end is None else end
            if self._token is not None:
                _active.reset(self._token)
                self._token = None
            self.tracer._deliver(self)

    def to_dict(self) -> Dict:
        return {
            "name": self.name, "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "start": self.start, "end": self.end,
            "annotations": list(self.annotations), "kv": dict(self.kv),
        }


def parse_trace_id_candidates(raw: str) -> List[int]:
    """THE reading of a user-supplied trace id, shared by every query
    surface (per-daemon ``/ws/v1/traces?trace_id=``, the fleet
    doctor's ``/ws/v1/fleet/traces/<id>``): an explicit ``0x`` form is
    hex; an ambiguous all-digit string is tried as BOTH hex and
    decimal — span JSON prints ids decimal while the slow-trace log
    line and fleet endpoints print ``016x``, and either paste must
    resolve. Hex first (the printed fleet form); callers that filter
    by membership treat the result as a set. Empty list = unparseable."""
    raw = raw.strip().lower()
    base16 = raw[2:] if raw.startswith("0x") else raw
    bases = ((16, base16),) if raw.startswith("0x") \
        else ((16, base16), (10, raw))
    out: List[int] = []
    for base, s in bases:
        try:
            v = int(s, base)
        except ValueError:
            continue
        if v not in out:
            out.append(v)
    return out


def current_span() -> Optional[Span]:
    return _active.get()


class phase:
    """One phase of a loop that feeds the device: ``with phase(name,
    sink)`` adds the elapsed ``time.monotonic()`` seconds to
    ``sink[name]`` (a plain dict the caller owns). While a
    ``jax.profiler`` session runs, the phase is also an event on the
    calling thread's line of the profiler's host plane, so an idle gap
    of the device can be named by what the host was doing in it.

    Not a ``Span``: no ids, no sampling, no delivery, no ring — a phase
    happens ten times a step and belongs to no request. Request-lifetime
    spans never go to the profiler: they would cover every gap whole.
    ``jax.profiler`` is imported here, on first use by a thread that
    already drives JAX, so daemons that import ``tracing`` stay off it."""

    __slots__ = ("name", "sink", "_t0", "_ann")

    def __init__(self, name: str, sink: Dict[str, float]):
        self.name = name
        self.sink = sink

    def __enter__(self) -> "phase":
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.monotonic() - self._t0
        self._ann.__exit__(*exc)
        self.sink[self.name] = self.sink.get(self.name, 0.0) + dt
        return False


def current_context() -> Optional[SpanContext]:
    """Wire context of the active span, if any — what a client attaches
    to an outgoing RPC / data-transfer op / HTTP request."""
    sp = _active.get()
    return sp.context() if sp is not None else None


def carry_context(fn: Callable) -> Callable:
    """Span-aware thread seam: capture the CALLER's contextvars (incl.
    the active span) and run ``fn`` under them in whatever thread
    eventually calls the wrapper. Spans created inside the target
    thread then parent into the spawning trace instead of starting
    orphan roots — the helper behind the async checkpoint writer and
    the hedged-read pool (the async seams ISSUE 4 opened)."""
    ctx = contextvars.copy_context()

    def run(*args, **kwargs):
        return ctx.run(fn, *args, **kwargs)
    return run


class Tracer:
    """Per-process tracer with root-decided sampling and pluggable
    receivers."""

    def __init__(self, name: str = "htpu", sample_rate: float = 1.0,
                 rng: Optional[random.Random] = None):
        self.name = name
        self.sample_rate = sample_rate
        self._rng = rng or random
        self._receivers: List[Callable[[Span], None]] = []
        self._lock = threading.Lock()
        self.finished: List[Span] = []  # in-memory receiver (tests, /tracing)
        self._keep_in_memory = True
        self.max_kept = 1000

    def add_receiver(self, fn: Callable[[Span], None]) -> None:
        with self._lock:
            self._receivers.append(fn)

    def span(self, name: str, parent: Optional[SpanContext] = None) -> Span:
        """New span: child of ``parent`` (wire context), else of the active
        span, else a new trace root. Children inherit the root's sampling
        verdict; only a ROOT rolls the dice — so a trace is delivered
        all-or-nothing. Unsampled traces still produce Span objects
        (cheap) but aren't delivered."""
        cur = _active.get()
        if parent is not None:
            return Span(self, name, parent.trace_id, parent.span_id,
                        sampled=parent.sampled)
        if cur is not None:
            return Span(self, name, cur.trace_id, cur.span_id,
                        sampled=cur.sampled)
        sampled = (self.sample_rate >= 1.0 or
                   self._rng.random() < self.sample_rate)
        return Span(self, name, random.getrandbits(63), None,
                    sampled=sampled)

    def _deliver(self, span: Span) -> None:
        if not span.sampled:
            return
        with self._lock:
            if self._keep_in_memory:
                self.finished.append(span)
                if len(self.finished) > self.max_kept:
                    del self.finished[: len(self.finished) // 2]
            receivers = list(self._receivers)
        for r in receivers:
            try:
                r(span)
            except Exception as e:  # noqa: BLE001 — receiver is user code
                log.debug("span receiver %r failed: %s", r, e)

    def set_sample_rate(self, rate: float) -> None:
        """Runtime reconfiguration (ref: TracerConfigurationManager)."""
        self.sample_rate = rate


_global_tracer = Tracer()


def global_tracer() -> Tracer:
    return _global_tracer
