"""Driver of the ``open-loop`` and ``closed-loop`` kinds: requests through
the serving door (``ServingServer`` over HTTP, streaming) into
``DecodeEngine.submit``, wired as ``ServingReplica`` wires them — except
that the weights are made on the device from the seed, so no checkpoint
passes through the DFS in set-up.

Set-up: weights, engine, door; one short request per shared prefix (both
step shapes compile, the prefix cache holds the shared prompts); then the
pre-roll (open loop: ``preroll_s`` of the same traffic; closed loop: the
callers started ``stagger_s`` apart), so that the window opens on a
steady state. The window then runs ``--seconds``; every request due in it
counts, also when its first token comes after the close (the run waits).
"""

from __future__ import annotations

import ast
import gc
import http.client
import json
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from chipbench import compare, harness, traffic
from chipbench import weights as W
from chipbench.families import Served

FIRST_TOKEN_WAIT_S = 60.0
SAMPLE = 4               # requests compared with the reference
SAMPLE_FROM = 0.6        # ...drawn from those due in this part of the window


@dataclass
class Record:
    req: traffic.Request
    due_t: float
    sent_t: float = 0.0
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    error: Optional[str] = None


def _default(key: str):
    from hadoop_tpu.conf.registry import KEYS
    return ast.literal_eval(KEYS[key]["defaults"][0])


def build_replica(cell, params, cfg):
    """Engine and door as ``ServingReplica.__init__`` builds them. What
    the configuration file sets, it sets under the program's own conf
    keys (``harness.conf``); every other setting is the program's
    default, read from its generated conf registry, so that a PR that
    moves a default moves this benchmark with it."""
    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.serving.engine import DecodeEngine
    from hadoop_tpu.serving.metrics import ServingMetrics
    from hadoop_tpu.serving.server import ServingServer

    given = cell.harness["conf"]
    conf = Configuration()
    for k, v in given.items():
        conf.set(k, str(v))

    def val(key):
        return given.get(key, _default(key))

    metrics = ServingMetrics()
    queue = sched = None
    if val("serving.qos.enabled"):
        from hadoop_tpu.serving.qos import (DecayCostScheduler,
                                            FairAdmissionQueue)
        sched = DecayCostScheduler(val("serving.qos.levels"), conf)
        queue = FairAdmissionQueue(sched)
    engine = DecodeEngine(
        params, cfg,
        max_batch=val("serving.max.batch") or None,
        block_size=val("serving.kv.block.size"),
        num_blocks=val("serving.kv.num.blocks") or None,
        max_context=val("serving.max.context") or None,
        prefill_chunk=val("serving.prefill.chunk"),
        prefix_cache=val("serving.prefix_cache.enabled"),
        kv_host_bytes=val("serving.kv.host.bytes"),
        speculate_k=val("serving.speculate.k"),
        speculate_ngram=val("serving.speculate.ngram"),
        admission_queue=queue,
        hbm_bytes=val("serving.kv.hbm.bytes"),
        max_lanes=val("serving.max.lanes"),
        moe_capacity_factor=val("serving.moe.capacity.factor"),
        moe_shards=val("serving.moe.shards"),
        moe_a2a_codec=val("serving.moe.a2a.codec"),
        metrics=metrics)
    gate = None
    if queue is not None:
        from hadoop_tpu.serving.qos import QoSGate
        gate = QoSGate(conf, engine, metrics=metrics, scheduler=sched)
    server = ServingServer(engine, conf, bind=("127.0.0.1", 0), qos=gate)
    return engine, server


def _send(port: int, rec: Record) -> None:
    """One streamed request; token times on this client's clock."""
    rec.sent_t = time.monotonic()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        body = json.dumps({"tokens": rec.req.prompt, "stream": True,
                           "max_new_tokens": rec.req.max_new_tokens})
        conn.request("POST", "/v1/generate?user.name=bench", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec.error = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return
        for line in resp:
            if not line.strip():
                continue
            msg = json.loads(line)
            if "token" in msg:
                rec.times.append(time.monotonic())
                rec.tokens.append(int(msg["token"]))
            elif msg.get("done"):
                rec.error = msg.get("error")
                rec.done = rec.error is None
                return
            elif "error" in msg:
                rec.error = msg["error"]
                return
        rec.error = rec.error or "stream ended without a summary"
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


class Load:
    """The load generator: threads in the process that holds the chip."""

    def __init__(self, port: int):
        self.port = port
        self.records: List[Record] = []
        self.threads: List[threading.Thread] = []
        self.lock = threading.Lock()
        self.stop = threading.Event()

    def _spawn(self, target, *args) -> None:
        t = threading.Thread(target=target, args=args, daemon=True)
        self.threads.append(t)
        t.start()

    def fire(self, req: traffic.Request, due_t: float) -> Record:
        rec = Record(req, due_t)
        with self.lock:
            self.records.append(rec)
        _send(self.port, rec)
        return rec

    def open_loop(self, reqs: List[traffic.Request], t0: float) -> None:
        def dispatch():
            for r in reqs:
                wait = t0 + r.due_s - time.monotonic()
                if wait > 0 and self.stop.wait(wait):
                    return
                self._spawn(self.fire, r, t0 + r.due_s)
        self._spawn(dispatch)

    def closed_loop(self, stream: traffic.Stream, first, t0: float) -> None:
        def caller(req):
            wait = t0 + req.due_s - time.monotonic()
            if wait > 0 and self.stop.wait(wait):
                return
            due = t0 + req.due_s
            while not self.stop.is_set():
                self.fire(req, due)
                with self.lock:
                    req = stream.take()
                due = time.monotonic()
        for req in first:
            self._spawn(caller, req)


def _sleep_until(t: float) -> None:
    time.sleep(max(0.0, t - time.monotonic()))


# the engine's TTFT stages -> the observation each one's seconds go under
STAGES = {"queue": "queue_wait_s", "prefill_wait": "prefill_wait_s",
          "prefill": "prefill_service_s"}


def _counters(engine) -> dict:
    """The engine's record at one moment. Beside the six counts every
    engine has: seconds by phase of its loop, (sum, count) of each TTFT
    stage's histogram, the iteration histogram's cumulative counts by
    bound, and every number of its metrics registry by the registry's own
    name — each only where the program keeps it, so a parent without one
    reads nothing there and the metrics over it are left out."""
    c = {"steps": engine.steps, "seen": engine.prefix_tokens_seen,
         "matched": engine.prefix_tokens_matched,
         "occ": len(engine.occupancy_log),
         "backlog": engine.queue_depth + engine.num_prefilling,
         "compiles": engine.decode_compiles + engine.prefill_compiles}
    phase_s = getattr(engine, "phase_s", None)
    if phase_s is not None:
        c["phase_s"] = dict(phase_s)
    m = getattr(engine, "metrics", None)
    stages = getattr(m, "ttft_stage_hist", None)
    if stages is not None:
        c["stages"] = {s: h.buckets()[1:] for s, h in stages.items()}
    iterations = getattr(m, "iteration_hist", None)
    if iterations is not None:
        c["iterations"] = iterations.buckets()[0]
    snapshot = getattr(m, "snapshot", None)
    if snapshot is not None:
        c["snapshot"] = {k: v for k, v in snapshot().items()
                         if isinstance(v, (int, float))
                         and not isinstance(v, bool)}
    return c


def window_deltas(a: dict, b: dict) -> dict:
    """What the engine's record gained between the window's opening
    (``a``) and its close (``b``), under the names the metric files read.
    ``counter.<name>`` is the gain of the registry's ``<name>`` (a gauge's
    gain means little; a file reads the counters it knows)."""
    obs: dict = {}
    then, now = a.get("stages", {}), b.get("stages", {})
    for stage, key in STAGES.items():
        if stage in then and stage in now:
            obs[key] = now[stage][0] - then[stage][0]
    if "prefill" in then and "prefill" in now:
        obs["first_tokens"] = now["prefill"][1] - then["prefill"][1]
    then, now = a.get("phase_s"), b.get("phase_s")
    if then is not None and now:
        phases = {k: v - then.get(k, 0.0) for k, v in now.items()}
        obs["phase_window_s"] = phases
        obs["phase_busy_s"] = sum(v for k, v in phases.items()
                                  if k != "engine.wait")
        obs["phase_host_s"] = obs["phase_busy_s"] \
            - phases.get("engine.readback", 0.0)
    if "iterations" in a and "iterations" in b:
        # the longest iteration of the window, as its bucket's upper bound
        # (a ladder of doublings: 0.128, 0.256, ... s)
        below = 0
        for (bound, n0), (_, n1) in zip(a["iterations"], b["iterations"]):
            if n1 - n0 > below:
                obs["iteration_max_s"] = bound
            below = n1 - n0
    before = a.get("snapshot", {})
    for name, v in b.get("snapshot", {}).items():
        if name in before:
            obs["counter." + name] = v - before[name]
    return obs


def run(cell, devices, *, seed, seconds, traced, t_start, control=None,
        fault=None):
    import jax
    import jax.numpy as jnp

    compiles = harness.CompileCounter()
    family = cell.family
    model, tr = cell.model, cell.traffic
    cfg = family.model_config(model, cell.harness)
    dtype = jnp.dtype(cfg.dtype)
    params = jax.jit(lambda k: family.make_params(model, k, dtype))(
        W.seed_key(seed))
    engine, server = build_replica(cell, params, cfg)
    del params
    engine.start()
    server.start()
    load = Load(server.port)
    filler = traffic.Filler(tr, cfg.vocab_size, seed)
    try:
        # ---- one short request per shared prefix: compiles both step
        # shapes, leaves the shared prompts in the prefix cache
        for i, prefix in enumerate(filler.shared):
            warm = traffic.Request(traffic.Slot(-1, 0.0, 1, 2),
                                   [int(t) for t in prefix] + [i + 1], 2)
            rec = load.fire(warm, time.monotonic())
            if not rec.done:
                raise RuntimeError(f"warm-up request failed: {rec.error}")
        load.records.clear()

        # ---- pre-roll, then the window, on one clock
        slots = traffic.schedule(tr, seconds)
        reqs = filler.fill(slots)
        lead = max(0.0, -min(r.due_s for r in reqs)) + 0.2
        t0 = time.monotonic() + lead
        if tr["kind"] == "open-loop":
            load.open_loop(reqs, t0)
        else:
            load.closed_loop(traffic.Stream(tr, filler, len(reqs)), reqs, t0)
        tracer = harness.Tracer(traced)
        _sleep_until(t0)
        c_open, jit_open = _counters(engine), compiles.count
        setup_s = t0 - t_start
        t1 = t0 + seconds
        _sleep_until(t1 - harness.TRACE_SECONDS)
        tracer.due(t1 - time.monotonic())
        _sleep_until(t1)
        c_close, jit_close = _counters(engine), compiles.count
        occupancy = list(engine.occupancy_log[c_open["occ"]:c_close["occ"]])
        tracer.stop()
        load.stop.set()     # no new request is sent past the close

        # ---- every request due in the window is waited for
        with load.lock:
            recs = list(load.records)
        due_in = [r for r in recs if t0 <= r.due_t < t1]
        # the sample compared with the reference: requests that ran
        # through the window's load (pre-roll ones too) and were due
        # early enough to be finished about when it closes
        if tr["kind"] == "closed-loop":
            cands = [r for r in recs if r.done and r.times
                     and t0 <= r.times[-1] < t1]
        else:
            cands = [r for r in recs
                     if r.due_t - t0 <= SAMPLE_FROM * seconds]
        sample = _pick(cands, seed)
        must = due_in if tr["kind"] == "open-loop" else []
        deadline = time.monotonic() + FIRST_TOKEN_WAIT_S
        while time.monotonic() < deadline:
            if all(r.times or r.error for r in must) and \
                    all(r.done or r.error for r in sample):
                break
            time.sleep(0.05)
        # judged before the door closes: what is cut off after this was
        # abandoned by the harness, not failed by the system
        failed = sum(1 for r in due_in if r.error) + \
            sum(1 for r in must if not r.times and not r.error)
        peak = harness.memory_peak_bytes(devices)
        lanes = engine.max_batch
        chunk = engine.prefill_chunk
    finally:
        load.stop.set()
        server.stop()
        engine.stop()
    for t in load.threads:
        t.join(timeout=10.0)
    del engine, server
    gc.collect()
    trace = tracer.reduce(devices)

    # ---- the window's numbers, from the clients' clocks
    ttft = [((r.times[0] if r.times else time.monotonic()) - r.due_t) * 1e3
            for r in due_in] if tr["kind"] == "open-loop" else []
    seen = c_close["seen"] - c_open["seen"]
    matched = c_close["matched"] - c_open["matched"]
    steps = c_close["steps"] - c_open["steps"]
    hit = matched / seen if seen else 0.0
    itl, n_tok, served = [], 0, []
    for r in recs:
        inside = [j for j, t in enumerate(r.times) if t0 <= t < t1]
        n_tok += len(inside)
        itl += [(r.times[j] - r.times[j - 1]) * 1e3 for j in inside if j]
        if inside:
            served.append(Served(len(r.req.prompt), hit, inside))
    work = family.serve_work(model, served)
    obs = {
        "setup_s": setup_s, "window_s": seconds, "steps": steps,
        "ttft_ms": ttft, "itl_ms": itl, "tokens_out": n_tok,
        "late_ms": [(r.sent_t - r.due_t) * 1e3 for r in due_in]
        if tr["kind"] == "open-loop" else [],
        "occupancy_share": (100.0 * sum(occupancy) / len(occupancy) / lanes)
        if occupancy else None,
        "prompt_tokens_seen": seen, "prompt_tokens_matched": matched,
        "prefill_lane_steps": (seen - matched) / chunk,
        "compiles_in_window": (jit_close - jit_open)
        + (c_close["compiles"] - c_open["compiles"]),
        "memory_peak_gb": peak / 1e9 if peak else None,
        "requests_due": len(due_in),
        "backlog_open": c_open["backlog"], "backlog_close": c_close["backlog"],
        "model_flops": work["flops"], "model_bytes": work.get("bytes"),
    }
    obs.update(window_deltas(c_open, c_close))

    print(f"window: {len(due_in)} requests due, backlog "
          f"{c_open['backlog']} -> {c_close['backlog']}, {steps} steps",
          file=sys.stderr)

    # ---- correct: the served tokens against the plain reference
    checks = _compare(cell, seed, sample, obs, control)
    if fault == "token" and sample and sample[0].tokens:
        # for the proofs: what the comparison reads when a served token
        # is not the one the model chose
        sample[0].tokens[len(sample[0].tokens) // 2] ^= 1
        faulty: dict = {}
        _compare(cell, seed, sample, faulty, None)
        obs["fault_token"] = faulty["served_gap_stats"]
    return harness.Outcome(obs, checks, attempted=len(due_in), failed=failed,
                           devices=devices, trace=trace,
                           memory_peak_bytes=peak)


def _pick(cands: List[Record], seed: int) -> List[Record]:
    """The longest request, and ``SAMPLE - 1`` more drawn from the seed."""
    if not cands:
        return []
    def size(r):
        return len(r.req.prompt) + r.req.max_new_tokens
    order = sorted(range(len(cands)), key=lambda i: -size(cands[i]))
    rest = order[1:]
    rng = np.random.RandomState([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0x5A3D1E])
    more = rng.permutation(len(rest))[:SAMPLE - 1]
    return [cands[order[0]]] + [cands[rest[i]] for i in more]


def _compare(cell, seed, sample: List[Record], obs: dict, control) -> dict:
    family = cell.family
    model, tr = cell.model, cell.traffic
    limits = cell.harness["limits"]
    n = SAMPLE
    s_pad = -(-traffic.max_request_tokens(tr) // 128) * 128
    p_pad = tr["output_tokens"]["hi"]
    tokens = np.zeros((n, s_pad), np.int32)
    positions = -np.ones((n, p_pad), np.int64)
    served = np.zeros((n, p_pad), np.int64)
    short = 0
    for i, r in enumerate(sample):
        seq = r.req.prompt + r.tokens
        tokens[i, :len(seq)] = seq
        p = len(r.req.prompt)
        positions[i, :len(r.tokens)] = p + np.arange(len(r.tokens))
        served[i, :len(r.tokens)] = r.tokens
        if len(r.tokens) != r.req.max_new_tokens:
            short += 1
            print(f"answer cut short: request {r.req.slot.index} prompt "
                  f"{p} asked {r.req.max_new_tokens} got {len(r.tokens)} "
                  f"done={r.done} error={r.error!r}", file=sys.stderr)
    x = family.hidden_states(model, seed, tokens)
    gaps, _ = family.score(model, seed, x, positions, served)
    obs["served_tokens_compared"] = int((positions >= 0).sum())
    numbers = compare.served(gaps)
    obs["served_gap_stats"] = numbers
    checks = {k: [numbers[k], limits[k]] for k in limits}
    checks["answers_cut_short"] = [float(short), 0.0]
    if control:
        obs["control"] = {}
        for q in control.split(","):
            xq = family.hidden_states(model, seed, tokens, q)
            _, first = family.score(model, seed, xq, positions, served, q)
            cg, _ = family.score(model, seed, x, positions,
                                    np.where(first >= 0, first, 0))
            obs["control"][q] = compare.served(cg)
    return checks
