"""The per-layer metrics that read the program's OWN record — the engine
loop's phases, the per-request TTFT timeline, the histogram of iterations,
and the device time by compiled program and named scope — as a tool:

    python3 -m chipbench.inside --workload <cell> --seed <n> --seconds <s>

It runs the cell's own driver, traced, exactly as ``chipbench.run --trace
1`` does, prints that run's ordinary line, and prints one more line last:
``{"inside": {metric: value}, "phases": ..., "modules": ..., "scopes":
...}``. Each metric is read from its file ``chipbench/metrics/<name>.json``
through ``readers.read``, as ``chipbench.run`` will read it. It cannot
yet: that takes new keys in ``serve_cell._counters`` / ``obs``, the two
readers of ``scopes.READERS`` in ``readers.READERS``, one call in
``harness.Tracer.reduce`` and the entries in ``BENCHMARK.json`` — lines in
files the benchmark already has, which only a ``benchmark`` PR may touch
(``PERF.md`` §7 lists them). ``Taps`` applies those three edits at run
time and nothing else; **the PR that makes them deletes this file and its
test.** The benchmark's own runs never come here.

On a program without the record (a commit before ISSUE 26) a metric finds
nothing to read and is left out; nothing raises.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402
from typing import Optional  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the metric files this tool reads, by the kind of the cell's traffic
SERVE = ("engine.queue_wait_ms.chat", "engine.prefill_wait_ms.chat",
         "engine.prefill_service_ms.chat", "engine.step_busy_ms.chat",
         "engine.host_ms_per_step.chat", "engine.iter_max_ms.chat",
         "engine.step_device_ms.chat", "engine.device_share.kv_gather.chat",
         "engine.device_share.attn.chat", "engine.device_share.mlp.chat",
         "engine.device_share.head_sample.chat",
         "engine.device_share.scan_carry.chat",
         "engine.device_share.unscoped.chat")
TRAIN = ("train.step_device_ms", "train.device_share.attn",
         "train.device_share.mlp", "train.device_share.head_xent",
         "train.device_share.optimizer", "train.device_share.unscoped")
STAGES = {"queue": "queue_wait_s", "prefill_wait": "prefill_wait_s",
          "prefill": "prefill_service_s"}


def engine_record(engine) -> Optional[dict]:
    """The engine's own record at one moment (``None`` on a program that
    keeps none): seconds by phase, (sum, count) of each TTFT stage's
    histogram, the iteration histogram's cumulative counts by bound."""
    m = getattr(engine, "metrics", None)
    if not hasattr(engine, "phase_s") or not hasattr(m, "iteration_hist"):
        return None
    rec = {stage: m.ttft_stage_hist[stage].buckets()[1:] for stage in STAGES}
    rec["phase_s"] = dict(engine.phase_s)
    rec["iterations"] = m.iteration_hist.buckets()[0]
    return rec


class Taps:
    """The three edits, applied at run time: the engine's record when the
    window opens and closes (``serve_cell._counters`` is called exactly
    there), the trace's reduction by module and scope under the
    reduction's ``scopes`` key, the two trace readers."""

    def __init__(self):
        self.marks = []

    def install(self) -> None:
        from chipbench import harness, readers, scopes, serve_cell
        counters, reduce = serve_cell._counters, harness.Tracer.reduce

        def tapped_counters(engine):
            self.marks.append(engine_record(engine))
            return counters(engine)

        def tapped_reduce(tracer, devices):
            by_name = scopes.reduce(scopes.load_dir(tracer.dir)) \
                if tracer.dir is not None else None
            red = reduce(tracer, devices)
            if red is not None:
                red["scopes"] = by_name
            return red

        serve_cell._counters = tapped_counters
        harness.Tracer.reduce = tapped_reduce
        readers.READERS.update(scopes.READERS)


def observe(marks, obs: dict) -> None:
    """Window deltas of the engine's record, into ``obs`` under the keys
    ``serve_cell.run`` will give them; nothing where a mark is missing."""
    if len(marks) != 2 or None in marks:
        return
    a, b = marks
    for stage, key in STAGES.items():
        obs[key] = b[stage][0] - a[stage][0]
    obs["first_tokens"] = b["prefill"][1] - a["prefill"][1]
    phases = {k: v - a["phase_s"].get(k, 0.0)
              for k, v in b["phase_s"].items()}
    obs["phase_window_s"] = phases
    obs["phase_busy_s"] = sum(v for k, v in phases.items()
                              if k != "engine.wait")
    obs["phase_host_s"] = obs["phase_busy_s"] \
        - phases.get("engine.readback", 0.0)
    # the longest iteration of the window, as its bucket's upper bound
    # (a ladder of doublings: 0.128, 0.256, ... s)
    below = 0
    for (bound, n0), (_, n1) in zip(a["iterations"], b["iterations"]):
        if n1 - n0 > below:
            obs["iteration_max_s"] = bound
        below = n1 - n0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--benchmark", default="BENCHMARK.json")
    args = ap.parse_args(argv)

    from chipbench import harness, readers
    taps = Taps()
    taps.install()
    cell, devices, driver = harness.open_cell(args.workload, args.benchmark)
    # an executable loaded from the persistent compile cache keeps the
    # names it was compiled with: the cache's key leaves metadata out, so
    # a program whose scopes alone changed would read under its old ones
    # (my chip run, PR 26: the fused step came back from the cache with no
    # scope at all). With the metadata in the key this tool compiles its
    # own entries, once per state of the source; ``open_cell`` has to set
    # the same before the driver's runs can read a scope.
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    out = driver(cell, devices, seed=args.seed, seconds=args.seconds,
                 traced=True, t_start=T_START)
    harness.emit(cell, out, True)

    observe(taps.marks, out.obs)
    names = TRAIN if cell.traffic["kind"] == "packed" else SERVE
    metrics = {n: readers.read(harness.metric_spec(n), out, cell)
               for n in names}
    line = {"inside": {k: v for k, v in metrics.items() if v is not None}}
    # a traced run's ordinary line holds the per-layer metrics only; what
    # tracing costs is read from the same run's end-to-end numbers
    line["end_to_end"] = {
        m["name"]: readers.read(harness.metric_spec(m["name"]), out, cell)
        for m in cell.end_to_end}
    if devices[0].platform != "tpu":
        line["rehearsal"] = True
    if "phase_window_s" in out.obs:
        line["phases"] = out.obs["phase_window_s"]
        line["first_tokens"] = out.obs["first_tokens"]
        line["steps"] = out.obs["steps"]
        late = out.obs.get("late_ms")
        line["late_ms_mean"] = sum(late) / len(late) if late else None
    by_name = (out.trace or {}).get("scopes")
    if by_name:
        line.update(by_name, busy_s=out.trace["busy_s"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
