"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the look for the chips, compile counting, the traced
window, and the result line.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# A traced run measures its whole window like any other; the profiler runs
# over the last seconds of it only, because a trace of a whole serving
# window is hundreds of MB. Counters and host-clock metrics are of the
# whole window; busy_s, window_s and the breakdown are of the traced slice.
TRACE_SECONDS = 10.0


def _load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file
    traffic_name: str
    traffic: dict           # the traffic file
    end_to_end: List[dict]  # metric entries that this cell reports
    per_layer: List[dict]

    @property
    def model(self) -> dict:
        """The model's own settings: the file's top-level scalars, under
        the keys of the source's ``config.json``."""
        return {k: v for k, v in self.config.items()
                if not isinstance(v, (dict, list))}

    @property
    def harness(self) -> dict:
        """What the deployment sets beside the model (context, KV budget,
        the limits of the comparison): the file's ``harness`` group."""
        return self.config["harness"]

    @property
    def family(self):
        """The architecture's module, found by the file's ``model_type``:
        ``ModelConfig``, weights, plain reference and the work needed."""
        from chipbench import families
        return families.load(self.model)

    @property
    def platform(self) -> str:
        """A stand-in configuration for the CPU rehearsal says so."""
        return self.harness.get("platform", "tpu")


def load_cell(name: str, benchmark: str = "BENCHMARK.json") -> Cell:
    bench = _load(benchmark)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {benchmark} "
                         f"(there are: {sorted(cells)})")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    tdir = os.path.join(os.path.dirname(conf["file"]), "..", "traffic")
    traffic = _load(os.path.normpath(
        os.path.join(tdir, w["traffic"] + ".json")))

    def mine(entries):
        return [m for m in entries
                if name in m.get("workloads", [name])]
    return Cell(name, w["chips"], w["config"], _load(conf["file"]),
                w["traffic"], traffic, mine(bench["end_to_end"]),
                mine(bench["per_layer"]))


def metric_spec(name: str) -> dict:
    """The reader of a metric: ``chipbench/metrics/<name>.json``."""
    return _load(os.path.join("chipbench", "metrics", name + ".json"))


def open_cell(workload: str, benchmark: str):
    """What ``run`` and ``proof`` both start with: the cell, the compile
    cache, the chips, the traffic kind's driver."""
    cell = load_cell(workload, benchmark)
    cell.family     # a model_type without a module ends the run here
    from hadoop_tpu.util.jaxcache import configure_compile_cache
    configure_compile_cache()
    import jax
    # keep every compile, also where the operator placed the cache: a
    # program that compiles in about a second is otherwise compiled anew
    # by every run, and set-up wanders with it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # an executable loaded from the cache keeps the names it was compiled
    # with, because the cache's key leaves metadata out: a program whose
    # scopes alone changed would be read under its old ones (my chip run,
    # PR 26: the fused step came back with no scope at all)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from chipbench import kinds
    return cell, find_devices(cell), kinds.driver(cell.traffic["kind"])


def find_devices(cell: Cell):
    """The chips the cell asks for, or exit without a result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != cell.platform:
        raise SystemExit(
            f"{cell.name} runs on {cell.platform!r}; jax found "
            f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips; jax "
                         f"found {len(devs)}")
    return devs[:cell.chips]


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip, as the device reports it (0 where it
    reports none, as the CPU does: the readers then leave it out)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileCounter:
    """Backend compiles and persistent-cache loads seen by this process:
    either inside the window means a program was not warmed up."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name in self.NAMES:
            self.count += 1


class Tracer:
    """The profiler over the last ``TRACE_SECONDS`` of a traced run's
    window. The driver calls ``due(left)`` from its window with the
    seconds the window still has, ``stop`` once it has closed, and
    ``reduce`` when the program's state is freed."""

    def __init__(self, on: bool):
        self.on = on
        self.dir: Optional[str] = None

    def due(self, left_s: float) -> None:
        """Starts the profiler once, when ``left_s`` is down to the slice."""
        if not self.on or self.dir is not None or left_s > TRACE_SECONDS:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        if self.dir is not None:
            import jax
            jax.profiler.stop_trace()

    def reduce(self, devices) -> Optional[dict]:
        """Read the trace, delete it, return the reduction. On a TPU a
        trace without every chip's plane is an error, never a number from
        somewhere else; on the CPU rehearsal there is nothing to read."""
        if self.dir is None:
            return None
        from chipbench import scopes, trace
        try:
            red = trace.reduce(trace.load_events(self.dir))
            if red is not None:
                # device seconds by compiled program and by the program's
                # named scopes, for the ``trace-module-ms`` and
                # ``trace-scope-share`` readers
                red["scopes"] = scopes.reduce(scopes.load_dir(self.dir),
                                              scopes.named_in_metrics())
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if devices[0].platform != "tpu":
            return None
        seen = red["chips_seen"] if red else 0
        if seen != len(devices):
            raise SystemExit(f"the trace holds the device operations of "
                             f"{seen} chips; the cell ran on {len(devices)}")
        return red


@dataclass
class Outcome:
    """What a cell's driver hands back."""
    obs: Dict[str, Any]                  # raw observations for readers
    checks: Dict[str, List[float]]       # name -> [number, limit]
    attempted: int
    failed: int
    devices: list = field(default_factory=list)
    trace: Optional[dict] = None
    memory_peak_bytes: int = 0

    @property
    def correct(self) -> bool:
        return self.failed == 0 and bool(self.checks) and all(
            v == v and v <= lim for v, lim in self.checks.values())


def emit(cell: Cell, out: Outcome, traced: bool) -> int:
    from chipbench import readers
    entries = cell.per_layer if traced else cell.end_to_end
    on_chip = out.devices[0].platform == "tpu"
    metrics = {}
    for m in entries:
        if not on_chip and m["source"] != "program_counter":
            continue    # a CPU never speaks under a device metric's name
        val = readers.read(metric_spec(m["name"]), out, cell)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    dev = {"platform": out.devices[0].platform,
           "kind": out.devices[0].device_kind, "count": len(out.devices),
           "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": dev}
    if traced and out.trace and on_chip:
        dev["busy_s"] = out.trace["busy_s"]
        dev["window_s"] = out.trace["window_s"]
        line["breakdown"] = {"device_ops": out.trace["device_ops"],
                             "idle_gaps": out.trace["idle_gaps"]}
    if not on_chip:
        line["rehearsal"] = True
    line["check"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in out.checks.items()}
    for k, (v, lim) in out.checks.items():
        print(f"check {k}: {v!r} limit {lim!r} "
              f"{'ok' if v == v and v <= lim else 'FAIL'}",
              file=sys.stderr)
    print(f"correct={out.correct} attempted={out.attempted} "
          f"failed={out.failed}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
