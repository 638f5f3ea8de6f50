"""From a profiler trace to the program's own names: device seconds per
compiled program (the "XLA Modules" line) and per named scope of the
program (``jax.named_scope`` in ``hadoop_tpu``: ``attn``, ``mlp``,
``kv_gather``, ...), read from the same ``.xplane.pb`` as ``trace.py``
reads. Checked on a small recorded trace
(``chipbench/tests/scopes_small.json``).

An event here is ``{"plane", "line", "name", "start_ns", "dur_ns"}`` plus,
on the "XLA Ops" line, ``"path"``: the operation's ``op_name`` as JAX wrote
it into the HLO metadata, for instance
``jit(train_step)/transpose(jvp(attn))/while/body/mul``. A scope is the
innermost of the program's names on that path; an operation of the
backward pass counts under its forward scope (``transpose(jvp(attn))`` is
``attn``). An operation under none of them counts as ``scan_carry`` where
its path lies on a ``while`` — what a ``lax.scan`` does for itself around
its body: slicing what it carries for each layer, writing the carry back
(the serving step's KV pools), and what the compiler hoisted out of the
body to the loop's level, none of which a ``jax.named_scope`` inside the
body can reach — and as ``unscoped`` otherwise. Only leaf operations
count, as ``trace._leaves`` defines a leaf.

The two readers at the end read a run's reduction, which
``harness.Tracer.reduce`` puts under ``trace["scopes"]``.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional

from chipbench import trace

MODULES_LINE = "XLA Modules"
# the names the program gives (hadoop_tpu: models/decoder.py, models/moe.py,
# parallel/train.py, parallel/optimizer.py, serving/engine.py); a metric
# file that names another adds it (``named_in_metrics``)
SCOPES = frozenset((
    "embed", "attn", "mlp", "moe", "head_xent", "grad_norm", "optimizer",
    "attn_proj", "kv_update", "kv_gather", "head_sample"))
SCAN_CARRY = "scan_carry"
UNSCOPED = "unscoped"
# the stat of an operation's event metadata that carries its op_name path
PATH_STAT = "tf_op"
# "jit_train_step(1234567890)" on the modules line -> "train_step"
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
# one part of a path with the transformations around it taken off:
# "transpose(jvp(attn))" -> "attn"
_BARE = re.compile(r"^(?:\w+\()*([\w.\-]*)\)*$")


def module_name(name: str) -> str:
    return _MODULE.match(name.strip()).group(1)


def named_in_metrics() -> frozenset:
    """``SCOPES`` and every scope a metric file names
    (``chipbench/metrics/*.json`` with a ``scope`` key): a scope the program
    gains later is known here from the day a file reads it."""
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "metrics")
    found = set(SCOPES)
    for path in glob.glob(os.path.join(here, "*.json")):
        with open(path) as f:
            scope = json.load(f).get("scope")
        if scope not in (None, SCAN_CARRY, UNSCOPED):
            found.add(scope)
    return frozenset(found)


def scope_of(path: Optional[str], names=SCOPES) -> str:
    """The innermost of the program's ``names`` on an ``op_name`` path;
    failing one, whether the path lies on a loop."""
    parts = [m.group(1) for m in map(_BARE.match,
                                     (path or "").rstrip(":").split("/"))
             if m]
    for part in reversed(parts):
        if part in names:
            return part
    return SCAN_CARRY if "while" in parts else UNSCOPED


# ---- the .xplane.pb, read as protobuf wire format. An operation's
# op_name path (``tf_op``) is a stat of its event's *metadata*, which
# ``jax.profiler.ProfileData`` does not hand out (it gives the stats of
# the event itself), so the few messages needed are walked here: XSpace
# {1: planes}, XPlane {2: name, 3: lines, 4: event_metadata<id, msg>,
# 5: stat_metadata<id, msg>}, XLine {2: name, 3: timestamp_ns, 4: events},
# XEvent {1: metadata_id, 2: offset_ps, 3: duration_ps}, XEventMetadata
# {1: id, 2: name, 5: stats}, XStatMetadata {1: id, 2: name}, XStat
# {1: metadata_id, 5: str_value, 7: ref_value}.

def _fields(buf: memoryview):
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            val = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, val


def _map_entry(buf: memoryview) -> memoryview:
    return next(v for f, v in _fields(buf) if f == 2)


def _plane_events(plane: memoryview, name: str) -> List[dict]:
    top = list(_fields(plane))
    stat_names = {}
    for f, v in top:
        if f == 5:
            sm = dict(_fields(_map_entry(v)))
            stat_names[sm.get(1, 0)] = str(sm.get(2, b""), "utf-8")
    path_ids = {i for i, s in stat_names.items() if s == PATH_STAT}
    meta = {}       # metadata id -> (name, path)
    for f, v in top:
        if f != 4:
            continue
        mid, mname, path = 0, "", None
        for g, w in _fields(_map_entry(v)):
            if g == 1:
                mid = w
            elif g == 2:
                mname = str(w, "utf-8")
            elif g == 5:
                st = dict(_fields(w))
                if st.get(1) in path_ids:
                    path = str(st[5], "utf-8") if 5 in st \
                        else stat_names.get(st.get(7))
        meta[mid] = (mname, path)
    out = []
    for f, v in top:
        if f != 3:
            continue
        line = list(_fields(v))
        lname = next((str(w, "utf-8") for g, w in line if g == 2), "")
        if lname not in (MODULES_LINE, trace.OPS_LINE):
            continue
        t0_ps = next((w for g, w in line if g == 3), 0) * 1000
        for g, w in line:
            if g != 4:
                continue
            ev = dict(_fields(w))
            if not ev.get(3):
                continue
            mname, path = meta.get(ev.get(1), ("", None))
            e = {"plane": name, "line": lname, "name": mname,
                 "start_ns": (t0_ps + ev.get(2, 0)) / 1e3,
                 "dur_ns": ev[3] / 1e3}
            if lname == trace.OPS_LINE:
                e["path"] = path
            out.append(e)
    return out


def load_events(pb_path: str) -> List[dict]:
    """The device planes' "XLA Modules" and "XLA Ops" events."""
    with open(pb_path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for f, plane in _fields(space):
        if f != 1:
            continue
        name = next((str(v, "utf-8") for g, v in _fields(plane) if g == 2),
                    "")
        if name.startswith("/device:TPU:"):
            out.extend(_plane_events(plane, name))
    return out


def load_dir(trace_dir: str) -> List[dict]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_events(paths[0])


def reduce(events: List[dict], names=SCOPES) -> Optional[dict]:
    """``modules``: name -> {"count", "seconds"} of the program's WHOLE
    runs (seconds summed, both averaged over the chips): on each chip the
    first and the last event of the modules line are left out, because a
    profiler started or stopped while a program runs cuts that run's
    event short (the benchmark starts it mid-window: the first
    ``jit_train_step`` of a slice read 156.8 ms of 205.6, and the mean
    over 50 events 0.46% low). ``scopes``: scope -> device seconds of its
    leaf operations, averaged over the chips, ``scan_carry`` and
    ``unscoped`` among them; ``leaf_s``: their sum; ``unnamed_top``: what
    the time under those two is made of, ``[operation, seconds, path]``
    — the path is ``None`` on what the compiler added (copies of weights
    and of donated buffers, layout converts). ``None`` where no operation
    ran on a TPU."""
    chips: Dict[str, List[dict]] = defaultdict(list)
    runs: Dict[str, List[dict]] = defaultdict(list)
    for e in events:
        if not e["plane"].startswith("/device:TPU:"):
            continue
        if e["line"] == trace.OPS_LINE:
            chips[e["plane"]].append(e)
        elif e["line"] == MODULES_LINE:
            runs[e["plane"]].append(e)
    if not chips:
        return None
    n = len(chips)
    modules: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for line in runs.values():
        for e in sorted(line, key=lambda e: e["start_ns"])[1:-1]:
            m = modules[module_name(e["name"])]
            m[0] += 1
            m[1] += e["dur_ns"]
    by_scope: Dict[str, float] = defaultdict(float)
    unnamed: Dict[tuple, float] = defaultdict(float)
    for ops in chips.values():
        for e in trace._leaves(ops):
            scope = scope_of(e.get("path"), names)
            by_scope[scope] += e["dur_ns"]
            if scope in (SCAN_CARRY, UNSCOPED):
                unnamed[(trace.short_name(e["name"]),
                         e.get("path"))] += e["dur_ns"]
    scopes = {k: v / n / 1e9 for k, v in sorted(by_scope.items())}
    return {
        "modules": {k: {"count": c / n, "seconds": ns / n / 1e9}
                    for k, (c, ns) in sorted(modules.items())},
        "scopes": scopes,
        "leaf_s": sum(scopes.values()),
        "unnamed_top": [[name, v / n / 1e9, path] for (name, path), v in
                        sorted(unnamed.items(),
                               key=lambda kv: -kv[1])[:trace.TOP]],
    }


def module_ms(red: Optional[dict], module: str) -> Optional[float]:
    """Mean device milliseconds of one whole run of a compiled program;
    ``None`` (never 0) where the trace holds no such run."""
    m = (red or {}).get("modules", {}).get(module)
    if not m or not m["count"]:
        return None
    return m["seconds"] / m["count"] * 1e3


def scope_share(red: Optional[dict], scope: str,
                busy_s: Optional[float]) -> Optional[float]:
    """A scope's device seconds as a share (%) of the device's busy
    seconds (``trace.reduce``'s ``busy_s``, averaged over the chips the
    same way); ``None`` where the trace lacks the scope."""
    s = (red or {}).get("scopes", {}).get(scope)
    if s is None or not busy_s:
        return None
    return 100.0 * s / busy_s


def _read_module_ms(spec: dict, out, cell) -> Optional[float]:
    return module_ms((out.trace or {}).get("scopes"), spec["module"])


def _read_scope_share(spec: dict, out, cell) -> Optional[float]:
    t = out.trace or {}
    return scope_share(t.get("scopes"), spec["scope"], t.get("busy_s"))


# for ``readers.READERS``: ``trace-module-ms`` (``module``) and
# ``trace-scope-share`` (``scope``)
READERS = {"trace-module-ms": _read_module_ms,
           "trace-scope-share": _read_scope_share}
