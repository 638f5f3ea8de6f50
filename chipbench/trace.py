"""From a profiler trace to numbers: device busy union, idle share, the
device operations that took most time, and the longest idle gaps named by
what the host was doing in them. Kept with the benchmark so that every PR
computes them the same way; checked on a small recorded trace
(``chipbench/tests/trace_small.json``).

An event is ``{"plane", "line", "name", "start_ns", "dur_ns"}``. Device
operations are the events of a ``/device:TPU:<n>`` plane's "XLA Ops" line
and of nothing else: a trace that holds no such plane reduces to ``None``,
never to numbers read from the host's threads.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
TOP = 10
NAME_MAX = 120
# a TPU op event is named by its whole HLO line: "%fusion.8 = bf16[1024,
# 1024]{1,0:T(8,128)...} fusion(...)". Keep the name and the first shape.
_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def short_name(name: str) -> str:
    m = _HLO.match(name)
    if not m:
        return name[:NAME_MAX]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:NAME_MAX]


def load_events(trace_dir: str) -> List[dict]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def _device_ops(events: List[dict]) -> Dict[str, List[dict]]:
    chips: Dict[str, List[dict]] = defaultdict(list)
    for e in events:
        if e["plane"].startswith("/device:TPU:") and e["line"] == OPS_LINE:
            chips[e["plane"]].append(e)
    return chips


def _union(ops: List[dict]) -> List[Tuple[float, float, str]]:
    """Merged busy intervals (start, end, name of the op that ends it)."""
    merged: List[List] = []
    for e in sorted(ops, key=lambda e: e["start_ns"]):
        s, t = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if merged and s <= merged[-1][1]:
            if t > merged[-1][1]:
                merged[-1][1], merged[-1][2] = t, e["name"]
        else:
            merged.append([s, t, e["name"]])
    return [tuple(m) for m in merged]


def _leaves(ops: List[dict]) -> List[dict]:
    """Operations that hold no other (a ``while`` spans its body's ops on
    the same line; counting it would count its body twice)."""
    ops = sorted(ops, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    out = []
    for i, e in enumerate(ops):
        end = e["start_ns"] + e["dur_ns"]
        if i + 1 < len(ops) and ops[i + 1]["start_ns"] < end:
            continue
        out.append(e)
    return out


def _host_spans(events: List[dict]) -> List[dict]:
    return [e for e in events
            if e["plane"] == "/host:CPU"
            and not e["line"].startswith("tf_XLA")
            and not e["name"].startswith("ThreadpoolListener")]


def _host_in(spans: List[dict], s: float, t: float) -> str:
    best, best_cover = "no host span", 0.0
    for e in spans:
        cover = min(t, e["start_ns"] + e["dur_ns"]) - max(s, e["start_ns"])
        if cover > best_cover:
            best, best_cover = e["name"], cover
    return best


def reduce(events: List[dict]) -> Optional[dict]:
    """``None`` where no operation ran on a TPU; ``chips_seen`` says how
    many chips' planes the trace held (the harness holds it against the
    chips the cell asked for)."""
    chips = _device_ops(events)
    if not chips:
        return None
    start = min(e["start_ns"] for ops in chips.values() for e in ops)
    end = max(e["start_ns"] + e["dur_ns"]
              for ops in chips.values() for e in ops)
    spans = _host_spans(events)
    busy, by_name, gaps = 0.0, defaultdict(float), []
    for i, (plane, ops) in enumerate(sorted(chips.items())):
        merged = _union(ops)
        busy += sum(t - s for s, t, _ in merged)
        for e in _leaves(ops):
            by_name[short_name(e["name"])] += e["dur_ns"]
        for (s0, t0, name), (s1, _, _) in zip(merged, merged[1:]):
            gaps.append((s1 - t0, i, t0, s1, short_name(name)))
    n = len(chips)
    top_gaps = sorted(gaps, reverse=True)[:TOP]
    return {
        "busy_s": busy / n / 1e9,
        "window_s": (end - start) / 1e9,
        "chips_seen": n,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[(f"chip_{i}: {_host_in(spans, s, t)} "
                        f"after {name}")[:NAME_MAX], g / 1e9]
                      for g, i, s, t, name in top_gaps],
    }
