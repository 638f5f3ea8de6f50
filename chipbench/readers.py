"""Per-metric readers. A metric is a file ``chipbench/metrics/<name>.json``
naming one of these readers and its arguments; a reader takes the run's
observations and returns the number, or ``None`` when there is nothing to
read (the harness then leaves the metric out — never a 0).

``value``        obs[of] * scale
``mean``         mean of the list obs[of]
``percentile``   q-th percentile of the list obs[of] (linear)
``ratio``        obs[num] / obs[den] * scale (None when den is 0)
``mfu``          obs[flops] / obs[window] / (chips * peak bf16 FLOP/s), %
``trace-idle``   1 - device busy union / traced window, %
``trace-module-ms``, ``trace-scope-share``   see ``scopes.py``

A reader that is none of these is looked for in the ``READERS`` of the
cell's family module (``chipbench/families/``), where an architecture's
own kernel keeps the reader of its roofline share beside its operation
and byte counts.
"""

from __future__ import annotations

from typing import Optional

from chipbench import peaks, scopes


def _value(spec, out, cell) -> Optional[float]:
    v = out.obs.get(spec["of"])
    return None if v is None else float(v) * spec.get("scale", 1.0)


def _mean(spec, out, cell) -> Optional[float]:
    xs = out.obs.get(spec["of"])
    return None if not xs else float(sum(xs)) / len(xs)


def _percentile(spec, out, cell) -> Optional[float]:
    xs = out.obs.get(spec["of"])
    if not xs:
        return None
    xs = sorted(xs)
    k = (len(xs) - 1) * spec["q"] / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def _ratio(spec, out, cell) -> Optional[float]:
    num, den = out.obs.get(spec["num"]), out.obs.get(spec["den"])
    if num is None or not den:
        return None
    return float(num) / float(den) * spec.get("scale", 1.0)


def _mfu(spec, out, cell) -> Optional[float]:
    flops = out.obs.get(spec.get("flops", "model_flops"))
    window = out.obs.get(spec.get("window", "window_s"))
    if not flops or not window:
        return None
    peak = peaks.peak(out.devices[0].device_kind, "bf16_flops")
    return 100.0 * flops / window / (len(out.devices) * peak)


def _trace_idle(spec, out, cell) -> Optional[float]:
    t = out.trace
    if not t or not t["busy_s"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


READERS = {"value": _value, "mean": _mean, "percentile": _percentile,
           "ratio": _ratio, "mfu": _mfu, "trace-idle": _trace_idle,
           **scopes.READERS}


def read(spec: dict, out, cell) -> Optional[float]:
    name = spec.get("reader")
    reader = READERS.get(name)
    if reader is None:
        own = getattr(cell.family, "READERS", {})
        reader = own.get(name)
        if reader is None:
            raise ValueError(f"metric reader {name!r} unknown (there are: "
                             f"{sorted({**READERS, **own})})")
    return reader(spec, out, cell)
