"""Live HBM ledger — "what is HBM spent on" as one scrape.

The bytes already exist, measured piecemeal: the weight plane reports
resident weight bytes, the engine sizes its KV pool against them, the
long-context plane knows its window+tail working set, the trainer holds
param/optimizer state and transient grad buckets. Answering "where did
the HBM go" today is an archaeology session across four surfaces. This
module unifies them: components register byte **providers** (zero-arg
callables returning live byte counts), and the ledger exposes

- ``htpu_hbm_bytes{component=...}`` gauges on every ``/prom`` (one
  family, label values drawn from the bounded literal set below — the
  tpulint ``metrics/unbounded-label`` contract),
- a ``hbm`` block on the serving ``/v1/health`` door and the trainer's
  ``/ws/v1/trainer`` endpoint,
- a cross-check against ``jax`` device memory stats where the backend
  reports them (TPU/GPU report ``bytes_in_use``; the CPU simulator
  reports nothing — the ledger then shows accounted bytes only).

Providers are owned: a component registers under an owner key and
unregisters on teardown, so a stopped engine's pool never haunts the
report. A provider that raises is skipped and counted in ``errors`` —
one broken surface must not take down the whole ledger.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, Optional, Tuple

log = logging.getLogger(__name__)

# The bounded component label set. Unknown components map to "other" so
# a registration can never mint an unbounded Prometheus series. Keep in
# sync with the literal tuple in _ensure_metrics below.
HBM_COMPONENTS = ("weights", "weights_dequantized", "moe_experts",
                  "kv_pool",
                  "longctx_window", "longctx_tail", "longctx_sampler",
                  "params", "opt_state", "grad_buckets", "other")


def device_memory_stats() -> Optional[Dict]:
    """Backend-reported device memory, where available. Never imports
    jax into a process that has not already paid for it (a DataNode
    scraping this ledger must stay light). Advisory off the chip (the
    CPU simulator reports nothing); on a TPU backend the platform and
    ``bytes_in_use`` are what the serving door's health check reads,
    so a failure there is logged, not swallowed."""
    import sys
    if "jax" not in sys.modules:
        return None
    import jax
    platform = None
    try:
        devs = jax.local_devices()
        if not devs:
            return None
        platform = devs[0].platform
        stats = devs[0].memory_stats() or {}
    except Exception:  # noqa: BLE001 — the ledger must outlive a
        # backend that cannot report; off the chip that is the norm
        if platform == "tpu":
            log.exception("device memory_stats() failed on a TPU backend")
        return None
    out = {"platform": platform}
    for key in ("bytes_in_use", "bytes_limit", "peak_bytes_in_use"):
        if key in stats:
            out[key] = int(stats[key])
    return out if len(out) > 1 else None


class HbmLedger:
    """Process-global registry of HBM byte providers."""

    # how long one provider sweep may serve the per-component gauges:
    # a /prom render reads all 10 component gauges back to back, and a
    # params/opt provider walks a whole pytree — 10 sweeps per scrape
    # would be pure redundant hot-path work
    CACHE_SECONDS = 0.25

    def __init__(self):
        self._lock = threading.Lock()
        # owner -> (component, provider)
        self._providers: Dict[str, Tuple[str, Callable[[], int]]] = {}
        self._reg = None
        # (monotonic stamp, components, errors) of the last sweep;
        # invalidated on register/unregister    guarded-by: _lock
        self._cache: Optional[Tuple[float, Dict[str, int], int]] = None

    def register(self, owner: str, component: str,
                 provider: Callable[[], int]) -> None:
        """Register ``provider`` as ``owner``'s contribution to
        ``component`` (re-registering an owner replaces it)."""
        if component not in HBM_COMPONENTS:
            component = "other"
        with self._lock:
            self._providers[owner] = (component, provider)
            self._cache = None
        self._ensure_metrics()

    def unregister(self, owner: str) -> None:
        with self._lock:
            self._providers.pop(owner, None)
            self._cache = None

    def unregister_prefix(self, prefix: str) -> None:
        """Drop every owner under ``prefix`` — component teardown
        (engine.stop drops its weights+pool in one call)."""
        with self._lock:
            for key in [k for k in self._providers
                        if k.startswith(prefix)]:
                del self._providers[key]
            self._cache = None

    # ------------------------------------------------------------ queries

    def component_bytes(self) -> Tuple[Dict[str, int], int]:
        """({component: live bytes}, provider-error count). One sweep
        serves every per-component gauge of a scrape (CACHE_SECONDS);
        any registration change invalidates it."""
        now = time.monotonic()
        with self._lock:
            if self._cache is not None and \
                    now - self._cache[0] < self.CACHE_SECONDS:
                return dict(self._cache[1]), self._cache[2]
            providers = list(self._providers.values())
        out: Dict[str, int] = {}
        errors = 0
        for component, provider in providers:
            try:
                b = int(provider())
            except Exception:  # noqa: BLE001 — a torn-down owner that
                # missed its unregister reads as an error count, not a
                # dead ledger
                errors += 1
                continue
            out[component] = out.get(component, 0) + b
        with self._lock:
            self._cache = (now, dict(out), errors)
        return out, errors

    def report(self) -> Dict:
        self._ensure_metrics()
        comps, errors = self.component_bytes()
        return {"components": comps,
                "total_bytes": sum(comps.values()),
                "providers": len(self._providers),
                "errors": errors,
                "device": device_memory_stats()}

    # ------------------------------------------------------------ metrics

    def _one_component(self, component: str) -> int:
        comps, _ = self.component_bytes()
        return comps.get(component, 0)

    def _ensure_metrics(self) -> None:
        """Callback gauges per component under ONE ``htpu_hbm_bytes``
        family; revalidated against the live metrics system so a test
        reset re-registers on next use."""
        from hadoop_tpu.metrics import metrics_system
        reg = metrics_system().source("hbm")
        if reg is self._reg:
            return
        # label values drawn from this literal tuple — the bounded-set
        # contract the tpulint metrics/unbounded-label checker enforces
        for c in ("weights", "weights_dequantized", "moe_experts",
                  "kv_pool",
                  "longctx_window", "longctx_tail", "longctx_sampler",
                  "params", "opt_state", "grad_buckets", "other"):
            reg.register_callback_gauge(
                "hbm_bytes_" + c,
                (lambda comp=c: self._one_component(comp)),
                prom_name="hbm_bytes", prom_labels={"component": c})
        self._reg = reg

    def reset_for_tests(self) -> None:
        with self._lock:
            self._providers.clear()
            self._cache = None
        self._reg = None


_LEDGER = HbmLedger()


def hbm_ledger() -> HbmLedger:
    return _LEDGER


def tree_nbytes(tree) -> int:
    """Total bytes of a pytree of arrays (params/opt state providers)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is None:
            size = getattr(leaf, "size", 0)
            itemsize = getattr(getattr(leaf, "dtype", None),
                               "itemsize", 0)
            nb = int(size) * int(itemsize)
        total += int(nb)
    return total
