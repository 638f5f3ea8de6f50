"""Decode-serving plane: continuous-batching TPU inference on YARN.

The compute plane inherited from the reference is batch-only (PAPER.md
§5.7/§5.8); this package opens the online workload. A serving replica is

    loader.py   checkpoint straight from DFS (hedged reads for stragglers)
    engine.py   continuous-batching decode engine over the paged KV pool
                (device-resident step state, in-graph stop scan, and a
                speculation lane verified in the same fused step)
    families/   the seam to a model family: a token's cache entry and
                the layers that read it (engine.py names no family)
    speculate.py  n-gram / prompt-lookup draft proposer per request
    weightplane.py  resident-weight dtype/layout policy behind
                serving.parity: int8 + per-group scales at load,
                dequantized in-register, freed HBM sized into lanes
    kvstore/    tiered fleet-wide KV cache: HBM radix -> host-RAM ring
                -> DFS prefix store (+ raw/int8 block codecs)
    longctx/    long-context plane (serving.parity=relaxed only):
                context-parallel prefill across the replica's mesh,
                KV streamed into the cold tiers, working-set decode
    server.py   /v1/generate (streaming) + /v1/prefill + /v1/health
                + /v1/admin/drain (autoscaler-initiated retirement)
    router.py   registry discovery, role- and prefix-affinity-aware
                balancing, prefill/decode disaggregation handoff
    qos.py      door QoS: per-tenant decay-cost fairness + load
                shedding (FairCallQueue ported to admission)
    autoscale/  the SLO control loop: scrape /prom + registry, grow
                and shrink the fleet, drain-aware scale-in
    service.py  the replica packaged as a YARN long-running service
    metrics.py  queue depth / occupancy / TTFT / per-tier KV wiring

Everything runs on the CPU mesh in tests and shards over ``tp`` via
``parallel.mesh`` on real hardware.
"""

from hadoop_tpu.serving.engine import (BlockPool, DecodeEngine, GenRequest,
                                       SamplingParams)
from hadoop_tpu.serving.loader import load_serving_params
from hadoop_tpu.serving.metrics import ServingMetrics

__all__ = [
    "BlockPool", "DecodeEngine", "GenRequest", "SamplingParams",
    "load_serving_params", "ServingMetrics",
]
