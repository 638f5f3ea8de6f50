"""The serving replica as a deployable unit.

Two faces:

- ``serving_service_spec`` packages N replicas as a YARN long-running
  service (``yarn.services``): the RM places the containers, the service
  AM restarts exited replicas (RESTART_ALWAYS), and ``flex`` scales the
  replica count at runtime — serving capacity is a YARN knob, exactly
  like every other long-running daemon on the cluster.

- ``replica_main`` is what runs inside each container (and behind
  ``hadoop-tpu serve``): pull the checkpoint from the DFS (hedged
  reads), build the engine + HTTP server, register in the service
  registry with an ephemeral lease, and on SIGTERM flip the registry
  record to draining, finish in-flight requests, then exit — the
  graceful-drain half of the router's balancing contract.
"""

from __future__ import annotations

import logging
import signal
import socket
import sys
import threading
import time
import uuid
from typing import List, Optional, Tuple

from hadoop_tpu.conf import Configuration
from hadoop_tpu.ipc.errors import RpcError
from hadoop_tpu.models.config import get_config
from hadoop_tpu.serving.loader import (IO_WORKERS_KEY,
                                       load_serving_params,
                                       serving_read_defaults)
from hadoop_tpu.serving.metrics import ServingMetrics
from hadoop_tpu.serving.router import replica_path
from hadoop_tpu.yarn.records import Resource
from hadoop_tpu.yarn.services import (RESTART_ALWAYS, Component,
                                      ServiceSpec)

log = logging.getLogger(__name__)


def serving_service_spec(name: str, *, checkpoint: str, preset: str,
                         replicas: int = 2,
                         registry_addr: Optional[str] = None,
                         resource: Optional[Resource] = None,
                         extra_args: Optional[List[str]] = None,
                         ) -> ServiceSpec:
    """YARN service spec: N identical replica containers."""
    cmd = [sys.executable, "-m", "hadoop_tpu.serving.service",
           "--replica", "--name", name,
           "--checkpoint", checkpoint, "--preset", preset,
           # containers land on arbitrary hosts: bind the wildcard so
           # the replica advertises its hostname, not some loopback the
           # router would resolve to its own machine
           "--host", "0.0.0.0"]
    if registry_addr:
        cmd += ["--registry", registry_addr]
    cmd += list(extra_args or [])
    return ServiceSpec(name, [
        Component("replica", replicas, cmd,
                  resource=resource or Resource(1024, 1),
                  restart_policy=RESTART_ALWAYS),
    ])


def autoscaler_service_spec(name: str, *, registry_addr: str,
                            service: str,
                            resource: Optional[Resource] = None,
                            extra_args: Optional[List[str]] = None,
                            ) -> ServiceSpec:
    """The SLO controller as its own YARN long-running service, placed
    next to the replica fleet it scales (one instance; the RM restarts
    it like any daemon — the controller is stateless, its hysteresis
    counters rebuild within a few polls)."""
    cmd = [sys.executable, "-m", "hadoop_tpu.serving.autoscale",
           "--registry", registry_addr, "--service", service]
    cmd += list(extra_args or [])
    return ServiceSpec(name, [
        Component("autoscaler", 1, cmd,
                  resource=resource or Resource(256, 1),
                  restart_policy=RESTART_ALWAYS),
    ])


class ServingReplica:
    """Engine + HTTP server + registry lease, wired for one process."""

    def __init__(self, conf: Configuration, *, name: str,
                 checkpoint: str, preset: str,
                 registry_addr: Optional[Tuple[str, int]] = None,
                 bind: Tuple[str, int] = ("127.0.0.1", 0),
                 instance: Optional[str] = None):
        from hadoop_tpu.fs import FileSystem, Path
        from hadoop_tpu.serving.engine import DecodeEngine
        from hadoop_tpu.serving.server import ServingServer
        self.conf = conf
        self.name = name
        self.instance = instance or \
            f"{socket.gethostname()}-{uuid.uuid4().hex[:8]}"
        serving_read_defaults(conf)
        cfg = get_config(preset)
        fs = FileSystem.get(checkpoint, conf)
        ckpt_dir = Path(checkpoint).path
        # the weight plane (serving/weightplane.py): serving.parity
        # picks the tier. bitwise (default) loads the checkpoint's own
        # dtypes untouched; relaxed streams each shard through the int8
        # quantizer at load so the full f32 model is never host-resident
        from hadoop_tpu.serving.weightplane import weightplane_from_conf
        weights = weightplane_from_conf(conf)
        t0 = time.monotonic()
        self.quantize_seconds = 0.0
        if weights.relaxed:
            from hadoop_tpu.serving.weightplane import quantized_load
            params, step, wreport = quantized_load(
                fs, ckpt_dir, cfg, weights,
                io_workers=conf.get_int(IO_WORKERS_KEY, 4))
            self.quantize_seconds = wreport["quantize_seconds"]
        else:
            params, step = load_serving_params(
                fs, ckpt_dir, cfg,
                io_workers=conf.get_int(IO_WORKERS_KEY, 4))
        self.load_seconds = round(time.monotonic() - t0, 3)
        self.step = step
        # the tiered KV cache: host-RAM spill ring byte budget, and the
        # DFS prefix store on the SAME filesystem the checkpoint came
        # from (the replica already holds a client with hedged reads
        # armed). role=prefill replicas require the DFS tier — without
        # it they could never ship finished KV to a decode replica.
        self.role = conf.get("serving.role", "mixed")
        if self.role not in ("prefill", "decode", "mixed"):
            raise ValueError(f"serving.role must be prefill/decode/"
                             f"mixed, got {self.role!r}")
        self.kv_host_bytes = conf.get_int("serving.kv.host.bytes", 0)
        # any explicitly role'd replica defaults the DFS tier ON: the
        # handoff needs the prefill side writing AND the decode side
        # reading the same store. A mixed (default) replica keeps
        # today's behavior unless the deployment opts in.
        kv_dfs = conf.get_bool("serving.kv.dfs.enable",
                               self.role != "mixed")
        if self.role == "prefill" and not kv_dfs:
            raise ValueError("a prefill-role replica needs the DFS KV "
                             "tier (serving.kv.dfs.enable)")
        self.kv_dfs_enabled = kv_dfs
        # runtime comm ledger gate (obs.comm.timing, default on): the
        # replica process owns the conf, so it configures the
        # process-global ledger the CP prefill dispatches record into
        from hadoop_tpu.obs.comm import comm_runtime
        comm_runtime().configure(conf)
        metrics = ServingMetrics()
        # door QoS (serving/qos.py): the decay scheduler + the fair
        # admission queue must exist BEFORE the engine (the queue is
        # the engine's pending queue) and the gate after it (the shed
        # decision reads live queue depth)
        self.qos_enabled = conf.get_bool("serving.qos.enabled", True)
        qos_queue = qos_sched = None
        if self.qos_enabled:
            from hadoop_tpu.serving.qos import (DecayCostScheduler,
                                                FairAdmissionQueue)
            qos_sched = DecayCostScheduler(
                conf.get_int("serving.qos.levels", 4), conf)
            qos_queue = FairAdmissionQueue(qos_sched)
        self.engine = DecodeEngine(
            params, cfg,
            # unset = engine default (4), or budget-derived lanes when
            # serving.kv.hbm.bytes is set
            max_batch=conf.get_int("serving.max.batch", 0) or None,
            block_size=conf.get_int("serving.kv.block.size", 16),
            num_blocks=conf.get_int("serving.kv.num.blocks", 0) or None,
            max_context=conf.get_int("serving.max.context", 0) or None,
            prefill_chunk=conf.get_int("serving.prefill.chunk", 16),
            prefix_cache=conf.get_bool("serving.prefix_cache.enabled",
                                       True),
            kv_host_bytes=self.kv_host_bytes,
            kv_store_fs=fs if kv_dfs else None,
            kv_store_dir=conf.get("serving.kv.dfs.dir", "/kvcache"),
            kv_dfs_min_refs=conf.get_int("serving.kv.dfs.min-refs", 1),
            kv_codec=conf.get("serving.kv.codec", "raw"),
            # speculative cold-fetch window: how many chain blocks one
            # DFS round trip reads ahead (longctx chains want this
            # sized so paging is O(chain/window) round trips)
            kv_fetch_window=conf.get_int("serving.kv.fetch.window", 4),
            # speculative decoding: k draft tokens per decode lane from
            # the per-request n-gram index, verified in the same fused
            # step (0 = off; exact sampling either way)
            speculate_k=conf.get_int("serving.speculate.k", 0),
            speculate_ngram=conf.get_int("serving.speculate.ngram", 3),
            admission_queue=qos_queue,
            # drain-aware scale-in: ship resident cached prefixes to
            # the DFS tier before this replica exits
            drain_persist=conf.get_bool("serving.kv.drain.persist",
                                        True),
            # fixed HBM budget: KV pool (and lanes, when
            # serving.max.batch is unset) sized against the MEASURED
            # resident-weight bytes — int8 weights become lanes, capped
            # by serving.max.lanes (step rows scale with the lane count)
            hbm_bytes=conf.get_int("serving.kv.hbm.bytes", 0),
            max_lanes=conf.get_int("serving.max.lanes", 16),
            quantize_seconds=self.quantize_seconds,
            # expert-parallel MoE serving: capacity-factor override
            # (0 = the model config's), expert-dim shard count across
            # the replica's chips (0 = auto), and the relaxed-tier
            # all2all payload codec for the dispatch/combine legs
            moe_capacity_factor=conf.get_float(
                "serving.moe.capacity.factor", 0.0),
            moe_shards=conf.get_int("serving.moe.shards", 0),
            moe_a2a_codec=conf.get("serving.moe.a2a.codec", "int8"),
            metrics=metrics)
        qos_gate = None
        if self.qos_enabled:
            from hadoop_tpu.serving.qos import QoSGate
            qos_gate = QoSGate(conf, self.engine, metrics=metrics,
                               scheduler=qos_sched)
        # the long-context plane (serving/longctx): CP prefill across
        # the replica's mesh + streamed tier ingest + working-set
        # decode for prompts >= serving.longctx.min.tokens. Relaxed
        # tier ONLY — the CP softmax reassociation is not bitwise.
        self.longctx_enabled = conf.get_bool("serving.longctx.enabled",
                                             False)
        if self.longctx_enabled and weights.relaxed:
            from hadoop_tpu.serving.longctx import \
                longctx_plane_from_conf
            self.engine.attach_longctx(
                longctx_plane_from_conf(conf, cfg, self.engine))
        elif self.longctx_enabled:
            raise ValueError(
                "serving.longctx.enabled requires serving.parity="
                "relaxed (context-parallel prefill reassociates the "
                "softmax — not bitwise vs the single-chip step)")
        self.server = ServingServer(self.engine, conf, bind=bind,
                                    qos=qos_gate,
                                    # the autoscaler's /v1/admin/drain
                                    # retires the WHOLE replica, not
                                    # just the door
                                    drain_cb=self.drain_and_stop)
        # advertise a reachable address: the bind host when concrete, the
        # hostname when bound to the wildcard (cross-host routing must
        # not resolve to some other machine's loopback)
        self.advertise_host = bind[0] if bind[0] not in ("", "0.0.0.0") \
            else socket.gethostname()
        self.reg = None
        self._registry_addr = registry_addr
        self._stopped = threading.Event()
        self._drain_lock = threading.Lock()
        # set when drain_and_stop has fully FINISHED (persist included)
        # — _stopped only means it began. The process main loop exits
        # on this one: leaving on _stopped would kill the daemon
        # drain thread mid-persist and strand half-written KV blocks
        self.drained = threading.Event()

    def start(self) -> None:
        self.engine.start()
        self.server.start()
        self._top_source = None
        if self.qos_enabled and self.server.qos is not None:
            # /ws/v1/top on this replica's chassis reads the door's
            # decay-cost accounting — the serving twin of nntop, no
            # second counter (obs/top.py)
            from hadoop_tpu.obs.top import register_top_source
            self._top_source = f"serving.{self.name}.tenants"
            register_top_source(self._top_source,
                                self.server.qos.sched.snapshot)
        if self._registry_addr:
            from hadoop_tpu.registry.registry import (HEARTBEAT_ATTR,
                                                      RegistryClient,
                                                      ServiceRecord,
                                                      record_ttl)
            self.reg = RegistryClient(self._registry_addr, self.conf)
            self._record_ttl = record_ttl(self.conf)
            self.record = ServiceRecord(
                replica_path(self.name, self.instance),
                endpoints={"http":
                           f"{self.advertise_host}:{self.server.port}"},
                attributes={"state": "serving",
                            "slots": str(self.engine.max_batch),
                            "step": str(self.step),
                            # liveness stamp: routers/autoscalers skip
                            # the record once this ages past the TTL,
                            # even before the registry sweep evicts it
                            HEARTBEAT_ATTR: f"{time.time():.3f}",
                            # checkpoint pull latency: the fleet-level
                            # cold-start signal the autoscaler scales
                            # AHEAD of (a 5-minute load means growing
                            # 5 minutes before saturation)
                            "load_seconds": str(self.load_seconds),
                            # the weight plane: resident dtype +
                            # measured bytes + quantize-at-load cost —
                            # an autoscaler/dashboard reads capacity
                            # and cold-start directly off the record
                            "weight_dtype":
                                self.engine.weight_plane()["dtype"],
                            "weight_bytes":
                                str(self.engine.weight_bytes),
                            "quantize_seconds":
                                str(self.quantize_seconds),
                            # expert placement: count/shards/resident
                            # bytes (0s on dense) — the autoscaler sees
                            # an MoE replica's real HBM split without
                            # scraping /v1/health
                            "experts": str(self.engine.cfg.n_experts),
                            "expert_shards":
                                str(self.engine.expert_shards),
                            "expert_bytes":
                                str(self.engine.expert_bytes),
                            # disaggregation + tier capacities: the
                            # router routes long prompts to role=prefill
                            # and decodes on decode/mixed; an autoscaler
                            # reads the tier budgets for drain planning
                            "role": self.role,
                            "kv_host_bytes": str(self.kv_host_bytes),
                            # KV capacity in routable units: the
                            # router's prefill capacity gate computes
                            # a prompt's paged working set from these
                            # and never offers a monster prompt to a
                            # replica that cannot hold it
                            "kv_block_bytes":
                                str(self.engine.block_nbytes),
                            "kv_block_size":
                                str(self.engine.block_size),
                            "kv_hbm_blocks":
                                str(self.engine.pool.num_usable),
                            "longctx": "1" if self.longctx_enabled
                                       else "0",
                            # the plane's pinned prompt budget: the
                            # router's capacity gate treats a
                            # longctx+DFS replica as unbounded only
                            # UP TO this — offering a prompt past it
                            # would fail at the replica's door
                            "longctx_max_tokens": str(
                                self.engine.longctx_stats().get(
                                    "max_tokens", 0)),
                            "kv_dfs": "1" if self.kv_dfs_enabled
                                      else "0"})
            # the heartbeat loop below refreshes the record (stamp +
            # live load) — it IS the renewal, so no auto_renew twin
            self.reg.register(self.record, ttl_s=self._record_ttl,
                              auto_renew=False)
            from hadoop_tpu.util.misc import Daemon
            Daemon(self._heartbeat_loop,
                   f"replica-heartbeat-{self.instance}").start()
        log.info("serving replica %s/%s up on :%d (checkpoint step %d)",
                 self.name, self.instance, self.server.port, self.step)

    def _heartbeat_loop(self) -> None:
        """Refresh the registry record at a third of its TTL: the stamp
        keeps staleness checks green, the re-register keeps the lease
        alive (and recreates the record after a registry restart), and
        the live load attributes give the autoscaler a signal even when
        it cannot reach the replica's own door."""
        from hadoop_tpu.registry.registry import HEARTBEAT_ATTR
        period = max(0.2, self._record_ttl / 3.0)
        while not self._stopped.wait(period):
            self.record.attributes.update({
                HEARTBEAT_ATTR: f"{time.time():.3f}",
                "queue_depth": str(self.engine.queue_depth),
                "active": str(self.engine.num_active)})
            try:
                self.reg.register(self.record, ttl_s=self._record_ttl,
                                  auto_renew=False)
            except (RpcError, OSError) as e:
                # a dead registry must not kill the replica; the next
                # beat retries and re-registration heals a restart
                log.debug("registry heartbeat failed: %s", e)

    def drain_and_stop(self, timeout: float = 60.0) -> None:
        # atomic check-and-set: a SIGTERM racing an /v1/admin/drain
        # must yield exactly ONE drain sequence (two concurrent
        # engine.stop calls would race _thread=None against join)
        with self._drain_lock:
            mine = not self._stopped.is_set()
            self._stopped.set()
        if not mine:
            # a drain is already running on another thread: wait for
            # IT to finish rather than returning mid-persist
            self.drained.wait(timeout)
            return
        try:
            if self.reg is not None:
                # flip the record before unregistering so routers that
                # hold a cached copy see 'draining' on their next
                # refresh even if the lease outlives us briefly
                self.record.attributes["state"] = "draining"
                try:
                    self.reg.register(self.record, ttl_s=10.0,
                                      auto_renew=False)
                except (RpcError, OSError) as e:  # drain must not hang
                    log.debug("draining-state publish failed: %s",
                              e)                  # on a dead registry
            self.server.drain(timeout=timeout)
            if self.reg is not None:
                try:
                    self.reg.unregister(self.record.path)
                except (RpcError, OSError) as e:
                    log.debug("unregister on drain failed: %s", e)
                self.reg.close()
            self.server.stop()
        finally:
            if getattr(self, "_top_source", None):
                from hadoop_tpu.obs.top import unregister_top_source
                unregister_top_source(self._top_source)
            self.drained.set()


def replica_main(argv: List[str],
                 conf: Optional[Configuration] = None) -> int:
    """Entry point of one replica process (container / `serve` CLI)."""
    conf = conf or Configuration()
    args = dict(name="serving", checkpoint=None, preset="tiny",
                registry=None, port=0, host="127.0.0.1", role=None)
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--replica":
            i += 1
            continue
        key = a.lstrip("-").replace("-", "_")
        if key in args and i + 1 < len(argv):
            args[key] = argv[i + 1]
            i += 2
        else:
            print(f"unknown serve option {a}", file=sys.stderr)
            return 2
    if not args["checkpoint"]:
        print("usage: serve --checkpoint URI --preset NAME "
              "[--name SVC] [--registry HOST:PORT] [--port N]",
              file=sys.stderr)
        return 2
    if args["role"]:
        conf.set("serving.role", str(args["role"]))
    registry_addr = None
    if args["registry"]:
        host, _, port = str(args["registry"]).rpartition(":")
        registry_addr = (host or "127.0.0.1", int(port))
    # a fresh replica compiles both step shapes; the persistent cache
    # makes that a once-per-machine cost (util/jaxcache.py)
    from hadoop_tpu.util.jaxcache import configure_compile_cache
    configure_compile_cache()
    replica = ServingReplica(
        conf, name=str(args["name"]), checkpoint=str(args["checkpoint"]),
        preset=str(args["preset"]), registry_addr=registry_addr,
        bind=(str(args["host"]), int(args["port"])))
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    replica.start()
    try:
        while not stop.wait(0.5):
            if replica.drained.is_set():
                # an autoscaler retired us through /v1/admin/drain and
                # the drain FINISHED (prefixes persisted, in-flight
                # requests delivered) — exit the container cleanly
                break
    finally:
        replica.drain_and_stop()
    return 0


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    sys.exit(replica_main(sys.argv[1:]))
