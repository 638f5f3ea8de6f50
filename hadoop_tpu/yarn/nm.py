"""NodeAgent: the per-host daemon that runs containers.

Parity with the reference NodeManager (ref: nodemanager/NodeManager.java
(1,055 LoC), containermanager/ContainerManagerImpl.java:933 startContainers,
localizer/ (resource localization), launcher/ContainerLaunch.java:103/:194,
DefaultContainerExecutor, monitor/ContainersMonitorImpl.java:60,
logaggregation/LogAggregationService.java): registers with the RM, runs
containers as real OS processes in per-container work dirs with localized
resources and captured stdout/stderr, monitors them, reports exits on the RM
heartbeat, executes cleanup commands, and aggregates finished containers'
logs to the DFS.

TPU-first: the node advertises ``tpu_chips`` and assigns each container an
exclusive chip set via ``HTPU_TPU_CHIPS`` (comma-separated indices) — the
device-plugin role (ref: resourceplugin/ GPU/FPGA plugins), expressed as env
isolation because TPU chips bind per-process via runtime env.

The reference's setuid C container-executor (main.c:656) maps to the
``executor`` seam: DefaultExecutor (same-uid subprocess) here; the native
launcher lands with hadoop_tpu/native.
"""

from __future__ import annotations

import logging
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from hadoop_tpu.conf import Configuration
from hadoop_tpu.ipc import Client, Server, get_proxy
from hadoop_tpu.service import AbstractService
from hadoop_tpu.util.misc import Daemon
from hadoop_tpu.yarn.records import (Container, ContainerId,
                                     ContainerLaunchContext, ContainerStatus,
                                     NodeId, Resource)

log = logging.getLogger(__name__)


class ContainerExecutor:
    """Seam for container launch (ref: server/nodemanager/ContainerExecutor
    .java; LinuxContainerExecutor.java:519 launchContainer is the native
    variant)."""

    def launch(self, workdir: str, commands: List[str],
               env: Dict[str, str]) -> subprocess.Popen:
        raise NotImplementedError

    def signal(self, proc: subprocess.Popen, sig: int) -> None:
        raise NotImplementedError


class DefaultExecutor(ContainerExecutor):
    """Same-uid subprocess with its own process group.
    Ref: DefaultContainerExecutor.java."""

    def launch(self, workdir: str, commands: List[str],
               env: Dict[str, str]) -> subprocess.Popen:
        full_env = dict(os.environ)
        full_env.update(env)
        out = open(os.path.join(workdir, "stdout"), "wb")
        err = open(os.path.join(workdir, "stderr"), "wb")
        return subprocess.Popen(
            commands, cwd=workdir, env=full_env, stdout=out, stderr=err,
            start_new_session=True)  # own pgid → kill the whole tree

    def signal(self, proc: subprocess.Popen, sig: int) -> None:
        try:
            os.killpg(os.getpgid(proc.pid), sig)
        except (ProcessLookupError, PermissionError):
            pass


class NativeExecutor(ContainerExecutor):
    """Launch through the C++ htpu-container-executor binary: the
    container runs in its own session with rlimits (and a cgroup when
    configured) applied BEFORE user code starts — the reference's
    LinuxContainerExecutor.java:519 → native launch_container_as_user
    chain, with the setuid arm active only when the binary runs as root.
    Selected via conf ``yarn.nodemanager.container-executor.class =
    native`` when the binary is built."""

    def __init__(self, mem_limit_mb: int = 0, nofile: int = 8192,
                 cgroup_root: str = ""):
        import hadoop_tpu.native as _nat
        binary = os.path.join(os.path.dirname(
            os.path.abspath(_nat.__file__)), "htpu-container-executor")
        if not os.path.exists(binary):
            _nat._build()
        if not os.path.exists(binary):
            raise FileNotFoundError(
                "htpu-container-executor not built (no toolchain?)")
        self.binary = binary
        self.mem_limit_mb = mem_limit_mb
        self.nofile = nofile
        self.cgroup_root = cgroup_root

    def launch(self, workdir: str, commands: List[str],
               env: Dict[str, str]) -> subprocess.Popen:
        full_env = dict(os.environ)
        full_env.update(env)
        cgroup = "-"
        if self.cgroup_root:
            cgroup = os.path.join(self.cgroup_root,
                                  os.path.basename(workdir))
        argv = [self.binary, workdir,
                os.path.join(workdir, "stdout"),
                os.path.join(workdir, "stderr"),
                str(self.mem_limit_mb), str(self.nofile), cgroup,
                "--"] + commands
        return subprocess.Popen(argv, cwd=workdir, env=full_env,
                                stdout=subprocess.DEVNULL,
                                start_new_session=True)

    def signal(self, proc: subprocess.Popen, sig: int) -> None:
        try:
            os.killpg(os.getpgid(proc.pid), sig)
        except (ProcessLookupError, PermissionError):
            pass


class _KilledBeforeLaunch(Exception):
    """Internal: stop_container won the race against the launch step."""


class _RunningContainer:
    def __init__(self, container: Container, ctx: ContainerLaunchContext,
                 workdir: str, chips: List[int]):
        self.container = container
        self.ctx = ctx
        self.workdir = workdir
        self.chips = chips
        self.proc: Optional[subprocess.Popen] = None
        self.state = "NEW"
        self.exit_code: Optional[int] = None
        self.diagnostics = ""
        self.start_ts = time.time()
        self.published_volumes = []
        # closes the kill-during-localization hole: _kill and the launch
        # step synchronize on this, so a stop that lands before the
        # process exists prevents the launch instead of no-oping (the
        # process would otherwise run forever unmanaged)
        self.killed = False
        self.lock = threading.Lock()


class ContainerManagerProtocol:
    """NM's RPC surface (ref: ContainerManagerImpl.java:933 startContainers;
    ClientAMProtocol-ish status calls)."""

    def __init__(self, nm: "NodeAgent"):
        self.nm = nm

    def start_container(self, container_wire: Dict, ctx_wire: Dict) -> Dict:
        container = Container.from_wire(container_wire)
        ctx = ContainerLaunchContext.from_wire(ctx_wire)
        self.nm.start_container(container, ctx)
        return {"ok": True}

    def stop_container(self, container_id_wire: Dict) -> bool:
        self.nm.stop_container(ContainerId.from_wire(container_id_wire))
        return True

    def get_container_status(self, container_id_wire: Dict) -> Optional[Dict]:
        cid = ContainerId.from_wire(container_id_wire)
        rc = self.nm.containers.get(cid)
        if rc is None:
            return None
        return ContainerStatus(cid, rc.state, rc.exit_code
                               if rc.exit_code is not None else -1000,
                               rc.diagnostics).to_wire()


class NodeAgent(AbstractService):
    def __init__(self, conf: Configuration, rm_addr: Tuple[str, int],
                 work_root: Optional[str] = None,
                 executor: Optional[ContainerExecutor] = None):
        super().__init__("NodeAgent")
        self.rm_addr = rm_addr
        self.work_root = work_root or conf.get(
            "yarn.nodemanager.local-dirs", "/tmp/htpu-nm")
        if executor is None and conf.get(
                "yarn.nodemanager.container-executor.class", "") == "native":
            executor = NativeExecutor(
                mem_limit_mb=conf.get_int(
                    "yarn.nodemanager.container.memory-limit-mb", 0),
                cgroup_root=conf.get(
                    "yarn.nodemanager.cgroups.root", ""))
        self.executor = executor or DefaultExecutor()
        self.containers: Dict[ContainerId, _RunningContainer] = {}
        self._lock = threading.Lock()
        self._completed_unreported: List[ContainerStatus] = []
        self._stop_event = threading.Event()
        self._client: Optional[Client] = None
        self.rpc: Optional[Server] = None
        self._chip_pool: List[int] = []
        self.aux_services: List = []

    # ------------------------------------------------------------- lifecycle

    def service_init(self, conf: Configuration) -> None:
        os.makedirs(self.work_root, exist_ok=True)
        # Auxiliary services (ref: containermanager/AuxServices.java — how
        # ShuffleHandler rides the NM): conf lists module:Class entries; each
        # gets start()/stop() and injects env into every container.
        self.aux_services = []
        for ref in conf.get_list("yarn.nodemanager.aux-services"):
            mod, _, name = ref.partition(":")
            import importlib
            cls = getattr(importlib.import_module(mod), name)
            self.aux_services.append(cls(conf, self.work_root))
        self.resource = Resource(
            conf.get_int("yarn.nodemanager.resource.memory-mb", 8192),
            conf.get_int("yarn.nodemanager.resource.cpu-vcores", 8),
            conf.get_int("yarn.nodemanager.resource.tpu-chips", 0))
        self._chip_pool = list(range(self.resource.tpu_chips))
        self.heartbeat_interval = conf.get_time_seconds(
            "yarn.nodemanager.heartbeat.interval", 1.0)
        self._client = Client(conf)
        bind_host = conf.get("yarn.nodemanager.bind-host", "127.0.0.1")
        self.rpc = Server(conf, bind=(bind_host, 0), num_handlers=4,
                          name="nm")
        self.rpc.register_protocol("ContainerManagerProtocol",
                                   ContainerManagerProtocol(self))
        self.host = bind_host
        # ATSv2-style per-app timeline collectors (ref:
        # PerNodeTimelineCollectorsAuxService): spun up with an app's
        # first container here, stopped when the RM reports the app
        # finished (heartbeat response).
        # CSI adaptor (ref: yarn-csi CsiAdaptorServices on the NM)
        from hadoop_tpu.yarn.csi import CsiAdaptor
        try:
            self.csi = CsiAdaptor()
        except Exception:  # noqa: BLE001 — volume support is optional
            self.csi = None
        self.timeline = None
        if conf.get_bool("yarn.timeline-service.enabled", False):
            from hadoop_tpu.conf.keys import YARN_TIMELINE_STORE_DIR
            from hadoop_tpu.yarn.timeline import TimelineCollectorManager
            self.timeline = TimelineCollectorManager(
                conf.get(YARN_TIMELINE_STORE_DIR,
                         os.path.join(self.work_root, "timeline")),
                backend=conf.get(
                    "yarn.timeline-service.store.backend", "auto"))

    def service_start(self) -> None:
        for aux in self.aux_services:
            aux.start()
        self.rpc.start()
        self.node_id = NodeId(self.host, self.rpc.port)
        self._rm = get_proxy("ResourceTrackerProtocol", self.rm_addr,
                             client=self._client)
        Daemon(self._heartbeat_loop, f"nm-{self.rpc.port}").start()
        log.info("NodeAgent %s up (%r)", self.node_id, self.resource)

    def service_stop(self) -> None:
        self._stop_event.set()
        with self._lock:
            running = list(self.containers.values())
        for rc in running:
            self._kill(rc)
        for aux in self.aux_services:
            try:
                aux.stop()
            except Exception as e:  # noqa: BLE001 — aux is plugin code
                log.debug("aux service stop failed: %s", e)
        if self.timeline is not None:
            self.timeline.stop_all()
        if self.rpc:
            self.rpc.stop()
        if self._client:
            self._client.stop()

    @property
    def nm_address(self) -> str:
        return f"{self.host}:{self.rpc.port}"

    # ------------------------------------------------------------ containers

    def start_container(self, container: Container,
                        ctx: ContainerLaunchContext) -> None:
        cid = container.container_id
        with self._lock:
            if cid in self.containers:
                return  # idempotent retry
            chips = self._take_chips(container.resource.tpu_chips)
            workdir = os.path.join(self.work_root, str(cid))
            rc = _RunningContainer(container, ctx, workdir, chips)
            self.containers[cid] = rc
        if self.timeline is not None:
            self.timeline.collector_for(str(cid.app_id)).put_entity(
                "YARN_CONTAINER", str(cid), "CREATED",
                node=str(self.node_id) if hasattr(self, "node_id")
                else "", memory_mb=container.resource.memory_mb)
        Daemon(self._launch, f"launch-{cid}", args=(rc,)).start()

    def _take_chips(self, n: int) -> List[int]:
        if n > len(self._chip_pool):
            # refuse rather than under-allocate: a TPU job granted fewer
            # chips than its resource ask (or zero, which disables the
            # accelerator runtime entirely) would run wrong silently
            raise IOError(f"insufficient TPU chips: want {n}, "
                          f"have {len(self._chip_pool)}")
        chips = self._chip_pool[:n]
        del self._chip_pool[:n]
        return chips

    def _launch(self, rc: _RunningContainer) -> None:
        """Localize → launch → wait. Ref: ContainerLaunch.call:194."""
        cid = rc.container.container_id
        try:
            os.makedirs(rc.workdir, exist_ok=True)
            rc.state = "LOCALIZING"
            self._localize(rc)
            self._publish_volumes(rc)
            env = dict(rc.ctx.env)
            for aux in self.aux_services:
                env.update(aux.container_env())
                if rc.ctx.service_data and hasattr(aux, "initialize_app"):
                    # per-app payloads for aux services (ref:
                    # AuxServices.initializeApplication — the shuffle
                    # service learns the job's token secret this way);
                    # idempotent, so per-container delivery is fine
                    try:
                        aux.initialize_app(rc.ctx.service_data)
                    except Exception as e:  # noqa: BLE001 — advisory
                        log.warning("aux service_data init failed: %s", e)
            env["HTPU_CONTAINER_ID"] = str(cid)
            env["HTPU_WORK_DIR"] = rc.workdir
            if rc.chips:
                # advisory: no libtpu variable is derived from it (one
                # visible-chips variable does not isolate two processes
                # on the installed libtpu — README, "One process per
                # chip"), so a host runs one chip-holding container
                env["HTPU_TPU_CHIPS"] = ",".join(map(str, rc.chips))
            else:
                # A chip belongs to one process at a time: a container
                # granted no chips that initializes JAX must land on
                # the CPU, not take the device from the container that
                # was granted it.
                env["JAX_PLATFORMS"] = "cpu"
            with rc.lock:
                if rc.killed:
                    raise _KilledBeforeLaunch()
                rc.proc = self.executor.launch(rc.workdir,
                                               rc.ctx.commands, env)
            rc.state = "RUNNING"
            exit_code = rc.proc.wait()
            rc.exit_code = exit_code
            rc.state = "COMPLETE"
            if exit_code != 0:
                rc.diagnostics = self._tail_stderr(rc)
        except _KilledBeforeLaunch:
            rc.state = "COMPLETE"
            rc.exit_code = -105  # the reference's KILLED_BY_RESOURCEMANAGER
            rc.diagnostics = "killed before launch"
        except Exception as e:  # noqa: BLE001
            rc.state = "COMPLETE"
            rc.exit_code = -1001
            rc.diagnostics = f"launch failed: {e}"
            log.warning("Container %s launch failed: %s", cid, e)
        finally:
            # volumes must unmount BEFORE the workdir is ever rmtree'd
            # (a live fuse mount under rmtree would walk the DFS)
            self._unpublish_volumes(rc)
            with self._lock:
                self._chip_pool.extend(rc.chips)
                self._completed_unreported.append(ContainerStatus(
                    cid, "COMPLETE", rc.exit_code, rc.diagnostics))
            if self.timeline is not None:
                # Publish only through a LIVE collector, atomically — a
                # straggler finishing after the app's collector stopped
                # must be dropped, not resurrect it (put_if_active holds
                # the manager lock across check+put; the old
                # has_collector/collector_for pair raced the linger
                # timer into re-creating a stopped collector).
                # resource-time metrics ride the FINISHED event so the
                # ATSv2 reader can aggregate flow-run cost.
                dur = max(0.0, time.time() - rc.start_ts)
                self.timeline.put_if_active(
                    str(cid.app_id),
                    "YARN_CONTAINER", str(cid), "FINISHED",
                    exit_code=rc.exit_code,
                    duration_s=round(dur, 3),
                    memory_mb=rc.container.resource.memory_mb,
                    vcores=rc.container.resource.vcores,
                    mb_seconds=round(
                        dur * rc.container.resource.memory_mb, 1),
                    vcore_seconds=round(
                        dur * rc.container.resource.vcores, 3))

    def _publish_volumes(self, rc: _RunningContainer) -> None:
        """CSI volume publish under the workdir (ref: yarn-csi's
        ContainerVolumePublisher running before ContainerLaunch)."""
        vols = getattr(rc.ctx, "volumes", None) or []
        if not vols:
            return
        if self.csi is None:
            raise IOError("container requests volumes but this NM has "
                          "no CSI adaptor")
        published = []
        try:
            for v in vols:
                target = os.path.join(rc.workdir,
                                      v.get("target", "volume"))
                self.csi.node_publish_volume(v["driver"], v["id"], target,
                                             v.get("options"))
                published.append((v, target))
        except Exception:
            for v, target in published:
                try:
                    self.csi.node_unpublish_volume(v["driver"], v["id"],
                                                   target)
                except (OSError, IOError) as e:
                    log.debug("rollback unpublish failed: %s", e)
            raise
        rc.published_volumes = published

    def _unpublish_volumes(self, rc: _RunningContainer) -> None:
        for v, target in getattr(rc, "published_volumes", None) or []:
            try:
                self.csi.node_unpublish_volume(v["driver"], v["id"],
                                               target)
            except Exception as e:  # noqa: BLE001
                log.warning("unpublish of %s failed: %s", v.get("id"), e)
        rc.published_volumes = []

    def _localize(self, rc: _RunningContainer) -> None:
        """Fetch DFS resources into the work dir.
        Ref: containermanager/localizer/ResourceLocalizationService."""
        if not rc.ctx.local_resources:
            return
        from hadoop_tpu.fs import FileSystem
        for name, uri in rc.ctx.local_resources.items():
            dst = os.path.join(rc.workdir, name)
            if uri.startswith("file:") or uri.startswith("/"):
                src = uri[len("file://"):] if uri.startswith("file://") \
                    else uri
                shutil.copyfile(src, dst)
            else:
                fs = FileSystem.get(uri, self.config)
                from hadoop_tpu.fs.filesystem import Path
                with open(dst, "wb") as f:
                    f.write(fs.read_all(Path(uri).path))
                fs.close()

    def _tail_stderr(self, rc: _RunningContainer, n: int = 2048) -> str:
        try:
            with open(os.path.join(rc.workdir, "stderr"), "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def stop_container(self, cid: ContainerId) -> None:
        with self._lock:
            rc = self.containers.get(cid)
        if rc is not None:
            self._kill(rc)

    def _kill(self, rc: _RunningContainer) -> None:
        """SIGTERM, grace, SIGKILL. Ref: ContainerLaunch.cleanupContainer."""
        with rc.lock:
            rc.killed = True  # a not-yet-launched process must never start
            if rc.proc is None or rc.proc.poll() is not None:
                return
        self.executor.signal(rc.proc, signal.SIGTERM)

        def force_kill():
            try:
                rc.proc.wait(timeout=2.0)
            except subprocess.TimeoutExpired:
                self.executor.signal(rc.proc, signal.SIGKILL)
        Daemon(force_kill, "container-killer").start()

    # -------------------------------------------------------------- RM link

    def _heartbeat_loop(self) -> None:
        registered = False
        while not self._stop_event.is_set():
            statuses: List[ContainerStatus] = []
            try:
                if not registered:
                    # report live containers so a restarted RM re-adopts
                    # them (work-preserving restart; ref:
                    # NMContainerStatus in RegisterNodeManagerRequest)
                    with self._lock:
                        live = [rc.container.to_wire()
                                for rc in self.containers.values()
                                if rc.state in ("NEW", "LOCALIZING",
                                                "RUNNING")]
                    resp0 = self._rm.register_node_manager(
                        self.node_id.to_wire(), self.resource.to_wire(),
                        self.nm_address, live)
                    for cw in (resp0 or {}).get("cleanup", []):
                        self.stop_container(ContainerId.from_wire(cw))
                    registered = True
                with self._lock:
                    statuses = self._completed_unreported
                    self._completed_unreported = []
                resp = self._rm.node_heartbeat(
                    self.node_id.to_wire(), [s.to_wire() for s in statuses])
                if resp.get("action") == "reregister":
                    registered = False
                    continue
                for cw in resp.get("cleanup", []):
                    cid = ContainerId.from_wire(cw)
                    self.stop_container(cid)
                    with self._lock:
                        rc = self.containers.pop(cid, None)
                    if rc is not None and os.path.isdir(rc.workdir):
                        shutil.rmtree(rc.workdir, ignore_errors=True)
                if self.timeline is not None:
                    for app_id in resp.get("finished_apps", []):
                        self.timeline.stop_collector(app_id)
            except Exception as e:  # noqa: BLE001 — survive RM bounces
                if statuses:
                    with self._lock:  # don't lose exit reports
                        self._completed_unreported = (
                            statuses + self._completed_unreported)
                log.debug("NM heartbeat failed (%s); retrying", e)
                registered = False
                self._rm = get_proxy("ResourceTrackerProtocol", self.rm_addr,
                                     client=self._client)
            self._stop_event.wait(self.heartbeat_interval)
