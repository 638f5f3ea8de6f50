"""End-to-end YARN tests: real RM + node agents + subprocess containers.
(Parity targets: ref TestDistributedShell, MiniYARNCluster-based RM/NM
integration tests.)"""

import os
import time

import pytest

from hadoop_tpu.conf import Configuration
from hadoop_tpu.examples.distributed_shell import submit
from hadoop_tpu.testing.minicluster import MiniYARNCluster
from hadoop_tpu.yarn.client import YarnClient
from hadoop_tpu.yarn.records import (ApplicationSubmissionContext, AppState,
                                     ContainerLaunchContext, Resource)


@pytest.fixture(scope="module")
def cluster():
    with MiniYARNCluster(num_nodes=2) as c:
        yield c


@pytest.fixture(scope="module")
def yc(cluster):
    client = YarnClient(cluster.rm_addr,
                        Configuration(other=cluster.conf))
    yield client
    client.close()


def test_cluster_registration(cluster, yc):
    metrics = yc.cluster_metrics()
    assert metrics["num_node_managers"] == 2
    total = Resource.from_wire(metrics["total_resource"])
    assert total.memory_mb == 2 * 4096
    nodes = yc.nodes()
    assert len(nodes) == 2


def test_distributed_shell_end_to_end(cluster, yc, tmp_path):
    """Canonical acceptance: AM + 3 task containers, all real processes."""
    marker_dir = str(tmp_path)
    app_id = submit(
        cluster.rm_addr,
        ["bash", "-c",
         f"echo task-$HTPU_SHELL_INDEX > {marker_dir}/out-$HTPU_SHELL_INDEX"],
        n=3, resource=Resource(256, 1),
        conf=Configuration(other=cluster.conf))
    report = yc.wait_for_completion(app_id, timeout=60)
    assert report.state == AppState.FINISHED, report.diagnostics
    files = sorted(os.listdir(marker_dir))
    assert files == ["out-0", "out-1", "out-2"]
    assert open(os.path.join(marker_dir, "out-1")).read().strip() == "task-1"


def test_failing_command_fails_app(cluster, yc):
    app_id = submit(cluster.rm_addr, ["bash", "-c", "exit 3"], n=1,
                    conf=Configuration(other=cluster.conf))
    report = yc.wait_for_completion(app_id, timeout=60)
    # The AM observes the nonzero container exit and unregisters FAILED;
    # the app as a whole records the failure.
    assert report.state in (AppState.FAILED, AppState.FINISHED)
    assert report.final_status == AppState.FAILED or \
        "failed" in report.diagnostics


def test_kill_application(cluster, yc):
    app_id = submit(cluster.rm_addr, ["sleep", "300"], n=1,
                    conf=Configuration(other=cluster.conf))
    # Let it reach RUNNING, then kill.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if yc.application_report(app_id).state == AppState.RUNNING:
            break
        time.sleep(0.1)
    yc.kill_application(app_id)
    report = yc.wait_for_completion(app_id, timeout=30)
    assert report.state == AppState.KILLED


def test_am_failure_retries_then_fails(cluster, yc):
    """An AM that crashes is retried up to max_attempts, then the app fails.
    Ref: RMAppImpl attempt-retry transitions."""
    app_id_obj, _ = YarnClient(
        cluster.rm_addr, Configuration(other=cluster.conf)
    ).create_application()
    ctx = ApplicationSubmissionContext(
        app_id_obj, "crashy-am",
        ContainerLaunchContext(["bash", "-c", "exit 7"]),
        am_resource=Resource(128, 1), max_attempts=2)
    yc.rm.submit_application(ctx.to_wire())
    report = yc.wait_for_completion(app_id_obj, timeout=60)
    assert report.state == AppState.FAILED
    assert report.attempt_no == 2
    assert "exited 7" in report.diagnostics or "attempts" in report.diagnostics


def test_tpu_chip_isolation(cluster, yc, tmp_path):
    """Containers get disjoint HTPU_TPU_CHIPS assignments."""
    with MiniYARNCluster(num_nodes=1,
                         node_resource={"tpu_chips": 4}) as tpu_cluster:
        marker = str(tmp_path / "chips")
        os.makedirs(marker, exist_ok=True)
        app_id = submit(
            tpu_cluster.rm_addr,
            ["bash", "-c",
             f"echo $HTPU_TPU_CHIPS > {marker}/$HTPU_CONTAINER_ID"],
            n=2, resource=Resource(128, 1, 2),
            conf=Configuration(other=tpu_cluster.conf))
        client = YarnClient(tpu_cluster.rm_addr,
                            Configuration(other=tpu_cluster.conf))
        try:
            report = client.wait_for_completion(app_id, timeout=60)
            assert report.state == AppState.FINISHED, report.diagnostics
        finally:
            client.close()
        seen = set()
        for name in os.listdir(marker):
            chips = open(os.path.join(marker, name)).read().strip()
            chip_set = set(chips.split(","))
            assert len(chip_set) == 2
            assert not (seen & chip_set), "chip double-assignment"
            seen |= chip_set
        assert len(seen) == 4


def test_chipless_container_is_pinned_to_cpu(cluster, yc, monkeypatch):
    """A chip belongs to one process: a container granted no chips is
    launched with JAX_PLATFORMS=cpu (and none of the retired plug-in's
    pool variables), so initializing JAX there cannot take the device
    from the container that was granted it."""
    launched = []
    for nm in cluster.node_agents:
        def spy(workdir, commands, env, _real=nm.executor.launch):
            launched.append(dict(env))
            return _real(workdir, commands, env)
        monkeypatch.setattr(nm.executor, "launch", spy)
    app_id = submit(cluster.rm_addr, ["bash", "-c", "true"], n=1,
                    conf=Configuration(other=cluster.conf))
    report = yc.wait_for_completion(app_id, timeout=60)
    assert report.state == AppState.FINISHED, report.diagnostics
    assert launched
    for env in launched:
        assert "HTPU_TPU_CHIPS" not in env
        assert env.get("JAX_PLATFORMS") == "cpu"
        assert not any("POOL_IPS" in key for key in env)


def test_rm_restart_recovers_finished_state(cluster, yc, tmp_path):
    marker = str(tmp_path / "done")
    app_id = submit(cluster.rm_addr, ["bash", "-c", f"touch {marker}"], n=1,
                    conf=Configuration(other=cluster.conf))
    report = yc.wait_for_completion(app_id, timeout=60)
    assert report.state == AppState.FINISHED
    # State store has the outcome on disk.
    store = cluster.rm.state_store.load_all()
    entry = [d for d in store if d["state"] == AppState.FINISHED]
    assert entry, store
