"""End-to-end DFS tests on the in-process minicluster.

Parity targets: ref TestDistributedFileSystem, TestReplication,
TestFileCreation, TestDataTransferProtocol, TestFsck-adjacent flows — real
NN + 3 DNs, real RPC + streaming protocols, one process.
"""

import os
import time

import pytest

from hadoop_tpu.conf import Configuration
from hadoop_tpu.testing.minicluster import MiniDFSCluster


@pytest.fixture(scope="module")
def cluster():
    with MiniDFSCluster(num_datanodes=3) as c:
        yield c


@pytest.fixture(scope="module")
def fs(cluster):
    return cluster.get_filesystem()


def test_write_read_roundtrip(cluster, fs):
    data = os.urandom(300_000)  # < 1 block
    with fs.create("/roundtrip.bin") as out:
        out.write(data)
    with fs.open("/roundtrip.bin") as f:
        assert f.read() == data
    st = fs.get_file_status("/roundtrip.bin")
    assert st.length == len(data)
    assert not st.is_dir


def test_multi_block_file(cluster, fs):
    # 1 MB blocks (fast_conf) → 3.5 MB = 4 blocks.
    data = os.urandom(3 * 1024 * 1024 + 512 * 1024)
    with fs.create("/big.bin") as out:
        # Write in odd-sized chunks to exercise packet buffering.
        for off in range(0, len(data), 97_531):
            out.write(data[off:off + 97_531])
    with fs.open("/big.bin") as f:
        got = f.read()
    assert got == data
    locs = cluster.get_filesystem().client.get_block_locations("/big.bin")
    assert len(locs["blocks"]) == 4


def test_file_longer_than_the_ack_window_crosses_blocks(cluster):
    """Packet seqs are stream-global but each block's pipeline counts
    its own acks: a block that starts after the stream's first
    ``max-packets-in-flight`` packets must still send (it used to stall
    before its first packet — any file past 64 MB, e.g. a flagship
    checkpoint shard)."""
    from hadoop_tpu.dfs.client.filesystem import DistributedFileSystem
    conf = Configuration(other=cluster.conf)
    conf.set("dfs.client.write.max-packets-in-flight", "2")
    wfs = DistributedFileSystem([cluster.nn_addr], conf)
    try:
        data = os.urandom(3 * 1024 * 1024 + 17)   # 4 blocks, 8 seqs
        t0 = time.monotonic()
        wfs.write_all("/window.bin", data)
        assert time.monotonic() - t0 < 5.0         # no ack-timeout stall
        assert wfs.read_all("/window.bin") == data
    finally:
        wfs.close()


def test_replication_factor_honored(cluster, fs):
    with fs.create("/rep.bin", replication=2) as out:
        out.write(b"hello replication")
    time.sleep(0.3)  # let incremental reports land
    locs = fs.client.get_block_locations("/rep.bin")
    assert len(locs["blocks"]) == 1
    assert len(locs["blocks"][0]["locs"]) == 2


def test_empty_file(cluster, fs):
    with fs.create("/empty") as out:
        pass
    st = fs.get_file_status("/empty")
    assert st.length == 0
    with fs.open("/empty") as f:
        assert f.read() == b""


def test_mkdirs_listing_delete(cluster, fs):
    fs.mkdirs("/dir/sub")
    fs.write_all("/dir/a.txt", b"aaa")
    fs.write_all("/dir/b.txt", b"bbb")
    names = [s.path for s in fs.list_status("/dir")]
    assert names == ["/dir/a.txt", "/dir/b.txt", "/dir/sub"]
    assert fs.delete("/dir", recursive=True)
    assert not fs.exists("/dir")


def test_rename(cluster, fs):
    fs.write_all("/src.txt", b"content")
    fs.rename("/src.txt", "/dst.txt")
    assert not fs.exists("/src.txt")
    assert fs.read_all("/dst.txt") == b"content"


def test_overwrite_semantics(cluster, fs):
    fs.write_all("/ow.txt", b"v1")
    with pytest.raises(FileExistsError):
        with fs.create("/ow.txt", overwrite=False) as out:
            out.write(b"nope")
    fs.write_all("/ow.txt", b"v2", overwrite=True)
    assert fs.read_all("/ow.txt") == b"v2"


def test_seek_and_pread(cluster, fs):
    data = bytes(range(256)) * 5000  # 1.28 MB, crosses a block boundary
    fs.write_all("/seek.bin", data)
    with fs.open("/seek.bin") as f:
        f.seek(1000)
        assert f.read(100) == data[1000:1100]
        assert f.pread(1024 * 1024 - 50, 100) == \
            data[1024 * 1024 - 50:1024 * 1024 + 50]  # spans block edge
        f.seek(0)
        assert f.read(10) == data[:10]


def test_concurrent_writers_distinct_files(cluster, fs):
    import threading
    payload = {i: os.urandom(200_000) for i in range(6)}
    errs = []

    def write(i):
        try:
            fs.write_all(f"/conc/f{i}", payload[i])
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=write, args=(i,)) for i in payload]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    for i, data in payload.items():
        assert fs.read_all(f"/conc/f{i}") == data


def test_single_writer_enforced(cluster, fs):
    from hadoop_tpu.dfs.protocol.records import AlreadyBeingCreatedError
    out = fs.create("/locked.txt")
    out.write(b"partial")
    other = cluster.get_filesystem()
    try:
        with pytest.raises((AlreadyBeingCreatedError, FileExistsError)):
            other.create("/locked.txt", overwrite=True)
    finally:
        out.close()
        other.close()


def test_read_failover_on_dead_datanode(cluster, fs):
    """Kill a DN holding a replica; reads must fail over to survivors."""
    data = os.urandom(400_000)
    fs.write_all("/failover.bin", data)
    time.sleep(0.3)
    locs = fs.client.get_block_locations("/failover.bin")
    holder_uuids = {l["u"] for l in locs["blocks"][0]["locs"]}
    victim_idx = next(i for i, dn in enumerate(cluster.datanodes)
                      if dn is not None and dn.uuid in holder_uuids)
    cluster.kill_datanode(victim_idx)
    try:
        with fs.open("/failover.bin") as f:
            assert f.read() == data
    finally:
        cluster.restart_datanode(victim_idx)
        cluster.wait_active()


def test_re_replication_after_datanode_death(cluster, fs):
    """The RedundancyMonitor must restore replication after a DN dies."""
    data = os.urandom(100_000)
    fs.write_all("/heal.bin", data, overwrite=True)
    time.sleep(0.3)
    locs = fs.client.get_block_locations("/heal.bin")
    block_id = locs["blocks"][0]["b"]["id"]
    holders = {l["u"] for l in locs["blocks"][0]["locs"]}
    assert len(holders) == 3
    victim_idx = next(i for i, dn in enumerate(cluster.datanodes)
                      if dn is not None and dn.uuid in holders)
    victim_uuid = cluster.datanodes[victim_idx].uuid
    cluster.kill_datanode(victim_idx)
    # Not possible to reach 3 replicas with 2 nodes; bring up a fresh 4th DN.
    cluster.num_datanodes += 1
    cluster._start_datanode(len(cluster.datanodes))
    try:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            info = cluster.namenode.fsn.bm.get(block_id)
            live = {u for u in info.locations if u != victim_uuid}
            if len(live) >= 3:
                break
            time.sleep(0.2)
        else:
            pytest.fail(f"block never re-replicated: {info.locations}")
        with fs.open("/heal.bin") as f:
            assert f.read() == data
    finally:
        cluster.restart_datanode(victim_idx)
        cluster.wait_active()


def test_corrupt_replica_detected_and_avoided(cluster, fs):
    data = os.urandom(50_000)
    fs.write_all("/corrupt.bin", data, overwrite=True)
    time.sleep(0.3)
    locs = fs.client.get_block_locations("/corrupt.bin")
    block_id = locs["blocks"][0]["b"]["id"]
    holders = [l["u"] for l in locs["blocks"][0]["locs"]]
    dn_idx = next(i for i, dn in enumerate(cluster.datanodes)
                  if dn is not None and dn.uuid == holders[0])
    assert cluster.corrupt_replica(block_id, dn_idx)
    # Fresh reader (no cached dead-node state): must transparently survive.
    fs2 = cluster.get_filesystem()
    with fs2.open("/corrupt.bin") as f:
        assert f.read() == data


def test_namenode_restart_preserves_namespace(cluster, fs):
    data = os.urandom(150_000)
    fs.write_all("/persist/f.bin", data, overwrite=True)
    fs.mkdirs("/persist/dir")
    cluster.restart_namenode()
    cluster.wait_active()
    fs2 = cluster.get_filesystem()
    assert fs2.exists("/persist/f.bin")
    assert fs2.exists("/persist/dir")
    assert fs2.read_all("/persist/f.bin") == data


def test_namenode_restart_after_checkpoint(cluster, fs):
    fs.write_all("/ckpt/a.bin", b"before checkpoint", overwrite=True)
    fs.client.nn.save_namespace()
    fs.write_all("/ckpt/b.bin", b"after checkpoint", overwrite=True)
    cluster.restart_namenode()
    cluster.wait_active()
    fs2 = cluster.get_filesystem()
    assert fs2.read_all("/ckpt/a.bin") == b"before checkpoint"
    assert fs2.read_all("/ckpt/b.bin") == b"after checkpoint"


def test_lease_recovery_on_abandoned_writer(cluster, fs):
    """A writer that vanishes must not lock the file forever — and flushed
    data must survive via block recovery (rbw replicas finalized at their
    length; ref: internalReleaseLease → block recovery)."""
    payload = b"some data that will be recovered"
    out = fs.create("/abandoned.txt")
    out.write(payload)
    out.flush()
    # Simulate writer death: stop renewing (kill the renewer + client ref).
    fs.client._renewer_stop.set()
    deadline = time.monotonic() + 20
    fs2 = cluster.get_filesystem()
    recovered = False
    while time.monotonic() < deadline:
        try:
            if fs2.client.nn.recover_lease("/abandoned.txt", "taker"):
                recovered = True
                break
        except Exception:
            pass
        time.sleep(0.3)
    assert recovered
    assert fs2.read_all("/abandoned.txt") == payload  # flushed bytes durable
    # Restart the renewer thread machinery for later tests.
    fs.client._renewer_stop = None
    fs.client._open_files = 0


def test_datanode_report_and_stats(cluster, fs):
    stats = fs.client.nn.get_stats()
    assert stats["live_datanodes"] >= 3
    assert not stats["safemode"]
    report = fs.client.nn.get_datanode_report("live")
    assert len(report) >= 3
    assert all(r["st"] == "live" for r in report)


def test_short_circuit_local_read(cluster, fs):
    """Same-host reads take the direct-file path (ref:
    ShortCircuitCache.java:72 / BlockReaderFactory.java:354-381)."""
    from hadoop_tpu.dfs.client.shortcircuit import ShortCircuitCache
    data = os.urandom(2 * 1024 * 1024 + 12345)  # spans blocks
    with fs.create("/sc.bin") as out:
        out.write(data)
    cache = ShortCircuitCache.get()
    hits0, reqs0 = cache.hits, cache.requests
    with fs.open("/sc.bin") as f:
        assert f.read() == data
    assert cache.hits > hits0          # local path actually taken
    assert cache.requests > reqs0


def test_short_circuit_disabled_by_conf(cluster, fs):
    from hadoop_tpu.dfs.client.streams import DFSInputStream
    data = os.urandom(10_000)
    fs.write_all("/sc3.bin", data)
    # flag plumbed through the stream (TCP path still correct)
    s = DFSInputStream(fs.client, "/sc3.bin")
    assert s._short_circuit_ok  # default on
    fs.client.conf.set("dfs.client.read.shortcircuit", "false")
    try:
        s2 = DFSInputStream(fs.client, "/sc3.bin")
        assert not s2._short_circuit_ok
        assert s2.read() == data  # remote path works
    finally:
        fs.client.conf.set("dfs.client.read.shortcircuit", "true")


def test_short_circuit_fallback_when_replica_moved(cluster, fs):
    """A stale cached path falls back to TCP instead of failing."""
    from hadoop_tpu.dfs.client import shortcircuit as scmod
    data = os.urandom(100_000)
    with fs.create("/sc2.bin") as out:
        out.write(data)
    cache = scmod.ShortCircuitCache.get()
    with fs.open("/sc2.bin") as f:
        assert f.read(10) == data[:10]
    # poison every cached slot's data fd; next read must still succeed
    import os as _os
    with cache._lock:
        for slot in cache._slots.values():
            _os.close(slot.data_fd)
            slot.data_fd = -1  # EBADF on pread; close() is a no-op
    with fs.open("/sc2.bin") as f:
        assert f.read() == data


def test_unaligned_flush_mid_write(cluster, fs):
    """hflush at a non-chunk-aligned offset must not corrupt checksums:
    the DN re-covers the straddling chunk when the next packet arrives
    (ref: BlockReceiver partial-chunk handling)."""
    a, b, c = os.urandom(1000), os.urandom(50_001), os.urandom(700)
    with fs.create("/unaligned_flush.bin") as out:
        out.write(a)
        out.flush()          # 1000 % 512 != 0 → partial trailing chunk
        out.write(b)
        out.flush()
        out.write(c)
    assert fs.read_all("/unaligned_flush.bin") == a + b + c


def test_short_circuit_fds_survive_dn_restart(cluster, fs):
    """A cached fd grant outlives the granting DN: finalized block bytes
    at a genstamp are immutable, so the open descriptors stay correct
    across a DN restart (the reference's slot invalidation exists to
    reclaim space, not for correctness) — and after the restart, NEW
    grants flow through the recreated domain socket."""
    from hadoop_tpu.dfs.client.shortcircuit import ShortCircuitCache
    data = os.urandom(500_000)
    with fs.create("/scr.bin") as out:
        out.write(data)
    cache = ShortCircuitCache.get()
    hits0 = cache.hits
    with fs.open("/scr.bin") as f:
        assert f.read() == data        # populate fd slots
    assert cache.hits > hits0

    cluster.restart_datanode(0)
    cluster.wait_active()

    # cached fds still serve the immutable bytes
    hits1 = cache.hits
    with fs.open("/scr.bin") as f:
        assert f.read() == data
    assert cache.hits > hits1

    # and a fresh file gets NEW grants via the recreated socket
    data2 = os.urandom(100_000)
    fs.write_all("/scr2.bin", data2)
    reqs = cache.requests
    assert fs.read_all("/scr2.bin") == data2
    assert cache.requests > reqs


def test_domain_socket_concurrent_grants_and_bad_peers(cluster, fs):
    """The fd-passing server under load: N threads grab grants for
    different blocks concurrently while garbage peers poke the socket —
    every legitimate read stays correct (slot refcounting + per-conn
    isolation)."""
    import socket as _socket
    import threading

    data = {}
    for i in range(4):
        data[i] = os.urandom(300_000)
        fs.write_all(f"/dsc/f{i}", data[i])

    from hadoop_tpu.dfs.client.shortcircuit import ShortCircuitCache
    cache = ShortCircuitCache.get()
    dn = cluster.datanodes[0]
    sock_path = dn.domain_server.path
    errs = []

    def garbage():
        try:
            s = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
            s.connect(sock_path)
            s.sendall(b"\x00\x00\x00\x05junk!")
            s.close()
        except OSError:
            pass

    def reader(i):
        try:
            for _ in range(5):
                with fs.open(f"/dsc/f{i}") as f:
                    assert f.read() == data[i]
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=reader, args=(i,)) for i in data]
    threads += [threading.Thread(target=garbage) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert cache.hits > 0


def test_non_default_bytes_per_checksum_roundtrip(tmp_path):
    """dfs.bytes-per-checksum != 512: the replica meta stores the
    writer's chunking and the read setup reply echoes it, so readers
    verify with the WRITER's bpc instead of assuming the default
    (review finding: clients hard-coded 512 and failed every block
    written with another chunk size)."""
    import os as _os

    from hadoop_tpu.testing.minicluster import MiniDFSCluster, fast_conf

    conf = fast_conf()
    conf.set("dfs.replication", "1")
    conf.set("dfs.bytes-per-checksum", "2048")
    # force the remote (TCP) read path so the bpc rides the setup reply
    conf.set("dfs.client.read.shortcircuit", "false")
    with MiniDFSCluster(num_datanodes=1, conf=conf,
                        base_dir=str(tmp_path)) as c:
        c.wait_active()
        fs = c.get_filesystem()
        payload = _os.urandom(300_001)  # odd size: partial last chunk
        fs.write_all("/bpc.bin", payload)
        assert fs.read_all("/bpc.bin") == payload


def test_remote_reads_on_multivolume_datanode(tmp_path):
    """OP_READ_BLOCK against a multi-volume DN: the VolumeSet must
    accept the xceiver's eager-open handle (review finding — a
    signature mismatch made every remote read on multi-volume DNs die
    with TypeError before the setup reply)."""
    import os as _os

    from hadoop_tpu.testing.minicluster import MiniDFSCluster, fast_conf

    conf = fast_conf()
    conf.set("dfs.replication", "1")
    conf.set("dfs.datanode.volumes", "3")
    conf.set("dfs.client.read.shortcircuit", "false")  # force TCP reads
    with MiniDFSCluster(num_datanodes=1, conf=conf,
                        base_dir=str(tmp_path)) as c:
        c.wait_active()
        fs = c.get_filesystem()
        payload = _os.urandom(200_000)
        fs.write_all("/mv.bin", payload)
        assert fs.read_all("/mv.bin") == payload
