"""Async checkpointing: crash safety, fencing, and the vpp host reorder.

The contract under test (parallel/checkpoint.py + trainer.save):

- an interval save blocks the caller only for the host snapshot; the
  DFS write rides a background writer fenced at the next save /
  restore / train-exit;
- a writer killed mid-write leaves a manifest-less directory that
  ``try_restore`` never sees (the previous complete checkpoint wins)
  and that the next retention sweep removes;
- a failed write surfaces exactly once, at the next fence;
- interleaved (vpp) plans reorder the stacked layer axis to LOGICAL
  order on the HOST, off the device step path, producing the same
  bytes the old device-side reorder wrote.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.models import get_config
from hadoop_tpu.parallel import MeshPlan
from hadoop_tpu.parallel.checkpoint import (AsyncCheckpointWriter,
                                            latest_step, list_checkpoints,
                                            load_checkpoint,
                                            snapshot_tree, write_snapshot)
from hadoop_tpu.testing.minicluster import MiniDFSCluster

BATCH = 8


@pytest.fixture(scope="module")
def cluster():
    with MiniDFSCluster(num_datanodes=3) as c:
        yield c


@pytest.fixture(scope="module")
def fs(cluster):
    return cluster.get_filesystem()


@pytest.fixture(scope="module")
def token_file(fs):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, 200_000, dtype=np.uint16)
    fs.mkdirs("/adata")
    fs.write_all("/adata/tokens.bin", toks.tobytes())
    return "/adata/tokens.bin"


class _FailingFS:
    """Delegating FileSystem wrapper whose write_all starts raising
    after ``allow`` more calls once armed — the 'kill the writer
    mid-write' fault."""

    def __init__(self, inner):
        self._inner = inner
        self._armed = False
        self._allow = 0
        self.failures = 0

    def arm(self, allow: int) -> None:
        self._armed, self._allow = True, allow

    def disarm(self) -> None:
        self._armed = False

    def write_all(self, path, data):
        if self._armed:
            if self._allow <= 0:
                self.failures += 1
                raise IOError("injected mid-write crash")
            self._allow -= 1
        return self._inner.write_all(path, data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _trainer(fs, token_file, ckpt_dir, **kw):
    from hadoop_tpu.parallel.trainer import Trainer
    cfg = get_config("tiny")
    kw.setdefault("plan", MeshPlan(dp=8))
    plan = kw.pop("plan")
    return Trainer(cfg, plan, fs, token_file, ckpt_dir, batch=BATCH,
                   lr=1e-2, ckpt_interval=kw.pop("interval", 0), **kw)


# ----------------------------------------------------------- writer unit

def test_writer_runs_in_background_and_fences():
    w = AsyncCheckpointWriter()
    gate = threading.Event()
    done = threading.Event()

    def job():
        gate.wait(10.0)
        done.set()

    w.submit(job)
    assert w.in_flight
    assert not done.is_set()
    gate.set()
    w.wait()
    assert done.is_set() and not w.in_flight


def test_writer_error_surfaces_exactly_once_at_fence():
    w = AsyncCheckpointWriter()

    def boom():
        raise IOError("dfs fell over")

    w.submit(boom)
    with pytest.raises(IOError, match="dfs fell over"):
        w.wait()
    w.wait()  # cleared: does not raise twice


def test_writer_submit_fences_previous_and_keeps_order():
    w = AsyncCheckpointWriter()
    order = []
    gate = threading.Event()

    def first():
        gate.wait(10.0)
        order.append(1)

    def second():
        order.append(2)

    w.submit(first)
    release = threading.Timer(0.05, gate.set)
    release.start()
    w.submit(second)   # must fence job 1 before starting job 2
    w.wait()
    assert order == [1, 2]


# ------------------------------------------------------- trainer saves

def test_async_save_blocks_only_for_snapshot(fs, token_file):
    """save(wait=False) returns while the (slowed) DFS write is still
    in flight; wait_for_checkpoint() fences it durable."""
    t = _trainer(fs, token_file, "/ackpt/async")
    t.step = 3
    gate = threading.Event()
    orig = fs.write_all

    def slow_write_all(path, data):
        gate.wait(10.0)
        return orig(path, data)

    fs.write_all = slow_write_all
    try:
        t0 = time.monotonic()
        t.save(wait=False)
        returned_after = time.monotonic() - t0
        assert t._ckpt_writer.in_flight
        assert latest_step(fs, "/ackpt/async") is None  # not durable yet
        gate.set()
        t.wait_for_checkpoint()
    finally:
        fs.write_all = orig
        gate.set()
    assert latest_step(fs, "/ackpt/async") == 3
    # the blocking part (fence+snapshot of a tiny model) is far from
    # the gated write; generous bound only guards gross regressions
    assert returned_after < 5.0


def test_writer_crash_leaves_previous_checkpoint_winning(fs, token_file):
    ffs = _FailingFS(fs)
    t = _trainer(ffs, token_file, "/ackpt/crash")
    t.step = 5
    t.save()                     # durable baseline at step 5

    ffs.arm(allow=2)             # die after 2 shard writes, no manifest
    t.step = 7
    t.save(wait=False)
    with pytest.raises(IOError, match="injected"):
        t.wait_for_checkpoint()  # the fence surfaces the failure
    ffs.disarm()

    # the torn step-7 dir has no manifest: invisible to restore
    assert latest_step(fs, "/ackpt/crash") == 5
    t2 = _trainer(fs, token_file, "/ackpt/crash")
    assert t2.try_restore()
    assert t2.step == 5
    # the next successful save's retention sweep removes the orphan
    t2.step = 9
    t2.save()
    assert list_checkpoints(fs, "/ackpt/crash") == [5, 9]
    assert not fs.exists("/ackpt/crash/step_000000000007")


def test_explicit_save_is_durable_on_return(fs, token_file):
    t = _trainer(fs, token_file, "/ackpt/durable")
    t.step = 11
    t.save()
    assert not t._ckpt_writer.in_flight
    assert latest_step(fs, "/ackpt/durable") == 11


def test_sync_mode_never_spawns_writer(fs, token_file):
    t = _trainer(fs, token_file, "/ackpt/sync", async_ckpt=False)
    t.step = 2
    t.save(wait=False)           # async off: wait flag is irrelevant
    assert not t._ckpt_writer.in_flight
    assert latest_step(fs, "/ackpt/sync") == 2


def test_snapshot_is_isolated_from_later_updates(fs):
    """The snapshot copies shard bytes: mutating (rebinding) the live
    tree after submit must not change what lands on disk."""
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    snap = snapshot_tree(tree)
    tree["w"] = tree["w"] * 100.0
    write_snapshot(fs, "/ackpt/iso", 1, snap)
    like = {"w": np.zeros(8, np.float32)}
    out, _ = load_checkpoint(fs, "/ackpt/iso", like)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(8, dtype=np.float32))


def test_vpp_host_reorder_matches_device_reorder(fs, token_file):
    """An interleaved-plan save must persist LOGICAL layer order — the
    host-side snapshot permutation produces exactly what the old
    device-side logical_layer_order wrote."""
    from hadoop_tpu.parallel.train import logical_layer_order
    t = _trainer(fs, token_file, "/ackpt/vpp",
                 plan=MeshPlan(dp=2, pp=2, vpp=2))
    t.step = 1
    t.save()
    expect = logical_layer_order(t.params, t.cfg, t.plan)
    like = {"params": jax.tree_util.tree_map(np.asarray,
                                             jax.device_get(t.params)),
            "opt": jax.tree_util.tree_map(np.asarray,
                                          jax.device_get(t.opt)),
            "data_pos": np.zeros(2, np.int32)}
    out, step = load_checkpoint(fs, "/ackpt/vpp", like)
    assert step == 1
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(out["params"]),
            jax.tree_util.tree_leaves_with_path(expect)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(jax.device_get(b)),
            err_msg=str(pa))
    # and the moments permuted with the params (non-zero1 plans)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(out["opt"].mu),
            jax.tree_util.tree_leaves_with_path(
                logical_layer_order(t.opt.mu, t.cfg, t.plan))):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(jax.device_get(b)),
            err_msg=str(pa))


def test_train_exit_fence_raises_write_failure(fs, token_file):
    """A failed ASYNC interval write must surface from train() itself
    (the exit fence), not vanish: the regression was exc_info() being
    consulted inside the except block, where it reports the just-caught
    write error and never looks 'clean'. The step_fn is stubbed so the
    loop runs without the multichip trace."""
    ffs = _FailingFS(fs)
    t = _trainer(ffs, token_file, "/ackpt/fence", interval=2)
    t.step_fn = lambda p, o, tok, tgt: (p, o, {"loss": jnp.zeros(())})
    ffs.arm(allow=1)             # interval save at step 2 dies mid-write
    with pytest.raises(IOError, match="injected"):
        t.train(2)
    ffs.disarm()
    # surfaced exactly once: the next fence is clean
    t.wait_for_checkpoint()


def test_step_exception_not_masked_by_write_failure(fs, token_file):
    """When a STEP raises, a concurrent write failure is logged, not
    allowed to replace the real error."""
    ffs = _FailingFS(fs)
    t = _trainer(ffs, token_file, "/ackpt/fence2", interval=1)
    calls = {"n": 0}

    def step_fn(p, o, tok, tgt):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("step blew up")
        return p, o, {"loss": jnp.zeros(())}

    t.step_fn = step_fn
    ffs.arm(allow=1)             # the step-1 interval save dies too
    with pytest.raises(RuntimeError, match="step blew up"):
        t.train(2)
    ffs.disarm()


def test_interval_crash_resumes_bit_exact_with_inflight(fs, token_file):
    """Kill the ASYNC interval save's writer mid-write during train();
    the run must surface the failure at the train-exit fence, restore
    must land on the previous complete checkpoint, and resume must
    continue the reference loss curve bit-exactly (cursor semantics
    preserved with prefetched batches in flight)."""
    ref = _trainer(fs, token_file, "/ackpt/ref",
                   plan=MeshPlan(dp=2, tp=2))
    ref_losses = ref.train(6)

    a = _trainer(fs, token_file, "/ackpt/mid",
                 plan=MeshPlan(dp=2, tp=2), interval=2)
    a.train(2)                   # durable step-2 checkpoint
    a.wait_for_checkpoint()
    ffs = _FailingFS(fs)
    a.fs = ffs
    ffs.arm(allow=1)
    with pytest.raises(IOError, match="injected"):
        a.train(2)               # interval save at step 4 dies; fence
        a.wait_for_checkpoint()  # (whichever fence fires first raises)
    ffs.disarm()

    b = _trainer(fs, token_file, "/ackpt/mid",
                 plan=MeshPlan(dp=2, tp=2))
    assert b.try_restore()
    assert b.step == 2
    b_losses = b.train(4)
    np.testing.assert_allclose(b_losses, ref_losses[2:], rtol=1e-6)
