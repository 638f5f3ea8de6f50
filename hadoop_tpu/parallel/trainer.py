"""Training driver: sharded step + DFS dataloader + DFS checkpoints.

The integration layer the reference spreads across its AM/history/state-
store machinery: run the jitted sharded train step over a DFS-resident
token stream, checkpoint params + optimizer + data cursor to the DFS on
an interval, and resume exactly after a crash (same loss curve as an
uninterrupted run — the test asserts this bit-for-bit on CPU).
"""

from __future__ import annotations

import logging
import queue
import threading
from collections import deque
from typing import Dict, Optional

import time

import jax
import jax.numpy as jnp

from hadoop_tpu.fs import FileSystem
from hadoop_tpu.metrics import metrics_system
from hadoop_tpu.models.config import ModelConfig
from hadoop_tpu.tracing.tracer import global_tracer
from hadoop_tpu.parallel.checkpoint import (AsyncCheckpointWriter,
                                            latest_step, load_checkpoint,
                                            read_manifest,
                                            reorder_snapshot_axis0,
                                            snapshot_tree, write_snapshot)
from hadoop_tpu.parallel.data import TokenDataset
from hadoop_tpu.parallel.elastic import ElasticConfig
from hadoop_tpu.parallel.mesh import MeshPlan, make_mesh, param_specs
from hadoop_tpu.parallel.lowp import ParityConfig
from hadoop_tpu.parallel.overlap import DEFAULT_OVERLAP, OverlapConfig
from hadoop_tpu.parallel.train import (init_sharded, make_data_sharding,
                                       make_train_step, zero1_layout)
from hadoop_tpu.parallel.optimizer import AdamWState
from hadoop_tpu.util.jaxcache import configure_compile_cache

log = logging.getLogger(__name__)


class Trainer:
    def __init__(self, cfg: ModelConfig, plan: MeshPlan, fs: FileSystem,
                 data_path: str, ckpt_dir: str, *, batch: int,
                 lr: float = 3e-4, optimizer: str = "adamw",
                 zero1: bool = False, remat=False,
                 ckpt_interval: int = 100, keep: int = 3,
                 data_dtype: str = "uint16",
                 n_microbatches: Optional[int] = None,
                 pipeline_schedule: str = "1f1b",
                 overlap: Optional[OverlapConfig] = None,
                 parity: Optional[ParityConfig] = None,
                 async_ckpt: bool = True, rank: int = 0,
                 elastic: Optional[ElasticConfig] = None,
                 doctor_poll=None):
        # before the first compile: the flagship step takes about a
        # minute to build, once per machine with the persistent cache
        configure_compile_cache()
        self.cfg, self.plan, self.fs = cfg, plan, fs
        self.ckpt_dir = ckpt_dir
        self.ckpt_interval = ckpt_interval
        self.keep = keep
        self.batch = batch
        self.zero1 = zero1 and optimizer == "adamw"
        # everything the train step's build needs, kept so apply_plan
        # (the elastic reshard seam) can rebuild for a different plan
        self._n_microbatches_arg = n_microbatches
        self._build_kwargs = dict(
            lr=lr, optimizer=optimizer, zero1=zero1, remat=remat,
            pipeline_schedule=pipeline_schedule, overlap=overlap,
            parity=parity)
        # parallel.ckpt.async: save() blocks only for the host snapshot;
        # the DFS write (and the vpp logical reorder) runs on a
        # background writer fenced at the next save / restore /
        # train-exit (checkpoint.AsyncCheckpointWriter).
        self.async_ckpt = async_ckpt
        self._ckpt_writer = AsyncCheckpointWriter()
        self.data = TokenDataset(fs, data_path, batch=batch,
                                 seq=cfg.max_seq, dtype=data_dtype)
        self._build_for_plan(plan)
        self.step = 0
        self.losses: list = []
        # latest loss per ABSOLUTE step index: under the elastic plane
        # a resume rewinds and re-runs steps, so self.losses alone can
        # carry duplicates; this map always holds one (the newest)
        # loss per step — what the loss-curve A-B guard compares.
        self.loss_by_step: Dict[int, float] = {}
        # elastic controller (parallel/elastic): polls the doctor's
        # trainer verdicts every elastic.poll.steps steps and, on a
        # flagged/dead rank, hands train() a shrunken plan to resume
        # under via apply_plan + reshard-on-restore.
        self.elastic = None
        if elastic is not None and elastic.enabled:
            from hadoop_tpu.parallel.elastic.controller import \
                ElasticController
            self.elastic = ElasticController(self, elastic,
                                             poll_fn=doctor_poll)
        # Step anatomy as a LIVE surface (profile_train's one-shot
        # accounting, always on): /jmx and /prom see exactly where a
        # step's wall time goes — data wait vs dispatched step vs the
        # checkpoint snapshot/fence the async writer still charges the
        # loop for. The metric set is THE shared definition in
        # obs/trainer.py (rank-labeled /prom families the fleet doctor
        # windows per rank); a dryrun subprocess worker builds the same
        # set, so the families can never fork.
        from hadoop_tpu.obs.trainer import TrainerStepMetrics
        self.rank = int(rank)
        m = TrainerStepMetrics(rank=self.rank)
        self.step_metrics = m
        self._m_steps = m.steps
        self._m_data_wait = m.data_wait
        self._m_data_wait_hist = m.data_wait_hist
        self._m_step_wall = m.step_wall
        self._m_step_wall_hist = m.step_wall_hist
        self._m_ckpt_snapshot = m.ckpt_snapshot
        self._m_ckpt_write = m.ckpt_write
        self._m_ckpt_fence = m.ckpt_fence
        # Live HBM ledger: this trainer's resident state, alongside the
        # serving components (obs/hbm.py). grad_buckets is the overlap
        # pass's transient packing buffer bound — the concat each
        # bucketed collective materializes at peak.
        from hadoop_tpu.obs.comm import comm_runtime
        from hadoop_tpu.obs.hbm import hbm_ledger, tree_nbytes
        self._comm = comm_runtime()
        ov = overlap if overlap is not None else DEFAULT_OVERLAP
        led = hbm_ledger()
        self._hbm_owner = f"trainer@{id(self)}."
        # providers hold a WEAK ref: a replaced trainer that was never
        # close()d must not pin its whole params+opt state in the
        # process-global ledger forever (a dead ref reports 0 bytes —
        # truthfully: that state is collectable)
        import weakref
        ref = weakref.ref(self)

        def _tree(attr):
            t = ref()
            return tree_nbytes(getattr(t, attr)) if t is not None else 0

        led.register(f"{self._hbm_owner}params", "params",
                     lambda: _tree("params"))
        led.register(f"{self._hbm_owner}opt", "opt_state",
                     lambda: _tree("opt"))
        led.register(f"{self._hbm_owner}buckets", "grad_buckets",
                     lambda: (ov.bucket_bytes if ov.enabled else 0)
                     if ref() is not None else 0)
        self._tracer = global_tracer()
        # Cursor of the last batch a completed step CONSUMED — set only
        # while train() runs (the prefetch thread advances the dataset
        # ahead of consumption, so the dataset's own cursor overstates
        # progress mid-run). None outside train(); save() then reads
        # the dataset directly.
        self._inflight_cursor: Optional[Dict] = None

    def _build_for_plan(self, plan: MeshPlan) -> None:
        """Mesh + step_fn + data sharding + fresh sharded state for one
        plan — the slice of construction ``apply_plan`` re-runs when
        the elastic controller shrinks the mesh."""
        kw = self._build_kwargs
        n_microbatches = self._n_microbatches_arg
        if n_microbatches is None:
            # pipeline plans need M > 1 (interleaved REQUIRES pp | M;
            # plain 1F1B with M=1 is a full bubble); single-stage plans
            # run unsplit
            n_microbatches = max(1, plan.pp * getattr(plan, "vpp", 1))
        plan.validate(self.cfg, self.batch, self.cfg.max_seq,
                      n_microbatches=n_microbatches)
        self.plan = plan
        self.mesh = make_mesh(plan)
        self.step_fn = make_train_step(
            self.cfg, plan, self.mesh, lr=kw["lr"],
            optimizer=kw["optimizer"], zero1=kw["zero1"],
            # params/opt are donated (the step's default): at flagship
            # width two copies of the ~10 GB state do not fit one chip,
            # and self.params/self.opt are rebound to the outputs
            remat=kw["remat"],
            n_microbatches=n_microbatches,
            pipeline_schedule=kw["pipeline_schedule"],
            overlap=kw["overlap"], parity=kw["parity"])
        self.data_sharding = make_data_sharding(self.mesh)
        self.params, self.opt = init_sharded(
            jax.random.PRNGKey(0), self.cfg, plan, self.mesh,
            zero1=self.zero1)

    def apply_plan(self, new_plan: MeshPlan) -> bool:
        """Rebuild this trainer for a new mesh plan and resume from the
        newest snapshot via reshard-on-restore (the elastic
        controller's actuation seam; callable directly for a manual
        reshard). Must not run under a live train() segment — the
        prefetch thread shares the dataset. Returns whether a
        checkpoint was restored; without one the state is freshly
        initialized and the step count restarts at 0."""
        self._ckpt_writer.wait()   # fence: an in-flight write lands
        #                            before the plan that wrote it dies
        old_step = self.step
        self._build_for_plan(new_plan)
        restored = self.try_restore()
        if not restored:
            self.step = 0
            log.warning("apply_plan(%s): no checkpoint to restore; "
                        "reinitialized from step 0 (was step %d)",
                        new_plan, old_step)
        return restored

    # -------------------------------------------------------- persistence

    def _state_tree(self):
        return {"params": self.params, "opt": self.opt}

    def save(self, wait: Optional[bool] = None) -> str:
        """Checkpoint the current state.

        ``wait=False`` (what the step loop's interval saves pass): block
        only for the host snapshot (device→host copies of the unique
        shards) plus a fence on any PREVIOUS in-flight write; the DFS
        write itself — and the vpp logical-reorder, which permutes
        whole layer stacks — runs on the background writer, fenced at
        the next save / restore / train-exit. The data cursor is
        captured at call time, so in-flight prefetched batches are
        accounted exactly as before. A crash (or writer failure)
        mid-write leaves a manifest-less directory the next retention
        sweep removes — the previous complete checkpoint keeps winning.

        Default (``wait=None`` → True): an EXPLICIT save is durable on
        return, exactly like the old synchronous path — only saves
        issued from inside the training loop ride the background
        writer. ``async_ckpt=False`` forces every save synchronous.
        """
        if wait is None:
            wait = True
        t_fence = time.monotonic()
        self._ckpt_writer.wait()  # fence: surfaces a prior write failure
        self._m_ckpt_fence.add(time.monotonic() - t_fence)
        tree = self._state_tree()
        # The data cursor rides as an extra leaf, split into two int32
        # halves: datasets beyond 2**31 tokens are ordinary LM scale and
        # a single int32 would overflow (or wrap negative) and resume
        # the stream at the wrong position.
        cursor = (self._inflight_cursor if self._inflight_cursor
                  is not None else self.data.state())
        pos = cursor["pos"] % max(self.data.total_tokens, 1)
        tree = dict(tree, data_pos=jnp.asarray(
            [pos >> 31, pos & 0x7FFFFFFF], jnp.int32))
        with self._tracer.span("trainer.ckpt.snapshot") as ssp:
            t_snap = time.monotonic()
            snap = snapshot_tree(tree)
            self._m_ckpt_snapshot.add(time.monotonic() - t_snap)
            ssp.add_kv("step", str(self.step))
        step, fs, ckpt_dir, keep = self.step, self.fs, self.ckpt_dir, \
            self.keep
        reorder = self._vpp_snapshot_reorder()
        m_write, tracer = self._m_ckpt_write, self._tracer
        # the manifest carries the writing plan (captured NOW — the
        # elastic controller may swap self.plan before the background
        # write lands) so a restore under any other plan knows to go
        # through the host-side reshard
        from hadoop_tpu.parallel.elastic.reshard import manifest_meta
        meta = manifest_meta(self.plan, zero1=self.zero1)

        def write():
            # the writer thread carries the submitter's context
            # (AsyncCheckpointWriter wraps with carry_context), so this
            # span lands in the same trace as the snapshot above
            with tracer.span("trainer.ckpt.write") as wsp:
                t_w = time.monotonic()
                path = write_snapshot(fs, ckpt_dir, step,
                                      reorder(snap) if reorder else snap,
                                      keep=keep, meta=meta)
                m_write.add(time.monotonic() - t_w)
                wsp.add_kv("step", str(step))
            log.info("checkpoint step %d -> %s", step, path)

        if self.async_ckpt:
            self._ckpt_writer.submit(write)
            if wait:
                t_fence = time.monotonic()
                self._ckpt_writer.wait()
                self._m_ckpt_fence.add(time.monotonic() - t_fence)
        else:
            write()
        return f"{self.ckpt_dir}/step_{step:012d}"

    def _vpp_snapshot_reorder(self):
        """Host-side logical-reorder closure for interleaved plans.

        Checkpoints persist the LOGICAL layer order so they stay
        portable across plans (interleaved placement permutes the
        stacked layer axis on device; see train.physical_layer_order).
        Adam moments mirror the params tree, so they permute the same
        way. ZeRO-1 state is flat slices — plan-locked either way —
        left as stored. Running the permutation on the host snapshot
        keeps the device free of the full permuted copy the old
        device-side ``logical_layer_order`` materialized."""
        if getattr(self.plan, "vpp", 1) <= 1:
            return None
        import numpy as _np

        from hadoop_tpu.parallel.pipeline import \
            interleaved_layer_permutation
        inv = _np.argsort(interleaved_layer_permutation(
            self.cfg.n_layers, self.plan.pp, self.plan.vpp))
        prefixes = ["['params']['layers']"]
        if not self.zero1:
            prefixes += ["['opt'].mu['layers']", "['opt'].nu['layers']"]

        def match(name: str) -> bool:
            return any(name.startswith(p) for p in prefixes)

        return lambda snap: reorder_snapshot_axis0(snap, inv, match)

    def wait_for_checkpoint(self) -> None:
        """Block until any in-flight async checkpoint write completes
        (re-raising its failure, if it failed)."""
        self._ckpt_writer.wait()

    def close(self) -> None:
        """Retire this trainer from the process-global ledgers. Without
        this, a replaced trainer (elastic restart, a bench loop) keeps
        its params/opt providers registered — the HBM report double-
        counts AND the ledger's provider closures pin the dead
        trainer's whole state in memory."""
        from hadoop_tpu.obs.hbm import hbm_ledger
        hbm_ledger().unregister_prefix(self._hbm_owner)

    def _target_spec_tree(self):
        """Placement specs for the CURRENT plan's state tree."""
        specs = param_specs(self.cfg, self.plan)
        if self.zero1:
            _, _, z1_specs, _ = zero1_layout(self.cfg, self.plan)
            opt_specs = AdamWState(
                count=jax.sharding.PartitionSpec(), mu=z1_specs,
                nu=z1_specs)
        else:
            opt_specs = AdamWState(
                count=jax.sharding.PartitionSpec(), mu=specs, nu=specs)
        return {"params": specs, "opt": opt_specs,
                "data_pos": jax.sharding.PartitionSpec()}

    def try_restore(self) -> bool:
        """Resume from the newest complete checkpoint, if any.

        Reads the manifest's plan block first: a snapshot written
        under a DIFFERENT mesh plan restores through the host-side
        reshard (parallel/elastic/reshard.py — ZeRO-1 slices
        reassembled to global moments and re-sliced for this plan); a
        matching plan takes the direct placement path, bit-identical
        to what was saved; a legacy manifest (no plan block) restores
        as same-plan with a DeprecationWarning."""
        self._ckpt_writer.wait()  # a restore must see the newest save
        step = latest_step(self.fs, self.ckpt_dir)
        if step is None:
            return False
        from hadoop_tpu.parallel.elastic.reshard import resolve_restore
        manifest = read_manifest(self.fs, self.ckpt_dir, step)
        mode, saved_plan, saved_zero1 = resolve_restore(
            manifest, self.plan, self.zero1)
        spec_tree = self._target_spec_tree()
        if mode == "reshard":
            tree, got = self._load_resharded(step, saved_plan,
                                             saved_zero1, spec_tree)
        else:
            like = dict(self._state_tree(),
                        data_pos=jnp.zeros((2,), jnp.int32))
            tree, got = load_checkpoint(self.fs, self.ckpt_dir, like,
                                        step=step, mesh=self.mesh,
                                        specs=spec_tree)
        self.params, self.opt = tree["params"], tree["opt"]
        if getattr(self.plan, "vpp", 1) > 1:
            from hadoop_tpu.parallel.train import physical_layer_order
            self.params = physical_layer_order(self.params, self.cfg,
                                               self.plan)
            if not self.zero1:
                self.opt = type(self.opt)(
                    self.opt.count,
                    physical_layer_order(self.opt.mu, self.cfg,
                                         self.plan),
                    physical_layer_order(self.opt.nu, self.cfg,
                                         self.plan))
        hi, lo = (int(x) for x in tree["data_pos"])
        self.data.restore({"pos": (hi << 31) | lo})
        self.step = got
        log.info("restored step %d from %s", got, self.ckpt_dir)
        return True

    def _load_resharded(self, step: int, saved_plan: MeshPlan,
                        saved_zero1: bool, spec_tree):
        """Cross-plan restore: assemble the snapshot to HOST arrays in
        the saved plan's layout (params and pp stage shards come back
        global for free — the manifest stores global logical shapes),
        convert the optimizer moments through global layout for this
        plan (elastic/reshard.py), then place everything under the
        target mesh. Returns ``(tree, step)`` like load_checkpoint."""
        from jax.sharding import NamedSharding
        from hadoop_tpu.parallel.elastic.reshard import reshard_opt_state
        sds = jax.ShapeDtypeStruct
        pshapes = jax.tree_util.tree_map(
            lambda p: sds(p.shape, p.dtype), self.params)
        if saved_zero1:
            _, shape_tree, _, _ = zero1_layout(self.cfg, saved_plan)
            # shape_tree's leaves are shape TUPLES — without is_leaf,
            # tree_map would descend into them int by int
            moments = jax.tree_util.tree_map(
                lambda s: sds(tuple(s), jnp.float32), shape_tree,
                is_leaf=lambda s: isinstance(s, tuple))
        else:
            moments = jax.tree_util.tree_map(
                lambda p: sds(p.shape, jnp.float32), self.params)
        like = {"params": pshapes,
                "opt": AdamWState(count=sds((), jnp.int32), mu=moments,
                                  nu=moments),
                "data_pos": sds((2,), jnp.int32)}
        tree, got = load_checkpoint(self.fs, self.ckpt_dir, like,
                                    step=step)
        opt = reshard_opt_state(
            tree["opt"], self.params, param_specs(self.cfg, self.plan),
            saved_plan, self.plan, zero1_a=saved_zero1,
            zero1_b=self.zero1)

        def place(x, s):
            return jax.device_put(x, NamedSharding(self.mesh, s))

        return {"params": jax.tree_util.tree_map(
                    place, tree["params"], spec_tree["params"]),
                "opt": jax.tree_util.tree_map(
                    place, opt, spec_tree["opt"]),
                "data_pos": tree["data_pos"]}, got

    # -------------------------------------------------------------- train

    # In-flight step bound: losses older than this are forced to host,
    # which (a) backpressures async dispatch so the host can't run
    # unboundedly ahead of the device and (b) keeps the host busy with
    # the NEXT batch's DFS read while the device works. The old loop
    # float()ed every step — a full sync serializing read → transfer →
    # step (the "host input pipeline" item of VERDICT r4 weak #7).
    MAX_INFLIGHT = 16

    def train(self, n_steps: int) -> list:
        """Run ``n_steps`` more steps; returns the losses of every step
        executed.

        The dataloader runs in a background prefetch thread (DFS read +
        host→device transfer overlap the device step); each prefetched
        batch carries the dataset cursor as of ITS production, and the
        checkpoint cursor tracks the last batch a completed step
        consumed — so a mid-run save resumes bit-exactly even with
        batches in flight.

        Under the elastic plane the target is ABSOLUTE: an eviction
        ends the running step segment (the prefetch thread drains and
        the dataset cursor rewinds first), the controller reshards onto
        the shrunken plan, and the loop re-runs the steps lost since
        the restored snapshot — the call still returns with
        ``self.step == start + n_steps``. The returned list includes
        re-run steps; ``self.loss_by_step`` keeps exactly one (the
        newest) loss per step index."""
        if self.elastic is None:
            return self._train_segment(n_steps)
        target = self.step + n_steps
        out: list = []
        while self.step < target:
            out.extend(self._train_segment(target - self.step))
            if self.elastic.pending:
                self.elastic.resume()
        return out

    def _train_segment(self, n_steps: int) -> list:
        """One uninterrupted run of the step loop (train() without the
        elastic replan seam). Ends early only when the elastic
        controller marks an eviction pending."""
        zombie = getattr(self, "_zombie_producer", None)
        if zombie is not None:
            if zombie.is_alive():
                raise RuntimeError(
                    "a previous train()'s prefetch thread is still "
                    "stuck in a dataset read; the dataset cannot be "
                    "shared with a new run")
            self._zombie_producer = None
            if self._inflight_cursor is not None:
                # the stuck thread has since died: rewind to the
                # consumed position it left unrestored
                if self.data.state() != self._inflight_cursor:
                    self.data.restore(self._inflight_cursor)
                self._inflight_cursor = None
        out: list = []
        pending: deque = deque()   # device-side loss scalars, oldest first
        q: queue.Queue = queue.Queue(maxsize=2)
        abort = threading.Event()

        def produce():
            try:
                for _ in range(n_steps):
                    rows = self.data.next_batch()
                    item = (
                        jax.device_put(jnp.asarray(rows[:, :-1], jnp.int32),
                                       self.data_sharding),
                        jax.device_put(jnp.asarray(rows[:, 1:], jnp.int32),
                                       self.data_sharding),
                        self.data.state())
                    while not abort.is_set():
                        try:
                            q.put(item, timeout=0.5)
                            break
                        except queue.Full:
                            continue
                    if abort.is_set():
                        return
            except BaseException as e:  # surfaced from the consumer loop
                while not abort.is_set():
                    try:
                        q.put(e, timeout=0.5)
                        break
                    except queue.Full:
                        continue

        producer = threading.Thread(target=produce, daemon=True,
                                    name="trainer-prefetch")
        producer.start()
        step_failed = False
        try:
            for _ in range(n_steps):
                t_step = time.monotonic()
                item = q.get()
                data_wait = time.monotonic() - t_step
                if isinstance(item, BaseException):
                    raise item
                tokens, targets, cursor = item
                # always-on step anatomy: one span per step (the root
                # of that step's trace — an interval save's snapshot/
                # write spans join it) + the live data-wait/step-wall
                # split. step_fn dispatches asynchronously, so
                # "step wall" is dispatch-to-dispatch time; the
                # MAX_INFLIGHT float() below is where a device stall
                # would surface in it.
                with self._tracer.span("trainer.step") as stsp:
                    stsp.add_kv("step", str(self.step + 1))
                    stsp.add_kv("data_wait_ms",
                                f"{data_wait * 1e3:.2f}")
                    # runtime comm ledger dispatch seam: the first call
                    # traces the step INSIDE this window (binding every
                    # collective site's static bytes to "trainer.step");
                    # every call advances the per-site byte counters and
                    # records this window's host wall — with this span's
                    # trace id as the bucket exemplar — into the
                    # htpu_comm histograms. Nothing enters the graph.
                    with self._comm.step("trainer.step"):
                        self.params, self.opt, metrics = self.step_fn(
                            self.params, self.opt, tokens, targets)
                        self.step += 1
                        self._inflight_cursor = cursor
                        pending.append((self.step, metrics["loss"]))
                        # materialize as they age out so self.losses
                        # stays current even if a later step raises;
                        # this float() is the DELIBERATE bounded-in-
                        # flight backpressure sync (see MAX_INFLIGHT
                        # above), not a stray stall
                        while len(pending) > self.MAX_INFLIGHT:
                            s, dev = pending.popleft()
                            val = float(  # lint: disable=jit/blocking-in-step
                                dev)
                            out.append(val)
                            self.losses.append(val)
                            self.loss_by_step[s] = val
                    if self.ckpt_interval and \
                            self.step % self.ckpt_interval == 0:
                        # interval saves ride the background writer:
                        # the step loop pays only the host-snapshot
                        # time (the train-exit fence below guarantees
                        # durability); the save's snapshot/write spans
                        # join this step's trace
                        self.save(wait=False)
                self._m_steps.incr()
                self._m_data_wait.add(data_wait)
                self._m_data_wait_hist.add(data_wait)
                step_wall = time.monotonic() - t_step
                self._m_step_wall.add(step_wall)
                self._m_step_wall_hist.add(step_wall)
                if self.elastic is not None and \
                        self.step % self.elastic.cfg.poll_steps == 0:
                    # DELIBERATE host-side doctor poll, cadence-gated
                    # and outside the jitted step: the elastic plane's
                    # sensing seam (an HTTP read of
                    # /ws/v1/fleet/doctor, never per-step)
                    if self.elastic.on_step(self.step):
                        # evict pending: end this segment so the
                        # prefetch thread drains and the cursor
                        # rewinds before the mesh is rebuilt
                        break
        except BaseException:
            step_failed = True
            raise
        finally:
            abort.set()
            # Drain completed steps' losses even when a step raised —
            # self.losses must not end up behind self.step by up to
            # MAX_INFLIGHT entries.
            while pending:
                s, dev = pending.popleft()
                try:
                    val = float(dev)
                except Exception:  # noqa: BLE001 — a failed step's loss
                    break
                out.append(val)
                self.losses.append(val)
                self.loss_by_step[s] = val
            producer.join(timeout=10.0)
            if producer.is_alive():
                # Pathological: producer stuck (e.g. a hung DFS read)
                # past its abort checks. It still owns self.data, so
                # don't rewind under it — keep the in-flight cursor so
                # a later save() records the consumed position, and
                # make the next train() refuse until the thread dies.
                log.warning("prefetch thread did not exit within 10s; "
                            "keeping the in-flight data cursor")
                self._zombie_producer = producer
            elif self._inflight_cursor is not None:
                # Rewind the dataset's own cursor to the consumed
                # position so save()/state() outside train() agree with
                # what actually trained — but only when the producer
                # really read ahead (restore() drops the read buffer,
                # which would force a pointless DFS re-read on the
                # common all-consumed exit).
                if self.data.state() != self._inflight_cursor:
                    self.data.restore(self._inflight_cursor)
                self._inflight_cursor = None
            # Completion fence at train-exit, AFTER the drain/join/
            # rewind so a failed write never skips the loss and cursor
            # bookkeeping above: a caller returning from train() must
            # find its interval checkpoints durable (and learn about a
            # failed write here, not at some later save). When a STEP
            # exception is propagating (tracked explicitly — exc_info()
            # lies both inside except blocks and when train() is called
            # from a caller's handler), the write failure is logged
            # instead of masking it.
            try:
                self._ckpt_writer.wait()
            except Exception:
                if not step_failed:
                    raise
                log.exception("async checkpoint write failed during "
                              "train()")
        return out
