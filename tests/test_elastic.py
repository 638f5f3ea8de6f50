"""Elastic training plane: reshard-on-restore math + controller policy.

The contracts under test (parallel/elastic/):

- ZeRO-1 moment leaves convert EXACTLY between plan layouts through the
  global param-shaped intermediate — including non-power-of-two shrinks
  (dp 8→6) and padded slices — and same-plan conversion is the
  untouched-object passthrough (the bit-identical restore path);
- ``resolve_restore`` classifies manifests: same-plan, reshard, legacy
  (pre-plan-block → DeprecationWarning), and pp/vpp changes are refused
  loudly;
- the controller's streak policy: demote exactly once per flagged
  streak, evict on dead/flagged thresholds onto the largest healthy
  sub-mesh, hysteresis after a resume, evicted ranks never re-evicted;
- the retention sweep leaves an auditable (path, reason) breadcrumb per
  removal.
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from hadoop_tpu.conf import Configuration
from hadoop_tpu.fs.filesystem import LocalFileSystem
from hadoop_tpu.parallel import MeshPlan
from hadoop_tpu.parallel.checkpoint import (_retain, list_checkpoints,
                                            read_manifest, snapshot_tree,
                                            write_snapshot)
from hadoop_tpu.parallel.elastic import ElasticConfig, elastic_from_conf
from hadoop_tpu.parallel.elastic.controller import (ElasticController,
                                                    pick_shrunken_plan)
from hadoop_tpu.parallel.elastic.reshard import (MANIFEST_FORMAT,
                                                 check_reshardable,
                                                 global_to_zero1_state,
                                                 manifest_meta,
                                                 plan_from_meta,
                                                 reshard_opt_state,
                                                 reshard_zero1_leaf,
                                                 resolve_restore,
                                                 zero1_state_to_global)
from hadoop_tpu.parallel.optimizer import AdamWState


# ---------------------------------------------------- reshard layout math

def test_zero1_roundtrip_replicated_leaf():
    plan = MeshPlan(dp=8)
    g = np.arange(48, dtype=np.float32).reshape(12, 4)
    state = global_to_zero1_state(g, P(), plan)
    assert state.shape == (8, 6)          # z=8, K=48/8
    back = zero1_state_to_global(state, P(), g.shape, plan)
    np.testing.assert_array_equal(back, g)


def test_zero1_roundtrip_with_padding():
    # local size 10 over z=8 pads to K=2 per slice; the pad tail must
    # stay zero and never leak into the reassembled global array
    plan = MeshPlan(dp=8)
    g = np.arange(10, dtype=np.float32)
    state = global_to_zero1_state(g, P(), plan)
    assert state.shape == (8, 2)
    assert state.sum() == g.sum()         # pad contributed nothing
    back = zero1_state_to_global(state, P(), g.shape, plan)
    np.testing.assert_array_equal(back, g)


def test_reshard_dp8_to_dp6_non_power_of_two():
    plan_a, plan_b = MeshPlan(dp=8), MeshPlan(dp=6)
    g = np.random.default_rng(0).normal(
        size=(12, 5)).astype(np.float32)   # 60 elements: pads under dp=8
    state_a = global_to_zero1_state(g, P(), plan_a)
    state_b = reshard_zero1_leaf(state_a, P(), g.shape, plan_a, plan_b)
    assert state_b.shape == (6, 10)
    np.testing.assert_array_equal(
        zero1_state_to_global(state_b, P(), g.shape, plan_b), g)


def test_reshard_sharded_leaf_across_dp():
    # a tp-sharded leaf: spec axes lead the state shape, dp slices the
    # per-shard flattened remainder
    spec = P("tp", None)
    plan_a, plan_b = MeshPlan(dp=4, tp=2), MeshPlan(dp=2, tp=2)
    g = np.random.default_rng(1).normal(size=(8, 6)).astype(np.float32)
    state_a = global_to_zero1_state(g, spec, plan_a)
    assert state_a.shape == (2, 4, 6)     # (tp, dp, K=24/4)
    state_b = reshard_zero1_leaf(state_a, spec, g.shape, plan_a, plan_b)
    assert state_b.shape == (2, 2, 12)
    np.testing.assert_array_equal(
        zero1_state_to_global(state_b, spec, g.shape, plan_b), g)


def test_reshard_tuple_axis_leaf():
    # stage-stacked + tp dims share one array dim via a tuple spec
    spec = P(("pp", "tp"))
    plan_a = MeshPlan(dp=2, pp=2, tp=2)
    plan_b = MeshPlan(dp=1, pp=2, tp=2)   # dp shrink, pp unchanged
    g = np.random.default_rng(2).normal(size=(8, 4)).astype(np.float32)
    state_a = global_to_zero1_state(g, spec, plan_a)
    assert state_a.shape == (2, 2, 2, 4)  # (pp, tp, dp, K=8/2)
    state_b = reshard_zero1_leaf(state_a, spec, g.shape, plan_a, plan_b)
    np.testing.assert_array_equal(
        zero1_state_to_global(state_b, spec, g.shape, plan_b), g)


def test_zero1_state_shape_mismatch_refused():
    with pytest.raises(ValueError, match="does not match plan layout"):
        zero1_state_to_global(np.zeros((4, 2), np.float32), P(),
                              (12,), MeshPlan(dp=8))


def test_reshard_opt_state_same_plan_is_passthrough():
    # THE bit-identical contract: same plan + same zero1 flag returns
    # the object untouched (no copy, no float round-trip)
    plan = MeshPlan(dp=4)
    g = np.ones((8,), np.float32)
    state = global_to_zero1_state(g, P(), plan)
    opt = AdamWState(count=np.int32(7), mu={"w": state},
                     nu={"w": state})
    out = reshard_opt_state(opt, {"w": g}, {"w": P()}, plan, plan,
                            zero1_a=True, zero1_b=True)
    assert out is opt


def test_reshard_opt_state_zero1_to_plain_and_back():
    plan = MeshPlan(dp=4)
    g = np.random.default_rng(3).normal(size=(8, 3)).astype(np.float32)
    z = global_to_zero1_state(g, P(), plan)
    opt_z = AdamWState(count=np.int32(2), mu={"w": z}, nu={"w": z})
    shapes, specs = {"w": g}, {"w": P()}
    # zero1 → plain: moments land global
    opt_p = reshard_opt_state(opt_z, shapes, specs, plan, plan,
                              zero1_a=True, zero1_b=False)
    np.testing.assert_array_equal(opt_p.mu["w"], g)
    # plain → zero1: back to slices
    opt_z2 = reshard_opt_state(opt_p, shapes, specs, plan, plan,
                               zero1_a=False, zero1_b=True)
    np.testing.assert_array_equal(opt_z2.nu["w"], z)


# ------------------------------------------------------ restore classify

def test_resolve_restore_same_plan():
    plan = MeshPlan(dp=2)
    manifest = {"meta": manifest_meta(plan, zero1=True)}
    assert resolve_restore(manifest, plan, True) == \
        ("same-plan", plan, True)


def test_resolve_restore_cross_plan():
    saved = MeshPlan(dp=4)
    manifest = {"meta": manifest_meta(saved, zero1=True)}
    mode, got_plan, got_z1 = resolve_restore(manifest, MeshPlan(dp=2),
                                             True)
    assert (mode, got_plan, got_z1) == ("reshard", saved, True)
    # a zero1-flag flip alone also reshards (layouts differ)
    mode, _, _ = resolve_restore(manifest, saved, False)
    assert mode == "reshard"


def test_resolve_restore_refuses_pp_change():
    manifest = {"meta": manifest_meta(MeshPlan(dp=2, pp=2), zero1=False)}
    with pytest.raises(ValueError, match="pipeline stage count"):
        resolve_restore(manifest, MeshPlan(dp=2, pp=1), False)
    with pytest.raises(ValueError, match="pipeline stage count"):
        check_reshardable(MeshPlan(pp=2, vpp=2, dp=2),
                          MeshPlan(pp=2, vpp=1, dp=2))


def test_resolve_restore_legacy_manifest_warns():
    with pytest.warns(DeprecationWarning, match="no plan block"):
        mode, plan, z1 = resolve_restore({"step": 3, "leaves": {}},
                                         MeshPlan(dp=2), True)
    assert (mode, plan, z1) == ("legacy", None, True)


def test_plan_from_meta_unknown_format_refused():
    meta = manifest_meta(MeshPlan(dp=2), zero1=False)
    assert plan_from_meta(meta) == MeshPlan(dp=2)
    assert meta["format"] == MANIFEST_FORMAT
    with pytest.raises(ValueError, match="unknown checkpoint meta"):
        plan_from_meta(dict(meta, format="htpu-ckpt-plan-99"))


def test_manifest_meta_rides_written_checkpoint(tmp_path):
    fs = LocalFileSystem()
    base = str(tmp_path / "ck")
    plan = MeshPlan(dp=2)
    write_snapshot(fs, base, 5, snapshot_tree({"w": np.ones(4)}),
                   meta=manifest_meta(plan, zero1=True))
    mode, saved, z1 = resolve_restore(read_manifest(fs, base, 5),
                                      plan, True)
    assert (mode, saved, z1) == ("same-plan", plan, True)


# ------------------------------------------------------ retention sweep

def test_retention_sweep_breadcrumbs(tmp_path):
    fs = LocalFileSystem()
    base = str(tmp_path / "ck")
    snap = snapshot_tree({"w": np.arange(4.0)})
    for s in (1, 2, 3):
        write_snapshot(fs, base, s, snap, keep=10)
    # a crashed publish: step dir with shards but no manifest
    orphan = f"{base}/step_{9:012d}"
    fs.mkdirs(orphan)
    fs.write_all(f"{orphan}/shard_000000.bin", b"xx")
    swept = dict(_retain(fs, base, keep=2))
    assert swept == {f"{base}/step_{1:012d}": "retention",
                     orphan: "crash-mid-write"}
    assert list_checkpoints(fs, base) == [2, 3]


# ------------------------------------------------------- shrink planning

def test_pick_shrunken_plan_non_power_of_two():
    assert pick_shrunken_plan(MeshPlan(dp=4), healthy=3, batch=12,
                              min_dp=1) == MeshPlan(dp=3)


def test_pick_shrunken_plan_respects_batch_divisibility():
    # 8 % 3 != 0 → falls through to dp=2
    assert pick_shrunken_plan(MeshPlan(dp=4), healthy=3, batch=8,
                              min_dp=1) == MeshPlan(dp=2)


def test_pick_shrunken_plan_respects_min_dp():
    assert pick_shrunken_plan(MeshPlan(dp=4), healthy=2, batch=12,
                              min_dp=3) is None


def test_pick_shrunken_plan_with_ep():
    got = pick_shrunken_plan(MeshPlan(dp=4, ep=2), healthy=2, batch=8,
                             min_dp=1)
    assert got == MeshPlan(dp=2, ep=2)    # batch % (dp' * ep) == 0


# --------------------------------------------------------- controller

class FakeTrainer:
    """Duck-typed ElasticController trainer contract."""

    def __init__(self, plan, batch=12, restore_step=30):
        self.plan = plan
        self.batch = batch
        self.step = 40
        self.restore_step = restore_step
        self.saves = []
        self.applied = []

    def save(self, wait=None):
        self.saves.append((self.step, wait))

    def apply_plan(self, plan):
        self.applied.append(plan)
        self.plan = plan
        self.step = self.restore_step
        return True


def doctor_report(flagged=(), dead=(), n=4):
    ranks = {f"rank-{r}": {"ok": f"rank-{r}" not in dead, "rank": r}
             for r in range(n)}
    return {"trainers": {
        "flagged": {name: {"signals": ["trainer.step_wall"]}
                    for name in flagged},
        "ranks": ranks}}


def _controller(trainer, reports, **cfg_kw):
    kw = dict(enabled=True, poll_steps=1, min_dp=1, demote_windows=2,
              evict_windows=10, dead_windows=2, cooldown_polls=0)
    kw.update(cfg_kw)
    feed = list(reports)
    return ElasticController(trainer, ElasticConfig(**kw),
                             poll_fn=lambda: feed.pop(0))


def test_controller_requires_poll_fn():
    with pytest.raises(ValueError, match="poll_fn"):
        ElasticController(FakeTrainer(MeshPlan(dp=4)),
                          ElasticConfig(enabled=True), poll_fn=None)


def test_demote_fires_once_per_streak():
    tr = FakeTrainer(MeshPlan(dp=4))
    flagged = doctor_report(flagged=["rank-1"])
    clear = doctor_report()
    ctl = _controller(tr, [flagged, flagged, flagged, clear,
                           flagged, flagged])
    for step in range(1, 4):
        assert ctl.on_step(step) is False
    # streak hit demote_windows=2 at poll 2; polls 3+ must not re-save
    assert tr.saves == [(40, False)]
    assert [e["decision"] for e in ctl.events] == ["demote"]
    ctl.on_step(4)                        # flag clears → streak resets
    ctl.on_step(5)
    assert ctl.on_step(6) is False        # fresh streak → second demote
    assert len(tr.saves) == 2


def test_dead_rank_evicts_and_reshards():
    tr = FakeTrainer(MeshPlan(dp=4))
    dead = doctor_report(dead=["rank-2"])
    ctl = _controller(tr, [dead] * 6, dead_windows=1, cooldown_polls=0)
    assert ctl.on_step(1) is True         # dead_windows=1 → immediate
    assert ctl.pending
    assert tr.applied == []               # decision only; no actuation
    assert ctl.on_step(2) is True         # pending short-circuits polls
    assert ctl.resume() is True
    assert tr.applied == [MeshPlan(dp=3)]  # healthy=3, 12 % 3 == 0
    assert not ctl.pending
    ev = {e["decision"]: e for e in ctl.events}
    assert ev["evict"]["ranks"] == ["rank-2"]
    assert ev["evict"]["plan_to"]["dp"] == 3
    assert ev["resume"]["lost_steps"] == 10   # step 40 → restored 30
    assert ev["resume"]["restored"] is True
    # the dead rank's roster row lingers — it must never evict again
    for step in (3, 4, 5):
        assert ctl.on_step(step) is False
    assert len([e for e in ctl.events
                if e["decision"] == "evict"]) == 1


def test_flagged_streak_evicts_at_threshold():
    tr = FakeTrainer(MeshPlan(dp=4))
    flagged = doctor_report(flagged=["rank-0"])
    ctl = _controller(tr, [flagged] * 5, demote_windows=2,
                      evict_windows=4)
    got = [ctl.on_step(s) for s in range(1, 5)]
    assert got == [False, False, False, True]
    assert tr.saves == [(40, False)]      # the demote at streak 2
    assert ctl.resume() is True
    assert tr.applied == [MeshPlan(dp=3)]


def test_cooldown_hysteresis_after_resume():
    tr = FakeTrainer(MeshPlan(dp=4))
    first_dead = doctor_report(dead=["rank-3"])
    then_dead = doctor_report(dead=["rank-3", "rank-1"])
    ctl = _controller(tr, [first_dead] + [then_dead] * 4,
                      dead_windows=1, cooldown_polls=2)
    assert ctl.on_step(1) is True
    ctl.resume()
    # rank-1 dies during cooldown: streak builds but decisions wait
    assert ctl.on_step(2) is False
    assert ctl.on_step(3) is False
    assert ctl.on_step(4) is True         # cooldown spent → evict
    ctl.resume()
    assert [p.dp for p in tr.applied] == [3, 2]


def test_evict_infeasible_raises():
    tr = FakeTrainer(MeshPlan(dp=2), batch=12)
    dead = doctor_report(dead=["rank-1"], n=2)
    ctl = _controller(tr, [dead], dead_windows=1, min_dp=2)
    with pytest.raises(RuntimeError, match="no dp in"):
        ctl.on_step(1)
    assert [e["decision"] for e in ctl.events] == ["evict-infeasible"]


def test_poll_failure_is_not_fatal():
    tr = FakeTrainer(MeshPlan(dp=4))

    def boom():
        raise OSError("doctor unreachable")

    ctl = ElasticController(tr, ElasticConfig(enabled=True),
                            poll_fn=boom)
    assert ctl.on_step(1) is False
    assert ctl.events == []


def test_controller_report_shape():
    tr = FakeTrainer(MeshPlan(dp=4))
    ctl = _controller(tr, [doctor_report(flagged=["rank-1"])])
    ctl.on_step(1)
    rep = ctl.report()
    assert rep["enabled"] is True
    assert rep["config"] == dataclasses.asdict(ctl.cfg)
    assert rep["plan"]["dp"] == 4
    assert rep["flagged_streaks"] == {"rank-1": 1}
    assert rep["evicted_ranks"] == []
    assert rep["events"] == []


# ------------------------------------------------------------- config

def test_elastic_config_validation():
    with pytest.raises(ValueError, match="must exceed"):
        ElasticConfig(demote_windows=3, evict_windows=3)
    with pytest.raises(ValueError, match="poll.steps"):
        ElasticConfig(poll_steps=0)
    with pytest.raises(ValueError, match="min-dp"):
        ElasticConfig(min_dp=0)


def test_elastic_from_conf():
    conf = Configuration(load_defaults=False)
    conf.set("elastic.enabled", "true")
    conf.set("elastic.poll.steps", "5")
    conf.set("elastic.min-dp", "2")
    conf.set("elastic.evict.windows", "7")
    got = elastic_from_conf(conf)
    assert got == ElasticConfig(enabled=True, poll_steps=5, min_dp=2,
                                evict_windows=7)
    assert elastic_from_conf(None) == ElasticConfig()


# ------------------------------------------------- trainer integration

def test_trainer_same_plan_restore_bit_identical(tmp_path):
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel.trainer import Trainer
    fs = LocalFileSystem()
    cfg = get_config("tiny", max_seq=32)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 50_000, dtype=np.uint16)
    data = str(tmp_path / "toks.bin")
    fs.write_all(data, toks.tobytes())
    ck = str(tmp_path / "ck")
    plan = MeshPlan(dp=4)
    tr = Trainer(cfg, plan, fs, data, ck, batch=8, zero1=True,
                 ckpt_interval=0)
    tr.train(3)
    tr.save()
    tr2 = Trainer(cfg, plan, fs, data, ck, batch=8, zero1=True,
                  ckpt_interval=0)
    assert tr2.try_restore() and tr2.step == 3
    for a, b in zip(jax.tree_util.tree_leaves((tr.params, tr.opt)),
                    jax.tree_util.tree_leaves((tr2.params, tr2.opt))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tr.close()
    tr2.close()


def test_trainer_reshard_restore_across_plans(tmp_path):
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel.mesh import param_specs
    from hadoop_tpu.parallel.trainer import Trainer
    fs = LocalFileSystem()
    cfg = get_config("tiny", max_seq=32)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 50_000, dtype=np.uint16)
    data = str(tmp_path / "toks.bin")
    fs.write_all(data, toks.tobytes())
    ck = str(tmp_path / "ck")
    plan_a, plan_b = MeshPlan(dp=4), MeshPlan(dp=2)
    tr = Trainer(cfg, plan_a, fs, data, ck, batch=8, zero1=True,
                 ckpt_interval=0)
    tr.train(3)
    tr.save()
    tr2 = Trainer(cfg, plan_b, fs, data, ck, batch=8, zero1=True,
                  ckpt_interval=0)
    assert tr2.try_restore() and tr2.step == 3
    # params restore to the same global values under either plan
    for a, b in zip(jax.tree_util.tree_leaves(tr.params),
                    jax.tree_util.tree_leaves(tr2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # moments agree exactly through their global layouts
    specs = param_specs(cfg, plan_a)
    flat = zip(
        jax.tree_util.tree_leaves_with_path(tr.opt.mu),
        jax.tree_util.tree_leaves(tr2.opt.mu),
        jax.tree_util.tree_leaves(tr.params),
        jax.tree_util.tree_leaves(specs))
    for (_, ma), mb, p, spec in flat:
        ga = zero1_state_to_global(np.asarray(ma), spec,
                                   np.shape(p), plan_a)
        gb = zero1_state_to_global(np.asarray(mb), spec,
                                   np.shape(p), plan_b)
        np.testing.assert_array_equal(ga, gb)
    tr.close()
    tr2.close()
