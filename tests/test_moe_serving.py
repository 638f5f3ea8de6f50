"""Expert-parallel MoE serving (serving/families/gqa.py ``moe_mlp`` + the
weight plane's expert stacks).

Pins the contracts the workload class ships under:

- the per-tensor policy table covers the expert stacks (int8
  per-expert, router stays f32) and the streamed quantize-at-load path
  is bit-identical to the in-memory application on an MoE checkpoint;
- capacity semantics at the serving seam: a top_k = n_experts
  degenerate config matches the dense path, dropped tokens pass the
  residual through EXACTLY (all-zero MLP contribution);
- the fused step stays compile-once per shape with routing enabled —
  capacity padding keeps shapes static;
- the relaxed tier's all2all payload quantization is measured on the
  comm ledger (``moe.dispatch``/``moe.combine``, >= 2x byte cut,
  honest per-step executions) and gated by the logits A-B guard, which
  must also REJECT a zeroed expert payload (falsifiability);
- expert placement is observable: the ``moe_experts`` HBM component,
  the ``htpu_hbm_bytes`` gauge, and the weight-plane/health fields.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.decoder import init_params
from hadoop_tpu.models.moe import capacity, route
from hadoop_tpu.serving import weightplane as wp
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams


@pytest.fixture(scope="module")
def moe_model():
    cfg = get_config("tiny-moe")
    return init_params(jax.random.PRNGKey(0), cfg), cfg


MOE_POLICY = wp.WeightPlaneConfig(tier="relaxed", group=16)
# MoE guard thresholds: near-tie routing flips spike single positions'
# logits, so the rel-err bound is wide and the argmax-agreement
# dimension carries the systematic-damage check (the falsifier test
# below proves the pair still discriminates)
MOE_AGREE, MOE_REL = 0.9, 3.0


# ------------------------------------------------ weight-plane coverage

def test_policy_quantizes_expert_stacks_router_stays_f32(moe_model):
    params, cfg = moe_model
    qp, rep = wp.quantize_params(params, cfg, MOE_POLICY)
    layers = qp["layers"]
    for k in sorted(wp.EXPERT_STACKS):
        assert wp.is_qtensor(layers[k]), k
        # per-expert grouping: leading [L, E] dims survive on payload
        # AND scales — a scale can never pair with another expert's q
        L, E = cfg.n_layers, cfg.n_experts
        assert layers[k]["q"].shape[:2] == (L, E)
        assert layers[k]["s"].shape[:2] == (L, E)
    # the router is value-critical and byte-irrelevant: stays f32
    assert not wp.is_qtensor(layers["router"])
    assert layers["router"].dtype == jnp.float32
    # 4 attn matmuls + 3 expert stacks
    assert rep["leaves_quantized"] == 7
    assert rep["moe_experts"] == cfg.n_experts
    # measured expert bytes: the int8 stacks are ~4x under f32
    eb_f32 = wp.expert_weight_bytes(params, cfg)
    eb_int8 = wp.expert_weight_bytes(qp, cfg)
    assert rep["expert_bytes"] == eb_int8
    assert eb_f32 > 3 * eb_int8 > 0
    # dense configs report zero (the component is MoE-only)
    dense_cfg = get_config("tiny")
    dense = init_params(jax.random.PRNGKey(0), dense_cfg)
    assert wp.expert_weight_bytes(dense, dense_cfg) == 0


def test_dequantize_round_trips_expert_stacks(moe_model):
    """dequantize_params restores the expert stacks' shapes/axes —
    run_weight_ab's reference forward depends on this."""
    params, cfg = moe_model
    qp, _ = wp.quantize_params(params, cfg, MOE_POLICY)
    back = wp.dequantize_params(qp, cfg)
    for k in sorted(wp.EXPERT_STACKS):
        a, b = params["layers"][k], back["layers"][k]
        assert a.shape == b.shape
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-2)


def test_expert_shard_count_rules():
    # auto: the largest divisor of n_experts that fits the devices
    assert wp.expert_shard_count(8, 0, 4) == 4
    assert wp.expert_shard_count(8, 0, 3) == 2
    assert wp.expert_shard_count(4, 0, 1) == 1
    assert wp.expert_shard_count(0, 0, 8) == 1     # dense: no shards
    # explicit: must divide the experts and fit the devices — loudly
    assert wp.expert_shard_count(8, 2, 4) == 2
    with pytest.raises(ValueError, match="divide"):
        wp.expert_shard_count(8, 3, 4)
    with pytest.raises(ValueError, match="device"):
        wp.expert_shard_count(8, 8, 4)


def test_streamed_moe_load_bit_identical(tmp_path, moe_model):
    """Quantize-at-load on an MoE checkpoint: the expert stacks stream
    through the same per-leaf transform and land BIT-identical to the
    in-memory policy application."""
    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    params, cfg = moe_model
    fs = LocalFileSystem()
    save_checkpoint(fs, f"{tmp_path}/ckpt", 3,
                    {"params": params, "opt": {}})
    qp_mem, _ = wp.quantize_params(params, cfg, MOE_POLICY)
    qp_load, step, report = wp.quantized_load(
        fs, f"{tmp_path}/ckpt", cfg, MOE_POLICY, io_workers=4)
    assert step == 3
    assert report["expert_bytes"] == wp.expert_weight_bytes(qp_mem, cfg)
    a = jax.tree_util.tree_leaves(qp_mem)
    b = jax.tree_util.tree_leaves(qp_load)
    assert len(a) == len(b)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))
    # and the streamed tree serves through the routed step
    eng = DecodeEngine(qp_load, cfg, max_batch=2, block_size=4,
                       max_context=64)
    assert len(eng.generate([[1, 2, 3]],
                            SamplingParams(max_new_tokens=3))[0]) == 3


# ----------------------------------------- capacity semantics at serving

def test_topk_equals_experts_matches_dense_path(moe_model):
    """top_k = n_experts with identical experts degenerates to ONE
    dense SwiGLU MLP (renormalized gates sum to 1), so the routed
    engine must match a dense engine built from expert 0's weights —
    same embed/attention tree, same greedy tokens."""
    params, cfg = moe_model
    deg_cfg = dataclasses.replace(cfg, top_k=cfg.n_experts)
    layers = dict(params["layers"])
    for k in sorted(wp.EXPERT_STACKS):
        w = layers[k]
        layers[k] = jnp.broadcast_to(w[:, :1], w.shape)
    moe_params = dict(params)
    moe_params["layers"] = layers

    dense_cfg = dataclasses.replace(cfg, n_experts=0)
    dense_layers = {k: (v[:, 0] if k in wp.EXPERT_STACKS else v)
                    for k, v in layers.items() if k != "router"}
    dense_params = dict(params)
    dense_params["layers"] = dense_layers

    prompts = [[7, 3, 11, 5], [2, 9]]
    sp = SamplingParams(max_new_tokens=6)
    eng_moe = DecodeEngine(moe_params, deg_cfg, max_batch=2,
                           block_size=4, max_context=64)
    eng_dense = DecodeEngine(dense_params, dense_cfg, max_batch=2,
                             block_size=4, max_context=64)
    assert eng_moe.generate(prompts, sp) == eng_dense.generate(prompts,
                                                               sp)


def test_dropped_token_residual_passthrough_exact(moe_model):
    """Tokens past every routed expert's capacity contribute EXACTLY
    zero MLP output (all-zero combine row -> exact 0.0 from the
    combine einsum), i.e. the residual passes through bit-for-bit.
    Routing is forced: every token picks experts 0 and 1, so with
    T=8, k=2, E=4, cf=1.25 the capacity is C=5 and tokens 5..7 drop."""
    params, cfg = moe_model
    D, E = cfg.d_model, cfg.n_experts
    assert capacity(8, cfg) == 5
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=64)
    lp = {k: v[0] for k, v in params["layers"].items()}
    # router: every token's logits are [big, 0, 0, 0] -> top-2 picks
    # experts 0 and 1 (top_k tie-break is by index, deterministic)
    router = np.zeros((D, E), np.float32)
    router[0, 0] = 1.0
    lp["router"] = jnp.asarray(router)
    x = jnp.tile(jnp.eye(1, D, 0, dtype=jnp.float32) * 5.0, (8, 1))
    y = eng._family.moe_mlp(x, lp)
    assert y.shape == (8, D)
    y = np.asarray(y)
    # kept rows produce a real MLP contribution...
    assert np.abs(y[:5]).max() > 0
    # ...dropped rows are EXACTLY zero — not small, zero
    assert np.array_equal(y[5:], np.zeros_like(y[5:]))
    # the same rule the engine/bench observability publishes
    assert eng.weight_plane()["expert_capacity"] == \
        capacity(eng.max_batch * (eng.spec_k + 1), cfg)
    # sanity on the forced routing itself
    dispatch, combine = route(x, lp["router"], cfg)
    assert float(jnp.sum(combine[5:])) == 0.0
    assert float(jnp.sum(dispatch[:5])) > 0


def test_compile_once_with_moe_enabled(moe_model):
    """Routing must not add shape families: both arms (bitwise f32 and
    relaxed int8) compile exactly one decode-only and one fused-prefill
    program across a mixed workload, and the relaxed arm replays
    deterministically."""
    params, cfg = moe_model
    qp, _ = wp.quantize_params(params, cfg, MOE_POLICY)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (9, 4, 17, 6)]
    sp = SamplingParams(max_new_tokens=6)
    for p in (params, qp):
        eng = DecodeEngine(p, cfg, max_batch=2, block_size=4,
                           max_context=64)
        outs = eng.generate(prompts, sp)
        assert all(len(o) == 6 for o in outs)
        assert eng.decode_compiles == 1, eng.decode_compiles
        assert eng.prefill_compiles == 1, eng.prefill_compiles
    eng2 = DecodeEngine(qp, cfg, max_batch=2, block_size=4,
                        max_context=64)
    assert eng2.generate(prompts, sp) == outs


# ------------------------------------------- relaxed tier: a2a + guard

def test_comm_ledger_records_quantized_a2a(moe_model):
    """The relaxed engine's dispatch/combine legs land on the comm
    ledger at the bounded MoE sites with >= 2x byte cut and honest
    per-step executions (comm_scale x the scan length, both shapes)."""
    from hadoop_tpu.parallel.lowp.quant import capture_comm
    params, cfg = moe_model
    qp, _ = wp.quantize_params(params, cfg, MOE_POLICY)
    eng = DecodeEngine(qp, cfg, max_batch=2, block_size=4,
                       max_context=64)
    with capture_comm() as led:
        eng.generate([[5, 1, 4, 2, 8, 3]],
                     SamplingParams(max_new_tokens=4))
    assert set(led.per_site) == {"moe.dispatch", "moe.combine"}
    for site, (payload, reference, execs) in led.per_site.items():
        assert 0 < payload < reference, site
        # two shape families traced, n_layers legs each per step
        assert execs == 2 * cfg.n_layers, (site, execs)
    assert led.ratio >= 2.0, led.ratio
    # bitwise serving records NOTHING at the MoE sites (the guard the
    # lint enforces lexically, proven dynamically here)
    eng32 = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                         max_context=64)
    with capture_comm() as led32:
        eng32.generate([[5, 1, 4]], SamplingParams(max_new_tokens=3))
    assert led32.per_site == {}


def test_a2a_codec_none_serves_without_payload_quant(moe_model):
    """serving.moe.a2a.codec=none: the relaxed engine still serves the
    int8 expert stacks but exchanges f32 payloads — zero MoE comm
    sites; an unknown codec fails loudly at construction."""
    from hadoop_tpu.parallel.lowp.quant import capture_comm
    params, cfg = moe_model
    qp, _ = wp.quantize_params(params, cfg, MOE_POLICY)
    eng = DecodeEngine(qp, cfg, max_batch=2, block_size=4,
                       max_context=64, moe_a2a_codec="none")
    with capture_comm() as led:
        out = eng.generate([[5, 1, 4]], SamplingParams(max_new_tokens=3))
    assert len(out[0]) == 3
    assert led.per_site == {}
    with pytest.raises(ValueError, match="codec"):
        DecodeEngine(qp, cfg, max_batch=2, block_size=4,
                     max_context=64, moe_a2a_codec="fp4")


def test_moe_guard_accepts_and_falsifier_rejects(moe_model):
    """Acceptance rides run_weight_ab at the MoE thresholds; the SAME
    thresholds must reject a zeroed expert payload (w_down int8 bytes
    zeroed, scales kept) — falsifiability of the acceptance."""
    params, cfg = moe_model
    qp, _ = wp.quantize_params(params, cfg, MOE_POLICY)
    report = wp.run_weight_ab(cfg, params, qp, wp=MOE_POLICY,
                              min_agree=MOE_AGREE, rel_tol=MOE_REL)
    assert report["accepted"], report
    assert report["greedy_agree"] >= MOE_AGREE
    broken = dict(qp)
    broken["layers"] = dict(qp["layers"])
    wd = qp["layers"]["w_down"]
    broken["layers"]["w_down"] = {"q": jnp.zeros_like(wd["q"]),
                                  "s": wd["s"]}
    falsifier = wp.run_weight_ab(cfg, params, broken, wp=MOE_POLICY,
                                 min_agree=MOE_AGREE, rel_tol=MOE_REL)
    assert not falsifier["accepted"], falsifier


def test_capacity_factor_override_widens_slots(moe_model):
    """serving.moe.capacity.factor overrides the checkpoint config's
    padding at the engine door (0 = keep the model's)."""
    params, cfg = moe_model
    e_default = DecodeEngine(params, cfg, max_batch=8, block_size=4,
                             max_context=64)
    e_wide = DecodeEngine(params, cfg, max_batch=8, block_size=4,
                          max_context=64, moe_capacity_factor=4.0)
    c_def = e_default.weight_plane()["expert_capacity"]
    c_wide = e_wide.weight_plane()["expert_capacity"]
    assert c_wide > c_def
    assert c_def == capacity(8, cfg)
    assert c_wide == capacity(
        8, dataclasses.replace(cfg, capacity_factor=4.0))


# --------------------------------------------------------- observability

def test_moe_experts_hbm_component_and_gauge(moe_model):
    """Resident expert bytes ride the live HBM ledger as the
    ``moe_experts`` component (beside, not inside, the dense weights
    remainder), surface as the htpu_hbm_bytes gauge, and unregister at
    stop()."""
    import re

    from hadoop_tpu.metrics import metrics_system
    from hadoop_tpu.metrics.prom import render_prom
    from hadoop_tpu.obs.hbm import HBM_COMPONENTS, hbm_ledger
    params, cfg = moe_model
    qp, _ = wp.quantize_params(params, cfg, MOE_POLICY)
    eng = DecodeEngine(qp, cfg, max_batch=2, block_size=4,
                       num_blocks=9, max_context=32)
    comps, errors = hbm_ledger().component_bytes()
    assert errors == 0
    assert comps["moe_experts"] == eng.expert_bytes > 0
    # the dense remainder excludes the expert stacks — no double count
    assert comps["weights"] == eng.weight_bytes - eng.expert_bytes
    assert comps["kv_pool"] == 9 * eng.block_nbytes
    text = render_prom(metrics_system())
    gauge = [ln for ln in text.splitlines()
             if 'component="moe_experts"' in ln
             and ln.startswith("htpu_hbm_bytes")]
    assert gauge and float(gauge[0].rsplit(" ", 1)[1]) == \
        eng.expert_bytes
    comp_labels = set(re.findall(
        r'htpu_hbm_bytes\{[^}]*component="([^"]+)"', text))
    assert comp_labels <= set(HBM_COMPONENTS)
    eng.stop()
    comps, _ = hbm_ledger().component_bytes()
    assert "moe_experts" not in comps and "weights" not in comps


def test_health_and_registry_surface_expert_placement(tmp_path,
                                                      moe_model):
    """/v1/health's weights block carries expert count/shards/bytes
    next to weight_dtype, and the replica's registry record advertises
    the same placement for the autoscaler."""
    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    from hadoop_tpu.registry import RegistryServer
    from hadoop_tpu.serving.service import ServingReplica
    params, cfg = moe_model
    save_checkpoint(LocalFileSystem(), f"{tmp_path}/ckpt", 1,
                    {"params": params, "opt": {}})
    conf = Configuration(load_defaults=False)
    conf.set("serving.parity", "relaxed")
    conf.set("serving.weights.group", "16")
    conf.set("serving.max.batch", "2")
    conf.set("serving.kv.block.size", "4")
    conf.set("serving.max.context", "64")
    reg_srv = RegistryServer(conf)
    reg_srv.init(conf)
    reg_srv.start()
    try:
        replica = ServingReplica(
            conf, name="moe", checkpoint=f"file://{tmp_path}/ckpt",
            preset="tiny-moe",
            registry_addr=("127.0.0.1", reg_srv.port), instance="i0")
        replica.start()
        try:
            eng = replica.engine
            status, health = replica.server._health({}, b"")
            assert status == 200
            weights = health["weights"]
            assert weights["parity"] == "relaxed"
            assert weights["experts"] == cfg.n_experts
            # auto placement: under the test harness's virtual CPU
            # devices the expert dim actually splits (1 on one chip)
            shards = wp.expert_shard_count(cfg.n_experts, 0,
                                           jax.local_device_count())
            assert weights["expert_shards"] == shards >= 1
            assert weights["expert_bytes"] == eng.expert_bytes > 0
            assert weights["expert_capacity"] > 0
            assert weights["a2a_codec"] == "int8"
            rec = reg_srv.list("/services/serving/moe")[0]
            assert rec.attributes["weight_dtype"] == "int8"
            assert rec.attributes["experts"] == str(cfg.n_experts)
            assert rec.attributes["expert_shards"] == str(shards)
            assert rec.attributes["expert_bytes"] == \
                str(eng.expert_bytes)
        finally:
            replica.drain_and_stop(timeout=15)
    finally:
        reg_srv.stop()
