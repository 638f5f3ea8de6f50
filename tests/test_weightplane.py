"""The serving weight plane (serving/weightplane.py): int8-resident
weights behind the ``serving.parity`` tier.

Pins the four contracts the tier ships under:

- the weight codec is the ONE public per-group int8 quantizer
  (``parallel.lowp.quantize_array``) with a loud shape/group contract
  and an SQNR floor on realistic weight distributions;
- ``serving.parity=bitwise`` (the default) is byte-identical serving:
  raw params, zero quantized code reachable, greedy tokens equal to
  the full-recompute reference;
- the relaxed tier's greedy outputs are accepted by the logits/output
  A-B guard with the compile-once contract intact, and the freed HBM
  converts into >= 2x lanes x context at a fixed budget;
- quantize-at-load streams per shard: peak host f32 bytes stay
  bounded below the full model, and the streamed tree is bit-identical
  to the in-memory policy application.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.conf import Configuration
from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.decoder import forward, init_params
from hadoop_tpu.serving import weightplane as wp
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("tiny")
    return init_params(jax.random.PRNGKey(0), cfg), cfg


FULL_POLICY = wp.WeightPlaneConfig(tier="relaxed", group=16,
                                   quant_embed=True, quant_head=True)


# ----------------------------------------------------- the weight codec

def test_weight_codec_sqnr_floor_on_winit_distributions():
    """Per-group int8 round-trip keeps >= 35 dB SQNR on the fan-in
    scaled gaussians ``init_params`` actually draws — via the PUBLIC
    lowp API (the promotion: one quantizer defines every int8
    surface)."""
    from hadoop_tpu.parallel.lowp import dequantize_array, quantize_array
    rng = np.random.default_rng(7)
    for fan_in, shape in ((64, (64, 128)), (128, (128, 64)),
                          (256, (256, 64))):
        x = rng.normal(0, fan_in ** -0.5, size=shape).astype(np.float32)
        for group in (8, 16, 64):
            q, s = quantize_array(x, group=group)
            y = dequantize_array(q, s, x.shape, np.float32)
            sqnr = 10 * np.log10(float((x ** 2).mean()) /
                                 float(((x - y) ** 2).mean()))
            assert sqnr >= 35.0, (fan_in, group, sqnr)
            assert s.size == -(-x.size // group)


def test_weight_codec_zeros_exact_and_scale_shape_contract():
    arr = np.zeros((2, 32, 48), np.float32)          # [L, D, N] weight
    qw = wp.quantize_weight(arr, 16, transpose=True)
    # transposed-and-grouped layout: [L, N, G, gs] + [L, N, G]
    assert qw["q"].shape == (2, 48, 2, 16)
    assert qw["q"].dtype == np.int8
    assert qw["s"].shape == (2, 48, 2)
    assert qw["s"].dtype == np.float32
    back = wp.dequantize_weight(qw, transpose=True)
    assert back.shape == arr.shape
    assert np.array_equal(back, arr)                 # zeros decode EXACT
    # realistic values round-trip allclose with the axes restored
    rng = np.random.default_rng(0)
    arr = rng.normal(0, 0.1, size=(2, 32, 48)).astype(np.float32)
    qw = wp.quantize_weight(arr, 16, transpose=True)
    back = wp.dequantize_weight(qw, transpose=True)
    assert np.allclose(back, arr, atol=2e-3)


def test_weight_codec_group_and_shape_mismatch_is_loud():
    # a contraction dim the group does not divide raises instead of
    # silently regrouping across rows (16 does not divide 60)
    arr = np.zeros((2, 60, 48), np.float32)   # transpose -> 60 last
    with pytest.raises(ValueError, match="group"):
        wp.quantize_weight(arr, 16, transpose=True)
    with pytest.raises(ValueError, match="group"):
        wp.quantize_weight(np.zeros((2, 48, 60), np.float32), 16,
                           transpose=False)
    # a scale plane that does not match the payload is a loud error,
    # never a silent dequantization against the wrong scales
    qw = wp.quantize_weight(np.zeros((4, 32), np.float32), 16,
                            transpose=False)
    qw_bad = {"q": qw["q"], "s": qw["s"][:2]}
    with pytest.raises(ValueError, match="scale"):
        wp.dequantize_weight(qw_bad, transpose=False)


def test_policy_table_and_measured_bytes(tiny_model):
    params, cfg = tiny_model
    qp, report = wp.quantize_params(params, cfg, FULL_POLICY)
    layers = qp["layers"]
    for key in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert wp.is_qtensor(layers[key]), key
    for key in ("attn_norm_w", "mlp_norm_w"):
        assert not wp.is_qtensor(layers[key]), key       # norms stay f32
    assert wp.is_qtensor(qp["embed"])
    assert wp.is_qtensor(qp["lm_head"])
    assert not wp.is_qtensor(qp["final_norm_w"])
    assert wp.is_quantized_tree(qp) and not wp.is_quantized_tree(params)
    # measured resident bytes: int8 + scale planes ~3-4x under f32
    ratio = wp.resident_weight_bytes(params) / \
        wp.resident_weight_bytes(qp)
    assert ratio >= 3.0, ratio
    assert report["leaves_quantized"] == 9
    desc = wp.describe_tree(qp)
    assert desc["dtype"] == "int8" and desc["int8_leaves"] == 9
    # default policy (no embed/head) keeps the gather + head f32
    qp2, _ = wp.quantize_params(
        params, cfg, wp.WeightPlaneConfig(tier="relaxed", group=16))
    assert not wp.is_qtensor(qp2["embed"])
    assert not wp.is_qtensor(qp2["lm_head"])
    assert wp.is_qtensor(qp2["layers"]["wq"])
    # a bitwise config reaching the quantizer is a wiring bug, not a
    # silent quantization — enforced by the module, not the call site
    with pytest.raises(ValueError, match="relaxed"):
        wp.quantize_params(params, cfg, wp.WeightPlaneConfig())


def test_tied_embeddings_flags_must_agree():
    cfg = get_config("tiny-gpt2")                    # tie_embeddings
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="tied"):
        wp.quantize_params(params, cfg, wp.WeightPlaneConfig(
            tier="relaxed", group=16, quant_head=True))
    # agreeing flags quantize the ONE matrix once, serving both faces
    qp, _ = wp.quantize_params(params, cfg, wp.WeightPlaneConfig(
        tier="relaxed", group=16, quant_embed=True, quant_head=True))
    assert wp.is_qtensor(qp["embed"])
    ab = wp.run_weight_ab(cfg, params, qp, min_agree=0.0, rel_tol=10.0)
    assert np.isfinite(ab["max_abs"])


# ------------------------------------------------- bitwise default tier

def test_bitwise_default_is_byte_identical_serving(tiny_model):
    """serving.parity unset -> bitwise: raw params, no quantized leaf,
    and the engine's greedy tokens still match the full-recompute
    reference exactly (the pre-weight-plane contract, untouched)."""
    params, cfg = tiny_model
    assert wp.weightplane_from_conf(None).tier == "bitwise"
    assert wp.weightplane_from_conf(
        Configuration(load_defaults=False)).tier == "bitwise"
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4,
                       max_context=64)
    assert not eng._relaxed_weights
    assert eng.weight_plane()["parity"] == "bitwise"
    assert eng.weight_plane()["dtype"] == "float32"
    prompt = [3, 1, 4, 1, 5]
    out = eng.generate([prompt], SamplingParams(max_new_tokens=6))[0]
    # reference: argmax through models.decoder.forward, step by step
    seq = list(prompt)
    for _ in range(6):
        logits = forward(params, jnp.asarray([seq]), cfg)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert out == seq[len(prompt):]


# ------------------------------------------------------ the relaxed tier

def test_quantized_engine_accepted_by_logits_guard(tiny_model):
    params, cfg = tiny_model
    qp, _ = wp.quantize_params(params, cfg, FULL_POLICY)
    report = wp.run_weight_ab(cfg, params, qp, wp=FULL_POLICY)
    assert report["accepted"], report
    assert report["greedy_agree"] >= 0.95
    # and the engine actually decodes through the int8 plane with the
    # compile-once contract intact
    eng = DecodeEngine(qp, cfg, max_batch=2, block_size=4,
                       max_context=64)
    assert eng._relaxed_weights
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=9).tolist()
               for _ in range(4)]
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
    assert all(len(o) == 6 for o in outs)
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1
    # deterministic: the same quantized plane replays the same tokens
    eng2 = DecodeEngine(qp, cfg, max_batch=2, block_size=4,
                        max_context=64)
    assert eng2.generate(prompts,
                         SamplingParams(max_new_tokens=6)) == outs


def test_guard_rejects_a_broken_weight_plane(tiny_model):
    """The guard must be falsifiable: zeroing a quantized layer's
    payload re-ranks the logits and the A-B rejects."""
    params, cfg = tiny_model
    qp, _ = wp.quantize_params(params, cfg, FULL_POLICY)
    broken = jax.tree_util.tree_map(lambda x: x, qp)   # deep-ish copy
    broken["layers"] = dict(qp["layers"])
    wo = qp["layers"]["wo"]
    broken["layers"]["wo"] = {"q": jnp.zeros_like(wo["q"]),
                              "s": wo["s"]}
    report = wp.run_weight_ab(cfg, params, broken, wp=FULL_POLICY)
    assert not report["accepted"]


def test_hbm_budget_converts_weight_bytes_into_lanes(tiny_model):
    """One fixed HBM budget, two planes: the engine sizes KV blocks and
    decode lanes against the MEASURED resident-weight bytes, so the
    int8 plane admits >= 2x the lanes x context."""
    params, cfg = tiny_model
    qp, _ = wp.quantize_params(params, cfg, FULL_POLICY)
    bs, mc = 4, 64
    bnb = 2 * cfg.n_layers * bs * cfg.n_kv_heads * cfg.head_dim * 4
    budget = wp.resident_weight_bytes(params) + \
        (2 * (mc // bs) + 2) * bnb
    # under a budget the engine takes the tree for its own (the stacks
    # it re-places are freed): the shared fixture hands over a copy
    e32 = DecodeEngine(jax.tree_util.tree_map(jnp.copy, params), cfg,
                       block_size=bs, max_context=mc, hbm_bytes=budget)
    e8 = DecodeEngine(qp, cfg, block_size=bs, max_context=mc,
                      hbm_bytes=budget)
    assert e32.max_batch == 2
    assert e8.max_batch >= 2 * e32.max_batch
    cap32 = e32.weight_plane()["lanes_x_context"]
    cap8 = e8.weight_plane()["lanes_x_context"]
    assert cap8 >= 2 * cap32, (cap8, cap32)
    assert e8.pool.num_usable >= 2 * e32.pool.num_usable
    # a budget the weights alone overflow is a loud error
    with pytest.raises(ValueError, match="hbm"):
        DecodeEngine(params, cfg, block_size=bs, max_context=mc,
                     hbm_bytes=wp.resident_weight_bytes(params) + bnb)


def test_quantize_at_load_streams_per_shard(tmp_path, tiny_model):
    """Quantize-at-load: the loader's per-leaf streaming keeps peak
    host f32 bytes bounded below the full model, and the streamed tree
    is BIT-identical to the in-memory policy application (one policy,
    two paths, zero drift)."""
    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    params, cfg = tiny_model
    fs = LocalFileSystem()
    save_checkpoint(fs, f"{tmp_path}/ckpt", 5,
                    {"params": params, "opt": {}})
    qp_mem, _ = wp.quantize_params(params, cfg, FULL_POLICY)
    qp_load, step, report = wp.quantized_load(
        fs, f"{tmp_path}/ckpt", cfg, FULL_POLICY, io_workers=4)
    assert step == 5
    assert 0 < report["peak_f32_bytes"] < report["total_f32_bytes"]
    assert report["weight_bytes"] == wp.resident_weight_bytes(qp_mem)
    assert report["quantize_seconds"] >= 0.0
    a = jax.tree_util.tree_leaves(qp_mem)
    b = jax.tree_util.tree_leaves(qp_load)
    assert len(a) == len(b)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))
    # and the streamed tree serves
    eng = DecodeEngine(qp_load, cfg, max_batch=2, block_size=4,
                       max_context=64)
    assert len(eng.generate([[1, 2, 3]],
                            SamplingParams(max_new_tokens=3))[0]) == 3


# ------------------------------------------------- observability surface

def test_weight_plane_rides_health_and_prom(tiny_model):
    """/v1/health reports the weight plane next to the cache stats and
    the htpu_weight_bytes gauge lands on /prom (same test as the
    traffic: the metrics system resets between tests)."""
    from hadoop_tpu.metrics import metrics_system
    from hadoop_tpu.metrics.prom import render_prom
    from hadoop_tpu.serving.metrics import ServingMetrics
    from hadoop_tpu.serving.server import ServingServer
    params, cfg = tiny_model
    qp, rep = wp.quantize_params(params, cfg, FULL_POLICY)
    eng = DecodeEngine(qp, cfg, max_batch=2, block_size=4,
                       max_context=64, metrics=ServingMetrics(),
                       quantize_seconds=rep["quantize_seconds"])
    server = ServingServer(eng, Configuration(load_defaults=False))
    status, health = server._health({}, b"")
    assert status == 200
    weights = health["weights"]
    assert weights["parity"] == "relaxed"
    assert weights["dtype"] == "int8"
    assert weights["weight_bytes"] == wp.resident_weight_bytes(qp)
    assert weights["quantize_seconds"] == rep["quantize_seconds"]
    assert weights["lanes_x_context"] == eng.max_batch * eng.s_max
    prom = render_prom(metrics_system())
    line = [ln for ln in prom.splitlines()
            if ln.startswith("htpu_weight_bytes")]
    assert line and float(line[0].rsplit(" ", 1)[1]) == \
        wp.resident_weight_bytes(qp)


def test_replica_lifecycle_relaxed_parity(tmp_path, tiny_model):
    """ServingReplica end-to-end under serving.parity=relaxed: the
    checkpoint streams through the quantizer at load, the registry
    record and /v1/health report the int8 weight plane, and the door
    serves greedy tokens."""
    import http.client
    import json as _json

    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    from hadoop_tpu.registry import RegistryServer
    from hadoop_tpu.serving.service import ServingReplica
    params, cfg = tiny_model
    save_checkpoint(LocalFileSystem(), f"{tmp_path}/ckpt", 2,
                    {"params": params, "opt": {}})
    conf = Configuration(load_defaults=False)
    conf.set("serving.parity", "relaxed")
    conf.set("serving.weights.group", "16")
    conf.set("serving.weights.embed", "true")
    conf.set("serving.weights.head", "true")
    reg_srv = RegistryServer(conf)
    reg_srv.init(conf)
    reg_srv.start()
    try:
        replica = ServingReplica(
            conf, name="wplane", checkpoint=f"file://{tmp_path}/ckpt",
            preset="tiny", registry_addr=("127.0.0.1", reg_srv.port),
            instance="i0")
        replica.start()
        rec = reg_srv.list("/services/serving/wplane")[0]
        assert rec.attributes["weight_dtype"] == "int8"
        assert int(rec.attributes["weight_bytes"]) == \
            replica.engine.weight_bytes
        assert float(rec.attributes["quantize_seconds"]) >= 0.0
        conn = http.client.HTTPConnection("127.0.0.1",
                                          replica.server.port, timeout=30)
        conn.request("GET", "/v1/health")
        health = _json.loads(conn.getresponse().read())
        assert health["weights"]["dtype"] == "int8"
        conn.request("POST", "/v1/generate", body=_json.dumps(
            {"tokens": [1, 2, 3], "max_new_tokens": 4}).encode())
        resp = conn.getresponse()
        body = _json.loads(resp.read())
        assert resp.status == 200 and len(body["tokens"]) == 4
        conn.close()
        replica.drain_and_stop(timeout=15)
    finally:
        reg_srv.stop()
