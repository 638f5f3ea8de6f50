"""Relaxed-parity plane: quantizer numerics, guard math, tier gating.

Three layers of coverage, mirroring test_overlap.py's structure:

- Primitive tests run the quantized collectives inside a bare
  shard_map against their exact forms and bound the error (SQNR /
  allclose) — plus the edge cases a codec must not mangle: all-zero
  groups decode exactly zero, denormals flush finite, integer buckets
  stay exact, and a mismatched payload header is a loud error.
- Tier-gating tests prove the contract tpulint enforces lexically:
  with the bitwise tier (the default) NO lowp entry point is
  reachable — poisoned quantizers don't fire — and the chunked
  collective matmul only compiles under the relaxed tier.
- Full-step A-B tests run the real train step relaxed vs bitwise
  (dp2×tp2+sp over ≥50 steps, zero1 dp8 over ≥50 steps) through the
  loss-curve guard, asserting acceptance AND the ≥2× quantized
  payload-byte contract. vma-gated like the seed parallel suite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from hadoop_tpu.parallel.lowp import (BITWISE_PARITY, RELAXED_PARITY,
                                      ParityConfig, parity_from_conf)
from hadoop_tpu.parallel.lowp.guard import (ParityGuardError,
                                            allclose_guard,
                                            loss_curve_report)
from hadoop_tpu.parallel.lowp.quant import (RelaxedQuant, capture_comm,
                                            decode_payload,
                                            encode_payload,
                                            psum_of_scatter_quantized,
                                            psum_quantized,
                                            psum_scatter_quantized)


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("x",))


def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _rq(codec="int8", group=64):
    return RelaxedQuant(codec=codec, group=group,
                        mesh_axis_sizes={"x": 4})


def _sqnr_db(ref, got):
    ref = np.asarray(ref, np.float64)
    err = ref - np.asarray(got, np.float64)
    return 10 * np.log10(np.sum(ref ** 2) / max(np.sum(err ** 2), 1e-30))


# ------------------------------------------------------ quantized psum

@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_psum_quantized_allclose_with_sqnr_bound(codec):
    mesh = _mesh()
    # mixed magnitudes per group stress the shared-scale design
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 200), jnp.float32) \
        * jnp.array([1e-3, 1.0, 50.0, 1e3])[:, None]
    ref = jax.jit(_smap(lambda t: jax.lax.psum(t, ("x",)), mesh,
                        (P("x", None),), P("x", None)))(x)
    got = jax.jit(_smap(lambda t: psum_quantized(t, ("x",), _rq(codec)),
                        mesh, (P("x", None),), P("x", None)))(x)
    ref, got = np.asarray(ref), np.asarray(got)
    # int8 at 4-rank headroom keeps ~5 bits; 20 dB is a loose floor
    # (measured ~28 dB int8, ~30 dB fp8 on this workload)
    assert _sqnr_db(ref, got) > 20.0
    assert np.abs(got - ref).max() / np.abs(ref).max() < 0.1


def test_psum_quantized_single_rank_is_exact_passthrough():
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
    rq = RelaxedQuant(codec="int8", mesh_axis_sizes={"x": 1})
    got = jax.jit(_smap(lambda t: psum_quantized(t, (), rq), mesh,
                        (P("x", None),), P("x", None)))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


def test_quantized_zeros_decode_exactly_zero():
    mesh = _mesh()
    got = jax.jit(_smap(lambda t: psum_quantized(t, ("x",), _rq()),
                        mesh, (P("x", None),), P("x", None)))(
        jnp.zeros((4, 64), jnp.float32))
    assert (np.asarray(got) == 0).all()


def test_quantized_denormals_flush_finite():
    # group amax below the scale floor: values flush to exact zero
    # instead of dividing by a denormal scale into inf/nan
    mesh = _mesh()
    got = jax.jit(_smap(lambda t: psum_quantized(t, ("x",), _rq()),
                        mesh, (P("x", None),), P("x", None)))(
        jnp.full((4, 64), 1e-38, jnp.float32))
    got = np.asarray(got)
    assert np.isfinite(got).all()


def test_integer_buckets_stay_exact_on_relaxed_tier():
    from hadoop_tpu.parallel.overlap import bucketed_psum
    mesh = _mesh()
    tree = {"i": jnp.arange(8, dtype=jnp.int32).reshape(4, 2)}
    axes = {"i": ("x",)}

    def run(t):
        return bucketed_psum(t, axes, 1 << 20, relaxed=_rq())
    got = jax.jit(_smap(run, mesh, ({"i": P("x", None)},),
                        {"i": P("x", None)}))(tree)
    ref = jax.jit(_smap(
        lambda t: {"i": jax.lax.psum(t["i"], ("x",))}, mesh,
        ({"i": P("x", None)},), {"i": P("x", None)}))(tree)
    np.testing.assert_array_equal(np.asarray(got["i"]),
                                  np.asarray(ref["i"]))


def test_wire_widens_past_int8_headroom():
    """127 // n hits zero at n >= 128 — the wire must widen to int16
    (still 2x under f32) instead of letting the int8 accumulator wrap,
    and refuse outright past the int16 range."""
    from hadoop_tpu.parallel.lowp.quant import _wire_for
    assert _wire_for(4) == (jnp.int8, 31)
    assert _wire_for(127) == (jnp.int8, 1)
    wire, qmax = _wire_for(256)
    assert wire == jnp.int16 and qmax == 32767 // 256
    assert qmax * 256 <= 32767          # the no-wrap invariant
    with pytest.raises(ValueError, match="int16 wire"):
        _wire_for(40000)


def test_relaxed_parity_requires_overlap_pass():
    """relaxed with the overlap pass disabled must be a loud error —
    silently building the bitwise graph would label bench rows and
    A-B arms 'relaxed' while measuring the bitwise tier."""
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel import MeshPlan, make_mesh
    from hadoop_tpu.parallel.overlap import OVERLAP_OFF
    from hadoop_tpu.parallel.train import make_train_step
    cfg = get_config("tiny")
    plan = MeshPlan(dp=2)
    mesh = make_mesh(plan)
    with pytest.raises(ValueError, match="overlap"):
        make_train_step(cfg, plan, mesh, overlap=OVERLAP_OFF,
                        parity=RELAXED_PARITY)


# --------------------------------------------------- quantized scatter

def test_psum_scatter_quantized_group_matches_reference():
    mesh = _mesh()
    y = jax.random.normal(jax.random.PRNGKey(1), (16, 100), jnp.float32)

    def sc_ref(t):           # psum + this rank's row of the [Z,K] bucket
        full = jax.lax.psum(t, ("x",))
        i = jax.lax.axis_index("x")
        return jax.lax.dynamic_slice_in_dim(full, i, 1, 0).reshape(-1)

    a = jax.jit(_smap(sc_ref, mesh, (P("x", None),), P("x")))(y)
    b = jax.jit(_smap(lambda t: psum_scatter_quantized(t, "x", _rq()),
                      mesh, (P("x", None),), P("x")))(y)
    assert _sqnr_db(np.asarray(a), np.asarray(b)) > 20.0


def test_psum_scatter_quantized_tensor_scale_dim1():
    # the megatron-SP activation shape: scatter the SEQUENCE dim (1)
    mesh = _mesh()
    z = jax.random.normal(jax.random.PRNGKey(2), (8, 8, 16), jnp.float32)

    def sct_ref(t):
        full = jax.lax.psum(t, ("x",))
        i = jax.lax.axis_index("x")
        return jax.lax.dynamic_slice_in_dim(full, i * 2, 2, 1)

    def sct_q(t):
        return psum_scatter_quantized(t, "x", _rq(), scatter_dimension=1,
                                      scale="tensor")

    a = jax.jit(_smap(sct_ref, mesh, (P("x",),), P("x", None, None)))(z)
    b = jax.jit(_smap(sct_q, mesh, (P("x",),), P("x", None, None)))(z)
    assert _sqnr_db(np.asarray(a), np.asarray(b)) > 20.0


def test_psum_scatter_quantized_group_rejects_bad_layout():
    with pytest.raises(ValueError, match=r"\[Z, K\] bucket layout"):
        psum_scatter_quantized(jnp.zeros((2, 3, 4)), "x", _rq())


@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_psum_of_scatter_quantized_full_range(codec):
    """The ZeRO-1 gather wire: disjoint contributions quantize at full
    range — int8 must land well above the headroom'd psum's SQNR."""
    mesh = _mesh()
    rows = jax.random.normal(jax.random.PRNGKey(3), (4, 150),
                             jnp.float32)

    def g_ref(t):
        t = t.reshape(-1)
        i = jax.lax.axis_index("x")
        buf = jnp.zeros((4, 150), t.dtype)
        buf = jax.lax.dynamic_update_slice(
            buf, t[None, :], (i, jnp.zeros((), jnp.int32)))
        return jax.lax.psum(buf, ("x",))

    def g_q(t):
        t = t.reshape(-1)
        i = jax.lax.axis_index("x")
        return psum_of_scatter_quantized(t, 4, i, ("x",),
                                         _rq(codec))[:, :150]

    a = jax.jit(_smap(g_ref, mesh, (P("x", None),), P(None, None)))(rows)
    b = jax.jit(_smap(g_q, mesh, (P("x", None),), P(None, None)))(rows)
    sqnr = _sqnr_db(np.asarray(a), np.asarray(b))
    assert sqnr > (25.0 if codec == "fp8" else 40.0)


# ------------------------------------------- straight-through backward

def test_quantized_psum_gradient_is_exact_transpose():
    """The STE contract: rint/clip have measure-zero gradients, so a
    naively differentiated quantized collective returns ZERO cotangents
    and training silently stalls. The backward must be the exact
    psum's transpose — the cotangent flows through untouched."""
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64), jnp.float32)

    def f(t):
        return jnp.sum(psum_quantized(t, ("x",), _rq(),
                                      scale="tensor") * 3.0)

    g = jax.jit(_smap(lambda t: jax.grad(f)(t), mesh,
                      (P("x", None),), P("x", None)))(x)
    assert (np.asarray(g) == 3.0).all()


def test_quantized_scatter_gradient_is_allgather_transpose():
    mesh = _mesh()
    z = jax.random.normal(jax.random.PRNGKey(2), (8, 8, 16), jnp.float32)

    def f(t):
        return jnp.sum(psum_scatter_quantized(
            t, "x", _rq(), scatter_dimension=1, scale="tensor") * 2.0)

    g = jax.jit(_smap(lambda t: jax.grad(f)(t), mesh,
                      (P("x",),), P("x",)))(z)
    assert (np.asarray(g) == 2.0).all()


def test_relaxed_project_gradients_flow_nonzero():
    """End-to-end through the quantized chunked projection: gradients
    must be finite and nonzero (the stall the STE exists to prevent)."""
    from hadoop_tpu.ops.collective_matmul import row_parallel_project
    mesh = _mesh()
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 24), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32),
                          jnp.float32)
    ctx = _ctx(relaxed_chunk_matmul=True, relaxed_codec="int8")

    def loss(w_, x_):
        return jnp.mean(row_parallel_project(x_, w_, ctx) ** 2)

    g = np.asarray(jax.jit(_smap(
        lambda ww, xx: jax.grad(loss)(ww, xx), mesh,
        (P("x", None), P(None, None, "x")), P("x", None)))(w, x))
    assert np.isfinite(g).all() and np.abs(g).max() > 0


# --------------------------------------------------------- comm ledger

def test_comm_ledger_proves_payload_reduction():
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32)
    fn = _smap(lambda t: psum_quantized(t, ("x",), _rq()), mesh,
               (P("x", None),), P("x", None))
    with capture_comm() as led:
        jax.jit(fn)(x)
    assert led.sites and led.payload_bytes > 0
    # f32 → int8 + per-64 f32 scales: 4 bytes → ~1.06 bytes per element
    assert led.ratio >= 2.0
    assert led.report()["ratio"] == round(led.ratio, 3)
    # recording is scoped to the capture
    before = led.payload_bytes
    jax.jit(_smap(lambda t: psum_quantized(t, ("x",), _rq(group=32)),
                  mesh, (P("x", None),), P("x", None)))(x)
    assert led.payload_bytes == before


# -------------------------------------------------- host payload codec

@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_payload_roundtrip(codec):
    x = np.random.default_rng(0).normal(size=(7, 33)).astype(np.float32)
    out, header = decode_payload(encode_payload(x, codec=codec))
    assert header["codec"] == codec
    assert out.shape == x.shape and out.dtype == x.dtype
    assert _sqnr_db(x, out) > 25.0
    # quantized payload is strictly smaller than the raw array past
    # the fixed header (the point of the wire codec)
    assert len(encode_payload(x, codec=codec)) < x.nbytes + 200


def test_payload_header_mismatches_are_loud():
    x = np.ones((4, 8), np.float32)
    blob = encode_payload(x, codec="int8")
    with pytest.raises(ValueError, match="codec"):
        decode_payload(blob, codec="fp8")
    with pytest.raises(ValueError, match="shape"):
        decode_payload(blob, shape=(8, 4))
    with pytest.raises(ValueError, match="dtype"):
        decode_payload(blob, dtype=np.float64)
    with pytest.raises(ValueError, match="truncated"):
        decode_payload(blob[:-3])
    with pytest.raises(ValueError, match="truncated"):
        decode_payload(b"\x00\x01")
    with pytest.raises(ValueError, match="codec"):
        encode_payload(x, codec="int4")


# ------------------------------------------------ chunked matmul tier

def _ctx(**kw):
    from hadoop_tpu.models.decoder import ParallelCtx
    return ParallelCtx(tp_axis="x", tp_size=4, tp_overlap_chunks=4, **kw)


def _project(ctx, x, w, bias, mesh, out_specs=P()):
    from hadoop_tpu.ops.collective_matmul import row_parallel_project
    ins = (P(None, None, "x"), P("x", None), P())
    return np.asarray(jax.jit(_smap(
        lambda x_, w_, b_: row_parallel_project(x_, w_, ctx, bias=b_),
        mesh, ins, out_specs))(x, w, bias))


def test_chunked_matmul_forward_value_exact_backward_reassociates():
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32),
                          jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 24), jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(2), (24,), jnp.float32)
    a = _project(_ctx(), x, w, bias, mesh)
    b = _project(_ctx(relaxed_chunk_matmul=True), x, w, bias, mesh)
    # forward: disjoint row chunks of the same product — bitwise
    np.testing.assert_array_equal(a, b)

    from hadoop_tpu.ops.collective_matmul import row_parallel_project

    def gw(ctx):
        def loss(w_, x_):
            return jnp.sum(
                row_parallel_project(x_, w_, ctx, bias=bias) ** 2)
        return np.asarray(jax.jit(_smap(
            lambda ww, xx: jax.grad(loss)(ww, xx), mesh,
            (P("x", None), P(None, None, "x")), P("x", None)))(w, x))

    ga, gb = gw(_ctx()), gw(_ctx(relaxed_chunk_matmul=True))
    # backward: the weight-grad contraction reassociates — allclose,
    # and NOT bitwise (the measured fact that parks this transform in
    # the relaxed tier; if it ever comes back bitwise the chunking
    # silently stopped happening)
    np.testing.assert_allclose(ga, gb, rtol=1e-5, atol=1e-5)
    assert not (ga == gb).all()


def test_chunked_matmul_megatron_sp_forward_value_exact():
    mesh = _mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32),
                          jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 24), jnp.float32)
    bias = jax.random.normal(jax.random.PRNGKey(2), (24,), jnp.float32)
    a = _project(_ctx(megatron_sp=True), x, w, bias, mesh,
                 out_specs=P(None, "x", None))
    b = _project(_ctx(megatron_sp=True, relaxed_chunk_matmul=True),
                 x, w, bias, mesh, out_specs=P(None, "x", None))
    np.testing.assert_array_equal(a, b)


def test_bitwise_tier_never_reaches_lowp_entry_points(monkeypatch):
    """The gating contract: with relaxed off, poisoned quantizers must
    never fire — through the bucketed collectives OR the tp reduce."""
    import hadoop_tpu.parallel.lowp.quant as quant
    from hadoop_tpu.ops.collective_matmul import row_parallel_project
    from hadoop_tpu.parallel.overlap import bucketed_psum

    def boom(*a, **k):
        raise AssertionError("lowp entry point reached on bitwise tier")

    monkeypatch.setattr(quant, "psum_quantized", boom)
    monkeypatch.setattr(quant, "psum_scatter_quantized", boom)
    monkeypatch.setattr(quant, "psum_of_scatter_quantized", boom)
    mesh = _mesh()
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (33,))}
    got = jax.jit(_smap(
        lambda t: bucketed_psum(t, {"a": ("x",)}, 1 << 20),
        mesh, ({"a": P()},), {"a": P()}))(tree)
    assert np.isfinite(np.asarray(got["a"])).all()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 8), jnp.float32)
    ctx = _ctx()
    out = jax.jit(_smap(
        lambda x_, w_: row_parallel_project(x_, w_, ctx), mesh,
        (P(None, None, "x"), P("x", None)), P()))(x, w)
    assert np.isfinite(np.asarray(out)).all()
    # and the relaxed tier DOES reach them (the poison fires at trace)
    rq = _rq()
    with pytest.raises(AssertionError, match="bitwise tier"):
        jax.jit(_smap(
            lambda t: bucketed_psum(t, {"a": ("x",)}, 1 << 20,
                                    relaxed=rq),
            mesh, ({"a": P()},), {"a": P()}))(tree)


# ----------------------------------------------------------- guard math

def test_loss_curve_report_accepts_close_curves():
    b = [5.0 - 0.05 * i for i in range(50)]
    r = [x * 1.02 for x in b]
    rep = loss_curve_report(b, r, rel_tol=0.25)
    assert rep["accepted"] and rep["max_rel_div"] < 0.03


def test_loss_curve_report_rejects_divergence_nonfinite_and_flat():
    b = [5.0 - 0.05 * i for i in range(50)]
    rep = loss_curve_report(b, [x * 2.0 for x in b], rel_tol=0.25)
    assert not rep.get("accepted") and "max_rel_div" in rep["reason"]
    rep = loss_curve_report(b, b[:-1] + [float("nan")], rel_tol=0.25)
    assert not rep.get("accepted") and rep["reason"] == "non-finite loss"
    rep = loss_curve_report(b, list(b[:1]) * 50, rel_tol=10.0)
    assert not rep.get("accepted") and "did not learn" in rep["reason"]
    rep = loss_curve_report(b, b[:10], rel_tol=0.25)
    assert not rep.get("accepted") and "length" in rep["reason"]


def test_allclose_guard_reports_and_raises():
    rep = allclose_guard("ok", [1.0, 2.0], [1.0, 2.0 + 1e-7])
    assert rep["max_abs"] < 1e-6
    with pytest.raises(ParityGuardError, match="max_abs"):
        allclose_guard("bad", np.ones(4), np.ones(4) * 1.5)
    with pytest.raises(ParityGuardError, match="arity"):
        allclose_guard("arity", [np.ones(2)], [np.ones(2), np.ones(2)])


# ----------------------------------------------------------------- conf

def test_parity_from_conf_defaults_and_overrides():
    from hadoop_tpu.conf import Configuration
    assert parity_from_conf(None) == BITWISE_PARITY
    conf = Configuration(load_defaults=False)
    assert parity_from_conf(conf) == ParityConfig()
    assert not parity_from_conf(conf).relaxed
    conf.set("parallel.parity", "relaxed")
    conf.set("parallel.lowp.codec", "fp8")
    conf.set("parallel.lowp.quant.buckets", "false")
    conf.set("parallel.lowp.quant.group", "256")
    conf.set("parallel.lowp.guard.steps", "20")
    conf.set("parallel.lowp.guard.rel-tol", "0.1")
    got = parity_from_conf(conf)
    assert got == ParityConfig(tier="relaxed", codec="fp8",
                               quant_buckets=False, group=256,
                               guard_steps=20, guard_rel_tol=0.1)
    assert got.relaxed


def test_parity_config_rejects_unknown_tier_and_codec():
    with pytest.raises(ValueError, match="parallel.parity"):
        ParityConfig(tier="fast-and-loose")
    with pytest.raises(ValueError, match="codec"):
        ParityConfig(codec="int4")
    with pytest.raises(ValueError, match="codec"):
        RelaxedQuant(codec="int4")


# ---------------------------------- partially synchronized activations


def test_sync_schedule_parsing_and_merge():
    from hadoop_tpu.parallel.lowp.syncpolicy import resolve_schedule
    assert resolve_schedule("full", 4) == ("sync",) * 4
    assert resolve_schedule("none", 4) == ("skip",) * 4
    assert resolve_schedule("none", 4, off_mode="stale") == ("stale",) * 4
    assert resolve_schedule("periodic:2", 4) == \
        ("sync", "skip", "sync", "skip")
    assert resolve_schedule("periodic:3", 7) == \
        ("sync", "skip", "skip", "sync", "skip", "skip", "sync")
    # periodic:1 ≡ full by construction
    assert resolve_schedule("periodic:1", 6) == ("sync",) * 6
    # layers: overrides merge with (and win over) the periodic base
    assert resolve_schedule("periodic:2+layers:1=sync,2=stale", 4) == \
        ("sync", "sync", "stale", "skip")
    assert resolve_schedule("layers:*=skip+layers:0=sync", 3) == \
        ("sync", "skip", "skip")
    # later clauses refine earlier IN SPEC ORDER: a trailing wildcard
    # really does force the whole stack
    assert resolve_schedule("layers:0=sync+layers:*=skip", 3) == \
        ("skip",) * 3


def test_sync_guard_tolerance_picked_on_resolved_schedule():
    """The loose schedule tolerance applies only when the RESOLVED
    schedule actually turns a sync off — periodic:1 / layers:*=sync /
    tp=1 build the exact full graph and keep the strict quantization
    bar."""
    from hadoop_tpu.parallel.lowp.guard import guard_rel_tol_for
    strict = RELAXED_PARITY.guard_rel_tol
    loose = RELAXED_PARITY.sync_guard_rel_tol
    assert guard_rel_tol_for(RELAXED_PARITY, 4, tp=2) == strict
    p1 = ParityConfig(tier="relaxed", relaxed_sync="periodic:1")
    assert guard_rel_tol_for(p1, 4, tp=2) == strict
    allsync = ParityConfig(tier="relaxed", relaxed_sync="layers:*=sync")
    assert guard_rel_tol_for(allsync, 4, tp=2) == strict
    p2 = ParityConfig(tier="relaxed", relaxed_sync="periodic:2")
    assert guard_rel_tol_for(p2, 4, tp=2) == loose
    assert guard_rel_tol_for(p2, 4, tp=1) == strict   # no tp, no sync


def test_sync_schedule_malformed_specs_raise_loud():
    from hadoop_tpu.parallel.lowp.syncpolicy import resolve_schedule
    for bad in ("", "sometimes", "periodic:", "periodic:x", "periodic:0",
                "layers:", "layers:1", "layers:1=never", "layers:x=skip",
                "layers:-1=skip", "full+none", "periodic:2+periodic:3"):
        with pytest.raises(ValueError, match="parallel.lowp.sync"):
            resolve_schedule(bad, 4)
    with pytest.raises(ValueError, match="out of range"):
        resolve_schedule("layers:9=skip", 4)
    with pytest.raises(ValueError, match="parallel.lowp.sync.mode"):
        resolve_schedule("periodic:2", 4, off_mode="maybe")
    # ParityConfig validates the grammar at config time
    with pytest.raises(ValueError, match="parallel.lowp.sync"):
        ParityConfig(relaxed_sync="periodic:zero")
    with pytest.raises(ValueError, match="parallel.lowp.sync.mode"):
        ParityConfig(relaxed_sync_mode="defer")


def test_sync_schedule_tp1_plans_forced_full_by_construction():
    """A plan without a tp axis has no sync to schedule: plan.ctx
    drops the schedule entirely (None == full), so tp=1 relaxed runs
    build the exact same graph whatever the conf says."""
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel import MeshPlan
    cfg = get_config("tiny")
    ctx = MeshPlan(dp=2).ctx(cfg, relaxed_sync=("skip",) * cfg.n_layers)
    assert ctx.relaxed_sync is None
    ctx2 = MeshPlan(dp=2, tp=2).ctx(
        cfg, relaxed_sync=("skip",) * cfg.n_layers)
    assert ctx2.relaxed_sync == ("skip",) * cfg.n_layers


def test_sync_schedule_policy_roundtrips_conf_and_bench_json():
    """The satellite pin: parallel.lowp.sync.* conf keys land on
    ParityConfig, and dataclasses.asdict carries them into bench JSON
    (the self-describing tier policy dict profile_train records)."""
    import dataclasses
    import json

    from hadoop_tpu.conf import Configuration
    conf = Configuration(load_defaults=False)
    conf.set("parallel.parity", "relaxed")
    conf.set("parallel.lowp.sync.schedule", "periodic:2+layers:0=stale")
    conf.set("parallel.lowp.sync.mode", "stale")
    got = parity_from_conf(conf)
    assert got.relaxed_sync == "periodic:2+layers:0=stale"
    assert got.relaxed_sync_mode == "stale"
    row = json.loads(json.dumps(dataclasses.asdict(got)))
    assert row["relaxed_sync"] == "periodic:2+layers:0=stale"
    assert row["relaxed_sync_mode"] == "stale"
    # defaults: schedule full, mode skip
    assert BITWISE_PARITY.relaxed_sync == "full"
    assert BITWISE_PARITY.relaxed_sync_mode == "skip"


def _tp_mesh_and_model(tp=2):
    from hadoop_tpu.models import get_config
    from hadoop_tpu.models.decoder import init_params
    from hadoop_tpu.parallel.mesh import (MeshPlan, make_mesh,
                                          param_specs)
    plan = MeshPlan(tp=tp)
    mesh = make_mesh(plan)
    cfg = get_config("tiny", max_seq=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    return plan, mesh, cfg, params, param_specs(cfg, plan), tokens


def _scheduled_forward(sched_spec, key, off_mode="skip"):
    """Trace + dispatch a tp=2 decoder forward under a sync schedule,
    through the REAL runtime dispatch seam; returns (out, profile)."""
    from hadoop_tpu.models.decoder import ParallelCtx, forward_hidden
    from hadoop_tpu.obs.comm import comm_runtime
    from hadoop_tpu.parallel.lowp.syncpolicy import resolve_schedule
    plan, mesh, cfg, params, specs, tokens = _tp_mesh_and_model()
    sched = resolve_schedule(sched_spec, cfg.n_layers, off_mode) \
        if sched_spec else None
    ctx = ParallelCtx(tp_axis="tp", tp_size=2, relaxed_sync=sched)
    fn = _smap(lambda p, t: forward_hidden(p, t, cfg, ctx), mesh,
               (specs, P(None, None)), P(None, None, None))
    rt = comm_runtime()
    with rt.step(key):
        out = jax.jit(fn)(params, tokens)
        out.block_until_ready()
    return np.asarray(out), rt.profile(key)


def test_periodic1_is_full_collective_count_identical():
    """periodic:1 ≡ full: bitwise-identical outputs AND an identical
    per-step ledger profile (payload/reference/executions), pinned at
    the dispatch seam."""
    full, prof_full = _scheduled_forward(None, "sync.t1.full")
    p1, prof_p1 = _scheduled_forward("periodic:1", "sync.t1.p1")
    np.testing.assert_array_equal(full, p1)
    assert prof_full == prof_p1
    assert prof_full["tp.psum"][2] > 0


def test_sync_schedule_runtime_ledger_proves_execution_drop():
    """The core ledger proof on the live dispatch seam: at periodic:2
    the scheduled tp sites execute HALF the collectives and move half
    the payload bytes per step (>=1.8x contract), while the reference
    bytes — what full would have moved — stay identical, and the
    skipped share records payload 0."""
    full, prof_full = _scheduled_forward(None, "sync.t2.full")
    p2, prof_p2 = _scheduled_forward("periodic:2", "sync.t2.p2")
    fp, fr, fe = prof_full["tp.psum"]
    sp_, sr, se = prof_p2["tp.psum"]
    assert fe > 0 and fp == fr          # full: every byte on the wire
    assert fr == sr                     # same reference work per step
    assert fe / max(se, 1) >= 1.8       # executions drop on schedule
    assert fp / max(sp_, 1) >= 1.8      # payload bytes drop with them
    assert sp_ * 2 == fr                # the skipped half moved ZERO
    assert se * 2 == fe                 # exactly on the periodic:2 beat
    # the schedule changes values (it is a relaxed transform), finitely
    assert not (full == p2).all() and np.isfinite(p2).all()


def test_skip_reduce_gradient_is_exact_collective_transpose():
    """The ISSUE-10 lesson applied to skips: a skipped forward sync
    must not zero the backward. skip's backward IS the exact psum's
    transpose (cotangent flows untouched); the megatron-SP skip's
    backward is the exact reduce-scatter's transpose (all_gather)."""
    from hadoop_tpu.models.decoder import ParallelCtx
    from hadoop_tpu.parallel.lowp.syncpolicy import skip_row_reduce
    mesh = _mesh()
    ctx = ParallelCtx(tp_axis="x", tp_size=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16), jnp.float32)

    def f(t):
        return jnp.sum(skip_row_reduce(t, ctx) * 3.0)

    g = jax.jit(_smap(lambda t: jax.grad(f)(t), mesh,
                      (P(None, None, "x"),), P(None, None, "x")))(x)
    assert (np.asarray(g) == 3.0).all()

    ctx_sp = ParallelCtx(tp_axis="x", tp_size=4, megatron_sp=True)

    def fsp(t):
        return jnp.sum(skip_row_reduce(t, ctx_sp) * 2.0)

    gsp = jax.jit(_smap(lambda t: jax.grad(fsp)(t), mesh,
                        (P(None, None, "x"),), P(None, None, "x")))(x)
    # transpose of the scatter is the all_gather of the cotangent:
    # every position receives its (constant) cotangent — nonzero
    assert (np.asarray(gsp) == 2.0).all()


def test_skip_reduce_forward_is_scaled_local_partial():
    """Forward semantics: skip == the rank's local partial scaled by
    tp (each partial is a 1/tp-magnitude sample of the row-parallel
    sum — the bare partial is a systematic bias, measured 67.6
    max_rel_div bare vs 1.45 scaled on the 50-step A-B), its own
    sequence block of it under megatron-SP; no collective executed."""
    from hadoop_tpu.models.decoder import ParallelCtx
    from hadoop_tpu.parallel.lowp.quant import capture_comm
    from hadoop_tpu.parallel.lowp.syncpolicy import skip_row_reduce
    mesh = _mesh()
    ctx = ParallelCtx(tp_axis="x", tp_size=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16), jnp.float32)
    with capture_comm() as led:
        got = jax.jit(_smap(lambda t: skip_row_reduce(t, ctx), mesh,
                            (P("x", None, None),),
                            P("x", None, None)))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x) * 4.0)
    assert led.executions == 0 and led.payload_bytes == 0
    assert led.reference_bytes > 0
    # megatron-SP: every rank holds the same partial (replicated in),
    # rank i keeps ITS OWN sequence block — reassembling the blocks
    # over the scatter dim reproduces the scaled partial, no psum
    ctx_sp = ParallelCtx(tp_axis="x", tp_size=4, megatron_sp=True)
    got_sp = jax.jit(_smap(lambda t: skip_row_reduce(t, ctx_sp), mesh,
                           (P(None, None, None),),
                           P(None, "x", None)))(x)
    np.testing.assert_array_equal(np.asarray(got_sp),
                                  np.asarray(x) * 4.0)


def test_stale_reduce_consumes_prev_correction_and_defers_collective():
    """Stale semantics at the seam: step 1 (zero correction) == skip
    (the tp-scaled local partial); the emitted correction is
    exact - scaled-local (the gain is absorbed); applying it makes the
    next same-input step EXACT; bytes ride the tp.stale site while
    the critical-path site records payload 0 / executions 0."""
    from hadoop_tpu.models.decoder import ParallelCtx
    from hadoop_tpu.parallel.lowp.quant import capture_comm
    from hadoop_tpu.parallel.lowp.syncpolicy import stale_row_reduce
    mesh = _mesh()
    ctx = ParallelCtx(tp_axis="x", tp_size=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16), jnp.float32)

    def step(t, corr):
        return stale_row_reduce(t, ctx, corr)

    zeros = jnp.zeros_like(x)
    with capture_comm() as led:
        out1, corr1 = jax.jit(_smap(
            step, mesh, (P("x", None, None), P("x", None, None)),
            (P("x", None, None), P("x", None, None))))(x, zeros)
    # step 1 with no correction behaves as skip (scaled local partial)
    np.testing.assert_array_equal(np.asarray(out1),
                                  np.asarray(x) * 4.0)
    local_bytes = x.nbytes // 4          # the per-rank shard the seam sees
    per = led.per_site
    assert per["tp.psum"] == [0, local_bytes, 0]    # critical path: off
    assert per["tp.stale"][2] == 1                  # deferred collective
    assert per["tp.stale"][0] == local_bytes
    # step 2 with step 1's correction reproduces the EXACT psum
    exact = jax.jit(_smap(lambda t: jax.lax.psum(t, ("x",)), mesh,
                          (P("x", None, None),), P("x", None, None)))(x)
    out2, _ = jax.jit(_smap(
        step, mesh, (P("x", None, None), P("x", None, None)),
        (P("x", None, None), P("x", None, None))))(x, corr1)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(exact),
                               rtol=1e-6, atol=1e-6)
    # a mis-shaped correction is a loud trace-time error, never a
    # silent broadcast
    with pytest.raises(ValueError, match="correction shape"):
        jax.jit(_smap(
            step, mesh, (P("x", None, None), P(None, None, None)),
            (P("x", None, None), P("x", None, None))))(
            x, jnp.zeros((2, 8, 16), jnp.float32))


def test_scheduled_layers_gradients_flow_nonzero():
    """End-to-end through an all-skip layer stack: parameter gradients
    must be finite and nonzero (the stall the straight-through
    backward exists to prevent)."""
    from hadoop_tpu.models.decoder import ParallelCtx, run_layers
    from hadoop_tpu.ops import rope_frequencies
    from hadoop_tpu.parallel.lowp.syncpolicy import resolve_schedule
    plan, mesh, cfg, params, specs, _ = _tp_mesh_and_model()
    sched = resolve_schedule("none", cfg.n_layers)
    ctx = ParallelCtx(tp_axis="tp", tp_size=2, relaxed_sync=sched)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq,
                                cfg.rope_theta)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model),
                          jnp.float32)

    def loss(layers, xx):
        return jnp.mean(
            run_layers(xx, layers, cfg, ctx, cos, sin) ** 2)

    g = jax.jit(_smap(
        lambda lp, xx: jax.grad(loss)(lp, xx), mesh,
        (specs["layers"], P(None, None, None)), specs["layers"]))(
        params["layers"], x)
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            jax.device_get(g)):
        a = np.asarray(leaf)
        assert np.isfinite(a).all(), path
        assert np.abs(a).max() > 0, path


def test_sync_schedule_machinery_unreachable_on_bitwise(monkeypatch):
    """Static + dynamic gating: the bitwise tier never resolves a
    schedule (even with the conf keys set) and never reaches the
    syncpolicy reduce seam; a relaxed ctx with a schedule hits it at
    trace time."""
    import hadoop_tpu.parallel.lowp.syncpolicy as sp
    from hadoop_tpu.models import get_config
    from hadoop_tpu.models.decoder import ParallelCtx, forward_hidden
    from hadoop_tpu.parallel import MeshPlan, make_mesh
    from hadoop_tpu.parallel.train import make_train_step

    def boom(*a, **k):
        raise AssertionError("syncpolicy reached on bitwise tier")

    monkeypatch.setattr(sp, "resolve_schedule", boom)
    cfg = get_config("tiny")
    plan = MeshPlan(dp=2, tp=2, megatron_sp=True)
    mesh = make_mesh(plan)
    # bitwise tier with the schedule CONF set: never resolved
    make_train_step(cfg, plan, mesh, donate=False,
                    parity=ParityConfig(tier="bitwise",
                                        relaxed_sync="periodic:2"))
    # relaxed tier resolves it at build time (the poison fires)
    with pytest.raises(AssertionError, match="bitwise tier"):
        make_train_step(cfg, plan, mesh, donate=False,
                        parity=ParityConfig(tier="relaxed",
                                            relaxed_sync="periodic:2"))
    monkeypatch.undo()
    monkeypatch.setattr(sp, "scheduled_row_reduce", boom)
    plan1, mesh1, cfg1, params, specs, tokens = _tp_mesh_and_model()
    # a ctx WITHOUT a schedule never touches the seam
    ctx = ParallelCtx(tp_axis="tp", tp_size=2)
    out = jax.jit(_smap(
        lambda p, t: forward_hidden(p, t, cfg1, ctx), mesh1,
        (specs, P(None, None)), P(None, None, None)))(params, tokens)
    assert np.isfinite(np.asarray(out)).all()
    # a scheduled relaxed ctx reaches it at trace time
    ctx_s = ParallelCtx(tp_axis="tp", tp_size=2,
                        relaxed_sync=("sync", "skip", "sync", "skip"))
    with pytest.raises(AssertionError, match="bitwise tier"):
        jax.jit(_smap(
            lambda p, t: forward_hidden(p, t, cfg1, ctx_s), mesh1,
            (specs, P(None, None)), P(None, None, None)))(params, tokens)


def test_sync_schedule_refuses_pipeline_plans_and_missing_state():
    """Loud edges: a non-full schedule on a pp plan is refused at
    train-step build (per-stage layer slices cannot index a global
    schedule), and a stale schedule without sync_state is refused at
    the layer loop."""
    from hadoop_tpu.models import get_config
    from hadoop_tpu.models.decoder import ParallelCtx, run_layers
    from hadoop_tpu.ops import rope_frequencies
    from hadoop_tpu.parallel import MeshPlan, make_mesh
    from hadoop_tpu.parallel.train import make_train_step
    cfg = get_config("tiny")
    plan = MeshPlan(dp=2, tp=2, pp=2)
    mesh = make_mesh(plan)
    with pytest.raises(ValueError, match="pp"):
        make_train_step(cfg, plan, mesh, donate=False,
                        n_microbatches=2,
                        parity=ParityConfig(tier="relaxed",
                                            relaxed_sync="periodic:2"))
    ctx = ParallelCtx(tp_axis="tp", tp_size=2,
                      relaxed_sync=("stale",) * cfg.n_layers)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq,
                                cfg.rope_theta)
    layers = {"w": jnp.zeros((cfg.n_layers, 2))}
    with pytest.raises(ValueError, match="sync_state"):
        run_layers(jnp.zeros((1, 8, 4)), layers, cfg, ctx, cos, sin)
    # and a schedule whose length disagrees with the traced stack
    ctx_bad = ParallelCtx(tp_axis="tp", tp_size=2,
                          relaxed_sync=("skip",) * (cfg.n_layers + 1))
    with pytest.raises(ValueError, match="schedule names"):
        run_layers(jnp.zeros((1, 8, 4)), layers, cfg, ctx_bad, cos, sin)


# ------------------------------------------------- full-step A-B (vma)

def test_relaxed_dp2_tp2_passes_loss_curve_guard_50_steps():
    """Acceptance rung: quantized tp reduces + chunked collective
    matmul, 50 steps, bounded trajectory divergence."""
    from hadoop_tpu.parallel import MeshPlan
    from hadoop_tpu.parallel.lowp.guard import run_loss_ab
    rep = run_loss_ab(MeshPlan(dp=2, tp=2, megatron_sp=True), steps=50)
    assert rep["accepted"], rep.get("reason")
    assert rep["comm"]["sites"] > 0          # quantized tp reduces fired
    assert rep["relaxed_final"] < rep["relaxed_first"]


def test_relaxed_zero1_dp8_guard_and_comm_contract_50_steps():
    """Acceptance rung: quantized ZeRO-1 reassembly, 50 steps, with the
    ≥2× collective-payload-byte reduction the ledger proves."""
    from hadoop_tpu.parallel import MeshPlan
    from hadoop_tpu.parallel.lowp.guard import run_loss_ab
    rep = run_loss_ab(MeshPlan(dp=8), zero1=True, steps=50)
    assert rep["accepted"], rep.get("reason")
    assert rep["comm"]["ratio"] >= 2.0


def test_relaxed_pp_grad_buckets_comm_contract():
    """Quantized gradient buckets ride the manual-schedule reduce; the
    payload contract holds there too."""
    from hadoop_tpu.parallel import MeshPlan
    from hadoop_tpu.parallel.lowp.guard import run_loss_ab
    rep = run_loss_ab(MeshPlan(dp=2, pp=2), steps=12, n_microbatches=2)
    assert rep["accepted"], rep.get("reason")
    assert rep["comm"]["ratio"] >= 2.0


def test_bitwise_parity_is_byte_identical_to_parity_unset():
    """parallel.parity=bitwise must build EXACTLY the unset graph:
    identical losses and parameters, bit for bit."""
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel import MeshPlan, make_mesh
    from hadoop_tpu.parallel.train import (init_sharded,
                                           make_data_sharding,
                                           make_train_step)
    cfg = get_config("tiny")
    plan = MeshPlan(dp=2, tp=2, megatron_sp=True)
    mesh = make_mesh(plan)
    ds = make_data_sharding(mesh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                           cfg.vocab_size, dtype=jnp.int32), ds)
    targets = jax.device_put(jnp.roll(tokens, -1, axis=1), ds)
    out = {}
    for label, par in (("unset", None), ("bitwise", BITWISE_PARITY)):
        step = make_train_step(cfg, plan, mesh, lr=1e-2, donate=False,
                               parity=par)
        params, opt = init_sharded(jax.random.PRNGKey(0), cfg, plan,
                                   mesh)
        losses = []
        for _ in range(3):
            params, opt, m = step(params, opt, tokens, targets)
            losses.append(float(m["loss"]))
        out[label] = (losses, jax.tree_util.tree_map(
            np.asarray, jax.device_get(params)))
    assert out["unset"][0] == out["bitwise"][0]
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(out["unset"][1]),
            jax.tree_util.tree_leaves_with_path(out["bitwise"][1])):
        np.testing.assert_array_equal(a, b, err_msg=str(pa))


def test_sync_schedule_periodic2_guard_and_ledger_50_steps():
    """Acceptance rung: partially synchronized activations at
    periodic:2 on dp2×tp2+sp — the 50-step loss-curve guard must
    accept, and the ledger must show the scheduled tp sites executing
    >=1.8x fewer collectives (and moving >=1.8x fewer payload bytes)
    per step than the full-schedule relaxed twin."""
    from hadoop_tpu.parallel import MeshPlan
    from hadoop_tpu.parallel.lowp.guard import run_loss_ab

    def tp_sites(rep):
        per = rep["comm"].get("per_site", {})
        e = sum(v["executions"] for s, v in per.items()
                if s in ("tp.psum", "tp.scatter"))
        p = sum(v["payload_bytes"] for s, v in per.items()
                if s in ("tp.psum", "tp.scatter"))
        return e, p

    plan = MeshPlan(dp=2, tp=2, megatron_sp=True)
    rep_full = run_loss_ab(plan, steps=50)
    rep_sync = run_loss_ab(plan, steps=50,
                           bitwise_losses=rep_full["bitwise_losses"],
                           parity=ParityConfig(
                               tier="relaxed",
                               relaxed_sync="periodic:2"))
    assert rep_sync["accepted"], rep_sync.get("reason")
    assert rep_sync["sync_schedule"] == "periodic:2"
    fe, fp = tp_sites(rep_full)
    se, sp_ = tp_sites(rep_sync)
    assert fe > 0 and fe / max(se, 1) >= 1.8
    assert fp / max(sp_, 1) >= 1.8
    assert rep_sync["relaxed_final"] < rep_sync["relaxed_first"]


def test_sync_schedule_all_skipped_rejects():
    """Falsifiability: a schedule that skips EVERY tp sync must be
    REJECTED by the loss-curve guard — otherwise the guard is not
    measuring anything and every acceptance above is vacuous."""
    from hadoop_tpu.parallel import MeshPlan
    from hadoop_tpu.parallel.lowp.guard import run_loss_ab
    rep = run_loss_ab(MeshPlan(dp=2, tp=2, megatron_sp=True), steps=50,
                      parity=ParityConfig(tier="relaxed",
                                          relaxed_sync="none"))
    assert not rep.get("accepted"), (
        "all-layers-skipped schedule was ACCEPTED: "
        f"max_rel_div={rep.get('max_rel_div')}")


def test_sync_schedule_stale_mode_guard_50_steps():
    """The stale mode: scheduled-off layers consume the previous
    step's reduced correction instead of skipping outright — the
    guard must accept, and the deferred bytes must show up under the
    tp.stale site while the critical-path tp sites record zero
    executions for the staled share."""
    from hadoop_tpu.parallel import MeshPlan
    from hadoop_tpu.parallel.lowp.guard import run_loss_ab
    rep = run_loss_ab(
        MeshPlan(dp=2, tp=2, megatron_sp=True), steps=50,
        parity=ParityConfig(tier="relaxed", relaxed_sync="periodic:2",
                            relaxed_sync_mode="stale"))
    assert rep["accepted"], rep.get("reason")
    per = rep["comm"].get("per_site", {})
    assert per.get("tp.stale", {}).get("executions", 0) > 0
    assert rep["relaxed_final"] < rep["relaxed_first"]


def test_bitwise_with_sync_conf_is_byte_identical_full_step():
    """A step built with parity=bitwise while the sync-schedule conf
    keys are set must be bit-identical to parity-unset — the schedule
    machinery is unreachable on the default tier."""
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel import MeshPlan, make_mesh
    from hadoop_tpu.parallel.train import (init_sharded,
                                           make_data_sharding,
                                           make_train_step)
    cfg = get_config("tiny")
    plan = MeshPlan(dp=2, tp=2, megatron_sp=True)
    mesh = make_mesh(plan)
    ds = make_data_sharding(mesh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                           cfg.vocab_size, dtype=jnp.int32), ds)
    targets = jax.device_put(jnp.roll(tokens, -1, axis=1), ds)
    out = {}
    for label, par in (
            ("unset", None),
            ("bitwise+sched", ParityConfig(tier="bitwise",
                                           relaxed_sync="periodic:2"))):
        step = make_train_step(cfg, plan, mesh, lr=1e-2, donate=False,
                               parity=par)
        params, opt = init_sharded(jax.random.PRNGKey(0), cfg, plan,
                                   mesh)
        losses = []
        for _ in range(3):
            params, opt, m = step(params, opt, tokens, targets)
            losses.append(float(m["loss"]))
        out[label] = losses
    assert out["unset"] == out["bitwise+sched"]


def test_chunked_matmul_compiles_only_under_relaxed(monkeypatch):
    """A poisoned chunked_matmul_reduce: the bitwise step never touches
    it, the relaxed step hits it at trace time."""
    import hadoop_tpu.ops.collective_matmul as cm
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel import MeshPlan, make_mesh
    from hadoop_tpu.parallel.train import (init_sharded,
                                           make_data_sharding,
                                           make_train_step)

    def boom(*a, **k):
        raise AssertionError("chunked matmul reached on bitwise tier")

    monkeypatch.setattr(cm, "chunked_matmul_reduce", boom)
    cfg = get_config("tiny")
    plan = MeshPlan(dp=2, tp=2, megatron_sp=True)
    mesh = make_mesh(plan)
    ds = make_data_sharding(mesh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                           cfg.vocab_size, dtype=jnp.int32), ds)
    targets = jax.device_put(jnp.roll(tokens, -1, axis=1), ds)
    step = make_train_step(cfg, plan, mesh, donate=False,
                           parity=BITWISE_PARITY)
    params, opt = init_sharded(jax.random.PRNGKey(0), cfg, plan, mesh)
    params, opt, m = step(params, opt, tokens, targets)   # no poison
    assert np.isfinite(float(m["loss"]))
    step_r = make_train_step(cfg, plan, mesh, donate=False,
                             parity=RELAXED_PARITY)
    with pytest.raises(AssertionError, match="bitwise tier"):
        step_r(params, opt, tokens, targets)
