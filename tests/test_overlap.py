"""Communication-overlap pass: bit-exact parity and bucketing units.

Two layers of coverage:

- Primitive tests run the bucketed collectives inside a bare shard_map
  against their per-leaf forms and assert BITWISE equality — the
  property the whole pass rests on (concatenation/chunking must change
  the schedule, never the sums).
- Full-step A-B tests build the real train step with the pass on vs
  off (dp2, dp2×tp2(+sp), zero1 dp8 — the combinations the MULTICHIP
  dryrun runs) and assert bit-identical losses AND parameters. These
  need shard_map's varying-manual-axes tracking (jax.typeof().vma),
  which the training path requires anyway; on older jax they skip like
  the rest of the multichip suite fails at seed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from hadoop_tpu.parallel.overlap import (DEFAULT_OVERLAP, OVERLAP_OFF,
                                         OverlapConfig, _pack_buckets,
                                         bucketed_gather_slices,
                                         bucketed_psum,
                                         bucketed_psum_scatter,
                                         overlap_from_conf,
                                         zero1_slice_meta)


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("x",))


def _smap(f, mesh, in_specs, out_specs):
    """shard_map with vma checking off (the primitive tests assert
    numerics, not spec inference)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --------------------------------------------------------------- packing

def test_pack_buckets_is_deterministic_and_size_bounded():
    sizes = [10, 20, 30, 5, 100, 1]
    buckets = _pack_buckets(sizes, itemsize=4, bucket_bytes=128)
    # in-order, every index exactly once
    assert [i for b in buckets for i in b] == list(range(len(sizes)))
    # no bucket over the cap unless it is a single oversized leaf
    for b in buckets:
        if len(b) > 1:
            assert sum(sizes[i] for i in b) * 4 <= 128
    # identical inputs → identical packing (the deterministic-order
    # contract the bit-exactness argument relies on)
    assert buckets == _pack_buckets(sizes, itemsize=4, bucket_bytes=128)


def test_pack_buckets_oversized_leaf_gets_own_bucket():
    buckets = _pack_buckets([1000, 2, 3], itemsize=4, bucket_bytes=64)
    assert buckets[0] == [0]
    assert buckets[1] == [1, 2]


def test_zero1_slice_meta_padding():
    z, k = zero1_slice_meta(np.zeros(10), ("x",), {"x": 4})
    assert (z, k) == (4, 3)          # 10 padded to 12 = 4*3
    z, k = zero1_slice_meta(np.zeros(8), (), {})
    assert (z, k) == (1, 8)


# ------------------------------------------------------------ collectives

def _tree():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    return {
        "a": jax.random.normal(ks[0], (33,), jnp.float32),
        "b": jax.random.normal(ks[1], (17, 5), jnp.float32),
        "c": jax.random.normal(ks[2], (64,), jnp.float32),
        "d": jax.random.normal(ks[3], (7,)).astype(jnp.bfloat16),
    }


@pytest.mark.parametrize("bucket_bytes", [1, 256, 1 << 20])
def test_bucketed_psum_bitexact_vs_per_leaf(bucket_bytes):
    mesh = _mesh()
    tree = _tree()
    axes = {"a": ("x",), "b": ("x",), "c": (), "d": ("x",)}

    def per_leaf(t):
        return jax.tree_util.tree_map(
            lambda g, a: jax.lax.psum(g, tuple(a)) if a else g, t, axes)

    def bucketed(t):
        return bucketed_psum(t, axes, bucket_bytes)

    specs = jax.tree_util.tree_map(lambda _: P(), tree)
    args = (specs,)
    ref = jax.jit(_smap(per_leaf, mesh, args, specs))(tree)
    got = jax.jit(_smap(bucketed, mesh, args, specs))(tree)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(ref),
            jax.tree_util.tree_leaves_with_path(got)):
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)), err_msg=str(pa))


@pytest.mark.parametrize("bucket_bytes", [1, 1 << 20])
def test_bucketed_psum_scatter_matches_psum_plus_slice(bucket_bytes):
    mesh = _mesh()
    sizes = {"x": 4}
    tree = _tree()
    red = {k: ("x",) for k in tree}
    sc = {k: ("x",) for k in tree}

    def ref(t):
        def leaf(g):
            z, k = zero1_slice_meta(g, ("x",), sizes)
            full = jax.lax.psum(g, ("x",)).reshape(-1)
            pad = z * k - full.size
            if pad:
                full = jnp.pad(full, (0, pad))
            i = jax.lax.axis_index("x")
            return jax.lax.dynamic_slice(full, (i * k,), (k,))
        return jax.tree_util.tree_map(leaf, t)

    def scattered(t):
        return bucketed_psum_scatter(t, red, sc, sizes, bucket_bytes)

    in_specs = (jax.tree_util.tree_map(lambda _: P(), tree),)
    out_specs = jax.tree_util.tree_map(lambda _: P("x"), tree)
    a = jax.jit(_smap(ref, mesh, in_specs, out_specs))(tree)
    b = jax.jit(_smap(scattered, mesh, in_specs, out_specs))(tree)
    for (pa, x), (_, y) in zip(
            jax.tree_util.tree_leaves_with_path(a),
            jax.tree_util.tree_leaves_with_path(b)):
        np.testing.assert_array_equal(
            np.asarray(x.astype(jnp.float32)),
            np.asarray(y.astype(jnp.float32)), err_msg=str(pa))


@pytest.mark.parametrize("bucket_bytes", [1, 1 << 20])
def test_bucketed_gather_matches_per_leaf_gather(bucket_bytes):
    mesh = _mesh()
    sizes = {"x": 4}
    params = _tree()
    leaf_axes = {k: ("x",) for k in params}

    def slices_of(t):
        """Rank-dependent slices (deterministic): leaf slice layout."""
        def leaf(p):
            z, k = zero1_slice_meta(p, ("x",), sizes)
            flat = p.reshape(-1)
            pad = z * k - flat.size
            if pad:
                flat = jnp.pad(flat, (0, pad))
            i = jax.lax.axis_index("x")
            return jax.lax.dynamic_slice(flat, (i * k,), (k,))
        return jax.tree_util.tree_map(leaf, t)

    def per_leaf(t):
        sl = slices_of(t)

        def leaf(p, s):
            z, k = zero1_slice_meta(p, ("x",), sizes)
            i = jax.lax.axis_index("x")
            full = jnp.zeros((z * k,), s.dtype)
            full = jax.lax.dynamic_update_slice(full, s, (i * k,))
            full = jax.lax.psum(full, ("x",))
            return full[:p.size].reshape(p.shape)
        return jax.tree_util.tree_map(leaf, t, sl)

    def bucketed(t):
        return bucketed_gather_slices(slices_of(t), t, leaf_axes, sizes,
                                      bucket_bytes)

    specs = jax.tree_util.tree_map(lambda _: P(), params)
    a = jax.jit(_smap(per_leaf, mesh, (specs,), specs))(params)
    b = jax.jit(_smap(bucketed, mesh, (specs,), specs))(params)
    for (pa, x), (_, y) in zip(
            jax.tree_util.tree_leaves_with_path(a),
            jax.tree_util.tree_leaves_with_path(b)):
        np.testing.assert_array_equal(
            np.asarray(x.astype(jnp.float32)),
            np.asarray(y.astype(jnp.float32)), err_msg=str(pa))


@pytest.mark.parametrize("megatron_sp", [False, True])
@pytest.mark.parametrize("chunks", [2, 4])
def test_chunked_row_parallel_reduce_bitexact(megatron_sp, chunks):
    from hadoop_tpu.models.decoder import ParallelCtx
    from hadoop_tpu.ops.collective_matmul import reduce_row_parallel
    mesh = _mesh()
    y = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32), jnp.float32)

    def run(n_chunks):
        ctx = ParallelCtx(tp_axis="x", tp_size=4,
                          megatron_sp=megatron_sp,
                          tp_overlap_chunks=n_chunks)
        out_spec = P(None, "x") if megatron_sp else P()
        prog = _smap(lambda t: reduce_row_parallel(t, ctx), mesh,
                     (P(),), out_spec)
        return np.asarray(jax.jit(prog)(y))

    np.testing.assert_array_equal(run(1), run(chunks))


# ----------------------------------------------------------------- conf

def test_overlap_from_conf_defaults_and_overrides():
    from hadoop_tpu.conf import Configuration
    assert overlap_from_conf(None) == DEFAULT_OVERLAP
    conf = Configuration(load_defaults=False)
    assert overlap_from_conf(conf) == OverlapConfig()
    conf.set("parallel.overlap.enabled", "false")
    conf.set("parallel.overlap.bucket.mb", "16")
    conf.set("parallel.overlap.tp.chunks", "8")
    conf.set("parallel.overlap.zero1.reduce-scatter", "false")
    got = overlap_from_conf(conf)
    assert got == OverlapConfig(enabled=False, bucket_mb=16, tp_chunks=8,
                                zero1_reduce_scatter=False)
    assert got.bucket_bytes == 16 << 20


# ------------------------------------------------------- full-step parity

def _run_plan_ab(plan, *, zero1=False, n_steps=3, optimizer="adamw",
                 n_microbatches=1):
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel import make_mesh
    from hadoop_tpu.parallel.train import (init_sharded,
                                           make_data_sharding,
                                           make_train_step)
    cfg = get_config("tiny")
    mesh = make_mesh(plan)
    ds = make_data_sharding(mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    tokens = jax.device_put(tokens, ds)
    targets = jax.device_put(jnp.roll(tokens, -1, axis=1), ds)
    out = {}
    for label, ov in (("on", DEFAULT_OVERLAP), ("off", OVERLAP_OFF)):
        step = make_train_step(cfg, plan, mesh, lr=1e-2, donate=False,
                               optimizer=optimizer, zero1=zero1,
                               n_microbatches=n_microbatches,
                               overlap=ov)
        params, opt = init_sharded(jax.random.PRNGKey(0), cfg, plan,
                                   mesh, zero1=zero1)
        losses = []
        for _ in range(n_steps):
            params, opt, m = step(params, opt, tokens, targets)
            losses.append(float(m["loss"]))
        out[label] = (losses, jax.tree_util.tree_map(
            np.asarray, jax.device_get(params)))
    return out


def _assert_ab_bitexact(out):
    on_l, on_p = out["on"]
    off_l, off_p = out["off"]
    assert on_l == off_l, f"losses diverged: on={on_l} off={off_l}"
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(on_p),
            jax.tree_util.tree_leaves_with_path(off_p)):
        np.testing.assert_array_equal(a, b, err_msg=str(pa))


def test_overlap_parity_dp2():
    from hadoop_tpu.parallel import MeshPlan
    _assert_ab_bitexact(_run_plan_ab(MeshPlan(dp=2)))


def test_overlap_parity_dp2_tp2():
    from hadoop_tpu.parallel import MeshPlan
    _assert_ab_bitexact(_run_plan_ab(
        MeshPlan(dp=2, tp=2, megatron_sp=True)))


def test_overlap_parity_zero1_dp8():
    from hadoop_tpu.parallel import MeshPlan
    _assert_ab_bitexact(_run_plan_ab(MeshPlan(dp=8), zero1=True))


def test_overlap_zero1_manual_schedule_close():
    """zero1 under the manual 1F1B schedule reduce-scatters the grads;
    slice values are bitwise but the grad-NORM accumulates slice-wise,
    so the clip scale (and later losses) may move by an ulp — assert
    tight closeness, not bit equality (see parallel/overlap.py)."""
    from hadoop_tpu.parallel import MeshPlan
    out = _run_plan_ab(MeshPlan(dp=2, pp=2), zero1=True, n_steps=3,
                       n_microbatches=2)
    np.testing.assert_allclose(out["on"][0], out["off"][0], rtol=1e-6)
