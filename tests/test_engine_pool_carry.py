"""The serving step changes the KV pools where they lie.

``engine._step_impl`` (llama / mixtral branch) carries both pools whole
through its layer scan, viewed ``[layers * blocks, bs, hkv, dh]``, and
writes and reads layer ``l``'s pages at ``l * blocks + page``. Pinned
here:

- the step is bit-equal — tokens and both returned pools, after every
  step — to a reference that takes each layer's slab out of the pool,
  runs the same layer on it under slab-local page numbers and stacks
  the slabs back: decode-only and fused shapes, speculation rows, an
  idle lane (its rows write the scratch page), llama and a tiny
  Mixtral, and a pool sharded over a 2-device tp mesh;
- neither compiled shape moves a pool or a slab: no ``dynamic-slice``,
  ``dynamic-update-slice`` or ``copy`` with such a result, and both
  pool outputs alias their donated inputs;
- still two compiled shapes, and a further workload traces nothing.
"""

import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.decoder import init_params
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams

# 37 blocks: no other array of the step has a dimension of 37 or 2 * 37
_BLOCKS = 37
_ENGINE = dict(max_batch=3, block_size=4, num_blocks=_BLOCKS,
               max_context=48, prefill_chunk=8)


@pytest.fixture(scope="module", params=["tiny", "tiny-moe"])
def model(request):
    cfg = get_config(request.param)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _slab_scan(real_scan):
    """``lax.scan`` for the reference: where the carry is (h, K pool,
    V pool) over (layers, page bases), every layer gets its own slab
    and base 0 — the pool is cut into slabs and stacked back, the page
    arithmetic of the flat view never runs."""
    def scan(f, init, xs, *a, **kw):
        pooled = (isinstance(init, tuple) and len(init) == 3
                  and isinstance(xs, tuple) and len(xs) == 2
                  and getattr(xs[1], "dtype", None) == jnp.int32)
        if not pooled:
            return real_scan(f, init, xs, *a, **kw)
        h, kflat, vflat = init
        layers, bases = xs
        n_layers = bases.shape[0]
        slab = kflat.shape[0] // n_layers
        k_out, v_out = [], []
        for l in range(n_layers):
            lp = jax.tree_util.tree_map(lambda w: w[l], layers)
            (h, kc, vc), _ = f(
                (h, kflat[l * slab:(l + 1) * slab],
                 vflat[l * slab:(l + 1) * slab]), (lp, jnp.int32(0)))
            k_out.append(kc)
            v_out.append(vc)
        return (h, jnp.concatenate(k_out), jnp.concatenate(v_out)), None
    return scan


def _noise_pools(eng):
    """Both pools filled with finite noise, placed as the engine placed
    them: a page the step must not touch then shows if it was."""
    for name, seed in (("_kp", 1), ("_vp", 2)):
        old = getattr(eng, name)
        new = jax.random.normal(jax.random.PRNGKey(seed), old.shape,
                                old.dtype)
        setattr(eng, name, jax.device_put(new, old.sharding))


def _lockstep(model, prompts, max_new, **kw):
    """Drive an engine and its slab-by-slab reference through the same
    requests; after every step both pools must be equal to the bit.
    Returns (engine, its tokens)."""
    params, cfg = model
    eng = DecodeEngine(params, cfg, **_ENGINE, **kw)
    ref = DecodeEngine(params, cfg, **_ENGINE, **kw)
    _noise_pools(eng)
    _noise_pools(ref)
    sp = SamplingParams(max_new_tokens=max_new)
    reqs = [eng.submit(p, sp) for p in prompts]
    refs = [ref.submit(p, sp) for p in prompts]
    steps = 0
    while not all(r.done.is_set() for r in refs):
        eng.step()
        # only the reference may trace under the patch
        with mock.patch.object(jax.lax, "scan", _slab_scan(jax.lax.scan)):
            ref.step()
        steps += 1
        np.testing.assert_array_equal(np.asarray(eng._kp),
                                      np.asarray(ref._kp))
        np.testing.assert_array_equal(np.asarray(eng._vp),
                                      np.asarray(ref._vp))
        assert steps < 200
    got = [r.wait(0) for r in reqs]
    assert got == [r.wait(0) for r in refs]
    assert all(r.done.is_set() for r in reqs)
    for e in (eng, ref):
        assert e.decode_compiles == 1 and e.prefill_compiles == 1
    return eng, got


def test_step_equals_slab_by_slab_reference(model):
    """Two prompts of unlike length on three lanes: fused steps while a
    prompt is chunked in, decode-only steps after, one lane idle all
    along (its rows land on the scratch page of every layer)."""
    eng, got = _lockstep(model, [[3, 17, 42, 99, 5, 8, 13, 21, 34, 55, 89],
                                 [7, 8, 9]], 6)
    assert all(len(g) == 6 for g in got)
    assert max(eng.occupancy_log) == 2 < eng.max_batch


def test_step_equals_reference_with_speculation_rows(model):
    """Draft rows: a lane writes up to k + 1 consecutive positions in one
    step and each row reads those before it in that very step."""
    _, cfg = model
    rng = np.random.default_rng(3)
    motif = rng.integers(0, cfg.vocab_size, size=2).tolist()
    eng, _ = _lockstep(model, [(motif * 8)[:16], [5, 6, 7]], 12,
                       speculate_k=3)
    assert eng.spec_proposed > 0


def test_step_equals_reference_on_a_tp_mesh():
    """The pool sharded over KV heads on two devices: merging the two
    leading (unsharded) axes keeps the sharding, and the pools come back
    placed as they went in."""
    from hadoop_tpu.parallel.mesh import MeshPlan
    cfg = get_config("tiny")
    model = (init_params(jax.random.PRNGKey(0), cfg), cfg)
    eng, _ = _lockstep(model, [[3, 17, 42, 99, 5, 8, 13, 21, 34], [7, 8]],
                       5, plan=MeshPlan(tp=2))
    for pool in (eng._kp, eng._vp):
        assert pool.sharding.is_equivalent_to(eng._kv_sharding, pool.ndim)
        assert len(pool.sharding.device_set) == 2


_RESULT = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (\([^=]*\)|\S+) ([\w\-]+)\(", re.M)


def _step_programs(eng):
    args = (eng.params, eng._kp, eng._vp, eng._dstate, eng._dz_drafts,
            eng._dz_lens)
    chunk = (jnp.zeros((eng.prefill_chunk,), jnp.int32),
             jnp.zeros((3,), jnp.int32))
    return {"decode": eng._step_fn.lower(*args, None).compile(),
            "fused": eng._step_fn.lower(*args, chunk).compile()}


def test_compiled_step_moves_no_pool_and_no_slab(model):
    """What the old ``xs -> ys`` scan cost, by the compiled program's own
    text: a slab sliced out and written back per layer, and a copy of
    each pool where the stacked result was not the donated buffer."""
    params, cfg = model
    eng = DecodeEngine(params, cfg, **_ENGINE)
    inner = "%d,%d,%d" % (_ENGINE["block_size"], cfg.n_kv_heads,
                          cfg.head_dim)
    moved = re.compile(r"\[(?:%d,%d|%d|%d),%s\]" % (
        cfg.n_layers, _BLOCKS, cfg.n_layers * _BLOCKS, _BLOCKS, inner))
    pool_bytes = eng._kp.size * eng._kp.dtype.itemsize
    for tag, compiled in _step_programs(eng).items():
        hits = [(op, shape) for shape, op in _RESULT.findall(
            compiled.as_text())
            if op in ("copy", "dynamic-slice", "dynamic-update-slice")
            and moved.search(shape)]
        assert not hits, (tag, hits)
        assert compiled.memory_analysis().alias_size_in_bytes \
            >= 2 * pool_bytes, tag


def test_two_shapes_and_a_further_workload_traces_nothing(model):
    params, cfg = model
    eng = DecodeEngine(params, cfg, **_ENGINE)
    eng.generate([[1], [2, 3, 4, 5]], SamplingParams(max_new_tokens=3))
    assert (eng.decode_compiles, eng.prefill_compiles) == (1, 1)
    assert eng._step_fn._cache_size() == 2
    eng.generate([[9, 8, 7, 6, 5, 4, 3, 2, 1, 9, 8]],
                 SamplingParams(max_new_tokens=4))
    assert (eng.decode_compiles, eng.prefill_compiles) == (1, 1)
    assert eng._step_fn._cache_size() == 2
