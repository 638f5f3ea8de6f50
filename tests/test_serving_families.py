"""The seam between ``DecodeEngine`` and a model family
(``hadoop_tpu/serving/families``).

- the engine's source names no family: what a token's cache entry is and
  which layers read it is the family's, asked for through ``Family``'s
  members alone;
- a family is additive: a toy one defined HERE (three pools, each
  spanning its own number of layers with a page of its own shape, a
  per-lane state, layers that change nothing, one stats column),
  registered by patching the dict, serves requests through the unedited
  engine and its column reaches the counter it names;
- what the engine derives from a family is pinned to the values the
  engine before the seam held (pool shapes, a page's bytes, the chain
  salt that DFS-persisted prefixes are keyed by, the read-back's width,
  ``weight_plane()``'s keys);
- the dense paged family places its tree once, at construction
  (``place_weights``: ``wq``, ``wk``, ``wv`` joined into ``wqkv``, in
  their place): the same tokens, bytes and pool as over the tree as
  loaded, which the planes that read the projections by name keep.
"""

import dataclasses
import pathlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.models import decoder, deepseek, init_params_for, ouro
from hadoop_tpu.models.config import PRESETS, get_config
from hadoop_tpu.serving import engine as engine_mod
from hadoop_tpu.serving import families
from hadoop_tpu.serving.engine import DecodeEngine, SamplingParams
from hadoop_tpu.serving.families.gqa import QKV, PagedKVFamily
from hadoop_tpu.serving.metrics import ServingMetrics
from hadoop_tpu.serving.weightplane import resident_weight_bytes


def test_engine_source_names_no_family():
    src = pathlib.Path(engine_mod.__file__).read_text()
    assert re.findall(r"_dsa|_dsv32|deepseek|cfg\.family", src) == []
    # the expert plane is a family's too: the one question left is
    # whether the HBM ledger gets a moe_experts component
    assert src.count("is_moe") == 1
    for name in families.FAMILIES:
        assert name not in src, name


def test_every_preset_has_a_family_and_its_counters_exist():
    assert {c.family for c in PRESETS.values()} <= set(families.FAMILIES)
    metrics = ServingMetrics("serving.test.families.names")
    for cls in set(families.FAMILIES.values()):
        for name in cls.counters:
            assert hasattr(getattr(metrics, name), "incr"), name


# ------------------------------------------------------------ a toy family

class ToyFamily(families.Family):
    """Entries 3 and 5 wide in every layer, and a third pool of ONE layer
    whose page holds a single number (the last length written there); a
    per-lane state (the rows a lane has run since it started, from the
    page it started after); a "layer" that writes each live row's
    position into its entry and leaves ``h`` alone; one stats column: the
    live rows."""
    counters = ("attn_pages_distinct",)
    salt_layout = (1, 8)
    refused = []

    def refuse(self, asked):
        self.refused.append(dict(asked))
        if asked.get("serving.speculate.k"):
            raise NotImplementedError("toy: serving.speculate.k")

    def pools(self, block_size):
        n = self.cfg.n_layers
        return [(n, (block_size, 3)), (n, (block_size, 5)), (1, (1,))]

    def lane_state(self, lanes):
        return (lanes, 2)

    def start_lane(self, lane, pools, slot, page):
        return lane.at[slot].set(jnp.stack(
            [page.astype(lane.dtype), jnp.zeros((), lane.dtype)]))

    def run_layers(self, params, h, pools, lane, rows):
        kp, vp, last = pools
        mark = rows["lens"].astype(kp.dtype)[:, None]
        kp = kp.at[0, rows["blk"], rows["off"]].set(
            jnp.broadcast_to(mark, (h.shape[0], 3)))
        vp = vp.at[0, rows["blk"], rows["off"]].set(
            jnp.broadcast_to(-mark, (h.shape[0], 5)))
        last = last.at[0, rows["blk"], 0].max(mark[:, 0])
        b = rows["B"]
        ran = rows["active"][:b].astype(lane.dtype)
        if rows["chunk_slot"] is not None:
            ran = ran.at[rows["chunk_slot"]].add(
                rows["chunk_n"].astype(lane.dtype))
        lane = lane.at[:, 1].add(ran)
        return h, (kp, vp, last), lane, \
            jnp.sum(rows["active"], dtype=jnp.int32)[None]

    def describe_experts(self, rows):
        return {"toy_rows": rows}


@pytest.fixture()
def toy():
    cfg = dataclasses.replace(get_config("tiny"), family="toy",
                              dtype="float32")
    key = jax.random.PRNGKey(5)
    params = {"embed": jax.random.normal(key, (cfg.vocab_size, cfg.d_model)),
              "final_norm_w": jnp.ones((cfg.d_model,)),
              "lm_head": jax.random.normal(jax.random.fold_in(key, 1),
                                           (cfg.d_model, cfg.vocab_size))}
    ToyFamily.refused = []
    with mock.patch.dict(families.FAMILIES, {"toy": ToyFamily}):
        yield params, cfg


def test_a_toy_family_serves_through_the_unedited_engine(toy):
    params, cfg = toy
    metrics = ServingMetrics("serving.test.families.toy")
    before = metrics.attn_pages_distinct.value()
    eng = DecodeEngine(params, cfg, max_batch=2, block_size=4, num_blocks=9,
                       max_context=32, prefill_chunk=8, metrics=metrics)
    assert eng._kp.shape == (cfg.n_layers, 9, 4, 3)
    assert eng._vp.shape == (cfg.n_layers, 9, 4, 5)
    assert [p.shape for p in eng._pools][2:] == [(1, 9, 1)]
    assert eng.block_nbytes == cfg.n_layers * 4 * 4 * (3 + 5) + 4
    assert eng.block_nbytes * 9 == sum(p.nbytes for p in eng._pools)
    assert eng._dstate["lane"].shape == (2, 2)
    assert eng.expert_shards == 0
    assert eng.weight_plane()["toy_rows"] == 2
    prompts = [[7, 3, 11, 5, 2], [9, 1]]
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=4))

    # layers that change nothing: a token follows from the one before
    def after(tok):
        h = decoder._norm(params["embed"][tok][None], params["final_norm_w"],
                          None, cfg)
        return int(jnp.argmax(h @ params["lm_head"]))
    for prompt, out in zip(prompts, outs):
        want, tok = [], prompt[-1]
        for _ in range(4):
            tok = after(tok)
            want.append(tok)
        assert out == want
    # the stats column reached the counter it names: every prompt token
    # and every decode step's token was one live row
    rows = sum(len(p) + 3 for p in prompts)
    assert metrics.attn_pages_distinct.value() - before == rows
    # and the rows landed in both pools' pages, layer 0, at their offsets
    kp, vp = np.asarray(eng._kp), np.asarray(eng._vp)
    written = kp[0, 1:, :, 0].ravel()
    assert sorted(written[written != 0]) == sorted(
        list(range(1, 5 + 4)) + list(range(1, 2 + 4)))
    assert np.array_equal(vp[0, 1:, :, 4], -kp[0, 1:, :, 2])
    assert not kp[1:].any()
    # the third pool rode the step too: a page's number is the largest
    # length written there
    last = np.asarray(eng._pools[2])[0, 1:, 0]
    assert last.max() == 5 + 3 and np.array_equal(
        last, kp[0, 1:, :, 0].max(axis=1))
    # and the lane state: both lanes started from nothing (page 0) and
    # ran their prompt's rows and their decode rows
    assert np.asarray(eng._dstate["lane"]).tolist() == [[0, 5 + 3],
                                                       [0, 2 + 3]]
    # a second request over the first one's cached page starts its lane
    # AFTER that page, and says so
    again = eng.submit(prompts[0][:4] + [1, 2],
                       SamplingParams(max_new_tokens=1))
    eng.step()
    assert again.prefix_tokens_reused == 4
    page = eng.prefix_cache.match_nodes(prompts[0][:4])[0].block
    assert np.asarray(eng._dstate["lane"])[0].tolist() == [page, 2]
    eng.stop()


def test_a_family_is_asked_by_conf_key_what_it_refuses(toy):
    params, cfg = toy
    kw = dict(max_batch=2, block_size=4, num_blocks=9, max_context=32)
    with pytest.raises(NotImplementedError, match="serving.speculate.k"):
        DecodeEngine(params, cfg, speculate_k=2, **kw)
    eng = DecodeEngine(params, cfg, **kw)
    asked = ToyFamily.refused[-1]
    assert set(asked) == {
        "serving.parity=relaxed", "a tp plan (serving.tp)",
        "serving.kv.host.bytes", "serving.kv.dfs.enable",
        "serving.speculate.k", "serving.moe.shards"}
    assert not any(asked.values())
    eng.attach_longctx(mock.Mock())
    assert ToyFamily.refused[-1] == {"serving.longctx.enable": True}
    eng._relaxed_longctx = None
    eng.stop()


# ------------------------------------------- pins taken from the parent

PINS = {
    "tiny": ((4, 9, 4, 2, 16), (4, 9, 4, 2, 16), 4096,
             "bf7f3adbf5a543b373d3e78f082448bcd7bbcdfb587dad4a484122dda7c8f185",
             (2, 6), set()),
    "tiny-moe": ((2, 9, 4, 2, 16), (2, 9, 4, 2, 16), 2048,
                 "19670316828032032e0608387260f35eb30dce94605cc2967180fe981732c4b8",
                 (2, 6), {"a2a_codec", "expert_capacity"}),
    "tiny-dsv32": ((3, 9, 4, 128), (3, 9, 4, 16), 6912,
                   "a2045a68068ed982674bd8b7b33b6b2cd267f1a2ca9e359ee30acb0e52705e91",
                   (2, 8), {"experts_from", "experts_routed"}),
    # pools passes x layers = 9 slots deep over 3 layers of weights, and
    # the salt names the slots; one stats column (``loop_passes``)
    "tiny-ouro": ((9, 9, 4, 4, 16), (9, 9, 4, 4, 16), 18432,
                  "76c56516387c332e5ecf7e904bdeee0fce1fb029feded73fd65e75b0520eb476",
                  (2, 7), set()),
}
# (``tiny-lfm2`` has three pools and a lane state: tests/test_lfm2.py)
PLANE_KEYS = {"dtype", "expert_bytes", "expert_shards", "experts",
              "hbm_bytes", "kv_capacity_tokens", "lanes", "lanes_x_context",
              "max_context", "parity", "quantize_seconds",
              "quantized_leaves", "weight_bytes"}


@pytest.mark.parametrize("name", sorted(PINS))
def test_what_the_engine_derives_is_what_it_held_before(name):
    kp_shape, vp_shape, nbytes, salt, packed, extra = PINS[name]
    cfg = get_config(name)
    init = {"tiny-dsv32": deepseek.init_params,
            "tiny-ouro": ouro.init_params}.get(name, decoder.init_params)
    eng = DecodeEngine(init(jax.random.PRNGKey(0), cfg), cfg, max_batch=2,
                       block_size=4, num_blocks=9, max_context=32,
                       prefill_chunk=8)
    assert (eng._kp.shape, eng._vp.shape) == (kp_shape, vp_shape)
    assert eng.block_nbytes == nbytes
    assert eng.block_nbytes * 9 == eng._kp.nbytes + eng._vp.nbytes
    assert len(eng._pools) == 2 and eng._dstate["lane"] is None
    assert eng.kvstore.chain_salt.hex() == salt
    # a page is as deep as the family's pools, where the tiers move it too
    assert eng.kvstore.block_shape[0] == eng._family.page_slots \
        == kp_shape[0]
    out = jax.eval_shape(eng._step_impl, eng.params, eng._kp, eng._vp,
                         eng._dstate, eng._dz_drafts, eng._dz_lens, None)
    assert out[3].shape == packed
    # the step's own counts ride the bundle before the family's
    assert out[3].shape[1] == 4 + len(engine_mod._STEP_COUNTERS) \
        + len(eng._family.counters)
    assert set(eng.weight_plane()) == PLANE_KEYS | extra
    eng.stop()


# ------------------------------------- the projections, placed at load

def loaded_engine(params, cfg, **kw):
    """An engine over the tree as loaded: three matmuls a layer."""
    with mock.patch.object(PagedKVFamily, "place_weights",
                           families.Family.place_weights):
        return DecodeEngine(params, cfg, **kw)


def copy_of(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


# repeats, so that the n-gram lane has drafts to propose
PROMPTS = [[5, 9, 2, 5, 9, 2, 5, 9, 2, 5, 9], [17, 3, 200, 41, 8]]
KW = dict(max_batch=2, block_size=4, max_context=64, prefill_chunk=8)


def served(eng, max_new=10):
    try:
        return eng.generate(PROMPTS, SamplingParams(max_new_tokens=max_new))
    finally:
        eng.stop()


def reference_greedy(fwd, params, prompt, max_new):
    seq = list(prompt)
    for _ in range(max_new):
        logits = fwd(params, jnp.asarray([seq + [0] * (32 - len(seq))]))
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[len(prompt):]


@pytest.mark.parametrize("name,spec_k", [
    ("tiny", 0), ("tiny", 2), ("tiny-gpt2", 0), ("tiny-gpt2", 2),
    ("tiny-moe", 0), ("tiny-moe", 2), ("tiny-ouro", 0)])
def test_the_placed_engine_serves_what_the_loaded_tree_serves(name, spec_k):
    cfg = get_config(name)
    params = init_params_for(cfg)(jax.random.PRNGKey(11), cfg)
    placed = DecodeEngine(params, cfg, speculate_k=spec_k, **KW)
    loaded = loaded_engine(params, cfg, speculate_k=spec_k, **KW)
    layers = placed.params["layers"]
    assert not set(QKV) & set(layers)
    assert set(QKV) <= set(loaded.params["layers"])
    np.testing.assert_array_equal(
        np.asarray(layers["wqkv"]),
        np.concatenate([np.asarray(params["layers"][k]) for k in QKV], -1))
    # in their place, not beside them: the tree's bytes did not change
    assert placed.weight_bytes == loaded.weight_bytes \
        == resident_weight_bytes(placed.params)
    assert placed._weight_desc == loaded._weight_desc
    assert placed.weight_plane() == loaded.weight_plane()
    assert placed.pool.num_blocks == loaded.pool.num_blocks
    # no budget was given: the caller's tree is still the caller's
    assert not any(params["layers"][k].is_deleted() for k in QKV)
    out = served(placed)
    assert out == served(loaded)
    if spec_k:
        assert placed.spec_proposed > 0
    if name in ("tiny", "tiny-gpt2"):
        fwd = jax.jit(lambda p, t: decoder.forward(p, t, cfg))
        assert out == [reference_greedy(fwd, params, p, 10)
                       for p in PROMPTS]


def test_under_a_budget_the_replaced_stacks_are_freed_and_the_pool_is_the_same():
    """``hbm_bytes`` counts the weights once: the pool is what the tree
    as loaded leaves room for, and the stacks the joined leaf replaces
    are freed (jit donation cannot: no output has a stack's shape) before
    the pools are made, though the caller still names them."""
    cfg = get_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(11), cfg)
    kw = dict(block_size=4, max_context=64, prefill_chunk=8)
    probe = loaded_engine(params, cfg, **kw)
    budget = probe.weight_bytes + 40 * probe.block_nbytes + 17
    probe.stop()
    mine, theirs = copy_of(params), copy_of(params)
    placed = DecodeEngine(mine, cfg, hbm_bytes=budget, **kw)
    loaded = loaded_engine(theirs, cfg, hbm_bytes=budget, **kw)
    assert all(mine["layers"][k].is_deleted() for k in QKV)
    assert not any(leaf.is_deleted() for k, leaf in mine["layers"].items()
                   if k not in QKV)
    assert not any(theirs["layers"][k].is_deleted() for k in QKV)
    assert placed.pool.num_blocks == loaded.pool.num_blocks == 40
    assert placed.max_batch == loaded.max_batch
    assert placed.weight_plane() == loaded.weight_plane()
    assert resident_weight_bytes(placed.params) == placed.weight_bytes
    assert served(placed) == served(loaded)
    # a budget the weights overflow is refused before the tree is touched
    with pytest.raises(ValueError, match="hbm"):
        DecodeEngine(params, cfg, hbm_bytes=probe.weight_bytes, **kw)
    assert not any(params["layers"][k].is_deleted() for k in QKV)


@pytest.mark.parametrize("name", ["tiny", "tiny-ouro"])
def test_a_checkpoint_through_the_loader_is_placed_and_serves_the_same(
        tmp_path, name):
    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.parallel.checkpoint import save_checkpoint
    from hadoop_tpu.serving.loader import load_serving_params
    cfg = get_config(name)
    params = init_params_for(cfg)(jax.random.PRNGKey(3), cfg)
    fs = LocalFileSystem()
    save_checkpoint(fs, f"{tmp_path}/m", 2, {"params": params})
    got, _ = load_serving_params(fs, f"{tmp_path}/m", cfg)
    eng = DecodeEngine(got, cfg, **KW)
    assert "wqkv" in eng.params["layers"]
    assert served(eng) == served(loaded_engine(params, cfg, **KW))


def _relaxed(params, cfg):
    from hadoop_tpu.serving import weightplane as wp
    policy = wp.WeightPlaneConfig(tier="relaxed", group=16)
    qparams, _ = wp.quantize_params(params, cfg, policy)
    return DecodeEngine(qparams, cfg, **KW), \
        DecodeEngine(wp.dequantize_params(qparams, cfg), cfg, **KW)


def _tp(params, cfg):
    from hadoop_tpu.parallel.mesh import MeshPlan
    return DecodeEngine(params, cfg, plan=MeshPlan(tp=2), **KW), \
        DecodeEngine(params, cfg, **KW)


@pytest.mark.parametrize("plane", [_relaxed, _tp])
def test_a_plane_that_reads_the_projections_by_name_keeps_the_loaded_tree(
        plane):
    """int8 scale groups (``weightplane`` quantizes ``wq`` by name) and a
    tp cut along N (``parallel.mesh.param_specs``): today's three
    matmuls over the tree as loaded, and the same tokens as the placed
    engine over the same values. (The long-context plane reads a tree of
    its own — the engine's only under ``serving.parity=relaxed``, where
    it is the loaded one: tests/test_longctx.py serves through placed
    engines.)"""
    cfg = get_config("tiny")
    params = decoder.init_params(jax.random.PRNGKey(11), cfg)
    eng, placed = plane(params, cfg)
    assert set(QKV) <= set(eng.params["layers"])
    assert "wqkv" not in eng.params["layers"]
    assert "wqkv" in placed.params["layers"]
    assert served(eng) == served(placed)
