"""The weights of a seed are bit for bit what they were before the
families were split out (PR 28), and so are the FLOPs a model needs:
``weights_digests.json`` was recorded from the parent commit's
``weights.make_params`` / ``make_leaf`` and ``flops.serve_flops``."""

import json
import os
import zlib

import pytest

from chipbench import families
from chipbench import weights as W

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(HERE, "weights_digests.json")) as f:
    RECORDED = json.load(f)


def model_of(path, **over):
    with open(os.path.join(ROOT, path)) as f:
        conf = json.load(f)
    return dict({k: v for k, v in conf.items()
                 if not isinstance(v, (dict, list))}, **over)


def crc(a) -> int:
    import numpy as np
    return zlib.crc32(np.asarray(a).tobytes())


@pytest.mark.parametrize("name", sorted(RECORDED["rehearsal"]))
def test_every_leaf_of_a_rehearsal_configuration(name):
    import jax
    import jax.numpy as jnp
    model = model_of(f"chipbench/tests/rehearsal/configs/{name}.json")
    family = families.load(model)
    key = W.seed_key(RECORDED["seed"])
    tree = jax.jit(lambda k: family.make_params(model, k, jnp.bfloat16))(key)
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    assert {jax.tree_util.keystr(p): crc(v) for p, v in leaves} \
        == RECORDED["rehearsal"][name]
    # a leaf alone, and a layer alone, are the tree's
    for (_, whole), path in zip(leaves, family.leaf_paths(model)):
        alone = jax.jit(lambda k, path=path: family.make_leaf(
            model, k, path, jnp.bfloat16))(key)
        assert crc(alone) == crc(whole), path
    last = model["num_hidden_layers"] - 1
    layer = jax.jit(lambda k: family.layer_params(
        model, k, last, jnp.bfloat16))(key)
    for leaf, value in layer.items():
        assert crc(value) == crc(tree["layers"][leaf][last]), leaf


@pytest.mark.parametrize("tag,config,path", [
    ("mistral.wq", "mistral-7b-v0.3.train-1chip", ("layers", "wq")),
    ("mistral.lm_head", "mistral-7b-v0.3.train-1chip", ("lm_head",)),
    ("mixtral.w_down", "mixtral-8x7b-v0.1.serve-1chip", ("layers", "w_down")),
])
def test_one_real_width_leaf(tag, config, path):
    """One layer deep, at the published widths (the expert stack whole)."""
    import jax
    import jax.numpy as jnp
    model = model_of(f"chipbench/configs/{config}.json", num_hidden_layers=1)
    family = families.load(model)
    leaf = jax.jit(lambda k: family.make_leaf(model, k, path, jnp.bfloat16))(
        W.seed_key(RECORDED["seed"]))
    assert list(leaf.shape) == RECORDED["real"][tag]["shape"]
    assert crc(leaf) == RECORDED["real"][tag]["crc32"]


@pytest.mark.parametrize("tag,config", [
    ("mistral", "mistral-7b-v0.3.serve-1chip"),
    ("mixtral", "mixtral-8x7b-v0.1.serve-1chip")])
def test_the_work_needed_is_the_parents_to_the_last_digit(tag, config):
    model = model_of(f"chipbench/configs/{config}.json")
    family = families.load(model)
    served = [families.Served(p, RECORDED["hit"], outputs)
              for p, outputs in RECORDED["requests"]]
    work = family.serve_work(model, served)
    assert repr(work["flops"]) == RECORDED["serve_flops"][tag]
    assert repr(family.train_flops_per_token(model, 4096)) \
        == RECORDED["train_flops_per_token"][tag]
