"""Weights from ``--seed``, made by the benchmark and handed to the program.

The tree has the layout ``hadoop_tpu.models.decoder`` takes (layer-stacked
leaves), but nothing here imports the program: the reference regenerates
the same values from the same seed, one layer at a time, after the
program's state is gone. Every matrix has a key of its own —
``fold_in(fold_in(seed_key, crc32(leaf)), index)`` with ``index`` the layer
(times the expert count, plus the expert, for an expert stack) — so a
stack is a sequential map over matrices and the largest float32 transient
is one matrix.

``model`` is the configuration file's ``model`` group (HF key names).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

# Values come from integer arithmetic alone, so that two programs that
# generate the same leaf (the program's init, the reference's, one leaf
# regenerated alone) agree bit for bit. A normal drawn through erf_inv did
# not: on the v5e 6% of a leaf's bfloat16 values came out one ulp apart
# between two jitted programs (my chip run, PR 24). k is the sum of four
# uniform 6-bit fields of one random word, centred: an integer in
# [-126, 126] with standard deviation K_STD, close to normal (Irwin-Hall).
K_STD = (4 * (64 ** 2 - 1) / 12.0) ** 0.5       # 36.95
NORM_STEP = 2.0 ** -10   # norm weights are 1 + k / 1024 (std 0.036), exact
#                          in float32: an all-ones weight would hide a
#                          dropped multiply


def seed_key(seed: int):
    """A key from any whole number up to 2**62 (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def freeze(model: dict) -> tuple:
    """The model group as a hashable static argument (scalars only)."""
    return tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, bool, type(None)))))


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    hq = model["num_attention_heads"]
    return {"D": d, "Hq": hq, "Hkv": model["num_key_value_heads"],
            "Dh": model.get("head_dim") or d // hq,
            "F": model["intermediate_size"], "V": model["vocab_size"],
            "L": model["num_hidden_layers"],
            "E": model.get("num_local_experts", 0)}


def layer_leaves(model: dict) -> dict:
    """name -> (matrix shape, fan_in, matrices per layer). fan_in None
    marks a norm vector."""
    m = dims(model)
    d, f, e = m["D"], m["F"], m["E"]
    qo, kv = m["Hq"] * m["Dh"], m["Hkv"] * m["Dh"]
    leaves = {
        "attn_norm_w": ((d,), None, 1),
        "wq": ((d, qo), d, 1), "wk": ((d, kv), d, 1),
        "wv": ((d, kv), d, 1), "wo": ((qo, d), qo, 1),
        "mlp_norm_w": ((d,), None, 1),
    }
    per = e or 1
    if e:
        leaves["router"] = ((d, e), d, 1)
    leaves["w_gate"] = ((d, f), d, per)
    leaves["w_up"] = ((d, f), d, per)
    leaves["w_down"] = ((f, d), f, per)
    return leaves


def top_leaves(model: dict) -> dict:
    m = dims(model)
    return {"embed": ((m["V"], m["D"]), m["D"], 1),
            "final_norm_w": ((m["D"],), None, 1),
            "lm_head": ((m["D"], m["V"]), m["D"], 1)}


def _matrix(key, index, shape, fan_in, dtype):
    bits = jax.random.bits(jax.random.fold_in(key, index), shape,
                           jnp.uint32)
    k = sum(((bits >> (6 * i)) & 63).astype(jnp.int32) for i in range(4))
    k = (k - 126).astype(jnp.float32)
    if fan_in is None:
        return (1.0 + k * NORM_STEP).astype(dtype)
    # one float32 multiply of an exact integer, then one rounding
    return (k * jnp.float32(fan_in ** -0.5 / K_STD)).astype(dtype)


def _stack(key, start, count, shape, fan_in, dtype):
    """Matrices ``start .. start+count`` of one leaf, one at a time."""
    return jax.lax.map(
        lambda i: _matrix(key, i, shape, fan_in, dtype),
        start + jnp.arange(count))


def layer_params(model: dict, key, layer, dtype) -> dict:
    """One layer's leaves (no leading layer axis; an expert stack keeps
    its expert axis). ``key`` is ``seed_key(seed)``; it and ``layer``
    may be traced, so one compiled program serves every seed."""
    out = {}
    for name, (shape, fan_in, per) in layer_leaves(model).items():
        k = _leaf_key(key, name)
        if per == 1:
            out[name] = _matrix(k, layer, shape, fan_in, dtype)
        else:
            out[name] = _stack(k, layer * per, per, shape, fan_in, dtype)
    return out


def top_params(model: dict, key, dtype) -> dict:
    return {name: _matrix(_leaf_key(key, name), 0, shape, fan_in, dtype)
            for name, (shape, fan_in, _) in top_leaves(model).items()}


def make_params(model: dict, key, dtype) -> dict:
    """The whole layer-stacked tree from ``seed_key(seed)``. Call under
    ``jax.jit`` (with the program's shardings as ``out_shardings`` where
    it has a mesh)."""
    n_layers = dims(model)["L"]
    layers = {}
    for name, (shape, fan_in, per) in layer_leaves(model).items():
        k = _leaf_key(key, name)
        flat = _stack(k, 0, n_layers * per, shape, fan_in, dtype)
        if per > 1:
            flat = flat.reshape((n_layers, per) + shape)
        layers[name] = flat
    tree = top_params(model, key, dtype)
    tree["layers"] = layers
    return tree


def make_leaf(model: dict, key, path: tuple, dtype):
    """One leaf of ``make_params``'s tree, alone: ``("embed",)`` or
    ``("layers", "wq")``."""
    if path[0] != "layers":
        shape, fan_in, _ = top_leaves(model)[path[0]]
        return _matrix(_leaf_key(key, path[0]), 0, shape, fan_in, dtype)
    shape, fan_in, per = layer_leaves(model)[path[1]]
    n_layers = dims(model)["L"]
    flat = _stack(_leaf_key(key, path[1]), 0, n_layers * per, shape,
                  fan_in, dtype)
    return flat.reshape((n_layers, per) + shape) if per > 1 else flat


def leaf_paths(model: dict):
    """Paths of ``make_params``'s leaves in tree-flatten order."""
    paths = [("embed",), ("final_norm_w",)]
    paths += [("layers", n) for n in sorted(layer_leaves(model))]
    paths.append(("lm_head",))
    return paths
