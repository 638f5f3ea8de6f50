"""``models.moe.moe_share``: the held experts run over the rows routed
to them and nothing else.

The plain reference is the form the routine had before it sorted its
assignments: every held expert over ALL rows, then a ``[T, E]`` gate of
mostly zeros. It lives here only. The routine must agree with it on
every row ``valid`` marks, give a row it does not mark the shared
expert's output alone, and count from the group sizes it hands the
matmul exactly what the reference counts from its one-hot choices — on
the portable path as it runs, and on the TPU kernel through Pallas's
interpreter.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.models import deepseek, lfm2
from hadoop_tpu.models.config import get_config
from hadoop_tpu.models.moe import EXPERT_LEAVES, moe_share, route_grouped
from hadoop_tpu.ops import swiglu
from hadoop_tpu.ops.grouped_matmul import grouped_matmul, row_tile

ATOL = 2e-5


def dense_reference(x2d, lp, cfg, valid=None):
    """(``y [T, D]`` float32 with every row routed, the shared expert's
    output or None, per-expert assignment counts ``[E]`` over the rows
    ``valid`` marks)."""
    e, lo = cfg.n_experts, cfg.experts_from
    idx, w = route_grouped(x2d, lp["router"], lp["router_bias"], cfg)
    local = idx - lo
    held = (local >= 0) & (local < e)
    hot = jax.nn.one_hot(jnp.where(held, local, e), e + 1,
                         dtype=jnp.float32)[..., :e]
    gate = jnp.sum(hot * w[..., None], axis=1)
    hidden = swiglu(jnp.einsum("td,edf->etf", x2d, lp["w_gate"]),
                    jnp.einsum("td,edf->etf", x2d, lp["w_up"]))
    ye = jnp.einsum("etf,efd->etd", hidden, lp["w_down"])
    y = jnp.einsum("te,etd->td", gate, ye.astype(jnp.float32))
    shared = None
    if "ws_gate" in lp:
        shared = (swiglu(x2d @ lp["ws_gate"], x2d @ lp["ws_up"])
                  @ lp["ws_down"]).astype(jnp.float32)
        y = y + shared
    if valid is not None:
        hot = hot * valid[:, None, None]
    return y, shared, jnp.sum(hot, axis=(0, 1)).astype(jnp.int32)


def stats_of(per_expert):
    return [int(per_expert.sum()), int((per_expert > 0).sum()),
            int(per_expert.max())]


# the two families at test size: every expert resident and no shared one
# (16 of 16, top 4) · a share of a wider router with a shared expert
# (8 of 32 from ``experts_from``, top 4 inside 2 of 4 groups)
FAMILIES = {"lfm2": ("tiny-lfm2", lfm2), "dsv32": ("tiny-dsv32", deepseek)}


def stacked(family, seed=0, **replace):
    """(cfg, the family's stacked expert layers ``[L, ...]``)."""
    preset, module = FAMILIES[family]
    cfg = get_config(preset)
    params = module.init_params(jax.random.PRNGKey(seed), cfg)
    return dataclasses.replace(cfg, **replace), params["moe_layers"]


def one_layer(layers, l):
    return jax.tree_util.tree_map(lambda a: a[l], layers)


def rows_for(seed, t, d=64):
    return jax.random.normal(jax.random.PRNGKey(100 + seed), (t, d),
                             jnp.float32)


VALID = {
    "all": lambda t: np.ones((t,), bool),
    "some": lambda t: np.arange(t) % 3 != 1,
    "none": lambda t: np.zeros((t,), bool),
}


def check(x, lp, cfg, valid, interpret):
    """``moe_share`` against the dense reference on one layer ``lp``."""
    want, shared, per_expert = dense_reference(
        x, lp, cfg, None if valid is None else jnp.asarray(valid))
    y, stats = moe_share(x, lp, cfg, busiest=True,
                         valid=None if valid is None else jnp.asarray(valid),
                         interpret=interpret)
    y, want = np.asarray(y), np.asarray(want)
    live = np.ones((x.shape[0],), bool) if valid is None else valid
    np.testing.assert_allclose(y[live], want[live], atol=ATOL)
    # a row nobody owns: the shared expert's output alone, or zero
    dead = np.zeros_like(want) if shared is None else np.asarray(shared)
    np.testing.assert_allclose(y[~live], dead[~live], atol=ATOL)
    assert np.asarray(stats).tolist() == stats_of(np.asarray(per_expert))
    return np.asarray(stats), np.asarray(per_expert)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged", "kernel"])
@pytest.mark.parametrize("valid", sorted(VALID))
@pytest.mark.parametrize("t", [6, 38], ids=["decode", "fused"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_share_equals_the_dense_form_on_live_rows(family, t, valid,
                                                  interpret):
    """Both families' layers, decode-only and fused row counts, every /
    some / no row live: live rows equal the reference, dead rows get the
    shared expert alone, and the counts are the reference's."""
    cfg, layers = stacked(family)
    mask = VALID[valid](t)
    stats, _ = check(rows_for(t, t), one_layer(layers, 1), cfg, mask,
                     interpret)
    if valid == "none":
        assert stats.tolist() == [0, 0, 0]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_dead_rows_choice_is_in_no_count(family):
    """Rows 0..3 alone are live: the counts are those of a call that was
    given rows 0..3 and nothing else — whatever the dead rows chose."""
    cfg, layers = stacked(family)
    lp = one_layer(layers, 0)
    x = rows_for(7, 12)
    mask = np.arange(12) < 4
    stats, per_expert = check(x, lp, cfg, mask, False)
    _, alone = moe_share(x[:4], lp, cfg, busiest=True)
    assert stats.tolist() == np.asarray(alone).tolist()
    _, _, everyone = dense_reference(x, lp, cfg)
    assert int(np.asarray(everyone).sum()) > int(per_expert.sum())


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged", "kernel"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_row_on_one_expert(family, interpret):
    """A bias that sends every row to one held expert: one group holds a
    row of every token, no room runs out, nothing is dropped."""
    cfg, layers = stacked(family)
    lp = one_layer(layers, 0)
    popular = cfg.experts_from + 2
    lp["router_bias"] = lp["router_bias"].at[popular].set(50.0)
    stats, per_expert = check(rows_for(3, 40), lp, cfg, None, interpret)
    assert stats[2] == 40 and per_expert[2] == 40


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged", "kernel"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_layer_is_read_out_of_the_stack_by_its_index(family, interpret):
    """The stacked leaves and a layer index — traced, as a scan gives it
    — equal that layer's leaves alone, for every layer of the stack; the
    other layers' experts are never multiplied (their groups are empty)."""
    cfg, layers = stacked(family)
    x = rows_for(11, 9)
    n_layers = layers["w_gate"].shape[0]
    run = jax.jit(lambda x, rest, l: moe_share(
        x, {**rest, **{n: layers[n] for n in EXPERT_LEAVES}}, cfg,
        busiest=True, layer=l, interpret=interpret))
    for l in range(n_layers):
        lp = one_layer(layers, l)
        want, _, per_expert = dense_reference(x, lp, cfg)
        rest = {n: a for n, a in lp.items() if n not in EXPERT_LEAVES}
        y, stats = run(x, rest, jnp.int32(l))
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=ATOL)
        assert np.asarray(stats).tolist() == stats_of(np.asarray(per_expert))


@pytest.mark.parametrize("experts_from", [0, 8, 24])
def test_a_subset_held_from_experts_from(experts_from):
    """The share of a wider router wherever it starts: the experts
    ``experts_from .. + 8`` of 32, against the reference holding the
    same slice; assignments to the other 24 read nothing here."""
    cfg, layers = stacked("dsv32", experts_from=experts_from)
    stats, _ = check(rows_for(experts_from, 20), one_layer(layers, 0), cfg,
                     VALID["some"](20), False)
    assert 0 < stats[0] < 20 * cfg.top_k


@pytest.mark.parametrize("first", [0, 5])
@pytest.mark.parametrize("m,sizes", [
    (32, [3, 0, 7, 1]), (32, [0, 0, 0, 0]), (48, [0, 40, 0, 8]),
    (256, [100, 1, 0, 59])], ids=["some", "none", "whole", "two-tiles"])
def test_grouped_matmul_kernel_against_a_loop(m, sizes, first):
    """The kernel through the interpreter over a stack whose other
    groups hold NaN: a group with no rows — every group outside ``first
    .. first + G`` among them — is never read. The portable path (on the
    CPU a masked dense product, where NaN times zero would show) over
    the same stack with a finite filler: no other group's weights reach
    a row."""
    k, n, g = 128, 256, len(sizes)
    keys = jax.random.split(jax.random.PRNGKey(m + first), 2)
    rows = jax.random.normal(keys[0], (m, k), jnp.float32)
    live = jax.random.normal(keys[1], (g, k, n), jnp.float32)
    hit = jnp.asarray(sizes) > 0
    want, at = np.zeros((m, n), np.float32), 0
    for j, size in enumerate(sizes):
        want[at:at + size] = np.asarray(rows[at:at + size]) @ \
            np.asarray(live[j])
        at += size
    assert m % row_tile(m) == 0
    for interpret, filler in ((False, 1e3), (True, jnp.nan)):
        stack = jnp.full((first + g + 3, k, n), filler, jnp.float32)
        stack = stack.at[first:first + g].set(
            jnp.where(hit[:, None, None], live, filler))
        got = np.asarray(grouped_matmul(
            rows, stack, jnp.asarray(sizes, jnp.int32), jnp.int32(first),
            interpret=interpret))
        np.testing.assert_allclose(got[:at], want[:at], atol=1e-4)


@pytest.mark.parametrize("tm", [16, 128])
@pytest.mark.parametrize("sizes", [
    [3, 0, 7, 1], [0, 0, 0, 0], [0, 40, 0, 8], [100, 1, 0, 59, 0, 96],
    [128, 128], [1] * 40], ids=["some", "none", "whole", "straddling",
                                "aligned", "ones"])
def test_the_visits_are_the_group_tile_pairs_that_hold_rows(sizes, tm):
    """The kernel's list of visits, made on the device by dense compares,
    against a loop: every (group, row tile) pair that holds a row, once,
    groups in order and a group's tiles in order — and no pair of a
    group with no rows."""
    from hadoop_tpu.ops.grouped_matmul import _visits
    m = -(-max(sum(sizes), 1) // tm) * tm + tm
    offsets, groups, tiles, n = _visits(jnp.asarray(sizes, jnp.int32), m,
                                        tm)
    want, at = [], 0
    for g, size in enumerate(sizes):
        if size:
            want += [(g, tile) for tile in range(at // tm,
                                                 (at + size - 1) // tm + 1)]
        at += size
    assert int(n) == len(want) <= groups.shape[0] == m // tm + len(sizes) - 1
    got = list(zip(np.asarray(groups)[:int(n)].tolist(),
                   np.asarray(tiles)[:int(n)].tolist()))
    assert got == want
    assert np.asarray(offsets).tolist() == [0] + np.cumsum(sizes).tolist()
