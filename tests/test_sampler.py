"""The sampler's rule (``serving/engine._mask_and_scale``, ``_sample``)
against its plain definition, kept HERE: the top-k threshold is the
``k``-th largest logit as a sort gives it. The engine finds the same
threshold without a sort (``ops/topk.kth_largest``), so the transformed
logits must be equal bit for bit — ties at the threshold, signed zeros,
masked entries, every ``k`` from 0 past the vocabulary, mixed in one
batch, over one or two leading axes; and a call whose rows are all greedy
is an arg-max that touches no random bits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hadoop_tpu.ops.topk import kth_largest, sortable
from hadoop_tpu.serving.engine import _NEG_INF, _mask_and_scale, _sample


def reference_mask_and_scale(logits, temps, topks):
    """The definition: sort, read the k-th largest, mask below it."""
    v = logits.shape[-1]
    srt = jnp.sort(logits, axis=-1)                       # ascending
    kidx = jnp.clip(v - topks, 0, v - 1)
    kth = jnp.take_along_axis(srt, kidx[..., None], axis=-1)[..., 0]
    masked = jnp.where((topks > 0)[..., None] & (logits < kth[..., None]),
                       _NEG_INF, logits)
    return masked / jnp.maximum(temps, 1e-6)[..., None]


V = 300       # no multiple of 256


def _logits(kind: str, shape):
    rng = np.random.RandomState(len(kind) * 7 + len(shape))
    x = rng.standard_normal(shape + (V,)).astype(np.float32) * 4
    if kind == "ties":
        # a few distinct values: every threshold is tied many times over
        x = np.round(x)
    elif kind == "zeros":
        x = np.round(x)
        x[..., ::3] = 0.0
        x[..., 1::6] = -0.0
    elif kind == "masked":
        x[..., rng.permutation(V)[:V // 2]] = _NEG_INF
        x[..., :2] = -np.inf
    elif kind == "constant":
        x[...] = 1.5
    elif kind == "wide":
        x = x * 1e30            # overflows to both infinities in places
    return x


KS = [0, 1, 2, 40, V - 1, V, V + 5, 10 * V]


@pytest.mark.parametrize("shape", [(6,), (3, 2)], ids=["TV", "BGV"])
@pytest.mark.parametrize("kind", ["plain", "ties", "zeros", "masked",
                                  "constant", "wide"])
@pytest.mark.parametrize("k", KS + ["mixed"])
def test_mask_and_scale_equals_the_sort_based_definition(kind, shape, k):
    logits = _logits(kind, shape)
    rows = int(np.prod(shape))
    if k == "mixed":
        topks = np.resize(np.asarray(KS + [-1], np.int32), rows)
    else:
        topks = np.full((rows,), k, np.int32)
    topks = topks.reshape(shape)
    temps = np.resize(np.asarray([0.0, 0.7, 1.0, 1.3], np.float32),
                      rows).reshape(shape)
    got = jax.jit(_mask_and_scale)(logits, temps, topks)
    want = jax.jit(reference_mask_and_scale)(logits, temps, topks)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k", [1, 2, 7, V - 1, V])
def test_kth_largest_is_the_sorted_rows_kth_value(k):
    x = _logits("ties", (5,))
    key = sortable(x)
    kth = jax.jit(kth_largest)(key, jnp.full((5,), k, jnp.int32))
    want = sortable(np.sort(x, axis=-1)[:, V - k])
    np.testing.assert_array_equal(np.asarray(kth), np.asarray(want))
    # the order of the keys is the order of the floats, -0.0 beside 0.0
    order = np.argsort(x[0], kind="stable")
    assert (np.diff(np.asarray(key)[0][order].astype(np.int64)) >= 0).all()
    assert int(sortable(jnp.float32(-0.0))) == int(sortable(jnp.float32(0.)))


def test_sample_with_every_row_greedy_is_argmax_and_draws_nothing(jaxpr_eqns):
    logits = _logits("ties", (6,))                  # ties: the first wins
    temps = np.zeros((6,), np.float32)
    topks = np.asarray([0, 1, 5, 0, V, 2], np.int32)
    want = np.argmax(logits, axis=-1)
    for seed in (0, 1):
        got = jax.jit(_sample)(logits, temps, topks,
                               jax.random.PRNGKey(seed))
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want)
    # the arm it takes holds no sort, no search and no random bits: the
    # key is an operand of the other arm alone
    closed = jax.make_jaxpr(_sample)(logits, temps, topks,
                                     jax.random.PRNGKey(0))
    conds = [e for e in closed.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    greedy_arm, draw_arm = conds[0].params["branches"]
    assert jaxpr_eqns(greedy_arm.jaxpr) == []       # hands the arg-max on
    assert {"random_bits", "cond"} <= {n for n, _ in
                                       jaxpr_eqns(draw_arm.jaxpr)}
    assert "sort" not in {n for n, _ in jaxpr_eqns(closed.jaxpr)}


@pytest.mark.parametrize("topks", [[0, 0, 0, 0], [0, 3, 0, 1]],
                         ids=["no-topk", "some-topk"])
def test_sample_draws_from_the_masked_rows_and_keeps_greedy_rows(topks):
    """Rows with a temperature draw from the reference's transformed
    logits under the call's key (what the engine did before it stopped
    sorting); greedy rows beside them are the arg-max."""
    logits = _logits("plain", (4,))
    temps = np.asarray([0.0, 0.9, 1.4, 0.9], np.float32)
    topks = np.asarray(topks, np.int32)
    key = jax.random.PRNGKey(5)
    got = np.asarray(jax.jit(_sample)(logits, temps, topks, key))
    scaled = reference_mask_and_scale(logits, temps, topks)
    drawn = np.asarray(jax.random.categorical(key, scaled, axis=-1))
    want = np.where(temps <= 0, np.argmax(logits, axis=-1), drawn)
    np.testing.assert_array_equal(got, want)
    if topks[3] == 1:
        assert got[3] == np.argmax(logits[3])       # top-1 is the arg-max
