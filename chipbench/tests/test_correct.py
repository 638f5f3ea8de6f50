"""What decides ``correct``, at a size a test run can hold: the control
(the reference one precision down, float8 for bfloat16) comes out not
correct, and so does a run whose timed path is broken underneath. These
skip the harness's look for a chip and drive the rest of a run."""

import pytest

from chipbench import harness, kinds

REHEARSAL = "chipbench/tests/rehearsal/BENCHMARK.json"
CELLS = ["tiny.train", "tiny.serve-chat", "tiny.serve-batch"]

def drive(workload, seed, **kw):
    import time

    import jax
    cell = harness.load_cell(workload, REHEARSAL)
    run = kinds.driver(cell.traffic["kind"])
    return cell, run(cell, jax.devices()[:cell.chips], seed=seed,
                     seconds=1.0, traced=False, t_start=time.monotonic(),
                     **kw)

@pytest.fixture(autouse=True)
def from_the_root(monkeypatch):
    monkeypatch.chdir(harness.ROOT)

@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("workload", CELLS)
def test_the_control_comes_out_not_correct(workload, seed):
    cell, out = drive(workload, seed, control="fp8")
    assert out.correct, out.checks
    limits = cell.harness["limits"]
    control = out.obs["control"]["fp8"]
    over = [k for k in limits if not control[k] <= limits[k]]
    assert over, (control, limits)

def test_a_step_that_returns_its_state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp
    from hadoop_tpu.parallel import train
    real = train.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)

        def unchanged(params, opt, tokens, targets):
            copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa
            _, _, metrics = step(copy(params), copy(opt), tokens, targets)
            return params, opt, metrics
        return unchanged
    monkeypatch.setattr(train, "make_train_step", broken)
    _, out = drive("tiny.train", 5)
    assert not out.correct
    assert out.checks["grad_gap"][0] == pytest.approx(1.0)
    assert out.checks["delta_gap"][0] == pytest.approx(1.0)

def test_half_of_the_batch_left_out(monkeypatch):
    from hadoop_tpu.parallel import train
    real = train.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)

        def half(params, opt, tokens, targets):
            n = tokens.shape[0] // 2
            return step(params, opt, tokens[:n], targets[:n])
        return half
    monkeypatch.setattr(train, "make_train_step", broken)
    _, out = drive("tiny.train", 5)
    assert not out.correct
    failing = [k for k, (v, lim) in out.checks.items() if not v <= lim]
    assert "grad_gap" in failing or "delta_gap" in failing

@pytest.mark.parametrize("workload", ["tiny.serve-chat", "tiny.serve-batch"])
def test_a_token_altered_where_it_is_produced(workload, monkeypatch):
    from hadoop_tpu.serving.engine import GenRequest
    real = GenRequest._deliver
    monkeypatch.setattr(GenRequest, "_deliver",
                        lambda self, token: real(self, token ^ 1))
    _, out = drive(workload, 5)
    assert not out.correct
    assert out.checks["answers_cut_short"][0] == 0

def test_an_answer_cut_short(monkeypatch):
    from hadoop_tpu.serving.engine import DecodeEngine
    real = DecodeEngine.submit

    def fewer(self, prompt, *a, **kw):
        handle = real(self, prompt, *a, **kw)
        sp = handle.sampling
        if sp.max_new_tokens > 2:
            sp.max_new_tokens -= 1
        return handle
    monkeypatch.setattr(DecodeEngine, "submit", fewer)
    _, out = drive("tiny.serve-chat", 5)
    assert not out.correct
    assert out.checks["answers_cut_short"][0] > 0
