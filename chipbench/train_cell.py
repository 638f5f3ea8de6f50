"""Driver of the ``packed`` kind: training steps back to back through
``hadoop_tpu.parallel.train.make_train_step`` as ``Trainer`` builds it.

Set-up builds ONE object — the compiled step with its state — drives it
from the seed through its first three steps on rows that all differ, and
hands that same object to the window. The reference follows those steps
after the window has closed and the program's state is freed.
"""

from __future__ import annotations

import time

from chipbench import compare, harness, traffic
from chipbench import weights as W

REF_STEPS = 3


def _flat(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(p): float(v) for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def run(cell, devices, *, seed, seconds, traced, t_start, control=None,
        fault=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from chipbench import reference
    from hadoop_tpu.parallel.mesh import MeshPlan, make_mesh, param_specs
    from hadoop_tpu.parallel.optimizer import AdamWState
    from hadoop_tpu.parallel.train import (make_data_sharding,
                                           make_train_step)

    compiles = harness.CompileCounter()
    family = cell.family
    model, tr = cell.model, cell.traffic
    cfg = family.model_config(model, cell.harness)
    plan = MeshPlan(**cell.harness.get("mesh_plan", {}))
    mesh = make_mesh(plan, devices)
    step = make_train_step(cfg, plan, mesh, **cell.harness["train_step"])
    shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                   param_specs(cfg, plan))
    dtype = jnp.dtype(cfg.dtype)
    key = W.seed_key(seed)

    @jax.jit
    def init(key):
        params = jax.lax.with_sharding_constraint(
            family.make_params(model, key, dtype), shard)
        zeros = lambda: jax.lax.with_sharding_constraint(   # noqa: E731
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params), shard)
        return params, AdamWState(jnp.zeros((), jnp.int32), zeros(),
                                  zeros())

    params, opt = init(key)
    rows = tr["batch_per_chip"] * len(devices)
    host_batches = traffic.packed_batches(tr, cfg.vocab_size, seed,
                                          rows)
    ds = make_data_sharding(mesh)
    batches = [(jax.device_put(t, ds), jax.device_put(g, ds))
               for t, g in host_batches]

    # ---- the first steps, through the window's own call and feed
    leaf_norms = jax.jit(reference.leaf_norms)
    prog = {"losses": []}
    for i in range(REF_STEPS):
        params, opt, metrics = step(params, opt, *batches[i])
        prog["losses"].append(metrics["loss"])
        if i == 0:
            mu1 = leaf_norms(opt.mu)
    prog["losses"] = [float(x) for x in prog["losses"]]
    prog["grad1"] = {k: v / (1.0 - reference.B1)
                     for k, v in _flat(mu1).items()}
    prog["delta"] = _program_delta(params, family, model, key, dtype)
    jax.block_until_ready((params, opt))
    setup_s = time.monotonic() - t_start

    # ---- the window: steps back to back, at most two in flight
    tracer = harness.Tracer(traced)
    c0 = compiles.count
    t0 = time.monotonic()
    n, prev = 0, None
    while True:
        tracer.due(t0 + seconds - time.monotonic())
        params, opt, metrics = step(params, opt,
                                    *batches[(REF_STEPS + n) % len(batches)])
        n += 1
        if prev is not None:
            prev.block_until_ready()
            if time.monotonic() - t0 >= seconds:
                break
        prev = metrics["loss"]
    jax.block_until_ready((params, opt, metrics))
    window_s = time.monotonic() - t0
    tracer.stop()
    in_window = compiles.count - c0
    last_loss = float(metrics["loss"])
    peak = harness.memory_peak_bytes(devices)
    del params, opt, metrics, batches, prev
    trace = tracer.reduce(devices)

    # ---- the reference follows the first steps
    ref_batches = [(jnp.asarray(t), jnp.asarray(g))
                   for t, g in host_batches[:REF_STEPS]]
    ref = _follow(family, model, seed, ref_batches, None)
    numbers = compare.training(prog, ref)
    _leaf_table(prog, ref)
    limits = cell.harness["limits"]
    checks = {k: [numbers[k], limits[k]] for k in limits}
    finite = 0.0 if last_loss == last_loss and abs(last_loss) < 1e30 else 1.0
    checks["window_loss_not_finite"] = [finite, 0.0]
    obs = {
        "setup_s": setup_s, "window_s": window_s, "steps": n,
        "tokens": n * rows * tr["seq_len"],
        "tokens_per_chip": n * rows * tr["seq_len"] / len(devices),
        "model_flops": n * rows * tr["seq_len"]
        * family.train_flops_per_token(model, tr["seq_len"]),
        "compiles_in_window": in_window,
        "memory_peak_gb": peak / 1e9 if peak else None,
        "losses": prog["losses"], "last_loss": last_loss,
    }
    if control:
        obs["control"] = {
            q: compare.training(_follow(family, model, seed, ref_batches, q),
                                ref)
            for q in control.split(",")}
    if fault == "half":     # the reference in the program's place, with
        # half of each row left out of the loss
        obs["fault_half"] = compare.training(
            _follow(family, model, seed, ref_batches, None, keep=0.5), ref)
    return harness.Outcome(obs, checks, attempted=n, failed=0,
                           devices=devices, trace=trace,
                           memory_peak_bytes=peak)


def _leaf_table(prog: dict, ref: dict) -> None:
    import sys
    print("losses program", prog["losses"], "reference", ref["losses"],
          file=sys.stderr)
    for k in ref["grad1"]:
        print(f"leaf {k}: grad1 {prog['grad1'][k]:.6g} / "
              f"{ref['grad1'][k]:.6g}  change {prog['delta'][k]:.6g} / "
              f"{ref['delta'][k]:.6g}  (program / reference)",
              file=sys.stderr)


def _follow(family, model, seed, batches, quant, keep=1.0) -> dict:
    losses, grad1, delta = family.follow(model, seed, batches, quant, keep)
    return {"losses": losses, "grad1": _flat(grad1), "delta": _flat(delta)}


def _program_delta(params, family, model, key, dtype) -> dict:
    """Per-leaf norm of what the first steps changed: each leaf against
    its value at the seed, regenerated alone so nothing large is held.
    The regenerated leaf is the OUTPUT of one program and the input of
    the next: inside one program the TPU compiler drops the rounding to
    bfloat16 before a subtraction in float32 (excess precision), and the
    difference then carries the rounding error of every element — 3% of
    the change's norm (my chip runs, PR 24)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap(a, b):
        return jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32))))

    out = {}
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for (path, leaf), names in zip(leaves, family.leaf_paths(model)):
        regen = jax.jit(lambda key, names=names: family.make_leaf(
            model, key, names, dtype))
        out[jax.tree_util.keystr(path)] = float(gap(leaf, regen(key)))
    return out
