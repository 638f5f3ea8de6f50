"""What the benchmark's compiled programs are made of, without a chip: the
train step of ``mistral-7b.train-4k`` and both shapes of the serving
step of ``mistral-7b.serve-chat`` (decode-only and fused with a prefill
chunk), compiled at the cells' own sizes for a *described* TPU v5e
(``on-chip-measurement`` §2.3), one JSON line each: HLO operations,
fusions, custom calls by target name, and ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 -m chipbench.tests.compiled_programs > here.jsonl
    (cd <other checkout> && JAX_PLATFORMS=cpu python3 -m chipbench.tests.compiled_programs) > there.jsonl
    diff here.jsonl there.jsonl

A change that should be metadata only (a ``jax.named_scope``, a kernel's
``name=``, a module's name) leaves every number the same and changes
``module`` and ``kernels`` alone. Nothing runs: this says nothing about
time. Takes about two minutes and 12 GB of host memory (the engine
allocates its KV pool on the CPU). ``jax.default_backend()`` still says
"cpu" here, so the program's own choice of the Pallas attention kernel
(``ops.attention``) is steered from this script while the train step is
traced; the serving step asks no such question.
"""

from __future__ import annotations

import json
import os
import re
import sys
from collections import Counter
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

_OP = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = .*?\s([\w\-]+)\(", re.M)
_KERNEL = re.compile(r'^\s*%?([\w.\-]+?)(?:\.\d+)? = .*'
                     r'custom_call_target="tpu_custom_call"', re.M)


def describe(tag: str, compiled) -> dict:
    text = compiled.as_text()
    ops = Counter(_OP.findall(text))
    mem = compiled.memory_analysis()
    return {
        "program": tag,
        "module": re.search(r"^HloModule ([\w.\-]+)", text, re.M).group(1),
        "hlo_ops": sum(ops.values()), "fusions": ops["fusion"],
        "custom_calls": ops["custom-call"], "whiles": ops["while"],
        "kernels": sorted(Counter(_KERNEL.findall(text)).items()),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
    }


def _abstract(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def train_step(chip):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from chipbench import harness
    from chipbench import weights as W
    from hadoop_tpu.parallel.mesh import MeshPlan, make_mesh, param_specs
    from hadoop_tpu.parallel.optimizer import AdamWState
    from hadoop_tpu.parallel.train import make_data_sharding, make_train_step

    cell = harness.load_cell("mistral-7b.train-4k")
    family = cell.family
    cfg = family.model_config(cell.model, cell.harness)
    plan = MeshPlan(**cell.harness.get("mesh_plan", {}))
    mesh = make_mesh(plan, [chip])
    step = make_train_step(cfg, plan, mesh, **cell.harness["train_step"])
    shapes = jax.eval_shape(
        lambda k: family.make_params(cell.model, k, jnp.dtype(cfg.dtype)),
        W.seed_key(1))
    params = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, param_specs(cfg, plan))
    f32 = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32,
                                       sharding=p.sharding), params)
    count = jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(
                                     mesh, jax.sharding.PartitionSpec()))
    tr = cell.traffic
    row = jax.ShapeDtypeStruct((tr["batch_per_chip"], tr["seq_len"]),
                               jnp.int32, sharding=make_data_sharding(mesh))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = step.lower(params, AdamWState(count, f32, f32), row, row)
    return lowered.compile()


def serving_steps(chip):
    """(decode-only, fused) of the engine ``serve_cell.build_replica``
    builds, from an engine of the cell's size made here on the CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, serve_cell
    from chipbench import weights as W

    cell = harness.load_cell("mistral-7b.serve-chat")
    family = cell.family
    cfg = family.model_config(cell.model, cell.harness)
    params = jax.jit(lambda k: family.make_params(
        cell.model, k, jnp.dtype(cfg.dtype)))(W.seed_key(1))
    engine, server = serve_cell.build_replica(cell, params, cfg)
    one = SingleDeviceSharding(chip)
    args = [_abstract(t, one) for t in (
        engine.params, engine._kp, engine._vp, engine._dstate,
        engine._dz_drafts, engine._dz_lens)]
    c = engine.prefill_chunk
    chunk = (jax.ShapeDtypeStruct((c,), np.int32, sharding=one),
             jax.ShapeDtypeStruct((3,), np.int32, sharding=one))
    return (engine._step_fn.lower(*args, None).compile(),
            engine._step_fn.lower(*args, chunk).compile())


def main() -> int:
    import jax
    from jax.experimental import topologies
    # what is compiled here cannot be read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = topo.devices[0]
    print(json.dumps(describe("train_step", train_step(chip))), flush=True)
    decode, fused = serving_steps(chip)
    print(json.dumps(describe("_step_impl.decode", decode)), flush=True)
    print(json.dumps(describe("_step_impl.fused", fused)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
