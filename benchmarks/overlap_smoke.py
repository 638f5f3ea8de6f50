"""CPU-mesh overlap smoke: A-B step parity + async-save blocking time.

CPU functional test: JAX_PLATFORMS=cpu in parent and children; no chip number.

Runs (in a SUBPROCESS, so the 8-virtual-device XLA flags are set before
jax initializes — same trick as the multichip dryrun) a dp2×tp2 train
step with the communication-overlap pass on and off and asserts the
losses are bit-identical, then measures how long a checkpoint blocks
the caller sync vs async. Mirrors the serving smoke's contract in
run_all.py: a failure is recorded as data, never a reason to lose the
other benches.

  python -m benchmarks.overlap_smoke          # prints the JSON record
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD = r"""
import json, time
from __graft_entry__ import _force_cpu_devices
_force_cpu_devices(8)
import jax, jax.numpy as jnp
from hadoop_tpu.models import get_config
from hadoop_tpu.parallel import MeshPlan, make_mesh
from hadoop_tpu.parallel.overlap import DEFAULT_OVERLAP, OVERLAP_OFF
from hadoop_tpu.parallel.train import (init_sharded, make_data_sharding,
                                       make_train_step)

cfg = get_config("tiny")
plan = MeshPlan(dp=2, tp=2, megatron_sp=True)
mesh = make_mesh(plan)
ds = make_data_sharding(mesh)
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                            cfg.vocab_size, dtype=jnp.int32)
tokens = jax.device_put(tokens, ds)
targets = jax.device_put(jnp.roll(tokens, -1, axis=1), ds)
N_STEPS = 3
out = {"plan": "dp2xtp2+sp", "steps": N_STEPS}
losses = {}
for label, ov in (("on", DEFAULT_OVERLAP), ("off", OVERLAP_OFF)):
    step = make_train_step(cfg, plan, mesh, donate=False, overlap=ov)
    params, opt = init_sharded(jax.random.PRNGKey(0), cfg, plan, mesh)
    ls, t0 = [], time.perf_counter()
    for _ in range(N_STEPS):
        params, opt, m = step(params, opt, tokens, targets)
        ls.append(float(m["loss"]))
    out[f"wall_s_{label}"] = round(time.perf_counter() - t0, 3)
    losses[label] = ls
out["losses"] = losses["on"]
assert losses["on"] == losses["off"], \
    f"overlap parity broken: on={losses['on']} off={losses['off']}"
out["parity"] = "bit-exact"

# async-save blocking time on the same state
import tempfile, shutil
from hadoop_tpu.fs import FileSystem
from hadoop_tpu.parallel.checkpoint import (AsyncCheckpointWriter,
                                            snapshot_tree, write_snapshot)
td = tempfile.mkdtemp(prefix="overlap-smoke-")
try:
    fs = FileSystem.get(f"file://{td}")
    t0 = time.perf_counter()
    snap = snapshot_tree({"params": params, "opt": opt})
    out["ckpt_blocking_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
    w = AsyncCheckpointWriter()
    t0 = time.perf_counter()
    w.submit(lambda: write_snapshot(fs, f"{td}/c", 1, snap))
    w.wait()
    out["ckpt_write_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
finally:
    shutil.rmtree(td, ignore_errors=True)
print("OVERLAP_SMOKE " + json.dumps(out))
"""


def run(timeout_s: float = 600.0) -> dict:
    """The A-B parity + ckpt record, raising on failure (run_all wraps)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the child sets its own device count
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=timeout_s, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith("OVERLAP_SMOKE "):
            return json.loads(line[len("OVERLAP_SMOKE "):])
    raise RuntimeError(
        f"overlap smoke produced no record (rc={proc.returncode}): "
        f"{proc.stderr.strip()[-2000:]}")


def main() -> None:
    print(json.dumps(run()))


if __name__ == "__main__":
    main()
