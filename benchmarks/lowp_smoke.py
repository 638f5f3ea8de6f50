"""CPU-mesh relaxed-parity smoke: loss-curve A-B + comm-byte contract.

CPU functional test: JAX_PLATFORMS=cpu in parent and children; no chip number.

Runs (in a SUBPROCESS, so the 8-virtual-device XLA flags are set before
jax initializes — same trick as overlap_smoke) the relaxed parity
tier's acceptance ladder on the tiny config:

- **dp2×tp2(+sp), 50 steps** — quantized tp reduces + true chunked
  collective matmul vs the bitwise tier; the loss-curve guard
  (parallel/lowp/guard.py) must accept the trajectory.
- **zero1 dp8, 50 steps** — quantized ZeRO-1 param reassembly; guard
  must accept AND the comm ledger must show ≥2× fewer collective
  payload bytes on the quantized buckets.
- **dp2×pp2 manual schedule, 12 steps** — quantized GRADIENT buckets
  (the bucketed psum path only the manual schedule exercises); ≥2×
  payload reduction asserted here too.
- **partial-sync schedule, dp2×tp2+sp, 50 steps** — the partially-
  synchronized activation schedule (parallel/lowp/syncpolicy.py) at
  ``periodic:2``: the loss-curve guard must accept, the ledger must
  show the scheduled tp sites executing ≥1.8× fewer collectives per
  step than the full-schedule relaxed rung, and the falsifiability
  arm (``none`` — every sync skipped) must REJECT.
- **bitwise is byte-identical** — a step built with parity=BITWISE
  must produce bit-identical losses to a step built with parity
  unset, proving zero lowp code executes on the default tier.

Mirrors the overlap_smoke contract in run_all.py: a failure is
recorded as data, never a reason to lose the other benches. The full
reports (loss trajectories, divergence, payload bytes) land in the
JSON so the relaxed tier's drift is a trajectory the next round reads,
not a boolean.

  python -m benchmarks.lowp_smoke          # prints the JSON record
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD = r"""
import json
from __graft_entry__ import _force_cpu_devices
_force_cpu_devices(8)
import jax, jax.numpy as jnp
from hadoop_tpu.models import get_config
from hadoop_tpu.parallel import MeshPlan, make_mesh
from hadoop_tpu.parallel.lowp import BITWISE_PARITY, RELAXED_PARITY
from hadoop_tpu.parallel.lowp.guard import run_loss_ab
from hadoop_tpu.parallel.train import (init_sharded, make_data_sharding,
                                       make_train_step)

out = {"steps": 50}

# ---- dp2×tp2(+sp): quantized tp reduces + chunked collective matmul
rep = run_loss_ab(MeshPlan(dp=2, tp=2, megatron_sp=True), steps=50)
out["dp2xtp2"] = {k: rep[k] for k in
                  ("accepted", "max_rel_div", "mean_rel_div",
                   "final_rel_div", "relaxed_first", "relaxed_final",
                   "bitwise_final", "comm", "codec") if k in rep}
out["dp2xtp2"]["losses_relaxed"] = rep.get("relaxed_losses")
out["dp2xtp2"]["losses_bitwise"] = rep.get("bitwise_losses")
assert rep.get("accepted"), f"dp2xtp2 guard rejected: {rep.get('reason')}"
# the partial-sync rungs below A-B the SAME plan/steps/seed — reuse
# this rung's bitwise twin instead of re-training it twice more
bit_tp = rep.get("bitwise_losses")

# ---- zero1 dp8: quantized ZeRO-1 reassembly, ≥2× payload contract
rep = run_loss_ab(MeshPlan(dp=8), zero1=True, steps=50)
out["zero1_dp8"] = {k: rep[k] for k in
                    ("accepted", "max_rel_div", "final_rel_div",
                     "relaxed_final", "bitwise_final", "comm") if k in rep}
assert rep.get("accepted"), f"zero1 guard rejected: {rep.get('reason')}"
ratio = rep["comm"].get("ratio")
assert ratio is not None and ratio >= 2.0, \
    f"zero1 quantized payload reduction {ratio} < 2x"

# ---- dp2×pp2: quantized gradient buckets on the manual schedule
rep = run_loss_ab(MeshPlan(dp=2, pp=2), steps=12, n_microbatches=2)
out["dp2xpp2"] = {k: rep[k] for k in
                  ("accepted", "max_rel_div", "final_rel_div",
                   "relaxed_final", "bitwise_final", "comm") if k in rep}
assert rep.get("accepted"), f"pp guard rejected: {rep.get('reason')}"
ratio = rep["comm"].get("ratio")
assert ratio is not None and ratio >= 2.0, \
    f"grad-bucket quantized payload reduction {ratio} < 2x"

# ---- partial-sync schedule (syncpolicy.py): periodic:2 on dp2×tp2+sp
from hadoop_tpu.parallel.lowp import ParityConfig


def _tp_site_execs(comm):
    return sum(v["executions"] for s, v in comm.get("per_site", {}).items()
               if s in ("tp.psum", "tp.scatter"))


full_execs = _tp_site_execs(out["dp2xtp2"]["comm"])
rep = run_loss_ab(MeshPlan(dp=2, tp=2, megatron_sp=True), steps=50,
                  bitwise_losses=bit_tp,
                  parity=ParityConfig(tier="relaxed",
                                      relaxed_sync="periodic:2"))
sync_execs = _tp_site_execs(rep["comm"])
exec_ratio = full_execs / max(sync_execs, 1)
out["partial_sync"] = {
    "schedule": "periodic:2", "mode": "skip",
    "guard_accepted": int(bool(rep.get("accepted"))),
    "max_rel_div": rep.get("max_rel_div"),
    "relaxed_final": rep.get("relaxed_final"),
    "tp_execs_full_per_step": full_execs,
    "tp_execs_sync_per_step": sync_execs,
    "skipped_per_step": full_execs - sync_execs,
    "exec_ratio": round(exec_ratio, 3),
    "comm": rep.get("comm")}
assert rep.get("accepted"), \
    f"partial-sync guard rejected: {rep.get('reason')}"
assert exec_ratio >= 1.8, \
    f"periodic:2 cut tp collective executions only {exec_ratio}x " \
    f"(full={full_execs}/step sync={sync_execs}/step)"
# falsifiability: a schedule that skips EVERY sync must reject — if it
# does not, the guard is not measuring anything
rep_none = run_loss_ab(MeshPlan(dp=2, tp=2, megatron_sp=True), steps=50,
                       bitwise_losses=bit_tp,
                       parity=ParityConfig(tier="relaxed",
                                           relaxed_sync="none"))
out["partial_sync"]["none_rejected"] = int(not rep_none.get("accepted"))
out["partial_sync"]["none_reason"] = rep_none.get("reason")
assert not rep_none.get("accepted"), \
    "all-layers-skipped schedule was ACCEPTED — the falsifiability " \
    "arm failed, the guard cannot be trusted"

# ---- the bitwise tier is byte-identical to parity-unset
cfg = get_config("tiny")
plan = MeshPlan(dp=2, tp=2, megatron_sp=True)
mesh = make_mesh(plan)
ds = make_data_sharding(mesh)
tokens = jax.device_put(
    jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                       cfg.vocab_size, dtype=jnp.int32), ds)
targets = jax.device_put(jnp.roll(tokens, -1, axis=1), ds)
losses = {}
for label, par in (("unset", None), ("bitwise", BITWISE_PARITY)):
    step = make_train_step(cfg, plan, mesh, donate=False, parity=par)
    params, opt = init_sharded(jax.random.PRNGKey(0), cfg, plan, mesh)
    ls = []
    for _ in range(3):
        params, opt, m = step(params, opt, tokens, targets)
        ls.append(float(m["loss"]))
    losses[label] = ls
assert losses["unset"] == losses["bitwise"], \
    f"BITWISE parity is not byte-identical: {losses}"
out["bitwise_bit_identical"] = True
print("LOWP_SMOKE " + json.dumps(out))
"""


def run(timeout_s: float = 900.0) -> dict:
    """The relaxed-rung record, raising on failure (run_all wraps)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the child sets its own device count
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=timeout_s, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith("LOWP_SMOKE "):
            return json.loads(line[len("LOWP_SMOKE "):])
    raise RuntimeError(
        f"lowp smoke produced no record (rc={proc.returncode}): "
        f"{proc.stderr.strip()[-2000:]}")


def main() -> None:
    print(json.dumps(run()))


if __name__ == "__main__":
    main()
