"""Training flight-recorder smoke: a straggler rank, caught and cleared.

CPU functional test: JAX_PLATFORMS=cpu in parent and children; no chip number.

The acceptance loop for the per-rank trainer telemetry plane, end to
end over real subprocess ranks:

  1. four worker subprocesses each run a real jitted comm-bearing step
     (bucketed psum over a 2-virtual-device mesh — the overlap pass's
     actual entry point, so the runtime comm ledger records REAL
     trace-time bytes and REAL dispatch walls) and publish step anatomy
     through the real ``TrainerStepMetrics`` + ``TrainerTelemetry``
     chassis (``/ws/v1/trainer``, ``/prom``, ``/ws/v1/traces``);
  2. rank 2 gets an INJECTED per-step latency (a flag file the parent
     controls — the detection decision reads only the reported means,
     and ``obs.doctor.slow.floor.ms=50`` sits far above single-box
     noise);
  3. the fleet doctor must flag exactly rank 2 at
     ``/ws/v1/fleet/doctor`` within 3 observation windows, and must
     UNFLAG it within the hysteresis history once the injection stops;
  4. the slow rank's ``htpu_comm_seconds`` histogram must show the
     collective tail (site mean >= 2x the healthy ranks') with a
     bucket exemplar whose trace id resolves through the doctor into
     an assembled trace.

Contract failures are RECORDED in the returned dict (``failures``),
not raised — run_all keeps its prior bench results either way.

Fault injection rides ``hadoop_tpu.testing.faults`` (the flag-file
API extracted from this smoke's original ad-hoc slow-file): the parent
arms per-rank kill/delay-ms/hang flags, workers call ``apply_faults``
once per step.

The ELASTIC leg (``run_elastic`` / ``--elastic``) closes the loop the
recorder only observes: a subprocess child trains a real zero1 dp=4
job, a rank is slowed (delay-ms flag → demote: protective checkpoint)
then KILLED (kill flag → evict), and the elastic controller reshards
onto dp=3 via reshard-on-restore — finishing with the loss-curve A-B
guard green against an uninterrupted dp=4 twin and strictly fewer
lost steps than the restart-from-checkpoint baseline.

  python -m benchmarks.flight_smoke             # recorder leg
  python -m benchmarks.flight_smoke --elastic   # elastic leg
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile
import time

N_RANKS = 4
SLOW_RANK = 2
DELAY_MS = 300
STEP_PACE = 0.02


# ---------------------------------------------------------------- worker

def worker_main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--port-file", required=True)
    ap.add_argument("--faults-dir", required=True)
    ap.add_argument("--stop-file", required=True)
    ap.add_argument("--max-seconds", type=float, default=120.0)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from hadoop_tpu.obs.comm import comm_runtime
    from hadoop_tpu.obs.trainer import (TrainerStepMetrics,
                                        TrainerTelemetry)
    from hadoop_tpu.parallel.overlap import bucketed_psum
    from hadoop_tpu.testing.faults import apply_faults
    from hadoop_tpu.tracing.tracer import global_tracer

    tracer = global_tracer()
    tracer.set_sample_rate(1.0)
    metrics = TrainerStepMetrics(rank=args.rank)
    telemetry = TrainerTelemetry(rank=args.rank, job="flight-smoke",
                                 metrics=metrics)
    with open(args.port_file + ".tmp", "w") as f:
        f.write(str(telemetry.port))
    os.replace(args.port_file + ".tmp", args.port_file)

    # a real comm-bearing step: matmul "work" + the overlap pass's
    # bucketed gradient psum over the 2-device mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    tree = {"w": jnp.ones((32, 32)), "b": jnp.ones((64,))}
    axes = {"w": ("dp",), "b": ("dp",)}

    def body(t):
        g = {"w": t["w"] @ t["w"].T * 1e-3, "b": t["b"] * 0.5}
        return bucketed_psum(g, axes, 1 << 20)

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                 out_specs=P()))
    rt = comm_runtime()
    deadline = time.monotonic() + args.max_seconds
    while time.monotonic() < deadline and \
            not os.path.exists(args.stop_file):
        t0 = time.monotonic()
        with tracer.span("trainer.step") as sp:
            sp.add_kv("rank", str(args.rank))
            with rt.step("trainer.step"):
                out = step(tree)
                jax.block_until_ready(out)
                # the injection seam: kill / delay-ms / hang flags the
                # parent arms (hadoop_tpu/testing/faults.py)
                apply_faults(args.faults_dir, args.rank)
        wall = time.monotonic() - t0
        metrics.steps.incr()
        metrics.step_wall.add(wall)
        metrics.step_wall_hist.add(wall)
        time.sleep(STEP_PACE)
    telemetry.close()
    return 0


# ---------------------------------------------------------------- parent

def run(quick: bool = False) -> dict:
    from hadoop_tpu.conf import Configuration
    from hadoop_tpu.http import http_get
    from hadoop_tpu.obs.doctor import FleetDoctor

    # quick: shorter observation windows + fewer recovery polls. The
    # rank count stays 4 — the detector's min-peers=3 needs a
    # population to be an outlier among, so that is the floor.
    window_s = 0.6 if quick else 1.0
    recovery_polls = 6 if quick else 8
    out: dict = {"failures": []}

    def check(ok: bool, what: str) -> None:
        if not ok:
            out["failures"].append(what)

    from hadoop_tpu.testing.faults import FaultInjector

    base = tempfile.mkdtemp(prefix="flight-smoke-")
    faults_dir = os.path.join(base, "faults")
    stop_file = os.path.join(base, "stop")
    inj = FaultInjector(faults_dir)
    inj.inject(SLOW_RANK, "delay-ms", str(DELAY_MS))
    procs = []
    ports = {}
    doctor = None
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)   # workers set their own device count
        for r in range(N_RANKS):
            pf = os.path.join(base, f"port-{r}")
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "benchmarks.flight_smoke",
                 "--worker", "--rank", str(r), "--port-file", pf,
                 "--faults-dir", faults_dir, "--stop-file", stop_file],
                env=env, cwd=os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__)))))
        deadline = time.monotonic() + 90.0
        for r in range(N_RANKS):
            pf = os.path.join(base, f"port-{r}")
            while not os.path.exists(pf):
                if time.monotonic() > deadline:
                    raise RuntimeError(f"rank {r} never came up")
                if procs[r].poll() is not None:
                    raise RuntimeError(
                        f"rank {r} exited rc={procs[r].returncode}")
                time.sleep(0.2)
            with open(pf) as f:
                ports[r] = int(f.read())
        slow_name = f"rank-{SLOW_RANK}"
        conf = Configuration(load_defaults=False)
        conf.set("obs.doctor.endpoints", ",".join(
            f"rank-{r}=127.0.0.1:{ports[r]}" for r in range(N_RANKS)))
        # the absolute floor sits far above single-box noise: only the
        # injected latency can clear it (the doctor_smoke precedent)
        conf.set("obs.doctor.slow.floor.ms", "50")
        doctor = FleetDoctor(conf)
        doctor.init(conf)
        doctor.start()
        # first poll establishes the cumulative baseline (no diff yet)
        doctor.poll_once()
        time.sleep(window_s)
        windows = 0
        flagged: list = []
        for windows in range(1, 4):
            time.sleep(window_s)
            report = doctor.poll_once()
            flagged = sorted(report["trainers"]["flagged"])
            if flagged == [slow_name]:
                break
        out["windows_to_flag"] = windows
        out["flagged"] = flagged
        check(flagged == [slow_name],
              f"flagged {flagged} != injected-slow [{slow_name}]")
        ranks = report["trainers"]["ranks"]
        check(len(ranks) == N_RANKS and
              all(r.get("ok") for r in ranks.values()),
              f"roster incomplete or unhealthy: {ranks}")
        # -------- recovery: stop the injection, hysteresis must clear
        inj.clear(SLOW_RANK, "delay-ms")
        recovered_in = None
        for w in range(1, recovery_polls):
            time.sleep(window_s)
            report = doctor.poll_once()
            if not report["trainers"]["flagged"]:
                recovered_in = w
                break
        out["windows_to_recover"] = recovered_in
        check(recovered_in is not None,
              "slow rank never unflagged after the injection stopped")
        # -------- comm ledger: the slow rank's collective tail
        means = {}
        proms = {}
        for r in range(N_RANKS):
            text = http_get("127.0.0.1", ports[r], "/prom",
                            5.0).decode()
            proms[r] = text
            m = re.search(
                r'htpu_comm_seconds_sum\{[^}]*site="bucket.psum"[^}]*\} '
                r'([0-9.e+-]+)', text)
            c = re.search(
                r'htpu_comm_seconds_count\{[^}]*site="bucket.psum"'
                r'[^}]*\} ([0-9.e+-]+)', text)
            if m and c and float(c.group(1)) > 0:
                means[r] = float(m.group(1)) / float(c.group(1))
        out["comm_means_ms"] = {r: round(v * 1e3, 2)
                                for r, v in means.items()}
        healthy = [v for r, v in means.items() if r != SLOW_RANK]
        check(len(means) == N_RANKS, f"comm histograms missing: {means}")
        check(bool(healthy) and SLOW_RANK in means and
              means[SLOW_RANK] >= 2.0 * max(healthy),
              f"slow rank's comm tail not visible: {means}")
        # -------- exemplar: a slow comm bucket resolves to a trace
        ex = re.search(
            r'htpu_comm_seconds_bucket\{[^}]*\} \d+ '
            r'# \{trace_id="([0-9a-f]+)"\}', proms[SLOW_RANK])
        check(ex is not None, "no exemplar on the slow rank's "
                              "htpu_comm_seconds buckets")
        if ex is not None:
            doctor.poll_once()        # pull the rank's span ring
            status, body = 0, b""
            try:
                body = http_get("127.0.0.1", doctor.port,
                                f"/ws/v1/fleet/traces/{ex.group(1)}",
                                5.0)
                status = 200
            except IOError:
                pass
            check(status == 200, "exemplar trace did not resolve "
                                 "through the doctor")
            if status == 200:
                tree = json.loads(body)
                out["exemplar_spans"] = tree.get("num_spans")
                check(tree.get("num_spans", 0) >= 1,
                      "assembled exemplar trace is empty")
    except Exception as e:  # noqa: BLE001 — smoke harness failure is a
        # recorded data point for the trajectory, never a crash
        out["failures"].append(f"{type(e).__name__}: {e}")
    finally:
        try:
            with open(stop_file, "w") as f:
                f.write("1")
        except OSError:
            pass
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
        if doctor is not None:
            doctor.stop()
        import shutil
        shutil.rmtree(base, ignore_errors=True)
    out["ok"] = not out["failures"]
    return out


# ------------------------------------------------------------ elastic leg

def _elastic_body() -> dict:
    """The elastic acceptance loop, in a process that already holds an
    8-virtual-device CPU mesh and vma-tracking jax.

    Two arms over the same token stream, tiny config, global batch 12:

    - reference: an uninterrupted zero1 dp=4 run of 36 steps;
    - elastic: the same job wired to an ElasticController. Rank 2 is
      slowed via the delay-ms flag at step 22 (→ demote: protective
      checkpoint at the next streak threshold) and KILLED via the kill
      flag at step 28 (→ evict: fence, shrink to the largest healthy
      sub-mesh dp=3 — non-power-of-two — reshard-on-restore from the
      protective snapshot, re-run the lost steps).

    The doctor FEED is scripted from the armed fault flags (the real
    FleetDoctor's detection path has its own leg above — this leg
    proves the ACTUATION half end to end): flags → trainer verdicts in
    the exact ``/ws/v1/fleet/doctor`` trainers shape the controller
    polls in production.

    Green means: loss-curve A-B guard ACCEPTED (elastic curve vs the
    uninterrupted twin, per absolute step index) and strictly fewer
    lost steps than restart-from-checkpoint (which would resume at the
    last INTERVAL save; the demote's protective snapshot is fresher).
    """
    import shutil

    import numpy as np

    from hadoop_tpu.fs import LocalFileSystem
    from hadoop_tpu.models import get_config
    from hadoop_tpu.parallel import MeshPlan
    from hadoop_tpu.parallel.checkpoint import list_checkpoints
    from hadoop_tpu.parallel.elastic import ElasticConfig
    from hadoop_tpu.parallel.lowp.guard import loss_curve_report
    from hadoop_tpu.parallel.trainer import Trainer
    from hadoop_tpu.testing.faults import FaultInjector

    N_STEPS, BATCH, INTERVAL = 36, 12, 12
    SLOW_AT, KILL_AT = 22, 28
    out: dict = {"failures": []}

    def check(ok: bool, what: str) -> None:
        if not ok:
            out["failures"].append(what)

    base = tempfile.mkdtemp(
        prefix="elastic-smoke-",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
    try:
        fs = LocalFileSystem()
        cfg = get_config("tiny", max_seq=32)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab_size, 120_000, dtype=np.uint16)
        data_path = os.path.join(base, "tokens.bin")
        fs.write_all(data_path, toks.tobytes())
        inj = FaultInjector(os.path.join(base, "faults"))

        def poll_fn():
            # scripted doctor feed: armed flags → the trainers section
            # shape FleetDoctor.poll_once() serves (obs/doctor.py)
            flagged, ranks = {}, {}
            for r in range(4):
                dead = inj.armed(r, "kill")
                ranks[f"rank-{r}"] = {"ok": not dead, "rank": r,
                                      "job": "elastic-smoke"}
                if inj.armed(r, "delay-ms") and not dead:
                    flagged[f"rank-{r}"] = {
                        "node": f"rank-{r}",
                        "signals": ["trainer.step_wall"]}
            return {"trainers": {"flagged": flagged, "ranks": ranks}}

        # -------- reference arm: uninterrupted dp=4
        ref = Trainer(cfg, MeshPlan(dp=4), fs, data_path,
                      os.path.join(base, "ckpt-ref"), batch=BATCH,
                      lr=1e-2, zero1=True, ckpt_interval=INTERVAL)
        ref.train(N_STEPS)
        ref.wait_for_checkpoint()
        ref_curve = [ref.loss_by_step[i] for i in range(1, N_STEPS + 1)]
        ref.close()

        # -------- elastic arm: slow → demote, kill → evict, reshard
        econf = ElasticConfig(enabled=True, poll_steps=2, min_dp=1,
                              demote_windows=2, evict_windows=6,
                              dead_windows=1, cooldown_polls=2)
        ckpt_dir = os.path.join(base, "ckpt-el")
        tr = Trainer(cfg, MeshPlan(dp=4), fs, data_path, ckpt_dir,
                     batch=BATCH, lr=1e-2, zero1=True,
                     ckpt_interval=INTERVAL, elastic=econf,
                     doctor_poll=poll_fn)
        tr.train(SLOW_AT)
        inj.inject(SLOW_RANK, "delay-ms", str(DELAY_MS))
        tr.train(KILL_AT - tr.step)          # demote fires in here
        inj.inject(SLOW_RANK, "kill")
        t0 = time.monotonic()
        tr.train(N_STEPS - tr.step)          # evict + reshard + replay
        out["elastic_tail_seconds"] = round(time.monotonic() - t0, 2)
        tr.wait_for_checkpoint()
        el_curve = [tr.loss_by_step[i] for i in range(1, N_STEPS + 1)]

        events = tr.elastic.events
        by_kind = {}
        for ev in events:
            by_kind.setdefault(ev["decision"], []).append(ev)
        out["events"] = [{k: ev[k] for k in ev
                          if k not in ("config",)} for ev in events]
        check(len(by_kind.get("demote", [])) == 1,
              f"expected exactly one demote: {by_kind.keys()}")
        check(len(by_kind.get("evict", [])) == 1,
              f"expected exactly one evict: {by_kind.keys()}")
        resumes = by_kind.get("resume", [])
        check(len(resumes) == 1 and resumes[0]["restored"],
              f"expected one restoring resume: {resumes}")
        check(tr.plan.dp == 3,
              f"largest healthy sub-mesh should be dp=3 (non-power-of-"
              f"two), got {tr.plan}")
        check(tr.step == N_STEPS, f"elastic arm ended at {tr.step}")

        # lost steps: elastic resumes from the demote's protective
        # snapshot; a restart-from-checkpoint baseline resumes from
        # the newest INTERVAL save before the kill
        if resumes:
            evict_step = by_kind["evict"][0]["step"]
            out["lost_steps"] = resumes[0]["lost_steps"]
            out["resume_seconds"] = resumes[0]["resume_seconds"]
            out["lost_steps_baseline"] = \
                evict_step - (evict_step // INTERVAL) * INTERVAL
            check(out["lost_steps"] < out["lost_steps_baseline"],
                  f"elastic lost {out['lost_steps']} steps, restart "
                  f"baseline loses {out['lost_steps_baseline']}")
            # the baseline's interval checkpoint must really exist —
            # the comparison is against a restartable state, not air
            steps_on_disk = list_checkpoints(fs, ckpt_dir)
            check((evict_step // INTERVAL) * INTERVAL in steps_on_disk,
                  f"baseline interval checkpoint missing: "
                  f"{steps_on_disk}")
        out["evictions"] = len(by_kind.get("evict", []))

        guard = loss_curve_report(ref_curve, el_curve, rel_tol=0.25)
        out["guard"] = {k: guard[k] for k in
                        ("accepted", "max_rel_div", "final_rel_div")
                        if k in guard}
        check(bool(guard.get("accepted")),
              f"loss-curve guard rejected the elastic arm: {guard}")
        tr.close()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory, never a crash
        out["failures"].append(f"{type(e).__name__}: {e}")
    finally:
        shutil.rmtree(base, ignore_errors=True)
    out["ok"] = not out["failures"]
    return out


def elastic_child_main() -> int:
    """Subprocess entry: force the 8-device CPU mesh BEFORE jax loads,
    then run the elastic body."""
    from __graft_entry__ import _force_cpu_devices
    _force_cpu_devices(8)
    print("ELASTIC_SMOKE " + json.dumps(_elastic_body()))
    return 0


def run_elastic(quick: bool = False, timeout_s: float = 900.0) -> dict:
    """Parent wrapper for the elastic leg (run_all records, never
    raises). ``quick`` is accepted for signature parity — the leg is
    one fixed tiny scenario either way."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # the child sets its own device count
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.flight_smoke",
         "--elastic-child"],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for line in proc.stdout.splitlines():
        if line.startswith("ELASTIC_SMOKE "):
            return json.loads(line[len("ELASTIC_SMOKE "):])
    raise RuntimeError(
        f"elastic smoke produced no record (rc={proc.returncode}): "
        f"{proc.stderr.strip()[-2000:]}")


def main() -> int:
    if "--worker" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--worker"]
        return worker_main(argv)
    if "--elastic-child" in sys.argv:
        return elastic_child_main()
    if "--elastic" in sys.argv:
        result = run_elastic()
        print(json.dumps(result, indent=2))
        return 0 if result.get("ok") else 1
    result = run()
    print(json.dumps(result, indent=2))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
