"""Run every storage/compute benchmark and record STORAGE_BENCH.json.

CPU functional test: JAX_PLATFORMS=cpu in parent and children; no chip number.

  python -m benchmarks.run_all [--out STORAGE_BENCH.json]
"""

from __future__ import annotations

import argparse
import json
import platform
import time


def _dynamometer(n_ops: int) -> dict:
    import os
    import tempfile

    from hadoop_tpu.testing.minicluster import MiniDFSCluster, fast_conf
    from hadoop_tpu.tools import dynamometer as dyn

    conf = fast_conf()
    conf.set("dfs.replication", "1")
    import shutil
    base = tempfile.mkdtemp(prefix="dynamometer-",
                            dir="/dev/shm" if os.path.isdir("/dev/shm")
                            else None)
    try:
        with MiniDFSCluster(num_datanodes=1, conf=conf,
                            base_dir=base) as c:
            c.wait_active()
            trace = os.path.join(base, "audit.log")
            dyn.generate_trace(trace, n_ops, workers=8)
            with open(trace) as f:
                return dyn.replay_parallel(c.default_fs, list(f),
                                           threads=8)
    finally:
        shutil.rmtree(base, ignore_errors=True)


def _lint_selfrun() -> dict:
    """tpulint self-run as a bench suite: the full tree against the
    committed baseline plus the conf-registry drift gate — a dirty
    tree or a stale registry is a trajectory failure like any other."""
    import os

    from hadoop_tpu.analysis import all_checkers, confscan
    from hadoop_tpu.analysis.core import (load_baseline, run_lint,
                                          split_baselined)
    from hadoop_tpu.conf import registry

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    t0 = time.perf_counter()
    checkers = all_checkers()
    findings = run_lint([os.path.join(repo, "hadoop_tpu")],
                        checkers=checkers, root=repo)
    baseline = load_baseline(os.path.join(repo, "LINT_BASELINE"))
    new, old = split_baselined(findings, baseline)
    gate_ok, diff = confscan.check_registry(repo)
    failures = [f.render() for f in new[:20]]
    if not gate_ok:
        failures.append(f"conf registry stale ({len(diff)} diff lines)")
    return {"checkers": len(checkers),
            "unbaselined": len(new),
            "baselined": len(old),
            "registry_keys": len(registry.KEYS),
            "registry_patterns": len(registry.PATTERNS),
            "registry_gate_ok": gate_ok,
            "wall_seconds": round(time.perf_counter() - t0, 2),
            "failures": failures}


def _code_hash() -> str:
    """Short git hash of the tree the suite ran against (the train-row
    precedent in BENCH_LOG.jsonl carries the same ``code`` field)."""
    import os
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))).stdout.strip()
    except Exception:  # noqa: BLE001 — no git = no hash, not no log
        return ""


def _suite_failures(result: dict) -> list:
    """Failure strings a suite reported, whatever its local shape:
    an ``error`` (the suite itself died) or a ``failures`` list (a
    contract inside it failed)."""
    if not isinstance(result, dict):
        return []
    out = []
    if result.get("error"):
        out.append(str(result["error"]))
    # both in-tree conventions: doctor/flight use "failures",
    # longctx/serve_bench contracts use "failed"
    for key in ("failures", "failed"):
        for f in result.get(key) or []:
            out.append(str(f))
    return out


# per-suite key metrics for the trajectory row: a list of (path into
# the suite result, logged name) per suite. Scalars only — the full
# result stays in --out.
_KEY_METRICS = {
    "nn_throughput_ops_per_sec": [(("create",), "create_ops_per_sec")],
    "dfsio": [(("write_mb_s",), "write_mb_s")],
    "terasort": [(("sort_bytes_per_sec",), "sort_bytes_per_sec")],
    "serving": [(("value",), "ttft_p50_ms")],
    "serving_speculate": [(("steps_ratio",), "steps_ratio")],
    "serving_quantized": [(("value",), "capacity_ratio")],
    # expert-parallel MoE serving (serving/families/gqa.moe_mlp): the lever
    # counts as moving when the trajectory shows sparse tokens/s priced
    # against dense-compute NEXT TO the ledger-measured a2a byte cut
    # and the guard verdict that bought it
    "serving_moe": [
        (("moe_tokens_per_sec",), "moe_tokens_per_sec"),
        (("dense_tokens_per_sec",), "moe_dense_tokens_per_sec"),
        (("moe_a2a_payload_ratio",), "moe_a2a_payload_ratio"),
        (("guard_accepted",), "moe_guard_accepted"),
        (("falsifier_rejected",), "moe_falsifier_rejected")],
    "trace_overhead": [(("step", "overhead_frac"), "overhead_frac")],
    "doctor": [(("windows_to_flag",), "windows_to_flag")],
    "flight_recorder": [(("windows_to_flag",), "windows_to_flag")],
    # elastic training plane (parallel/elastic): the lever only counts
    # as moving when the trajectory shows the eviction taken AND fewer
    # steps lost than a restart-from-checkpoint would lose
    "flight_elastic": [(("lost_steps",), "lost_steps"),
                       (("lost_steps_baseline",), "lost_steps_baseline"),
                       (("evictions",), "evictions"),
                       (("resume_seconds",), "resume_seconds")],
    # long-context pipelined decode (serving/longctx/decode): the
    # lever counts as moving when the trajectory shows decode tokens/s
    # NEXT TO the per-token dispatch budget and the double-buffer
    # window bytes it was bought with
    "serving_longctx": [
        (("decode_tokens_per_sec",), "longctx_decode_tokens_per_sec"),
        (("decode_dispatches_per_token",),
         "longctx_dispatches_per_token"),
        (("decode_hbm_window_bytes",), "longctx_hbm_window_bytes")],
    # partially-synchronized activations (parallel/lowp/syncpolicy):
    # the lever only counts as moving when the trajectory file shows
    # per-step collectives skipped AND the guard verdict next to them
    "lowp": [(("partial_sync", "skipped_per_step"),
              "sync_skipped_per_step"),
             (("partial_sync", "exec_ratio"), "sync_exec_ratio"),
             (("partial_sync", "guard_accepted"),
              "sync_guard_accepted")],
    # elastic-fleet storm (autoscaler + QoS door + SLO scoreboard):
    # the trajectory shows how far the fleet grew, that zero requests
    # failed, and that the DFS tier recovered after the drain
    "serving_storm": [(("value",), "peak_replicas"),
                      (("failed_requests",), "storm_failed_requests"),
                      (("hits_dfs_delta",), "storm_hits_dfs_delta"),
                      (("qos_heavy_sheds",), "storm_heavy_sheds")],
    # static-analysis plane: the self-run is healthy when it stays at
    # zero unbaselined findings with the registry gate green
    "lint": [(("unbaselined",), "unbaselined"),
             (("registry_keys",), "registry_keys"),
             (("wall_seconds",), "wall_seconds")],
}


def _bench_row(out: dict, quick: bool) -> dict:
    """The ``bench_suite`` trajectory row for one full run — built
    separately from the append so the trend sentinel can judge the
    row BEFORE it lands in the log."""
    summary = {}
    failures = []
    for suite, result in out.items():
        if suite in ("timestamp", "host", "wall_seconds"):
            continue
        fails = _suite_failures(result) if isinstance(result, dict) \
            else []
        failures.extend(f"{suite}: {f}" for f in fails)
        for paths, name in _KEY_METRICS.get(suite, []):
            node = result
            for k in paths:
                node = node.get(k) if isinstance(node, dict) else None
            if isinstance(node, (int, float)) and not isinstance(
                    node, bool):
                summary[f"{suite}.{name}"] = node
    return {"metric": "bench_suite",
            "timestamp": out.get("timestamp"),
            "code": _code_hash(),
            "quick": quick,
            "wall_seconds": out.get("wall_seconds"),
            "suites": sorted(k for k in out if k not in
                             ("timestamp", "host", "wall_seconds")),
            "key_metrics": summary,
            "failures": failures}


def _append_bench_log(path: str, row: dict, out: dict,
                      quick: bool) -> None:
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(row) + "\n")
    # the storm phase's per-class SLO verdict rides along as its own
    # scorecard row (availability / p99 attainment / burn per class,
    # joined to the fleet's htpu_build_info hash)
    slo = (out.get("serving_storm") or {}).get("slo") \
        if isinstance(out.get("serving_storm"), dict) else None
    if slo:
        from benchmarks.bench_trend import append_slo_scorecard
        append_slo_scorecard(path, slo, quick=quick)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="STORAGE_BENCH.json")
    ap.add_argument("--log", default="BENCH_LOG.jsonl",
                    help="bench trajectory log (one summary row per "
                         "suite run, appended)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes for smoke runs")
    args = ap.parse_args()

    import os
    import sys
    # before any suite imports jax: every in-process arm and every
    # child process stays on the CPU backend
    os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmarks import dfsio, nn_throughput, rpc_bench, terasort_bench

    # The whole "cluster" shares one interpreter here, so a packet's hop
    # chain is a chain of GIL handoffs; the default 5 ms switch interval
    # adds up to 15 ms/packet of scheduling latency on a ~3 ms work path.
    # Real deployments run one process per daemon and never see this.
    sys.setswitchinterval(0.001)

    scale = 0.2 if args.quick else 1.0
    out = {"timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "host": platform.node()}
    t0 = time.perf_counter()
    out["nn_throughput_ops_per_sec"] = nn_throughput.run(
        n_ops=int(5000 * scale))
    out["rpc"] = rpc_bench.run(seconds=5.0 * scale)
    from benchmarks import mprpc_bench
    out["rpc_multiprocess"] = mprpc_bench.run(seconds=5.0 * scale,
                                              workers=4)
    from benchmarks import mini_rpc_bench
    out["rpc_connection_setup"] = mini_rpc_bench.run(
        samples=int(30 * scale) or 10)
    out["dfsio"] = dfsio.run(n_files=4, mb_per_file=int(16 * scale) or 2)
    from benchmarks import codec_bench
    out["codecs"] = codec_bench.run(mb=int(64 * scale) or 8)
    # 400 MB: big enough that scheduling/launch overhead amortizes (the
    # canonical benchmark is run at terabyte scale for the same reason)
    out["terasort"] = terasort_bench.run(records=int(4_000_000 * scale))
    # SLS: the REAL RM behind its RPC services under a 1,000-node
    # simulated fleet (ref: SLSRunner.java). (The scheduler-direct mode
    # stays available as `python -m hadoop_tpu.tools.sls` for
    # interactive what-ifs; the RM-RPC number is the recorded one.)
    from hadoop_tpu.tools import sls
    out["sls"] = sls.run_rm(num_nodes=int(1000 * scale) or 200,
                            num_apps=int(40 * scale) or 8,
                            containers_per_app=50, sweeps=20)
    # Dynamometer: >=100K-op audit replay against a real NameNode over
    # real RPC (ref: hadoop-dynamometer AuditReplayMapper).
    out["dynamometer"] = _dynamometer(int(100_000 * scale) or 20_000)
    from benchmarks import nn_bench
    out["nnbench"] = nn_bench.run(maps=4, ops_per_map=int(200 * scale)
                                  or 40)
    # Serving plane: tiny-config shared-prefix smoke (compile-once per
    # shape + hit-rate > 0 + fewer engine steps with the prefix cache)
    # so decode-path perf regressions surface in the bench trajectory.
    # A smoke failure is recorded, not raised — it must not discard the
    # benches already computed above.
    try:
        from benchmarks import serve_bench
        out["serving"] = serve_bench.run_smoke()
    except Exception as e:  # noqa: BLE001 — any serving failure (even
        # an import-time one) is a data point for the trajectory, never
        # a reason to lose the storage/compute numbers computed above
        out["serving"] = {"error": f"{type(e).__name__}: {e}"}
    # Speculative-decoding smoke: same repetitive workload with the
    # speculation lane off then on — greedy outputs must match
    # token-for-token, speculation must strictly reduce engine steps
    # with at least one accepted draft, and both step shapes compile
    # exactly once. Recorded, not raised.
    try:
        from benchmarks import serve_bench
        out["serving_speculate"] = serve_bench.run_speculate_smoke()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["serving_speculate"] = {"error": f"{type(e).__name__}: {e}"}
    # Weight-plane smoke: the same tiny model served from f32- and
    # int8-resident weights under one fixed HBM budget — the int8 arm
    # must admit >= 2x the lanes x context (and KV blocks), the logits
    # A-B guard must accept the greedy outputs, and both step shapes
    # compile exactly once on both arms. Recorded, not raised.
    try:
        from benchmarks import serve_bench
        out["serving_quantized"] = serve_bench.run_quantized_smoke()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["serving_quantized"] = {"error": f"{type(e).__name__}: {e}"}
    # MoE serving smoke: one int8-expert checkpoint served sparse vs
    # dense-compute — the quantized all2all payload must measure >= 2x
    # below the f32 reference on the comm ledger, the logits A-B guard
    # must accept (and its zeroed-payload falsifier reject), and both
    # step shapes compile exactly once on both arms. Recorded, not
    # raised.
    try:
        from benchmarks import serve_bench
        out["serving_moe"] = serve_bench.run_moe_smoke()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["serving_moe"] = {"error": f"{type(e).__name__}: {e}"}
    # Replica-churn smoke: kill/restart an engine mid shared-prefix
    # workload over a miniDFS-backed KV store — fleet hit-rate must
    # recover via the DFS tier (post-restart hits > 0, strictly fewer
    # engine steps than the DFS-off arm). Recorded, not raised.
    try:
        from benchmarks import serve_bench
        out["serving_churn"] = serve_bench.run_churn_smoke()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["serving_churn"] = {"error": f"{type(e).__name__}: {e}"}
    # Long-context smoke: a prompt 8x one chip's KV budget prefilled
    # context-parallel across an 8-dev subprocess mesh, KV streamed
    # into the host/DFS tiers, decoded through the real door with an
    # exact single-chip reference match, CP guards accepted, hit-tier
    # counters live, and every longctx shape compiled exactly once.
    # Recorded, not raised.
    try:
        from benchmarks import longctx_smoke
        out["serving_longctx"] = longctx_smoke.run(quick=args.quick)
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["serving_longctx"] = {"error": f"{type(e).__name__}: {e}"}
    # Elastic-fleet storm smoke: step-function load against a mini-fleet
    # of real `hadoop-tpu serve` subprocesses + the autoscaler — fleet
    # must grow, hold TTFT p99 within the SLO after settling, scale back
    # in via the drain protocol with zero failed requests + post-drain
    # DFS hit-rate recovery, and shed a heavy tenant (429) under
    # overload before a light tenant degrades. Recorded, not raised.
    try:
        from benchmarks import serve_bench
        out["serving_storm"] = serve_bench.run_storm_smoke()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["serving_storm"] = {"error": f"{type(e).__name__}: {e}"}
    # Training plane: 8-virtual-device overlap smoke (A-B step counts +
    # bit-exact loss parity with the communication-overlap pass on vs
    # off, plus the async-save blocking-time split). Same recorded-not-
    # raised contract as the serving smoke.
    try:
        from benchmarks import overlap_smoke
        out["overlap"] = overlap_smoke.run()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["overlap"] = {"error": f"{type(e).__name__}: {e}"}
    # Relaxed-parity plane: loss-curve A-B acceptance (dp2×tp2 +
    # zero1-dp8 + pp grad buckets, 50 steps) with the ≥2× quantized
    # payload-byte contract and the bitwise-tier byte-identity proof.
    # Both tiers ride every future run of this ladder. Recorded, not
    # raised.
    try:
        from benchmarks import lowp_smoke
        out["lowp"] = lowp_smoke.run()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["lowp"] = {"error": f"{type(e).__name__}: {e}"}
    # Telemetry plane: tracing-on vs tracing-off step + DFS write/read
    # cost, with the <5% step-overhead bound recorded in the JSON
    # (exemplar bookkeeping now rides the on-arm — same bound).
    # Recorded-not-raised like the other smokes.
    try:
        from benchmarks import trace_overhead
        out["trace_overhead"] = trace_overhead.run(quick=args.quick)
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["trace_overhead"] = {"error": f"{type(e).__name__}: {e}"}
    # Fleet doctor: miniDFS + one injected-slow DN — exactly that DN
    # flagged within bounded report windows, NN placement deprioritizes
    # it, and a /prom exemplar resolves to an assembled cross-daemon
    # trace. Recorded-not-raised.
    try:
        from benchmarks import doctor_smoke
        out["doctor"] = doctor_smoke.run(quick=args.quick)
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["doctor"] = {"error": f"{type(e).__name__}: {e}"}
    # Training flight recorder: four subprocess trainer ranks, one with
    # injected per-step latency — the doctor must flag exactly that
    # rank within 3 observation windows and unflag it within the
    # hysteresis history; the slow rank's htpu_comm collective tail
    # must carry a doctor-resolvable exemplar. Recorded-not-raised.
    try:
        from benchmarks import flight_smoke
        out["flight_recorder"] = flight_smoke.run(quick=args.quick)
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["flight_recorder"] = {"error": f"{type(e).__name__}: {e}"}
    # Elastic training plane: slow→demote (protective snapshot), kill→
    # evict onto the largest healthy sub-mesh (dp 4→3, non-power-of-two)
    # with reshard-on-restore — loss-curve A-B guard vs an uninterrupted
    # twin must ACCEPT and the elastic arm must lose strictly fewer
    # steps than restart-from-checkpoint. Recorded-not-raised.
    try:
        from benchmarks import flight_smoke
        out["flight_elastic"] = flight_smoke.run_elastic(
            quick=args.quick)
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["flight_elastic"] = {"error": f"{type(e).__name__}: {e}"}
    # Static-analysis plane: tpulint self-run (all checkers against the
    # committed baseline) + the conf-registry drift gate, timed so a
    # creeping lint cost, a dirty tree, or a stale registry surfaces in
    # the bench trajectory. Recorded-not-raised.
    try:
        out["lint"] = _lint_selfrun()
    except Exception as e:  # noqa: BLE001 — recorded for the
        # trajectory; must not discard the benches already computed
        out["lint"] = {"error": f"{type(e).__name__}: {e}"}
    out["wall_seconds"] = round(time.perf_counter() - t0, 1)
    # One summary row per suite run into the bench trajectory log: the
    # log used to carry only hand-stamped train rows, so a regression
    # BETWEEN issues was invisible until someone re-ran a bench by
    # hand. Key metrics + failures per suite, appended, never rewritten.
    # The trend sentinel judges the new row against the history BEFORE
    # it lands — recorded, not raised: a regression between issues is a
    # data point in the trajectory, never a reason to lose the run.
    row = None
    try:
        from benchmarks import bench_trend
        row = _bench_row(out, quick=args.quick)
        out["bench_trend"] = bench_trend.check(
            bench_trend.load_rows(args.log) + [row])
    except Exception as e:  # noqa: BLE001 — the sentinel is
        # best-effort; a full bench run must never die on it
        out["bench_trend"] = {"error": f"{type(e).__name__}: {e}"}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    try:
        if row is not None:
            _append_bench_log(args.log, row, out, quick=args.quick)
    except Exception as e:  # noqa: BLE001 — the trajectory log is
        # best-effort; a full bench run must never die on it
        print(f"BENCH_LOG append failed: {type(e).__name__}: {e}")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
