"""The proofs behind each limit in ``PERF.md``: one process, many seeds.

    python3 -m chipbench.proof --workload <cell> --seeds 1,2,3 --seconds 5 \\
        [--control int8] [--fault half] [--out chiprun_out/proof.jsonl]

For every seed it drives the cell exactly as ``chipbench.run`` does
(same driver, same comparison) and prints the numbers compared, with the
control's and the planted fault's beside them where asked. The
benchmark's own runs never come here.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--benchmark", default="BENCHMARK.json")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--rates", default="",
                    help="the sweep for the knee: run an open-loop cell "
                         "at each of these rates instead of its own")
    args = ap.parse_args(argv)

    from chipbench import harness
    cell, devices, driver = harness.open_cell(args.workload, args.benchmark)
    rows = []
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates \
        else [None] * len(seeds)
    for i, rate in enumerate(rates):
        seed = seeds[i % len(seeds)]
        if rate is not None:
            cell.traffic["rate_per_s"] = rate
        kw = {"fault": args.fault} if args.fault else {}
        out = driver(cell, devices, seed=seed, seconds=args.seconds,
                     traced=False, t_start=time.monotonic(),
                     control=args.control or None, **kw)
        row = {"workload": cell.name, "seed": seed, "correct": out.correct,
               "attempted": out.attempted, "failed": out.failed,
               "check": {k: v for k, (v, _) in out.checks.items()}}
        for extra in ("control", "fault_half", "fault_token",
                      "served_gap_stats"):
            if extra in out.obs:
                row[extra] = out.obs[extra]
        if rate is not None:
            from chipbench import readers
            o = out.obs
            row.update(rate_per_s=rate, requests=o["requests_due"],
                       backlog_open=o["backlog_open"],
                       backlog_close=o["backlog_close"],
                       occupancy=o["occupancy_share"],
                       ttft_mean_ms=readers.read(
                           {"reader": "mean", "of": "ttft_ms"}, out, cell),
                       ttft_p90_ms=readers.read(
                           {"reader": "percentile", "of": "ttft_ms",
                            "q": 90}, out, cell),
                       itl_p90_ms=readers.read(
                           {"reader": "percentile", "of": "itl_ms",
                            "q": 90}, out, cell),
                       step_ms=1e3 * o["window_s"] / max(o["steps"], 1),
                       prefill_lane=o["prefill_lane_steps"]
                       / max(o["steps"], 1),
                       setup_s=o["setup_s"], tokens_out=o["tokens_out"])
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
