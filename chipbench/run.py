"""One run of one cell:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process loads the cell's configuration and traffic by the names in
``BENCHMARK.json``, warms that cell's programs (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last. (``chipbench.proof`` reads the
control and the planted faults.)
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--benchmark", default="BENCHMARK.json")
    args = ap.parse_args(argv)

    from chipbench import harness
    cell, devices, driver = harness.open_cell(args.workload, args.benchmark)
    out = driver(cell, devices, seed=args.seed, seconds=args.seconds,
                 traced=bool(args.trace), t_start=T_START)
    return harness.emit(cell, out, bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
