"""Faults planted in the PROGRAM, for the proofs (``chipbench.proof``
plants its own in what was served: ``--fault token``). One process, the
program patched before the proof drives it:

    python3 -m chipbench.proof_faults zero-state --workload <cell> --seeds 1,2 ...

``zero-state``   a lane that starts from a prefix hit (or resumes after a
                 preemption) begins with an all-zero recurrent state in
                 place of the state tail of the last page it maps
                 (``hadoop_tpu.models.lfm2.start_lane``).

Everything after the fault's name goes to ``chipbench.proof`` as it is.
The benchmark's own runs never come here.
"""

from __future__ import annotations

import contextlib
import sys
from unittest import mock


@contextlib.contextmanager
def zero_state():
    from hadoop_tpu.models import lfm2

    def from_nothing(lane, tail, slot, page):
        return lane.at[:, slot].set(0)
    with mock.patch.object(lfm2, "start_lane", from_nothing):
        yield


FAULTS = {"zero-state": zero_state}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in FAULTS:
        raise SystemExit(f"usage: chipbench.proof_faults <fault> <proof's "
                         f"arguments>; faults: {sorted(FAULTS)}")
    from chipbench import proof
    with FAULTS[argv[0]]():
        return proof.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
