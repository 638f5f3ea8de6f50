"""A fault planted in the PROGRAM of a looped family, for the proofs
(``chipbench/proof_faults.py`` holds the convolution family's): one
process, the program patched before the proof drives it:

    python3 -m chipbench.proof_fault_slots first-pass-slot --workload <cell> --seeds 1,2 ...

``first-pass-slot``   every pass of the layer stack writes and reads the
                      K/V slots of the FIRST pass
                      (``hadoop_tpu.serving.families.looped.pass_offset``):
                      within a step a row still finds its own pass's K
                      and V, since the step scatters before it attends,
                      but of the earlier tokens it finds what the LAST
                      pass left there.

Everything after the fault's name goes to ``chipbench.proof`` as it is.
The benchmark's own runs never come here.
"""

from __future__ import annotations

import contextlib
import sys
from unittest import mock


@contextlib.contextmanager
def first_pass_slot():
    from hadoop_tpu.serving.families import looped

    def pass_one(t, layers, n_blocks):
        return 0 * t
    with mock.patch.object(looped, "pass_offset", pass_one):
        yield


FAULTS = {"first-pass-slot": first_pass_slot}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in FAULTS:
        raise SystemExit(f"usage: chipbench.proof_fault_slots <fault> "
                         f"<proof's arguments>; faults: {sorted(FAULTS)}")
    from chipbench import proof
    with FAULTS[argv[0]]():
        return proof.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
