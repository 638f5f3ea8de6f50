"""Traffic ``kind`` -> the driver that runs it."""

from __future__ import annotations


def driver(kind: str):
    if kind == "packed":
        from chipbench.train_cell import run
    elif kind in ("open-loop", "closed-loop"):
        from chipbench.serve_cell import run
    else:
        raise ValueError(f"traffic kind {kind!r} unknown "
                         "(there are: packed, open-loop, closed-loop)")
    return run
