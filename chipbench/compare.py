"""The numbers that decide ``correct``. Each is a gap between what the
timed path produced and what the plain reference gives, read against a
limit from the configuration file.
"""

from __future__ import annotations

from statistics import median
from typing import Dict, List

EXCLUDE_BELOW = 1e-3   # of the median leaf's reference gradient


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep=None) -> float:
    """The worst leaf's gap between the program's norm and the
    reference's — not the norm of a difference — against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    names = [n for n in ref if keep is None or n in keep]
    med = median(ref[n] for n in names)
    return max(abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
               for n in names)


def training(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` / ``ref``: {"losses": [...], "grad1": {leaf: norm},
    "delta": {leaf: norm}}. A leaf whose reference gradient is nought to
    rounding (under a thousandth of the median leaf's) moves under Adam by
    round-off alone and is left out of the change."""
    n = min(len(prog["losses"]), len(ref["losses"]))
    loss_gap = max(abs(prog["losses"][i] - ref["losses"][i])
                   / abs(ref["losses"][i]) for i in range(n))
    med = median(ref["grad1"].values())
    moved = {k for k, g in ref["grad1"].items() if g >= EXCLUDE_BELOW * med}
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf_gap(prog["grad1"], ref["grad1"]),
            "delta_gap": worst_leaf_gap(prog["delta"], ref["delta"], moved)}


def served(gaps) -> Dict[str, float]:
    """Served tokens: at every served position, how far the served
    token's logit lies below the reference's best (positions marked -1
    hold no token). ``served_logit_gap`` is the widest; the 90th
    percentile stands beside it for a model with experts, where one
    flipped expert choice moves a single token's logits by whole units
    whatever the precision.
    No served token at all reads NaN, which no limit admits."""
    vals: List[float] = sorted(float(g) for g in gaps.ravel() if g >= 0.0)
    if not vals:
        nan = float("nan")
        return {"served_logit_gap": nan, "served_gap_p90": nan}
    return {"served_logit_gap": vals[-1],
            "served_gap_p90": vals[int(0.9 * (len(vals) - 1))]}
