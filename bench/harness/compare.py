"""The numbers that decide ``correct``: the program's first rounds against
the reference's, each number held to its limit from the cell's limits file.

* ``loss``       largest relative gap of a round's loss (the workers' mean
                 at the last local step), rounds 1-3
* ``inv_alpha``  largest relative gap of 1/α (the power consensus), rounds 1-3
* ``dtheta1``    the workers' weights' change in round 1 (local steps and
                 penalty gradient), by the worst leaf
* ``dTheta3_med`` the global model's change after round 3 (the OTA round),
                 by the median leaf: by the worst leaf it swings fiftyfold
                 from seed to seed, one leaf at a time, while every other
                 leaf reads alike
* ``lam3``       the duals after round 3 (the dual update), by the worst leaf
* ``noise1``     the receiver noise that round 1's Θ implies, by its mean
                 square per leaf, against the reference's own: where a
                 program draws its noise in a layout of its own (the
                 shard-local round), Θ and λ cannot be compared element
                 by element, and this is what stands in for them

A leaf's gap is the gap between the program's norm of that leaf and the
reference's, over the reference's norm of that leaf or of the median leaf,
whichever is larger.  A leaf whose reference norm is under a thousandth of
the median leaf's is nought to rounding and left out.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

def rel_max(got: List[float], want: List[float]) -> float:
    return max(abs(g - w) / abs(w) if math.isfinite(g) else math.inf
               for g, w in zip(got, want))


def leaf_gaps(got: Dict[str, float], want: Dict[str, float]) -> List[float]:
    med = statistics.median(want.values())
    return [abs(got[p] - w) / max(w, med) if math.isfinite(got[p])
            else math.inf for p, w in want.items() if w >= 1e-3 * med]


def worst_leaf(got: Dict[str, float], want: Dict[str, float]) -> float:
    return max(leaf_gaps(got, want))


def median_leaf(got: Dict[str, float], want: Dict[str, float]) -> float:
    return statistics.median(leaf_gaps(got, want))


def noise_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    """Worst leaf's relative gap of the mean-square receiver noise."""
    return max(abs(got[p] / want[p] - 1.0) if math.isfinite(got[p])
               else math.inf for p in want)


def readings(prog: dict, ref: dict, numbers) -> Dict[str, float]:
    """The cell's numbers (the keys of its limits) for ``prog`` against
    ``ref``, both in the shape ``harness.reference.run`` returns."""
    fns = {"loss": lambda: rel_max(prog["losses"], ref["losses"]),
           "inv_alpha": lambda: rel_max(prog["inv_alpha"], ref["inv_alpha"]),
           "dtheta1": lambda: worst_leaf(prog["dtheta1"], ref["dtheta1"]),
           "dTheta3_med": lambda: median_leaf(prog["dTheta3"],
                                              ref["dTheta3"]),
           "lam3": lambda: worst_leaf(prog["lam3"], ref["lam3"]),
           "noise1": lambda: noise_gap(ref["noise1_prog"], ref["noise1_ref"])}
    return {k: fns[k]() for k in numbers}


def verdict(read: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and, per number, its value beside its limit."""
    checks = {k: {"value": read[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
