"""The comparison that decides ``correct`` for a training cell.

Three numbers, each the worst over its parts, compared with the plain
reference's readings of the same steps from the same seed:

* ``loss_gap``: the largest relative gap of a checked step's mean loss;
* ``grad_gap``: over the leaves, the largest gap between the program's and
  the reference's norm of the first step's gradient (as AdamW got it),
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger (some gradients are all but zero);
* ``update_gap``: the same for each leaf's change over the checked steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's are left out: AdamW moves them by round-off alone.
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
# a leaf whose first gradient is under this share of the median leaf's
# is moved by round-off alone and is not compared
STILL_LEAF = 1e-3


def _worst(values) -> float:
    return max((v if math.isfinite(v) else math.inf) for v in values)


def _leaf_gap(got: dict, want: dict, leaves) -> float:
    floor = statistics.median(want[k] for k in leaves)
    return _worst(abs(got[k] - want[k]) / max(want[k], floor) for k in leaves)


def still(want: dict) -> list[str]:
    """The leaves the reference's first gradient leaves all but still."""
    g_med = statistics.median(want["grad"].values())
    return [k for k, v in want["grad"].items() if v < STILL_LEAF * g_med]


def gaps(got: dict, want: dict) -> dict[str, float]:
    """The numbers compared; ``got`` is the program's readings and ``want``
    the reference's (``losses``, ``grad`` and ``delta``)."""
    left_out = set(still(want))
    moved = [k for k in want["grad"] if k not in left_out]
    return {
        "loss_gap": _worst(abs(a - b) / abs(b)
                           for a, b in zip(got["losses"], want["losses"],
                                           strict=True)),
        "grad_gap": _leaf_gap(got["grad"], want["grad"], want["grad"]),
        "update_gap": _leaf_gap(got["delta"], want["delta"], moved),
    }


def checks(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """Each number beside its limit, in a fixed order."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS}


def passed(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())
