"""The numbers that decide ``correct``, each held to a limit of its
configuration (``limits`` in ``bench/configs/<config>.json``), taken
from the readings of ``bench/run.py`` (``readings``): the reference and
the program, or what stands in its place, through the same set-up steps.

* ``change_gap``: how far the change of the server state over the first
  three committed updates departs from the reference's, leaf by leaf:
  | ||S3 - S0|| - ||R3 - R0|| | over the larger of ||R3 - R0|| and the
  median leaf's, worst leaf.  A norm gap and not the norm of the
  difference: local Adam turns rounding into sign flips of small
  gradients, so element values drift while the size of the update stays.
  The median leaf is the median of the leaves the reference moves.  A
  leaf the reference holds exactly still (as the global model is before
  Eq. 3 has weight on an updated tier) is held to the median leaf: what
  the program moves there is a gap.  Leaves the reference moves by
  round-off alone (a change under a thousandth of the median leaf's)
  are left out.
* ``loss_gap``: for each of those updates, the tier model it wrote,
  scored by the reference's mean loss (the model's ``metrics``) over
  the live training rows of that update's clients; the largest gap to
  the reference's own tier model, relative.
* ``change_gap_med``, ``step_gap``, ``step_gap_med``, ``first_loss_gap``:
  the same gaps by the median leaf, per update's written tier model
  (change from the initial weights, worst step), and for the first
  update alone.  A configuration compares those of them that separate
  its sound runs from its control (``bench/calibrate.py`` reads them all).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.pytree import named

#: leaves whose reference change is under this share of the median
#: leaf's change are not compared (they move by round-off alone)
STILL_LEAF = 1e-3


def change_norms(start, prog, ref) -> Dict[str, list]:
    """Per leaf name (``bench/pytree.py``), [program's, reference's]
    norm of the change from ``start``; each of the three is a pytree or
    a dict of named leaves."""
    start, prog, ref = named(start), named(prog), named(ref)
    return {k: [float(np.linalg.norm(np.asarray(prog[k], np.float64)
                                     - start[k])),
                float(np.linalg.norm(np.asarray(ref[k], np.float64)
                                     - start[k]))] for k in start}


def leaf_gaps(norms: Dict[str, list]) -> Dict[str, float]:
    """Per leaf, the gap between the program's and the reference's norm
    of the change, over the larger of the reference's and the median
    leaf's.  The median is taken over the leaves the reference moves;
    a leaf it holds exactly still is held to the median leaf, and one it
    moves by round-off alone (under STILL_LEAF of the median) is left
    out."""
    moved = [r for _, r in norms.values() if r > 0]
    med = float(np.median(moved)) if moved else 0.0
    return {k: abs(p - r) / max(r, med) for k, (p, r) in norms.items()
            if (r == 0 and med > 0) or r >= STILL_LEAF * med > 0}


def _worst(gaps: Dict[str, float]) -> float:
    return max(gaps.values()) if gaps else float("inf")


def _median(gaps: Dict[str, float]) -> float:
    return float(np.median(list(gaps.values()))) if gaps else float("inf")


def _rel(pair) -> float:
    p, r = pair
    return abs(p - r) / max(abs(r), 1e-12)


def numbers(readings: Dict) -> Dict[str, float]:
    """Every candidate number of one run's readings."""
    final = leaf_gaps(readings["final"])
    steps = [leaf_gaps(s["leaves"]) for s in readings["steps"]]
    return {
        "change_gap": _worst(final),
        "change_gap_med": _median(final),
        "step_gap": max(_worst(g) for g in steps),
        "step_gap_med": max(_median(g) for g in steps),
        "loss_gap": max(_rel(s["loss"]) for s in readings["steps"]),
        "first_loss_gap": _rel(readings["steps"][0]["loss"]),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {"value", "limit"}} and whether every value is within its
    limit (a missing or non-finite value fails)."""
    checks = {k: {"value": values.get(k, float("nan")), "limit": lim}
              for k, lim in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return {"checks": checks, "correct": bool(ok)}
