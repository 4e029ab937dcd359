"""The numbers that decide ``correct`` for a training cell.

Both sides give :class:`chipbench.reference.gpt2.Readings` of the first
steps from the same weights and batches.  A cell compares some of these,
each against a limit of its own:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: over the leaves, the largest gap between the two norms of
  the first gradient, relative to the reference's norm of that leaf or of
  the median leaf, whichever is larger;
* ``delta_gap``: the same for the norm of each leaf's change over the
  steps, leaving out the leaves whose first reference gradient is under a
  thousandth of the median leaf's (round-off alone moves them under Adam);
* ``grad_elem_gap`` and ``delta_elem_gap``: element by element over each
  leaf's sample, the root mean square of the two sides' difference relative
  to the reference's root mean square of that leaf or of the median leaf,
  whichever is larger; the largest over the leaves (``*_elem_median``: the
  median leaf's).  A norm averages rounding away; these do not, so they
  tell a lower precision from the stated one.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Tuple

import numpy as np

#: a leaf whose first reference gradient is under this share of the median
#: leaf's takes no part in ``delta_gap``
STILL_LEAF = 1e-3


def _worst(gaps: Dict[str, float]) -> Tuple[float, str]:
    worst, where = 0.0, ""
    for n, gap in gaps.items():
        if not gap <= worst:            # a NaN is the worst of all
            worst, where = gap, n
            if math.isnan(gap):
                break
    return worst, where


def _median(gaps: Dict[str, float]) -> Tuple[float, str]:
    ordered = sorted((g, n) for n, g in gaps.items())
    return ordered[len(ordered) // 2]


def _norm_gaps(prog: Dict[str, float], ref: Dict[str, float], leaves
               ) -> Dict[str, float]:
    floor = statistics.median(ref[n] for n in leaves)
    return {n: abs(prog[n] - ref[n]) / max(ref[n], floor) for n in leaves}


def _rms(a: np.ndarray) -> float:
    return float(np.sqrt(np.mean(np.square(a.astype(np.float64)))))


def _elem_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
               leaves) -> Dict[str, float]:
    scale = {n: _rms(ref[n]) for n in leaves}
    floor = statistics.median(scale.values())
    return {n: _rms(prog[n] - ref[n]) / max(scale[n], floor) for n in leaves}


def numbers(prog, ref) -> Dict[str, Tuple[float, str]]:
    """{name: (value, where)} for every number a cell may compare."""
    loss = 0.0
    where = ""
    for i, (p, r) in enumerate(zip(prog.losses, ref.losses)):
        gap = abs(p - r) / abs(r)
        if not gap <= loss:
            loss, where = gap, f"step {i + 1}"
    leaves = sorted(ref.grad1)
    med = statistics.median(ref.grad1[n] for n in leaves)
    moved = [n for n in leaves if ref.grad1[n] >= STILL_LEAF * med]
    grad = _norm_gaps(prog.grad1, ref.grad1, leaves)
    delta = _norm_gaps(prog.delta, ref.delta, moved)
    grad_e = _elem_gaps(prog.grad1_sample, ref.grad1_sample, leaves)
    delta_e = _elem_gaps(prog.delta_sample, ref.delta_sample, moved)
    return {"loss_gap": (loss, where),
            "loss1_gap": (abs(prog.losses[0] - ref.losses[0])
                          / abs(ref.losses[0]), "step 1"),
            "grad_gap": _worst(grad),
            "grad_median_gap": _median(grad),
            "delta_gap": _worst(delta),
            "delta_median_gap": _median(delta),
            "grad_elem_gap": _worst(grad_e),
            "grad_elem_median": _median(grad_e),
            "delta_elem_gap": _worst(delta_e),
            "delta_elem_median": _median(delta_e)}


def checks(values: Dict[str, Tuple[float, str]], limits: Dict[str, float]
           ) -> Dict[str, dict]:
    """The numbers that have a limit, each beside it; ``ok`` is false for a
    NaN too."""
    out = {}
    for name, limit in limits.items():
        value, where = values[name]
        out[name] = {"value": value, "limit": limit,
                     "ok": bool(value <= limit), "where": where}
    return out
