"""Limits of a cell's comparison from ``calibrate.py``'s readings.

    python chipbench/calibrate.py --workload <cell> ... > <cell>.jsonl
    python chipbench/set_limits.py <cell>=<cell>.jsonl ... [--write <prec>]

For each cell and each reference precision in the readings, and for each
number a cell may compare: the lower reading is the largest of the sound
runs; the upper the least of the control's readings (the reference in
bfloat16 or the program's own bfloat16 path) where that is 3x the lower or
more, of the half-batch fault's where 10x, and of a frozen state (which
reads 1 on the gradient and change numbers) where 3x.  A number with an
upper reading gets the limit lower^(1/3) x upper^(2/3), rounded down to two
digits.  It prints, per cell and precision, the readings, the limits and
whether every control reading fails one of them.  ``--write <prec>`` writes
``limits/<cell>.json`` from that precision's limits.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

#: numbers that are steady from seed to seed (compared where they have an
#: upper reading); the worst-leaf element-wise ones only where no steady
#: number catches every control reading
STEADY = ("loss_gap", "grad_gap", "delta_gap", "grad_elem_median",
          "delta_elem_median")
WORST = ("grad_elem_gap", "delta_elem_gap")
FROZEN_READS_ONE = {"grad_gap", "delta_gap", "grad_elem_median",
                    "delta_elem_median", "grad_elem_gap", "delta_elem_gap"}
CONTROLS = ("bf16", "program_bf16")


def round_down(x: float) -> float:
    e = math.floor(math.log10(x)) - 1
    return float(f"{math.floor(x / 10 ** e) * 10 ** e:.3g}")


def limits(lines, precision, names):
    """({number: readings and limit}, whether every control reading fails a
    limit)."""
    def side(*names_):
        return [x for x in lines if x["side"] in names_
                and x.get("precision") == precision and "error" not in x]
    sound, ctrl, half = side("program"), side(*CONTROLS), side("half_batch")
    out = {}
    for n in names:
        lower = max(x[n] for x in sound)
        uppers = []
        if ctrl and min(x[n] for x in ctrl) >= 3 * lower:
            uppers.append(("control", min(x[n] for x in ctrl)))
        if half and min(x[n] for x in half) >= 10 * lower:
            uppers.append(("half_batch", min(x[n] for x in half)))
        if n in FROZEN_READS_ONE and 1.0 >= 3 * lower:
            uppers.append(("frozen_state", 1.0))
        if uppers and lower > 0:
            src, upper = min(uppers, key=lambda u: u[1])
            out[n] = {"lower": lower, "upper": upper, "from": src,
                      "limit": round_down(lower ** (1 / 3)
                                          * upper ** (2 / 3))}
    caught = bool(ctrl) and all(
        any(x[n] > r["limit"] for n, r in out.items()) for x in ctrl)
    return out, caught


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("readings", nargs="+", help="<cell>=<file.jsonl>")
    ap.add_argument("--write", metavar="PRECISION")
    args = ap.parse_args(argv)
    for item in args.readings:
        cell, path = item.split("=", 1)
        lines = [json.loads(x) for x in Path(path).read_text().splitlines()
                 if x.strip()]
        for prec in sorted({x.get("precision") for x in lines}, key=str):
            got, caught = limits(lines, prec, STEADY)
            if not caught:
                got, caught = limits(lines, prec, STEADY + WORST)
            print(json.dumps({"cell": cell, "precision": prec,
                              "control_caught": caught, "numbers": got}))
            if prec == args.write:
                dest = (Path(__file__).resolve().parent / "limits"
                        / f"{cell}.json")
                dest.write_text(json.dumps(
                    {n: r["limit"] for n, r in got.items()}, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
