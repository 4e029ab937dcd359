"""Readings that the limits of a cell's comparison are set from.

    python chipbench/calibrate.py --workload <cell> --seeds 1 2 ... \
        [--variants bf16 half_batch no_exchange program_bf16] \
        [--variant-seeds 3] [--precisions highest default]

In one process, on the cell's chips and at its own sizes: for each seed the
program's first steps (through the same set-up as a run) against the plain
reference at each of ``--precisions`` (matmul precisions; the
configuration's own when none is given), and then, on the first
``--variant-seeds`` seeds, each variant of the reference put in the
program's place (the ``bf16`` control and the planted faults), or the
program's own ``program_bf16`` path, against the same reference readings.
One JSON line per reading on standard output.  The benchmark's own runs
never do this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402  (also puts the checkout and src/ on sys.path)

#: the program's own bfloat16 path (``dtype`` of the configuration)
PROGRAM_BF16 = "program_bf16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="*", default=[])
    ap.add_argument("--variant-seeds", type=int, default=3)
    ap.add_argument("--precisions", nargs="*", default=[None])
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)

    import jax
    from chipbench.compare import numbers
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: no TPU, or too few chips", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    path = run.load_module("paths", cell.traffic["path"])
    sess = path.Session(cell.conf, cell.traffic, args.seeds[0],
                        devices[:cell.chips])
    refs = {}
    for i, seed in enumerate(args.seeds):
        if i:
            sess.reset(seed)
        prog = sess.first_steps(run.CHECK_STEPS)
        host = sess.host_batches[:run.CHECK_STEPS]
        sess.free()
        for prec in args.precisions:
            t0 = time.perf_counter()
            ref = run.reference(cell, sess.ref_devices, seed, host,
                                precision=prec)
            ref_s = time.perf_counter() - t0
            if i < args.variant_seeds:
                refs[seed, prec] = (ref, host)
            print(json.dumps({"seed": seed, "side": "program",
                              "precision": prec, "reference_s": ref_s,
                              "losses": prog.losses, "ref_losses":
                              ref.losses, **_flat(numbers(prog, ref))}),
                  flush=True)
    for variant in args.variants:
        if variant == PROGRAM_BF16:
            del sess
            sess = path.Session(dict(cell.conf, dtype="bfloat16"),
                                cell.traffic, args.seeds[0],
                                devices[:cell.chips])
        for i, seed in enumerate(args.seeds[:args.variant_seeds]):
            got = {}
            for prec in args.precisions:
                ref, host = refs[seed, prec]
                try:
                    if variant == PROGRAM_BF16 and not got:
                        if i:
                            sess.reset(seed)
                        got[None] = sess.first_steps(run.CHECK_STEPS)
                        sess.free()
                    elif variant == "bf16" and not got:
                        # bfloat16 operands: the matmul precision is moot
                        got[None] = run.reference(cell, sess.ref_devices,
                                                  seed, host, variant)
                    elif variant not in (PROGRAM_BF16, "bf16"):
                        got[None] = run.reference(cell, sess.ref_devices,
                                                  seed, host, variant,
                                                  precision=prec)
                    out = _flat(numbers(got[None], ref))
                    out["losses"] = got[None].losses
                    out["ref_losses"] = ref.losses
                    if variant == PROGRAM_BF16:
                        out["plan_faults"] = sess.plan_faults
                except Exception as e:                 # noqa: BLE001
                    out = {"error": repr(e)}
                print(json.dumps({"seed": seed, "side": variant,
                                  "precision": prec, **out}), flush=True)
    return 0


def _flat(nums):
    return {k: v for k, (v, _) in nums.items()} | {
        f"{k}_where": w for k, (_, w) in nums.items()}


if __name__ == "__main__":
    sys.exit(main())
