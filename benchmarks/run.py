"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [BENCH] [--steps N] [--json]
    PYTHONPATH=src python benchmarks/run.py churn --churn-profile tiny --trace

Prints ``name,us_per_call,derived`` CSV lines.  ``BENCH`` selects benches by
name prefix (``churn`` runs ``churn_elastic``; ``--only`` remains the exact
form).  ``--json`` additionally writes one ``BENCH_<name>.json`` perf
artifact per bench from whatever the bench's ``run()`` returned (throughput
+ predicted pace per scheduler for ``joint_planning``) — CI uploads these so
the perf trajectory is tracked per commit instead of scrolling away in logs.

``--trace`` attaches the observability layer to the benches that support it
(currently the churn bench, including its closed-loop calibration demo):
each instrumented run writes ``TRACE_<name>.json`` (open in Perfetto),
``TRACE_<name>.jsonl`` and ``FLIGHT_<name>.jsonl`` artifacts and prints the
run report — per-stage timeline, comm/compute overlap fraction, straggler
heatmap, and the broker's decision log.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

if __package__ in (None, ""):           # `python benchmarks/run.py ...`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    __package__ = "benchmarks"          # noqa: A001 — relative imports below


def csv_writer(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


def write_json_artifact(name: str, result, wall_s: float) -> None:
    path = f"BENCH_{name}.json"
    payload = {"bench": name, "wall_seconds": wall_s, "result": result}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=str)
    print(f"# wrote {path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("bench", nargs="?", default=None,
                    help="run only benches whose name starts with this "
                         "prefix (e.g. 'churn', 'joint')")
    ap.add_argument("--only", default=None,
                    help="run exactly this bench (exact-name form of BENCH)")
    ap.add_argument("--steps", type=int, default=80,
                    help="convergence steps (Fig. 8)")
    ap.add_argument("--churn-profile", default="gpt2-xl",
                    choices=["gpt2-xl", "tiny"],
                    help="churn bench workload (tiny = CI smoke)")
    ap.add_argument("--churn-migration-mode", default=None,
                    choices=["stop", "overlap"],
                    help="force every elastic churn system onto one "
                         "migration mode (CI smokes the overlap defaults)")
    ap.add_argument("--joint-profile", default="gpt2-xl",
                    choices=["gpt2-xl", "tiny", "hetero"],
                    help="joint planning bench workload (tiny = CI smoke, "
                         "hetero = the mixed-width chain the perf baseline "
                         "is pinned on)")
    ap.add_argument("--serving-profile", default="geo",
                    choices=["geo", "tiny"],
                    help="swarm serving bench workload (tiny = CI smoke)")
    ap.add_argument("--trace", action="store_true",
                    help="record span traces + the broker flight recorder "
                         "on supporting benches; writes TRACE_*/FLIGHT_* "
                         "artifacts and prints the run report")
    ap.add_argument("--json", action="store_true",
                    help="write a BENCH_<name>.json artifact per bench")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from . import (ablation_microbatch, churn, convergence, gpu_table,
                   joint_planning, kernel_bench, latency, ratio_sweep,
                   roofline_table, serving, speedup_table)

    benches = {
        "churn_elastic": lambda: churn.run(
            csv_writer, profile=args.churn_profile,
            migration_mode=args.churn_migration_mode, trace=args.trace),
        "joint_planning": lambda: joint_planning.run(
            csv_writer, profile=args.joint_profile),
        "table1_gpu": lambda: gpu_table.run(csv_writer),
        "fig8_convergence": lambda: convergence.run(csv_writer,
                                                    steps=args.steps),
        "fig10_latency": lambda: latency.run(csv_writer),
        "fig11_ratio": lambda: ratio_sweep.run(csv_writer),
        "speedup_headline": lambda: speedup_table.run(csv_writer),
        "kernel_topk": lambda: kernel_bench.run(csv_writer),
        "serving_swarm": lambda: serving.run(
            csv_writer, profile=args.serving_profile, trace=args.trace),
        "ablation_nmicro": lambda: ablation_microbatch.run(csv_writer),
        "roofline": lambda: roofline_table.run(csv_writer),
    }
    if args.bench and not any(n.startswith(args.bench) for n in benches):
        print(f"# no bench matches prefix {args.bench!r}; "
              f"available: {sorted(benches)}", file=sys.stderr)
        raise SystemExit(2)
    failed = []
    for name, fn in benches.items():
        if args.only and args.only != name:
            continue
        if args.bench and not name.startswith(args.bench):
            continue
        t0 = time.time()
        try:
            result = fn()
            wall = time.time() - t0
            csv_writer(f"{name}__wall", wall * 1e6, "ok")
            if args.json:
                write_json_artifact(name, result, wall)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            csv_writer(f"{name}__wall", (time.time() - t0) * 1e6,
                       f"FAILED:{type(e).__name__}")
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
