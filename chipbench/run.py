"""One run of one benchmark cell on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``chipbench/configs/<config>.json``, its traffic in
``chipbench/traffic/<traffic>.json`` (which names the path,
``chipbench/paths/<path>.py``), the limits of its comparison in
``chipbench/limits/<cell>.json`` and each per-layer metric's reader in
``chipbench/metrics/<metric>.py``.

Set-up (timed as ``setup_s`` from the start of this process) builds the
step, makes weights and batches from the seed, compiles, and runs the first
steps through the window's own call, reading what the comparison needs.
The window then runs closed-loop steps for ``--seconds`` and ends on a
blocked result.  With ``--trace 1`` the window runs under the profiler and
the per-layer metrics are reported in place of the end-to-end ones.  After
the window the program's state is freed and the plain reference follows the
same first steps; ``correct`` holds when every compared number is within
its limit and no step failed.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before building anything.  The last line of standard output is one
JSON object; the compared numbers and their limits close standard error.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse                                                  # noqa: E402
import dataclasses                                               # noqa: E402
import importlib.util                                            # noqa: E402
import json                                                      # noqa: E402
import math                                                      # noqa: E402
import shutil                                                    # noqa: E402
import sys                                                       # noqa: E402
import tempfile                                                  # noqa: E402
from pathlib import Path                                         # noqa: E402
from typing import Any, Dict, List, Optional                     # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: steps of set-up that the reference follows
CHECK_STEPS = 3


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s ``BENCHMARK.json``, with every file
    it names."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    files = root / "chipbench"
    conf = _json(files / "configs" / f"{w['config']}.json")
    conf.setdefault("name", w["config"])
    traffic = _json(files / "traffic" / f"{w['traffic']}.json")
    limits = _json(files / "limits" / f"{name}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) and m["moves"] in moved]
    return Cell(name, w["chips"], conf, traffic, limits, e2e, per_layer)


def load_module(kind: str, name: str, root: Path = ROOT):
    """``chipbench/<kind>/<name>.py`` under ``root`` as a module."""
    path = root / "chipbench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def enable_compile_cache() -> str:
    """The program's persistent cache placement, keeping every program
    (also those that compile fast)."""
    import jax
    from repro.launch.cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts traces and compilations while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kwargs):
        if self.on and event in self.EVENTS:
            self.count += 1


@dataclasses.dataclass
class Record:
    """What a per-layer metric's reader reads."""
    steps: int
    window_s: float
    chips: int
    tokens_per_step: int
    flops_per_step: float
    peak: dict
    compile_s: float
    codec_edges: List[dict]
    trace: Any = None


def reference(cell: Cell, devices, seed: int, batches, variant: str
              = "reference", precision: Optional[str] = None):
    """Readings of the plain reference (or of one of its variants) over the
    first steps, at the configuration's matmul precision unless
    ``precision`` is given."""
    ref = load_module("reference", cell.conf["reference"])
    t = cell.traffic
    return ref.Trainer(cell.conf, t["compressed_edges"], t["codec_block"],
                       t["optimizer"], devices, variant, precision).run(
        seed, batches, CHECK_STEPS)


def compare(cell: Cell, prog, ref, plan_faults: List[str]) -> Dict[str, dict]:
    from chipbench.compare import checks, numbers
    values = numbers(prog, ref)
    print("numbers: " + json.dumps({k: v for k, (v, _) in values.items()}),
          file=sys.stderr)
    out = checks(values, cell.limits)
    out["plan_mismatch"] = {"value": len(plan_faults), "limit": 0,
                            "ok": not plan_faults,
                            "where": "; ".join(plan_faults)}
    return out


def _window(sess, seconds: float, counter: CompileCounter):
    """Closed-loop steps for ``seconds``; (steps done, failed, seconds)."""
    from chipbench.session import span
    done = failed = 0
    with span("bench.window"):
        counter.on = True
        t0 = time.perf_counter()
        while True:
            try:
                loss = sess.step()
            except Exception as e:                      # noqa: BLE001
                print(f"step {done + failed} raised: {e!r}", file=sys.stderr)
                failed += 1
                break
            if math.isfinite(loss):
                done += 1
            else:
                failed += 1
            if time.perf_counter() - t0 >= seconds:
                break
        sess.sync()
        elapsed = time.perf_counter() - t0
        counter.on = False
    return done, failed, elapsed


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            devices, start: float = START) -> dict:
    """Set-up, window, reference and comparison on ``devices``; the result
    object (with ``checks`` last)."""
    import jax
    from chipbench import arith
    from chipbench.trace_reduce import busy_ns, idle_by_span, load, top_ops

    cache = enable_compile_cache()
    path = load_module("paths", cell.traffic["path"])
    t0 = time.perf_counter()
    sess = path.Session(cell.conf, cell.traffic, seed, devices)
    t1 = time.perf_counter()
    prog = sess.first_steps(CHECK_STEPS)
    counter = CompileCounter()
    setup_s = time.perf_counter() - start
    print(f"setup_s={setup_s:.3f} compile_s={sess.compile_s:.3f} "
          f"step_bytes={sess.hbm_bytes} cache={cache}", file=sys.stderr)
    print(f"setup phases: start_to_session={t0 - start:.3f} "
          f"session={t1 - t0:.3f} first_steps={start + setup_s - t1:.3f} "
          + " ".join(f"{k}={v:.3f}" for k, v in sess.phases.items()),
          file=sys.stderr)

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(tdir, profiler_options=opts):
            done, failed, elapsed = _window(sess, seconds, counter)
    else:
        done, failed, elapsed = _window(sess, seconds, counter)
    print(f"window: steps={done} failed={failed} seconds={elapsed:.3f} "
          f"window_compiles={counter.count}", file=sys.stderr)
    used = sess.ref_devices
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        files = list(Path(tdir).rglob("*.xplane.pb"))
        tr = load(files[0])
        shutil.rmtree(tdir, ignore_errors=True)
        busy = [busy_ns(ops) / 1e9 for ops in tr.devices.values()]
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = tr.window_ns / 1e9
        rec = Record(steps=done, window_s=tr.window_ns / 1e9,
                     chips=cell.chips, tokens_per_step=sess.tokens_per_step,
                     flops_per_step=arith.train_flops(
                         cell.conf, cell.traffic["batch"],
                         cell.traffic["seq"]),
                     peak=arith.peaks(dev0.device_kind),
                     compile_s=sess.compile_s, codec_edges=sess.codec_edges,
                     trace=tr)
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(rec)
            if value is None:
                print(f"{m['name']}: nothing to read in this run",
                      file=sys.stderr)
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": top_ops(tr), "idle_gaps": idle_by_span(tr)}
    else:
        e2e = {"tokens_per_s": done * sess.tokens_per_step / elapsed,
               "step_hbm_gb": sess.hbm_bytes / 1e9, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    host = sess.host_batches[:CHECK_STEPS]
    faults = sess.plan_faults
    sess.free()
    del sess
    held = [(d.memory_stats() or {}).get("bytes_in_use") for d in used]
    print(f"bytes in use once the program's state is freed: {held}",
          file=sys.stderr)
    checks = compare(cell, prog, reference(cell, used, seed, host), faults)
    correct = failed == 0 and all(c["ok"] for c in checks.values())
    result = {"correct": correct, "attempted": done + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for name, c in checks.items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'} {c['where']}",
              file=sys.stderr)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices[:cell.chips])
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
