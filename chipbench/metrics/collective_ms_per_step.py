"""collective_ms_per_step: device time of the pipeline's exchange per step,
on the chip that spends most: the durations of its ``collective-permute``
events (the ``ppermute`` of boundary activations and their gradients).
Moves ``tokens_per_s``.

Awaiting a cell: only the gpipe path makes these events, and no workload in
``BENCHMARK.json`` uses it yet, so nothing reads this metric on a chip."""
from chipbench.trace_reduce import sum_ns


def _permute(op):
    return op.name.startswith("collective-permute")


def read(rec):
    if rec.trace is None or rec.steps == 0:
        return None
    found = [sum_ns(ops, _permute) for ops in rec.trace.devices.values()]
    if not any(k for _, k in found):
        return None
    return max(ns for ns, _ in found) / 1e6 / rec.steps
