"""device_idle_share: the share of the traced window in which no operation
ran on the chip, from the union of the ``XLA Ops`` events of its trace
plane; on several chips, the largest.  Moves ``tokens_per_s``."""

from chipbench.trace_reduce import busy_ns


def read(rec):
    if rec.trace is None or not any(rec.trace.devices.values()):
        return None
    w = rec.trace.window_ns
    return max(100.0 * (1.0 - busy_ns(ops) / w)
               for ops in rec.trace.devices.values())
