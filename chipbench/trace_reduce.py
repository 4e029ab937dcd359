"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds one event per executed HLO instruction, named by the
instruction's text (``%fusion.12 = f32[...] fusion(...)``), and a host plane
(``/host:CPU``) whose lines hold the host's spans, the benchmark's own
``bench.*`` annotations among them.  Both run on one clock.  The window is
the ``bench.window`` span; device events are clipped to it.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    start: float            # ns
    end: float              # ns
    name: str               # instruction name, e.g. "fusion.12"
    text: str               # the whole event name (instruction text)


@dataclasses.dataclass
class Trace:
    window: Interval
    devices: Dict[str, List[Op]]          # plane name -> ops in the window
    spans: List[Tuple[float, float, str]]  # bench.* host spans

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def op_name(text: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load(profile) -> Trace:
    """``profile``: a ``jax.profiler.ProfileData`` or a path to an xplane."""
    if not hasattr(profile, "planes"):
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(str(profile))
    spans, devices = [], {}
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.end_ns, e.name))
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        Op(e.start_ns, e.end_ns, op_name(e.name), e.name)
                        for e in line.events]
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w = windows[0][:2]
    clipped = {}
    for name, ops in devices.items():
        clipped[name] = [Op(max(o.start, w[0]), min(o.end, w[1]), o.name,
                            o.text)
                         for o in ops if o.end > w[0] and o.start < w[1]]
    return Trace(window=w, devices=clipped, spans=spans)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: List[Op]) -> float:
    """Length of the union of the intervals in which an op ran."""
    return sum(e - s for s, e in union((o.start, o.end) for o in ops))


def gaps(ops: List[Op], window: Interval) -> List[Interval]:
    """Intervals of the window in which no op ran."""
    out, t = [], window[0]
    for s, e in union((o.start, o.end) for o in ops):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def sum_ns(ops: List[Op], match) -> Tuple[float, int]:
    """Total duration and count of the ops for which ``match(op)``."""
    hits = [o for o in ops if match(o)]
    return sum(o.end - o.start for o in hits), len(hits)


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ops that took most device time, in seconds averaged over the
    devices."""
    tot: Dict[str, float] = defaultdict(float)
    for ops in trace.devices.values():
        for o in ops:
            tot[o.name] += (o.end - o.start) / 1e9
    k = max(1, len(trace.devices))
    return sorted(((name, t / k) for name, t in tot.items()),
                  key=lambda x: -x[1])[:n]


def span_at(trace: Trace, t: float) -> str:
    """Innermost ``bench.*`` span holding time ``t``, or ``"outside"``."""
    best: Optional[Tuple[float, str]] = None
    for s, e, name in trace.spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside"


def idle_by_span(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds of the devices (averaged over them), by the host span
    that was open at the middle of each gap."""
    tot: Dict[str, float] = defaultdict(float)
    for ops in trace.devices.values():
        for s, e in gaps(ops, trace.window):
            tot[span_at(trace, (s + e) / 2)] += (e - s) / 1e9
    k = max(1, len(trace.devices))
    return sorted(((name, t / k) for name, t in tot.items()),
                  key=lambda x: -x[1])[:n]
