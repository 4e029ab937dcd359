"""What the paths share: the host spans of the closed loop, the traffic's
batches, and the check of the planned compression."""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import numpy as np

from chipbench.traffic_gen import Feed, host_batches


def span(name: str):
    """A host span in the profiler's trace (nearly free when no trace
    runs)."""
    return jax.profiler.TraceAnnotation(name)


class Fetch:
    """A step's loss, fetched to the host inside a ``bench.fetch`` span."""

    def __init__(self, loss):
        self.loss = loss

    def __float__(self):
        with span("bench.fetch"):
            return float(self.loss)


class Dispatch:
    """A compiled step called inside a ``bench.dispatch`` span, its loss
    handed back as a :class:`Fetch`."""

    def __init__(self, compiled):
        self.compiled = compiled

    def __call__(self, params, state, batch):
        with span("bench.dispatch"):
            params, state, loss = self.compiled(params, state, batch)
        return params, state, Fetch(loss)


def make_feed(traffic: dict, conf: dict, seed: int,
              place: Callable[[Dict[str, np.ndarray]], dict]) -> tuple:
    """(feed, host batches): the seed's batches placed once by ``place``."""
    host = host_batches(traffic, conf["vocab_size"], seed)
    feed = Feed([place(b) for b in host],
                on_batch=lambda: span("bench.batch"))
    return feed, host


def bytes_needed(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def check_edges(found: List[dict], expected: List[dict]) -> List[str]:
    """Differences between the compression the program planned and the
    one the traffic states."""
    key = {e["after"]: e["k_per_block"] for e in expected}
    got = {e["after"]: e["k_per_block"] for e in found}
    return [f"edge after {a}: planned k_per_block {got.get(a)}, "
            f"stated {key.get(a)}"
            for a in sorted(set(key) | set(got)) if key.get(a) != got.get(a)]


def codec_edges(traffic: dict, sizes: Dict[str, int], calls: int
                ) -> List[dict]:
    """The stated compressed edges with their element counts, each run
    ``calls`` times a step in each direction."""
    return [{"after": e["after"], "n": sizes[e["after"]], "itemsize": 4,
             "k_per_block": e["k_per_block"],
             "block": traffic["codec_block"], "calls_per_step": 2 * calls}
            for e in traffic["compressed_edges"]]
