"""pipe_blocks_ms_per_step: device time per training step of the GPipe
step's blocks (``pipe/blocks``: a stage's layers in every tick, forward,
and backward with the forward recomputed), on the chip that spends most.
Nothing to read from a program without the GPipe scopes.  Moves
``tokens_per_s``.

The step's ops map to scopes through ``scope_reduce``'s table.  Each chip
is read apart, and control-flow instructions (``while``, ``conditional``,
``call``) are left out: their events span the ops of their bodies, which
count themselves.

It also prints, on standard error, each chip's step by scope
(``pipe scopes:``, ms per step by part and direction, by edge, ``optim``
and ``unscoped``) and the unscoped share of all op time.
"""
import json
import re
import sys
from collections import defaultdict

from chipbench.scope_reduce import labelled, table_for

#: the GPipe step's name for itself in a scope (``repro.obs.scopes.PIPE``)
PIPE = "pipe"
_CONTROL = re.compile(r"(?<![\w-])(?:while|conditional|call)\(")


def by_chip(rec):
    """``{chip: {scope: device ms per step}}`` (ops of no scope under
    None), or None when no op maps to a GPipe scope."""
    if rec.trace is None or rec.steps == 0:
        return None
    table = table_for(rec)
    if not table:
        return None
    out = {}
    for chip, ops in rec.trace.devices.items():
        ms = defaultdict(float)
        for o in ops:
            if not _CONTROL.search(o.text):
                ms[table.get(o.name)] += (o.end - o.start) / 1e6 / rec.steps
        out[chip] = dict(ms)
    if not any(getattr(s, "step", None) == PIPE for ms in out.values()
               for s in ms):
        return None
    return out


def part_ms(ms, *kinds):
    """Milliseconds of one chip's GPipe scopes of the given kinds."""
    return sum(v for s, v in ms.items() if getattr(s, "step", None) == PIPE
               and s.kind in kinds)


def read(rec):
    chips = by_chip(rec)
    if chips is None:
        return None
    total = sum(sum(ms.values()) for ms in chips.values())
    unscoped = sum(ms.get(None, 0.0) for ms in chips.values())
    print("pipe scopes: " + json.dumps(
        {chip: labelled(ms) for chip, ms in chips.items()}), file=sys.stderr)
    print(f"pipe unscoped share: {100.0 * unscoped / total:.3f}% of "
          f"{total:.3f} ms of ops a step over the chips", file=sys.stderr)
    return max(part_ms(ms, "blocks") for ms in chips.values())
