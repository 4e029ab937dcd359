"""rad_stage_ms_per_step: device time per training step of the ops in the
program's stage scopes (``rad/s<i>/fwd|bwd``: every stage's forward and
backward, the codec excluded), summed over the chips.  Nothing to read from
a program without scopes.  Moves ``tokens_per_s``.

It also prints, on standard error, the whole step by scope (``scopes:``,
ms per step by stage and direction, by edge and direction, ``optim`` and
``unscoped``), so that every traced run shows which stage and edge hold
the step.
"""
import json
import sys

from chipbench.scope_reduce import kind_ms, labelled, ms_by_scope


def read(rec):
    by = ms_by_scope(rec)
    if by is None:
        return None
    print("scopes: " + json.dumps(labelled(by)), file=sys.stderr)
    return kind_ms(rec, "stage")
