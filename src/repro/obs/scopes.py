"""Device scopes of the RAD training step: their names, and the scope an
op_name names.

The step opens a ``jax.named_scope`` around each piece of its work
(``core/rad.py``, ``launch/train.py``)::

    rad/s{i}/fwd                        stage i's forward (its ``jax.vjp``)
    rad/s{i}/bwd                        stage i's backward (its vjp closure)
    rad/edge/{producer}/s{j}/fwd|bwd    the codec on the boundary from op
                                        ``producer`` to stage j, activation
                                        (fwd) or gradient (bwd); opened only
                                        where the edge is compressed
    optim                               the optimizer update

XLA keeps the scope path in each instruction's ``metadata={op_name=...}``,
e.g. ``jit(step)/rad/s3/bwd/transpose(jvp())/dot_general``.  Scopes change
only metadata: the compiled program is otherwise the same.  Reading a
compiled module's instructions by scope is the benchmark's business
(``chipbench/scope_reduce.py``).
"""
from __future__ import annotations

import re
from typing import NamedTuple, Optional

CODEC = "codec"
STAGE = "stage"
#: the optimizer's scope, and its kind
OPTIM = "optim"
DIRECTIONS = ("fwd", "bwd")

_EDGE = re.compile(r"(?:^|[/(])rad/edge/([^/()]+)/s(\d+)/(fwd|bwd)(?=$|[/)])")
_STAGE = re.compile(r"(?:^|[/(])rad/s(\d+)/(fwd|bwd)(?=$|[/)])")
_OPTIM = re.compile(r"(?:^|[/(])optim(?=$|[/)])")


class Scope(NamedTuple):
    """Where an instruction's work belongs: ``kind`` is :data:`CODEC`,
    :data:`STAGE` or :data:`OPTIM`; ``where`` is ``"{producer}/s{j}"`` for
    an edge, ``"s{i}"`` for a stage, ``""`` for the optimizer."""

    kind: str
    where: str
    direction: str

    def __str__(self) -> str:
        if self.kind == OPTIM:
            return OPTIM
        if self.kind == CODEC:
            return f"rad/edge/{self.where}/{self.direction}"
        return f"rad/{self.where}/{self.direction}"


def _direction(backward: bool) -> str:
    return DIRECTIONS[int(bool(backward))]


def stage_scope(stage: int, backward: bool) -> str:
    """Scope of stage ``stage``'s forward or backward."""
    return f"rad/s{int(stage)}/{_direction(backward)}"


def edge_scope(producer: str, stage: int, backward: bool) -> str:
    """Scope of the codec on the boundary from op ``producer`` to stage
    ``stage``, in the activation's (forward) or gradient's direction."""
    if not producer or set(producer) & set("/()"):
        raise ValueError(f"producer {producer!r} cannot name a scope")
    return f"rad/edge/{producer}/s{int(stage)}/{_direction(backward)}"


def classify(op_name: str) -> Optional[Scope]:
    """The scope an instruction's op_name names, or None.  A codec scope
    anywhere in the path wins; otherwise the outermost stage scope; then
    the optimizer."""
    m = _EDGE.search(op_name)
    if m:
        return Scope(CODEC, f"{m.group(1)}/s{m.group(2)}", m.group(3))
    m = _STAGE.search(op_name)
    if m:
        return Scope(STAGE, f"s{m.group(1)}", m.group(2))
    if _OPTIM.search(op_name):
        return Scope(OPTIM, "", "")
    return None
