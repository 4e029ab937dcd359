"""Device scopes of the training steps: their names, and the scope an
op_name names.

The RAD step opens a ``jax.named_scope`` around each piece of its work
(``core/rad.py``, ``launch/train.py``)::

    rad/s{i}/fwd                        stage i's forward (its ``jax.vjp``)
    rad/s{i}/bwd                        stage i's backward (its vjp closure)
    rad/edge/{producer}/s{j}/fwd|bwd    the codec on the boundary from op
                                        ``producer`` to stage j, activation
                                        (fwd) or gradient (bwd); opened only
                                        where the edge is compressed
    optim                               the optimizer update

The GPipe step (``distributed/pipeline.py``) opens one around each part
of a tick::

    pipe/embed                          the embedding of a micro-batch
    pipe/blocks                         the stage's blocks
    pipe/head                           final norm, head and loss
    pipe/edge/{after}                   the codec on the pod-crossing edge
                                        after block ``after``
    pipe/exchange                       the ``ppermute`` to the next stage
    pipe/ticks                          the tick loop around all of these;
                                        what it holds outside them is the
                                        loop's own work (the carry, the
                                        replicated weights' gradient
                                        all-reduce its transpose makes in
                                        every tick)

Its backward runs under ``jax.grad``, which writes ``transpose(`` into the
op_name of every backward instruction: that, not the scope, tells the
direction (a block's forward recomputed in the backward counts as
backward).

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
#: the steps that name scopes
RAD, PIPE = "rad", "pipe"
#: the GPipe step's parts (each a kind); its edge's kind is :data:`CODEC`
EMBED, BLOCKS, HEAD, EXCHANGE, TICKS = ("embed", "blocks", "head",
                                        "exchange", "ticks")
PIPE_PARTS = (EMBED, BLOCKS, HEAD, EXCHANGE, TICKS)

_EDGE = re.compile(r"(?:^|[/(])rad/edge/([^/()]+)/s(\d+)/(fwd|bwd)(?=$|[/)])")
_STAGE = re.compile(r"(?:^|[/(])rad/s(\d+)/(fwd|bwd)(?=$|[/)])")
_OPTIM = re.compile(r"(?:^|[/(])optim(?=$|[/)])")
_PIPE_EDGE = re.compile(r"(?:^|[/(])pipe/edge/([^/()]+)(?=$|[/)])")
_PIPE = re.compile(r"(?:^|[/(])pipe/(%s)(?=$|[/)])" % "|".join(PIPE_PARTS))


class Scope(NamedTuple):
    """Where an instruction's work belongs.  In the RAD step ``kind`` is
    :data:`CODEC`, :data:`STAGE` or :data:`OPTIM`, and ``where`` is
    ``"{producer}/s{j}"`` for an edge, ``"s{i}"`` for a stage, ``""`` for
    the optimizer.  In the GPipe step (``step`` :data:`PIPE`) ``kind`` is
    one of :data:`PIPE_PARTS` with ``where`` ``""``, or :data:`CODEC` with
    ``where`` the block the edge follows."""

    kind: str
    where: str
    direction: str
    step: str = RAD

    def __str__(self) -> str:
        if self.kind == OPTIM:
            return OPTIM
        if self.step == PIPE:
            part = f"edge/{self.where}" if self.kind == CODEC else self.kind
            return f"pipe/{part}/{self.direction}"
        if self.kind == CODEC:
            return f"rad/edge/{self.where}/{self.direction}"
        return f"rad/{self.where}/{self.direction}"


def _direction(backward: bool) -> str:
    return DIRECTIONS[int(bool(backward))]


def _pipe_direction(op_name: str) -> str:
    return _direction("transpose(" in op_name)


def stage_scope(stage: int, backward: bool) -> str:
    """Scope of stage ``stage``'s forward or backward."""
    return f"rad/s{int(stage)}/{_direction(backward)}"


def edge_scope(producer: str, stage: int, backward: bool) -> str:
    """Scope of the codec on the boundary from op ``producer`` to stage
    ``stage``, in the activation's (forward) or gradient's direction."""
    if not producer or set(producer) & set("/()"):
        raise ValueError(f"producer {producer!r} cannot name a scope")
    return f"rad/edge/{producer}/s{int(stage)}/{_direction(backward)}"


def pipe_scope(part: str) -> str:
    """Scope of one of the GPipe step's :data:`PIPE_PARTS`."""
    if part not in PIPE_PARTS:
        raise ValueError(f"{part!r} is not a part of the GPipe step")
    return f"pipe/{part}"


def pipe_edge_scope(after: str) -> str:
    """Scope of the codec on the GPipe edge after block ``after``."""
    if not after or set(after) & set("/()"):
        raise ValueError(f"block {after!r} cannot name a scope")
    return f"pipe/edge/{after}"


def classify(op_name: str) -> Optional[Scope]:
    """The scope an instruction's op_name names, or None.  A codec scope
    anywhere in the path wins; otherwise the outermost stage, or the
    innermost GPipe part (every part lies inside ``pipe/ticks``); then the
    optimizer.  A GPipe op is backward when its op_name holds a
    ``transpose(``."""
    m = _EDGE.search(op_name)
    if m:
        return Scope(CODEC, f"{m.group(1)}/s{m.group(2)}", m.group(3))
    m = _PIPE_EDGE.search(op_name)
    if m:
        return Scope(CODEC, m.group(1), _pipe_direction(op_name), PIPE)
    m = _STAGE.search(op_name)
    if m:
        return Scope(STAGE, f"s{m.group(1)}", m.group(2))
    parts = _PIPE.findall(op_name)
    if parts:
        return Scope(parts[-1], "", _pipe_direction(op_name), PIPE)
    if _OPTIM.search(op_name):
        return Scope(OPTIM, "", "")
    return None
