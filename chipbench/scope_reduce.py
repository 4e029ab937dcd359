"""Device time of a traced run by the program's own scopes.

The program labels the RAD step's work with ``jax.named_scope``s
(``repro.obs.scopes``): each stage's forward and backward, the codec on
each compressed boundary edge in each direction, and the optimizer.  The
labels live in the compiled module's ``metadata={op_name=...}``, not in
the ops of ``trace_reduce.Trace``: a trace op maps by its instruction name
through the table that :func:`instruction_scopes` reads from the live
executable that ran it (:func:`live_step_scopes`).  A program without the
scopes gives no table, and the readers of this module then report nothing.

An instruction takes the scope of the matmul it holds, if it holds one,
else that of its own op_name.  XLA fuses the optimizer's update of a weight
into the fusion that computes the weight's gradient and names the fusion
after either; the matmul decides, so a stage's scope holds all of its
matmuls and ``optim`` holds only the updates XLA left outside them.  An
instruction without an op_name (a layout copy, a step of a split-out
reduction) takes the one scope its users share, else the one scope its
operands share.
"""
from __future__ import annotations

import re
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple

#: label of the ops that map to no scope
UNSCOPED = "unscoped"

# an HLO computation's header and instruction, as the text printer writes them
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MATMUL = re.compile(r"\s(?:dot|convolution)\(")
_FUSION = re.compile(r"\bfusion\(")
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def _classifier():
    """The program's ``classify(op_name)``, or None for a program without
    device scopes."""
    try:
        from repro.obs.scopes import classify
    except ImportError:
        return None
    return classify


def _computations(hlo_text: str) -> Dict[str, List[Tuple[str, str]]]:
    """``{computation: [(instruction, text after "=")]}``, in print order
    (each instruction after its operands)."""
    comps: Dict[str, List[Tuple[str, str]]] = {}
    body = None
    for line in hlo_text.splitlines():
        if body is None:
            m = _COMPUTATION.match(line)
            if m:
                body = comps.setdefault(m.group(1), [])
        elif line.strip() == "}":
            body = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                body.append((m.group(1), m.group(2)))
    return comps


def instruction_scopes(hlo_text: str, classify) -> Dict[str, object]:
    """``{instruction name: scope or None}`` for every instruction of an HLO
    module's text (the compiled, optimized module, whose instruction names
    a profiler trace shows), fusion bodies left out; ``classify`` maps an
    op_name to its scope.  The rules are the module docstring's."""
    comps = _computations(hlo_text)
    fused = {c for body in comps.values() for _, text in body
             if _FUSION.search(text) for c in _CALLS.findall(text)}

    def own(text):
        op = _OP_NAME.search(text)
        return classify(op.group(1)) if op else None

    def matmuls(comp: str) -> Counter:
        """Scopes of the matmuls in ``comp`` and the computations it
        calls."""
        out: Counter = Counter()
        for _, text in comps.get(comp, ()):
            scope = own(text) if _MATMUL.search(text) else None
            if scope is not None:
                out[scope] += 1
            for c in _CALLS.findall(text):
                out += matmuls(c)
        return out

    def matmul_scope(text: str):
        found: Counter = Counter()
        for c in _CALLS.findall(text):
            found += matmuls(c)
        return found.most_common(1)[0][0] if found else None

    table: Dict[str, object] = {}
    for comp, body in comps.items():
        if comp in fused:
            continue
        users: Dict[str, set] = defaultdict(set)
        orphans = []
        for name, text in reversed(body):
            operands = _REF.findall(text.split(", metadata=")[0])
            scope = matmul_scope(text) or own(text)
            if scope is None and not _OP_NAME.search(text):
                if len(users[name]) == 1:
                    scope = next(iter(users[name]))
                if scope is None:
                    orphans.append((name, operands))
            table[name] = scope
            for ref in operands:
                users[ref].add(scope)
        for name, operands in reversed(orphans):
            found = {table.get(r) for r in operands} - {None}
            if len(found) == 1:
                table[name] = found.pop()
    return table


def live_step_scopes(names) -> Optional[Dict[str, object]]:
    """The :func:`instruction_scopes` table of the live executable whose
    instruction names cover most of ``names`` (the ops of a trace), or None
    when the program has no scopes or no live executable names any of
    them."""
    classify = _classifier()
    if classify is None:
        return None
    import jax
    wanted = set(names)
    best, hits = None, 0
    for exe in jax.devices()[0].client.live_executables():
        for module in exe.hlo_modules():
            table = instruction_scopes(module.to_string(), classify)
            n = len(wanted.intersection(table))
            if n > hits:
                best, hits = table, n
    return best


#: the lookup the readers use (a test puts a table in its place)
lookup = live_step_scopes

#: (record, lookup, table) of the last lookup: the readers share it
_last: list = [None, None, None]


def table_for(rec):
    """The scope table covering ``rec``'s trace ops, or None."""
    if not (_last[0] is rec and _last[1] is lookup):
        names = {o.name for chip in rec.trace.devices.values() for o in chip}
        _last[:] = [rec, lookup, lookup(names) if names else None]
    return _last[2]


def ms_by_scope(rec) -> Optional[Dict[object, float]]:
    """``{scope: device ms per step}`` over the trace's ops, summed over the
    chips; ops that map to no scope under ``None``.  None when the trace is
    empty or no table maps any of its ops to a scope."""
    if rec.trace is None or rec.steps == 0:
        return None
    table = table_for(rec)
    ops = [o for chip in rec.trace.devices.values() for o in chip]
    if not table or all(table.get(o.name) is None for o in ops):
        return None
    out: Dict[object, float] = defaultdict(float)
    for o in ops:
        out[table.get(o.name)] += (o.end - o.start) / 1e6 / rec.steps
    return dict(out)


def kind_ms(rec, kind: str) -> Optional[float]:
    """Device ms per step of the scopes of one ``kind`` (``"codec"``,
    ``"stage"`` or ``"optim"``), or None when nothing maps."""
    by = ms_by_scope(rec)
    if by is None:
        return None
    return sum(ms for s, ms in by.items() if s is not None and s.kind == kind)


def labelled(by: Dict[object, float]) -> Dict[str, float]:
    """``{scope label: ms per step}``, the most time first."""
    named = {(UNSCOPED if s is None else str(s)): ms for s, ms in by.items()}
    return dict(sorted(named.items(), key=lambda kv: -kv[1]))
