"""The benchmark's readers on the GPipe step's device scopes, against a
synthetic four-chip trace and a scope table put in place of the
live-executable lookup (as ``chipbench/tests/test_scope_readers.py`` does
for the RAD step's): what they would read in a four-chip cell of that
step."""
import json

import pytest

from chipbench import scope_reduce
from chipbench import trace_reduce as T
from chipbench.run import Record, load_module
from repro.obs.scopes import classify

KERNEL = ' = f32[8]{0} custom-call(f32[8]{0} %p), ' \
         'custom_call_target="tpu_custom_call"'
BODY = "jit(step)/jvp()/shard_map/while/body/closed_call"
BACK = "jit(step)/transpose(jvp())/shard_map/while/body/closed_call"
EDGE = "cond/branch_1_fun/pipe/edge/block_23"
#: (instruction, opcode, ns on chip c is ns * (c + 1), op_name or None)
OPS = [
    ("fusion.1", "fusion", 300, f"{BODY}/pipe/blocks/while/body/dot_general"),
    ("fusion.2", "fusion", 500,
     f"{BACK}/pipe/blocks/while/body/checkpoint/dot_general"),
    ("fusion.3", "fusion", 40, f"{BODY}/pipe/embed/jit(_take)/gather"),
    ("fusion.4", "fusion", 60, f"{BACK}/pipe/embed/scatter-add"),
    ("fusion.5", "fusion", 70, f"{BODY}/pipe/head/dot_general"),
    ("fusion.6", "fusion", 90, f"{BACK}/pipe/head/dot_general"),
    ("_encode_pallas.3", "custom-call", 20,
     f"{BODY}/{EDGE}/jit(_encode_pallas)/pallas_call"),
    ("_decode_pallas.4", "custom-call", 10,
     f"{BACK}/{EDGE}/jit(_decode_pallas)/pallas_call"),
    ("collective-permute-start.1", "collective-permute-start", 8,
     f"{BODY}/pipe/exchange/ppermute"),
    ("collective-permute-done.1", "collective-permute-done", 2,
     f"{BODY}/pipe/exchange/ppermute"),
    ("all-reduce.2", "all-reduce", 30,
     "jit(step)/transpose(jvp())/shard_map/pipe/exchange/psum_invariant"),
    ("fusion.7", "fusion", 25, "jit(step)/optim/add"),
    ("copy.9", "copy", 5, None),
    # control flow: holds the ops above, never counted again
    ("while.5", "while", 5000, "jit(step)/jvp()/shard_map/while"),
    ("conditional.6", "conditional", 40, f"{BODY}/cond"),
    ("while.7", "while", 900, f"{BODY}/pipe/blocks/while"),
]
CHIPS = 4
STEPS = 2
MS = 1e-6 / STEPS                       # ns on one chip -> ms per step
SCALE = sum(range(1, CHIPS + 1))        # ns summed over the chips


def _text(name, opcode):
    if "pallas" in name:
        return f"%{name}{KERNEL}"
    return f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)"


def trace(ops=OPS):
    devices = {}
    for c in range(CHIPS):
        t, out = 0.0, []
        for name, opcode, ns, _ in ops:
            out.append(T.Op(t, t + ns * (c + 1), name, _text(name, opcode)))
            t += ns * (c + 1)
        devices[f"/device:TPU:{c}"] = out
    return T.Trace(window=(0.0, 1e6), devices=devices, spans=[])


def record(tr, steps=STEPS):
    return Record(steps=steps, window_s=tr.window_ns / 1e9, chips=CHIPS,
                  tokens_per_step=8, flops_per_step=1e6,
                  peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                  compile_s=1.0, codec_edges=[], trace=tr)


TABLE = {name: classify(op) if op else None for name, _, _, op in OPS}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(scope_reduce, "lookup", lambda names: dict(TABLE))


def read(name, rec):
    return load_module("metrics", name).read(rec)


def test_codec_readers_read_the_pod_edge(table):
    rec = record(trace())
    kernels = SCALE * (20 + 10) * MS
    assert read("codec_ms_per_step", rec) == pytest.approx(kernels)
    assert read("codec_scope_ms_per_step", rec) == pytest.approx(kernels)


def test_collective_reads_the_permutes_of_the_busiest_chip(table):
    got = read("collective_ms_per_step", record(trace()))
    assert got == pytest.approx(CHIPS * (8 + 2) * MS)


def test_optim_reads_the_gpipe_steps_adam(table):
    got = read("optim_ms_per_step", record(trace()))
    assert got == pytest.approx(SCALE * 25 * MS)


@pytest.mark.parametrize("reader", ("codec_scope_ms_per_step",
                                    "optim_ms_per_step"))
def test_scope_readers_read_nothing_without_a_scope_table(monkeypatch,
                                                          reader):
    monkeypatch.setattr(scope_reduce, "lookup", lambda names: None)
    assert read(reader, record(trace())) is None


def test_pipe_blocks_reads_the_busiest_chip_without_control_flow(table,
                                                                 capsys):
    got = read("pipe_blocks_ms_per_step", record(trace()))
    assert got == pytest.approx(CHIPS * (300 + 500) * MS)
    err = capsys.readouterr().err
    assert "pipe scopes: " in err and "pipe unscoped share: " in err
    line = next(x for x in err.splitlines() if x.startswith("pipe scopes: "))
    chips = json.loads(line[len("pipe scopes: "):])
    assert set(chips) == {f"/device:TPU:{c}" for c in range(CHIPS)}
    last = chips[f"/device:TPU:{CHIPS - 1}"]
    assert last["pipe/edge/block_23/fwd"] == pytest.approx(CHIPS * 20 * MS)
    assert last["unscoped"] == pytest.approx(CHIPS * 5 * MS)
    assert not any("while" in k or "conditional" in k for k in last)


def test_pipe_embed_head_sums_the_chips(table):
    got = read("pipe_embed_head_ms_per_step", record(trace()))
    assert got == pytest.approx(SCALE * (40 + 60 + 70 + 90) * MS)


@pytest.mark.parametrize("reader", ("pipe_blocks_ms_per_step",
                                    "pipe_embed_head_ms_per_step"))
def test_pipe_readers_read_nothing_without_gpipe_scopes(monkeypatch, reader):
    """No table (a program without scopes), a table whose scopes are not
    the GPipe step's (the RAD step's), or an empty trace: nothing to read,
    and nothing raised."""
    rec = record(trace())
    monkeypatch.setattr(scope_reduce, "lookup", lambda names: None)
    assert read(reader, rec) is None
    rad = {name: classify("jit(step)/rad/s3/fwd/dot_general")
           for name, _, _, _ in OPS}
    monkeypatch.setattr(scope_reduce, "lookup", lambda names: dict(rad))
    assert read(reader, record(trace())) is None
    assert read(reader, record(trace(), steps=0)) is None
