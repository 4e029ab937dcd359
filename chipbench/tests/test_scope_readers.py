"""The readers of the program's device scopes against a synthetic trace and
a scope table put in place of the live-executable lookup."""
import json
import sys

import pytest

from chipbench import scope_reduce
from chipbench import trace_reduce as T
from chipbench.run import Record, load_module
from repro.obs.scopes import classify

KERNEL = ' = f32[8]{0} custom-call(f32[8]{0} %p), ' \
         'custom_call_target="tpu_custom_call"'
EDGE = "jit(step)/rad/edge/head/s4"
#: (instruction, ns on each chip, op_name of its metadata or None)
OPS = [
    ("_encode_pallas.5", 100, f"{EDGE}/fwd/jit(_encode_pallas)/pallas_call"),
    ("copy.3", 20, f"{EDGE}/fwd/reshape"),
    ("_decode_pallas.6", 50, f"{EDGE}/bwd/jit(_decode_pallas)/pallas_call"),
    ("fusion.1", 300, "jit(step)/rad/s0/fwd/jvp()/dot_general"),
    ("fusion.2", 200, "jit(step)/rad/s3/bwd/transpose(jvp())/mul"),
    ("fusion.3", 80, "jit(step)/optim/sub"),
    ("copy.9", 10, None),
]
STEPS = 2
MS = 1e-6 / STEPS                       # ns on one chip -> ms per step


def trace(chips=2, extra=()):
    devices = {}
    for c in range(chips):
        t, ops = 0.0, []
        for name, ns, _ in list(OPS) + list(extra):
            ops.append(T.Op(t, t + ns, name, f"%{name}{KERNEL}"
                            if "pallas" in name else f"%{name} = f32[8]"))
            t += ns
        devices[f"/device:TPU:{c}"] = ops
    return T.Trace(window=(0.0, 2000.0), devices=devices, spans=[])


def record(tr, steps=STEPS):
    return Record(steps=steps, window_s=tr.window_ns / 1e9, chips=2,
                  tokens_per_step=8, flops_per_step=1e6,
                  peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                  compile_s=1.0, codec_edges=[], trace=tr)


TABLE = {name: classify(op) if op else None for name, _, op in OPS}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(scope_reduce, "lookup", lambda names: dict(TABLE))


def read(name, rec):
    return load_module("metrics", name).read(rec)


def test_each_reader_sums_its_scopes_over_the_chips(table):
    rec = record(trace())
    assert read("codec_scope_ms_per_step", rec) == pytest.approx(
        2 * (100 + 20 + 50) * MS)
    assert read("rad_stage_ms_per_step", rec) == pytest.approx(
        2 * (300 + 200) * MS)
    assert read("optim_ms_per_step", rec) == pytest.approx(2 * 80 * MS)


def test_scopes_and_the_unscoped_rest_add_up_to_all_op_time(table):
    extra = [("fusion.77", 40, None)]          # in no table: unscoped
    rec = record(trace(extra=extra))
    by = scope_reduce.ms_by_scope(rec)
    parts = (read("codec_scope_ms_per_step", rec)
             + read("rad_stage_ms_per_step", rec)
             + read("optim_ms_per_step", rec) + by[None])
    total = sum(o.end - o.start for ops in rec.trace.devices.values()
                for o in ops) * MS
    assert by[None] == pytest.approx(2 * (10 + 40) * MS)
    assert parts == pytest.approx(total)


def test_codec_scope_holds_at_least_the_codec_kernels(table):
    rec = record(trace())
    kernels = read("codec_ms_per_step", rec)
    assert kernels == pytest.approx(2 * (100 + 50) * MS)
    assert read("codec_scope_ms_per_step", rec) >= kernels


def test_the_scopes_line_names_every_scope_and_the_rest(table, capsys):
    read("rad_stage_ms_per_step", record(trace()))
    err = capsys.readouterr().err.splitlines()
    line = next(l for l in err if l.startswith("scopes: "))
    got = json.loads(line[len("scopes: "):])
    assert got == pytest.approx({
        "rad/s0/fwd": 2 * 300 * MS, "rad/s3/bwd": 2 * 200 * MS,
        "rad/edge/head/s4/fwd": 2 * 120 * MS,
        "optim": 2 * 80 * MS, "rad/edge/head/s4/bwd": 2 * 50 * MS,
        "unscoped": 2 * 10 * MS})
    assert list(got) == sorted(got, key=lambda k: -got[k])


READERS = ("codec_scope_ms_per_step", "rad_stage_ms_per_step",
           "optim_ms_per_step")


@pytest.mark.parametrize("reader", READERS)
def test_nothing_to_read_without_a_table(monkeypatch, reader):
    rec = record(trace())
    monkeypatch.setattr(scope_reduce, "lookup", lambda names: None)
    assert read(reader, rec) is None
    # a table of another program's instructions covers none of the ops
    monkeypatch.setattr(scope_reduce, "lookup", lambda names: {
        "fusion.5000": classify("jit(f)/optim/add")})
    assert read(reader, record(trace())) is None
    # a table that covers the ops but names no scope: a program without
    # scopes
    monkeypatch.setattr(scope_reduce, "lookup",
                        lambda names: dict.fromkeys(TABLE))
    assert read(reader, record(trace())) is None


@pytest.mark.parametrize("reader", READERS)
def test_nothing_to_read_from_a_program_without_the_scopes_module(
        monkeypatch, reader):
    monkeypatch.setitem(sys.modules, "repro.obs.scopes", None)
    assert read(reader, record(trace())) is None


@pytest.mark.parametrize("reader", READERS)
def test_nothing_to_read_from_an_empty_window(table, reader):
    assert read(reader, record(trace(), steps=0)) is None
    empty = T.Trace(window=(0.0, 1.0), devices={"/device:TPU:0": []},
                    spans=[])
    assert read(reader, record(empty)) is None
