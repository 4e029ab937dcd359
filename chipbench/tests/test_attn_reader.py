"""The attention kernel's reader, ``attn_ms_per_step``, against a small
recorded trace: two chips, the kernel's forward and backward calls among a
fusion and a codec kernel."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as T
from chipbench.run import Record, load_module

DATA = Path(__file__).resolve().parent / "data" / "attention.xplane.pbtxt"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    text = DATA.read_text()
    return T.load(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)))


def record(trace, steps):
    return Record(steps=steps, window_s=trace.window_ns / 1e9, chips=2,
                  tokens_per_step=8, flops_per_step=1e6,
                  peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                  compile_s=2.5, codec_edges=[], trace=trace)


def read(rec):
    return load_module("metrics", "attn_ms_per_step").read(rec)


def test_reads_the_kernel_calls_summed_over_the_chips(trace, capsys):
    # TPU:0: 100 + 250 + 100 + 250; TPU:1: 120 + 260 (ns), over 2 steps
    assert read(record(trace, steps=2)) == pytest.approx(1080e-6 / 2)
    assert "attn kernel events per step: 3\n" in capsys.readouterr().err


def test_reads_nothing_without_kernel_events(trace):
    others = {d: [o for o in ops if not o.name.startswith("_attn_")]
              for d, ops in trace.devices.items()}
    empty = T.Trace(window=trace.window, devices=others, spans=trace.spans)
    assert read(record(empty, steps=2)) is None
    assert read(record(trace, steps=0)) is None
