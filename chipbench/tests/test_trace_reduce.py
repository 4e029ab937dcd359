"""Trace reduction and the trace-reading metrics against a small recorded
trace: two chips, an overlap of two ops, gaps under two host spans."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as T
from chipbench.run import Record, load_module

DATA = Path(__file__).resolve().parent / "data" / "two_chips.xplane.pbtxt"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    text = DATA.read_text()
    return T.load(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)))


def record(trace, steps=1, codec_edges=()):
    return Record(steps=steps, window_s=trace.window_ns / 1e9, chips=2,
                  tokens_per_step=8, flops_per_step=1e6,
                  peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                  compile_s=2.5, codec_edges=list(codec_edges), trace=trace)


def test_window_and_clipping(trace):
    assert trace.window == (1000.0, 2000.0)
    ops = trace.devices["/device:TPU:0"]
    # fusion.9 started before the window: only its part inside counts
    assert (ops[0].name, ops[0].start, ops[0].end) == ("fusion.9", 1000, 1050)
    assert sorted(trace.devices) == ["/device:TPU:0", "/device:TPU:1"]


def test_busy_is_a_union(trace):
    # TPU:0: [1000, 1550] (three overlapping ops) + [1800, 1950]
    assert T.busy_ns(trace.devices["/device:TPU:0"]) == 700
    assert T.busy_ns(trace.devices["/device:TPU:1"]) == 400


def test_gaps_and_their_host_spans(trace):
    assert T.gaps(trace.devices["/device:TPU:0"], trace.window) == [
        (1550, 1800), (1950, 2000)]
    # the innermost bench.* span open at each gap's middle, averaged
    # over the two chips: (250 + 600) / 2 in bench.fetch, 50 / 2 in
    # bench.batch
    got = dict(T.idle_by_span(trace))
    assert got == pytest.approx({"bench.fetch": 425e-9,
                                 "bench.batch": 25e-9})


def test_top_ops(trace):
    got = dict(T.top_ops(trace))
    assert got["fusion.1"] == pytest.approx((300 + 150 + 400) / 2 * 1e-9)
    assert got["_encode_pallas.4"] == pytest.approx(150e-9)


def test_idle_share_takes_the_idlest_chip(trace):
    share = load_module("metrics", "device_idle_share").read(record(trace))
    assert share == pytest.approx(60.0)        # TPU:1 busy 400 of 1000


def test_codec_and_collective_readers(trace):
    codec = load_module("metrics", "codec_ms_per_step")
    assert codec.read(record(trace, steps=2)) == pytest.approx(300e-6 / 2)
    edge = {"n": 4096, "itemsize": 4, "k_per_block": 16, "block": 4096,
            "calls_per_step": 2}
    roof = load_module("metrics", "codec_roofline").read(
        record(trace, steps=2, codec_edges=[edge]))
    need = 2 * 2 * (4096 * 4 + 16 * 4 + 4096 / 8)      # bytes a step
    assert roof == pytest.approx(100 * (need / 1e9) / (300e-9 / 2))
    coll = load_module("metrics", "collective_ms_per_step")
    assert coll.read(record(trace)) == pytest.approx(50e-6)


def test_readers_report_nothing_rather_than_zero(trace):
    empty = T.Trace(window=trace.window, devices={"/device:TPU:0": []},
                    spans=trace.spans)
    for name in ("codec_ms_per_step", "codec_roofline",
                 "collective_ms_per_step", "device_idle_share"):
        assert load_module("metrics", name).read(record(empty)) is None
