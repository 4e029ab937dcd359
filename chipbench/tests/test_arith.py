"""The benchmark's own arithmetic: FLOPs, codec bytes, peaks."""
import json
import math
from pathlib import Path

import pytest

from chipbench import arith

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,tflop", [("gpt2-xl-rad12", 11.526),
                                        ("gpt2-xl-gpipe48", 40.155)])
def test_train_flops_pinned(name, tflop):
    """Per training step at 4 x 1024 tokens.  These are higher than the
    10.39 and 35.62 that ``repro.analysis.model_flops`` gives, because that
    copy counts the K and V projections of one sequence, not of every
    token of the batch."""
    got = arith.train_flops(conf(name), 4, 1024) / 1e12
    assert got == pytest.approx(tflop, abs=5e-4)


def test_forward_flops_by_hand():
    c = {"n_embd": 8, "n_head": 2, "n_layer": 3, "vocab_size": 10,
         "vocab_pad_to": 4}
    t, s, d, ff, v = 2 * 5, 5, 8, 32, 12
    per_layer = 2 * t * d * d * 4 + 2 * t * s * d + 2 * t * d * ff * 2
    assert arith.forward_flops(c, 2, 5) == 3 * per_layer + 2 * t * d * v


def test_k_per_block_matches_the_planned_edges():
    # gpt2-xl's boundary at 4 x 1024 and at 1 x 1024, ratio 300; the
    # 4 x 1024 x 50432 logits at the ratio AdaTopK gives them
    assert arith.k_per_block(4 * 1024 * 1600, 300.0, 4096) == 14
    assert arith.k_per_block(1024 * 1600, 300.0, 4096) == 14
    assert arith.k_per_block(4 * 1024 * 50432, 8.47730987381139, 4096) == 484
    assert arith.k_per_block(4096, 1.0, 4096) == 4096


def test_codec_bytes_counts_the_wire_format():
    n, s, k, b = 4096 * 3, 4, 10, 4096
    wire = 3 * k * s + n / 8
    assert arith.codec_bytes(n, s, k, b) == 2 * (n * s + wire)
    # the logits edge, one direction: ~1.90 GB of HBM traffic
    got = arith.codec_bytes(4 * 1024 * 50432, 4, 484, 4096)
    assert math.isclose(got, 1.8995e9, rel_tol=1e-3)


def test_peaks_known_and_unknown():
    p = arith.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        arith.peaks("TPU v9 imaginary")
