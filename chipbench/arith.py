"""The benchmark's own arithmetic: model FLOPs, codec bytes, device peaks.

Kept with the benchmark so that every change is measured against the same
yardstick.  Nothing here imports the program.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def forward_flops(conf: dict, batch: int, seq: int) -> float:
    """Forward FLOPs of a dense decoder-only transformer for ``batch``
    sequences of ``seq`` tokens: every matmul of the model (Q, K, V and
    output projections, the MLP, the vocabulary head) at 2 FLOPs per
    multiply-add, causal attention at half the S x S score and value work.

    Counted at the published widths in ``conf`` (GPT-2 keys) and its
    ``n_layer``; the head runs over the padded vocabulary the program
    computes.  Recomputation is never counted.
    """
    t = float(batch * seq)
    d = conf["n_embd"]
    d_ff = conf.get("n_inner") or 4 * d
    heads_width = conf["n_head"] * (d // conf["n_head"])
    proj = 2.0 * t * d * heads_width * 4          # q, k, v and out
    scores = 2.0 * 2.0 * t * seq * heads_width / 2.0
    mlp = 2.0 * t * d * d_ff * 2
    head = 2.0 * t * d * padded_vocab(conf)
    return conf["n_layer"] * (proj + scores + mlp) + head


def train_flops(conf: dict, batch: int, seq: int) -> float:
    """One training step: forward plus a backward of twice its work."""
    return 3.0 * forward_flops(conf, batch, seq)


def padded_vocab(conf: dict) -> int:
    pad = conf.get("vocab_pad_to", 1)
    return -(-conf["vocab_size"] // pad) * pad


def k_per_block(n: int, ratio: float, block: int) -> int:
    """Slots per block of the wire format for an edge of ``n`` elements at
    compression ``ratio`` (global k = ceil(n / ratio), split evenly)."""
    if ratio <= 1.0:
        return block
    k = max(1, math.ceil(n / ratio))
    blocks = -(-n // block)
    return max(1, -(-k // blocks))


def codec_bytes(n: int, itemsize: int, k_per_block: int, block: int) -> float:
    """HBM bytes one direction of a compressed edge must move: the encode
    reads the dense tensor and writes k values per block plus a one-bit
    mask of every element; the decode reads those and writes the dense
    tensor back.  This is the work of the wire format, whatever kernel
    does it."""
    blocks = -(-n // block)
    wire = blocks * k_per_block * itemsize + blocks * block / 8.0
    return 2.0 * (n * itemsize + wire)


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return table[device_kind]
