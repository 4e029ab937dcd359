"""codec_roofline: the codec's least time over its measured time.

Least time = the bytes the wire format needs on every compressed edge
(``arith.codec_bytes``: encode reads the dense tensor and writes k values
per block and a one-bit mask; decode reads those and writes the tensor),
in each direction and for each call a step makes, over the chip's peak HBM
bandwidth.  The codec has almost no arithmetic, so bandwidth bounds it.
Measured time = ``codec_ms_per_step``.  Moves ``tokens_per_s``.
"""
from chipbench.arith import codec_bytes
from chipbench.metrics.codec_ms_per_step import codec_ms


def read(rec):
    ms, count = codec_ms(rec)
    if not count or not rec.codec_edges:
        return None
    need = sum(e["calls_per_step"] * codec_bytes(
        e["n"], e["itemsize"], e["k_per_block"], e["block"])
        for e in rec.codec_edges)
    least_ms = 1e3 * need / rec.peak["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
