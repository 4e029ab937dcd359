"""codec_ms_per_step: device time of the top-k codec's Pallas kernels per
training step, summed over the chips.

The kernels appear in the ``XLA Ops`` line of a TPU trace as custom calls
(``custom_call_target="tpu_custom_call"``) named after the jitted wrappers
of ``repro.kernels.ops``: ``_encode_pallas.<n>``, ``_decode_pallas.<n>`` and
``_ef_encode_pallas.<n>`` (read from a v5e trace of the RAD AdaTopK step).
XLA's copies and transposes around them are not counted.  Moves
``tokens_per_s``.
"""
import re

from chipbench.trace_reduce import sum_ns

#: instruction names of the codec's kernels
CODEC_OPS = re.compile(r"^_(ef_)?(encode|decode)_pallas(\.\d+)?$")


def is_codec(op):
    return bool(CODEC_OPS.match(op.name)) and "tpu_custom_call" in op.text


def codec_ms(rec):
    """(codec milliseconds per step, kernel events) from the trace."""
    if rec.trace is None or rec.steps == 0:
        return None, 0
    total, count = 0.0, 0
    for ops in rec.trace.devices.values():
        ns, k = sum_ns(ops, is_codec)
        total, count = total + ns, count + k
    return total / 1e6 / rec.steps, count


def read(rec):
    ms, count = codec_ms(rec)
    return ms if count else None
