"""attn_ms_per_step: device time of the blocked attention kernel per
training step, summed over the chips.

The kernel's calls appear in the ``XLA Ops`` line of a TPU trace as custom
calls (``custom_call_target="tpu_custom_call"``) named after the jitted
wrappers of ``repro.kernels.flash_attention``: ``_attn_fwd_pallas.<n>`` and
``_attn_bwd_pallas.<n>``.  XLA's ops around them (the ``delta`` reduction
of the backward, the projections) are not counted.  Prints on stderr the
kernel events per step, which say how often the kernel engages: in the RAD
step a forward and a backward call a layer; in the GPipe step, on each
chip, three a layer and tick (the forward, the forward recomputed under
``jax.checkpoint``, the backward).  Nothing to read from a program without
the kernel.  Moves ``tokens_per_s``.
"""
import re
import sys

from chipbench.trace_reduce import sum_ns

#: instruction names of the attention kernel's calls
ATTN_OPS = re.compile(r"^_attn_(fwd|bwd)_pallas(\.\d+)?$")


def is_attn(op):
    return bool(ATTN_OPS.match(op.name)) and "tpu_custom_call" in op.text


def read(rec):
    if rec.trace is None or rec.steps == 0:
        return None
    total, count = 0.0, 0
    for ops in rec.trace.devices.values():
        ns, k = sum_ns(ops, is_attn)
        total, count = total + ns, count + k
    if not count:
        return None
    print(f"attn kernel events per step: {count / rec.steps:g}",
          file=sys.stderr)
    return total / 1e6 / rec.steps
