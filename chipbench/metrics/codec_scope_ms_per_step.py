"""codec_scope_ms_per_step: device time per training step of every op in
the program's codec scopes (``rad/edge/<producer>/s<j>/fwd|bwd``), summed
over the chips: the codec's kernels and the layout ops XLA puts around
them.  At least ``codec_ms_per_step``, which counts the kernels alone.
Nothing to read from a program without scopes.  Moves ``tokens_per_s``.
"""
from chipbench.scope_reduce import kind_ms


def read(rec):
    return kind_ms(rec, "codec")
