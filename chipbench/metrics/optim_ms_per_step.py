"""optim_ms_per_step: device time per training step of the ops in the
program's ``optim`` scope (the Adam update), summed over the chips.
Nothing to read from a program without scopes.  Moves ``tokens_per_s``.
"""
from chipbench.scope_reduce import kind_ms


def read(rec):
    return kind_ms(rec, "optim")
