"""pipe_embed_head_ms_per_step: device time per training step of the GPipe
step's embedding and head (``pipe/embed`` and ``pipe/head``: final norm,
head and loss, forward and backward), summed over the chips.  Every chip
runs both in every tick; only the last stage's active ticks need the head,
so this falls when the step stops doing masked work.  Read as
``pipe_blocks_ms_per_step`` reads.  Nothing to read from a program without
the GPipe scopes.  Moves ``tokens_per_s``.
"""
from chipbench.metrics.pipe_blocks_ms_per_step import by_chip, part_ms


def read(rec):
    chips = by_chip(rec)
    if chips is None:
        return None
    return sum(part_ms(ms, "embed", "head") for ms in chips.values())
