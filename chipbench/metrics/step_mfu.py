"""step_mfu: the whole training step's share of the chips' peak.

Model FLOPs of a step (``arith.train_flops``: forward and twice its work
backward, nothing recomputed) times the steps of the traced window, over
the window's seconds and the chips' peak from ``peaks.json``.  Moves
``tokens_per_s``.
"""


def read(rec):
    if rec.steps == 0:
        return None
    return (100.0 * rec.flops_per_step * rec.steps / rec.window_s
            / (rec.chips * rec.peak["flops_per_s"]))
