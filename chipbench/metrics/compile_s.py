"""compile_s: host seconds of the step's ``lower().compile()`` in set-up,
from the persistent cache on every run after a checkout's first.  Moves
``setup_s``."""


def read(rec):
    return rec.compile_s
