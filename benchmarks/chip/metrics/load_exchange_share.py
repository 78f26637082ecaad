"""Share of the engine's load time (``ckpt.load.state``) spent building
star forests (``ckpt.load.sf``) and broadcasting through them
(``ckpt.load.bcast``), percent."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.share_of(rec, ("ckpt.load.sf", "ckpt.load.bcast"),
                      "ckpt.load.state")
