"""Share of the engine's load time (``ckpt.load.state``) spent planning:
the region plan, the element lift and the fast path's test
(``ckpt.load.plan``), percent."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.share_of(rec, ("ckpt.load.plan",), "ckpt.load.state")
