"""Device-to-host bytes per second of the window's saves: the bytes of
the blocking fetches of the leaves' shards over their seconds
(``ckpt.snapshot.d2h``), 10^9 bytes per second."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.rate_GB_per_s(rec, "ckpt.snapshot.d2h")
