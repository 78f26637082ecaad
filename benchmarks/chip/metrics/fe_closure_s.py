"""Seconds per restart in the three topology closures of ``load_mesh``
(``fe.close``)."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.per_restart_s(rec, "fe.close")
