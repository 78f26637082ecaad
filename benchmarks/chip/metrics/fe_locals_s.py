"""Seconds per restart building the ranks' local plexes
(``fe.build_locals``)."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.per_restart_s(rec, "fe.build_locals")
