"""Seconds per restart resolving entity owners and growing the overlap
(``fe.owners``)."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.per_restart_s(rec, "fe.owners")
