"""Seconds per restart building the star forests that map the loaded
mesh to the saved numbering: directories, queries, composes
(``fe.directory``)."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.per_restart_s(rec, "fe.directory")
