"""Set-up seconds: process start to the window's start (imports, build,
compilation or its cache, weights and data, warm-up)."""


def read(rec):
    return rec.setup_s
