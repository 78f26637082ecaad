"""Seconds per N-to-M restart: all op time over the completed restarts."""


def read(rec):
    n = sum(op.get("restarts", 0) for op in rec.ops)
    return (rec.window_end - rec.window_t0) / n if n else None
