"""State bytes over the time spent in the device-to-host snapshot
(``snapshot_jax``), 10^9 bytes per second."""


def read(rec):
    times = rec.span_seconds("snapshot")
    if not times:
        return None
    return rec.counters["state_bytes"] * len(times) / sum(times) / 1e9
