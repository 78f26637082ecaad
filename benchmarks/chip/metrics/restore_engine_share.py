"""Share of the load time spent in the N-to-M engine
(``TensorCheckpoint.load_state``), percent."""


def read(rec):
    load = sum(op.get("load_s", 0.0) for op in rec.ops)
    if not load:
        return None
    return 100.0 * sum(op["load_state_s"] for op in rec.ops) / load
