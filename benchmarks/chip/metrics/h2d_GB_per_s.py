"""Bytes over the load time outside the engine: building the arrays on
the target devices after ``load_state``, 10^9 bytes per second."""


def read(rec):
    rest = sum(op.get("load_s", 0.0) - op.get("load_state_s", 0.0)
               for op in rec.ops)
    if rest <= 0:
        return None
    return sum(op["bytes"] for op in rec.ops) / rest / 1e9
