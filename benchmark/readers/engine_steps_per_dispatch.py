"""Decode steps fused into one device dispatch (``stats()`` deltas)."""


def read(run):
    c = run.counters
    if not c.get("dispatches"):
        return None
    return c["decode_steps"] / c["dispatches"]
