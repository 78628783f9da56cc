"""Share of the engine's decode slots holding a live request, averaged
over the decode steps of the window (``stats()`` deltas)."""


def read(run):
    c = run.counters
    if not c.get("decode_steps"):
        return None
    return 100.0 * c["occupancy_sum"] / c["decode_steps"]
