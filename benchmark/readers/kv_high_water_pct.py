"""Most pages of the KV pool ever allocated at once, as a share of the
pool (``stats()["kv_pages"]``; the pool's own high-water mark, which
includes the warm-up)."""


def read(run):
    kv = run.counters.get("kv_pages")
    if not kv or not kv.get("capacity"):
        return None
    return 100.0 * kv["high_water"] / kv["capacity"]
