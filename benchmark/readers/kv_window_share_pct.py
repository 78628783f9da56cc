"""The window layers' share of all the cache bytes held at high water
(``stats()["kv_pages"]``): the per-slot rings of the slots that were
ever live at once (``window_high_water_bytes``) over those and the
global layers' pages at their own high water (``high_water`` x
``page_bytes``). A window layer keeps its window and a page of
positions a slot whatever the context, so with 4 of 5 layers windowed
the share stays small; it would be 80 if they held whole contexts. An
engine whose model has no window layer reports no such bytes."""


def read(run):
    kv = run.counters.get("kv_pages")
    if not kv or "window_high_water_bytes" not in kv:
        return None
    window = kv["window_high_water_bytes"]
    held = window + kv["high_water"] * kv["page_bytes"]
    return 100.0 * window / held if held else None
