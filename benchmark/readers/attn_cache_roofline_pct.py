"""Share of the memory roofline the attention over a cache of two kinds
reaches: the bytes of K and V the window's attention calls NEEDED over
the chip's HBM bandwidth, over the time of the operations that read the
cache.

Needed (``flops/<family>.py`` ``kv_bytes_needed``): a global layer's
call reads every position its live lanes hold (``ctx_tokens`` on
``engine.dispatch``, summed over the steps of the chunk), a window
layer's call only the positions its window admits
(``ctx_window_tokens``: the same lanes and steps, each cut to the
window). That counts what the mathematics needed, so it reads the same
whatever implements the window layers: a walk of whole contexts, a
store that pads, or a second kernel all do more and score lower. The
operations' time is that of everything named ``paged_attention`` or
``window_attention`` in the device trace, its share of the traced
sub-window applied to the whole window, as ``paged_attn_roofline_pct``
does. A program whose spans carry no ``ctx_window_tokens`` (a model
with no window layer, an older commit), or a trace without the
kernels, has nothing to read.
"""

import program_spans

KERNELS = ("paged_attention", "window_attention")


def read(run):
    t, peaks, flops = run.trace, run.cell.peaks, run.cell.flops
    bursts = program_spans.bursts(run)
    if not t or not t["window_s"] or not peaks or not bursts \
            or not hasattr(flops, "kv_bytes_needed"):
        return None
    calls = [d["args"] for _, _, ds in bursts for d in ds
             if "ctx_window_tokens" in d["args"]]
    share = sum(v for n, v in t["per_name"].items()
                if any(k in n for k in KERNELS)) / t["window_s"]
    if not calls or not share:
        return None
    ctx = sum(a["ctx_tokens"] for a in calls)
    win = sum(a["ctx_window_tokens"] for a in calls)
    least_s = flops.kv_bytes_needed(run.cell.config, ctx, win) \
        / peaks["hbm_bytes_per_s"]
    run.say(f"attn_cache_roofline_pct: {ctx} positions attended a global "
            f"layer, {win} a window layer, {least_s * 1e3:.3f} ms at the "
            f"roofline, kernels {100 * share:.2f}% of the traced window")
    return 100.0 * least_s / (share * run.window_s)
