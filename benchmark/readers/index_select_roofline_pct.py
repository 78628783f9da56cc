"""Share of the memory roofline the selection stage reaches.

Needed: every decode step of every FULL-indexer layer reads the
indexer key of each position the slot holds: ``ctx_index_tokens`` (sum
over live lanes and steps of the context, on ``engine.dispatch``) x
full layers x ``index_head_dim`` x the pool's item size
(``flops/<family>.py`` ``index_bytes_needed``).

The stage's time is the scoring and the top-k, by the names a device
trace prints (``OPS``): the Mosaic kernel ``index_scores`` and the
exact top-k over its scores, which XLA runs as ``sort`` instructions
(``sort.<n>``: ``sort f32[32,14336]`` in the GLM-5.2 cell); the router's
top-8 of 256, where it is a sort too, is counted with them, so
the share reads a little low, never high; so do a prefill's sorts (the
dense path ranks a block of queries at a time), whose positions no
dispatch span counts. The time is the names' share
of the traced sub-window applied to the whole window, as
``paged_attn_roofline_pct`` does. A program without the count or a
trace without the kernel has nothing to read."""

import program_spans

#: name fragments of the stage's operations in a device trace
OPS = ("index_scores", "sort")


def read(run):
    t, peaks = run.trace, run.cell.peaks
    bursts = program_spans.bursts(run)
    if not t or not t["window_s"] or not peaks or not bursts \
            or not hasattr(run.cell.flops, "index_bytes_needed"):
        return None
    if not any(OPS[0] in n for n in t["per_name"]):
        return None
    share = sum(v for n, v in t["per_name"].items()
                if any(o in n for o in OPS)) / t["window_s"]
    scored = sum(d["args"].get("ctx_index_tokens", 0)
                 for _, _, ds in bursts for d in ds)
    if not share or not scored:
        return None
    least_s = run.cell.flops.index_bytes_needed(run.cell.config, scored) \
        / peaks["hbm_bytes_per_s"]
    run.say(f"index_select_roofline_pct: {scored} scored positions a full "
            f"layer, {least_s * 1e3:.3f} ms at the roofline, the stage "
            f"{100 * share:.2f}% of the traced window")
    return 100.0 * least_s / (share * run.window_s)
