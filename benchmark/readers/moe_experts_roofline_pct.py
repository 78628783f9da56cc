"""Share of the memory roofline the expert products reach.

A decode step of a sparse-expert layer is memory-bound: 16 rows a
matrix against 9.4 M parameters an expert. The least time the work
could take is the bytes it NEEDS over the chip's HBM bandwidth. Needed:
each expert layer-step reads the three matrices of every DISTINCT
expert its live rows were routed to, once: ``experts_touched`` x
``expert_bytes`` (``flops/<family>.py``). The program counts the
distinct experts where the work happens (live lanes and real prompt
positions only) and puts the sums on ``engine.sync`` (a burst's decode
steps) and ``engine.prefill`` (a prompt: the same products over more
rows). That counts what the routing needed, so it reads the same
whatever implements the products: a form that reads all the experts,
or routes dead slots and padding, does more and scores lower.

The operations' time is their share of the traced sub-window (the last
seconds) applied to the whole window, whose work the spans count, as
``paged_attn_roofline_pct`` does; the kernel is named ``moe_experts``
in the trace. A program without these counts, or a trace without the
kernel, has nothing to read.
"""

import program_spans

KERNEL = "moe_experts"


def touched(run):
    """-> (decode, prefill): distinct experts touched, summed over the
    window's expert layer-steps, or None where no span counts them."""
    bursts = program_spans.bursts(run)
    if not bursts:
        return None
    syncs = [s["args"] for _, s, _ in bursts if "experts_touched" in s["args"]]
    if not syncs:
        return None
    pre = [p["args"] for _, p in program_spans.admissions(run) or []]
    return (sum(a["experts_touched"] for a in syncs),
            sum(a.get("experts_touched", 0) for a in pre))


def read(run):
    t, peaks, got = run.trace, run.cell.peaks, touched(run)
    if not t or not t["window_s"] or not peaks or not got \
            or not hasattr(run.cell.flops, "expert_bytes"):
        return None
    share = sum(v for n, v in t["per_name"].items() if KERNEL in n) \
        / t["window_s"]
    if not share or not sum(got):
        return None
    least_s = sum(got) * run.cell.flops.expert_bytes(run.cell.config) \
        / peaks["hbm_bytes_per_s"]
    run.say(f"moe_experts_roofline_pct: {sum(got)} expert reads needed "
            f"({got[1]} of them by prefills), {least_s * 1e3:.1f} ms at the "
            f"roofline, kernel {100 * share:.2f}% of the traced window")
    return 100.0 * least_s / (share * run.window_s)
