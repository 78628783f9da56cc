"""``paged_attn_roofline_pct`` for a model whose pages are not in every
layer nor of every query head: the same arithmetic (the bytes the
window's calls of the paged kernel NEED over the HBM bandwidth, over
the kernel's time), with the bytes a position holds asked of the
family's ``flops`` file (``kv_bytes_per_position``: attention layers x
KV heads x head width x K and V x the pool's item size) instead of
``n_embd x n_layer``. A ``benchmark`` PR should fold the two readers
into one (PERF.md section 7)."""

import program_spans


def read(run):
    t, peaks = run.trace, run.cell.peaks
    bursts = program_spans.bursts(run)
    if not t or not t["window_s"] or not peaks or not bursts \
            or not hasattr(run.cell.flops, "kv_bytes_per_position"):
        return None
    share = sum(v for n, v in t["per_name"].items()
                if "paged_attention" in n) / t["window_s"]
    ctx = sum(d["args"]["ctx_tokens"] for _, _, ds in bursts for d in ds)
    if not share or not ctx:
        return None
    least_s = ctx * run.cell.flops.kv_bytes_per_position(run.cell.config) \
        / peaks["hbm_bytes_per_s"]
    run.say(f"paged_attn_roofline_pct.gqa: {ctx} attended positions, "
            f"{least_s * 1e3:.3f} ms at the roofline, kernel "
            f"{100 * share:.2f}% of the traced window")
    return 100.0 * least_s / (share * run.window_s)
