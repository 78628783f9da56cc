"""Share of the memory roofline the paged-attention kernel reaches.

The kernel is memory-bound (about one operation per byte read), so the
least time its work could take is the bytes it NEEDS over the chip's
HBM bandwidth. Needed: every call reads the K and the V of every
position its lanes hold, in every layer: ``ctx_tokens`` x layers x 2 x
heads x head width x bytes of the pool's dtype. The program sums
``ctx_tokens`` where it dispatches the call: a decode chunk's steps and
a verify call on ``engine.dispatch``, a prefill of the suffix behind a
cached prefix (the same kernel with more than one query, which share
one read of the context) on ``engine.prefill``. That counts live
contexts, so it reads the same whatever implements the kernel; a grid
that also walks dead page slots does more, and scores lower.

The kernel's time is its share of the traced sub-window (the last
seconds) applied to the whole window, whose work the spans count. That
holds where the traced seconds carry the same mix of calls as the
window, as in a cell the device spends decoding all through; where
prefills come in bursts, read it beside their count, which is printed.
"""

import program_spans


def kernel_names(per_name):
    """The Mosaic call as the trace spells it: by the kernel's own name,
    else (a program that gives it none) the cell's one custom call."""
    named = [n for n in per_name if "paged_attention" in n]
    return named or [n for n in per_name if n.endswith("[tpu_custom_call]")]


def needed_bytes(cfg, ctx_tokens):
    import jax.numpy as jnp

    width = int(cfg["n_embd"])            # heads x head width
    item = jnp.dtype(cfg["dtypes"]["kv_pool"]).itemsize
    return ctx_tokens * int(cfg["n_layer"]) * 2 * width * item


def read(run):
    t, peaks = run.trace, run.cell.peaks
    bursts = program_spans.bursts(run)
    if not t or not t["window_s"] or not peaks or not bursts:
        return None
    share = sum(t["per_name"][n] for n in kernel_names(t["per_name"])) \
        / t["window_s"]
    decode = sum(d["args"]["ctx_tokens"] for _, _, ds in bursts for d in ds)
    prefill = sum(p["args"].get("ctx_tokens", 0)
                  for _, p in program_spans.admissions(run) or [])
    ctx = decode + prefill
    if not share or not ctx:
        return None
    least_s = needed_bytes(run.cell.config, ctx) / peaks["hbm_bytes_per_s"]
    run.say(f"paged_attn_roofline_pct: {ctx} attended positions "
            f"({prefill} of them by suffix prefills), "
            f"{least_s * 1e3:.3f} ms at the roofline, kernel "
            f"{100 * share:.2f}% of the traced window")
    return 100.0 * least_s / (share * run.window_s)
