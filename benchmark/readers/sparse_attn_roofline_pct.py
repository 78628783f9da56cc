"""Share of the memory roofline the sparse latent attention reaches.

The stage is memory-bound at decode (one query a slot against rows
read once). Needed: every decode step of every layer reads the latent
row of each position the step SELECTED: ``ctx_selected_tokens`` (sum
over live lanes and steps of ``min(context, index_topk)``, on
``engine.dispatch``) x layers x (``kv_lora_rank + qk_rope_head_dim``)
x the pool's item size (``flops/<family>.py`` ``latent_bytes_needed``):
what the mathematics needs, whatever padding a row rests in and
whatever implements the read.

The stage's time is every operation it runs, as a device trace prints
them:

- the Mosaic kernel, by its name: ``sparse_latent_attention.<n>
  [tpu_custom_call]``;
- the gather of the selected rows in front of it. XLA runs it as a
  fusion that carries no name of its own (``fusion.<n>``); the
  breakdown prints it by kind and result, ``fusion bf16[<slots x
  index_topk>,<row>]`` with ``<row>`` the latent row padded to whole
  lane tiles (``fusion bf16[65536,640]`` in the GLM-5.2 cell: 32 slots
  x 2,048 rows of 640 lanes), and that is how it is found, among the
  breakdown's largest operations; beside it the fusion that makes the
  gather's row addresses, ``fusion s32[<slots x index_topk>]``, five a
  step like the gather itself. Where the kernel is in the trace and
  the fusion of the rows is not among them (another row width, slot
  count or top-k than the configuration states, or a gather too small
  to be listed), the stage's time is not known and there is NOTHING TO
  READ: the kernel alone is a tenth of the stage, and a share made
  from it would read ten times too high. A form that fetches the rows
  inside the kernel runs no such fusion and needs this reader taught
  so, by a ``benchmark`` PR.

The time is the operations' share of the traced sub-window applied to
the whole window, whose work the spans count, as
``paged_attn_roofline_pct`` does. A program without the count or a
trace without the kernel has nothing to read."""

import program_spans

KERNEL = "sparse_latent_attention"
LANES = 128
SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def gather_kinds(cfg, slots):
    """The gather's lines in the breakdown: the rows, ``fusion
    <dtype>[rows,row]``, and their addresses, ``fusion s32[rows]``."""
    row = -(-(int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))
            // LANES) * LANES
    dtype = SHORT.get(cfg["dtypes"]["kv_pool"], cfg["dtypes"]["kv_pool"])
    rows = int(slots) * int(cfg["index_topk"])
    return (f"fusion {dtype}[{rows},{row}]", f"fusion s32[{rows}]")


def read(run):
    t, peaks = run.trace, run.cell.peaks
    bursts = program_spans.bursts(run)
    if not t or not t["window_s"] or not peaks or not bursts \
            or not hasattr(run.cell.flops, "latent_bytes_needed"):
        return None
    kernel = sum(v for n, v in t["per_name"].items() if KERNEL in n)
    selected = sum(d["args"].get("ctx_selected_tokens", 0)
                   for _, _, ds in bursts for d in ds)
    if not kernel or not selected:
        return None
    kinds = gather_kinds(run.cell.config, run.counters.get("slots", 0))
    ops = dict((k, s) for k, s in t.get("device_ops", []))
    if not ops.get(kinds[0]):
        run.say(f"sparse_attn_roofline_pct: the kernel is in the trace and "
                f"the gather in front of it ({kinds[0]}) is not among the "
                "breakdown's operations: nothing to read")
        return None
    gather = sum(ops.get(k, 0.0) for k in kinds)
    share = (kernel + gather) / t["window_s"]
    least_s = run.cell.flops.latent_bytes_needed(run.cell.config, selected) \
        / peaks["hbm_bytes_per_s"]
    run.say(f"sparse_attn_roofline_pct: {selected} selected positions a "
            f"layer, {least_s * 1e3:.3f} ms at the roofline, the kernel "
            f"{100 * kernel / t['window_s']:.2f}% and the gather "
            f"({', '.join(kinds)}) {100 * gather / t['window_s']:.2f}% of the "
            "traced "
            "window")
    return 100.0 * least_s / (share * run.window_s)
