"""What the attention layers read of the contexts held, as a share of
attending every position: sum of ``ctx_selected_tokens`` over the
window's ``engine.dispatch`` spans (each live lane's context at each
step, cut to the most positions the indexer selects) over the sum of
``ctx_tokens`` (the whole contexts). 100 while every context is short
of ``index_topk``; lower = sparser. A program whose spans carry no
such count (a model that attends everything, an older commit) has
nothing to read."""

import program_spans


def read(run):
    bursts = program_spans.bursts(run)
    if not bursts:
        return None
    ds = [d["args"] for _, _, ds in bursts for d in ds
          if "ctx_selected_tokens" in d["args"]]
    held = sum(a["ctx_tokens"] for a in ds)
    if not ds or not held:
        return None
    return 100.0 * sum(a["ctx_selected_tokens"] for a in ds) / held
