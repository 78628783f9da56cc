"""Distinct experts a decode layer-step touches, as a share of the
experts a layer holds: ``experts_touched / expert_layer_steps`` summed
over the window's ``engine.sync`` spans (the program's own counts, live
lanes only), over ``num_experts``. With 16 live slots x 4 experts a
token over 64 experts, uniform routing would touch 63%: the bytes a
step must read follow this number."""

import program_spans


def read(run):
    bursts = program_spans.bursts(run)
    args = [s["args"] for _, s, _ in bursts or []
            if s["args"].get("expert_layer_steps")]
    if not args:
        return None
    steps = sum(a["expert_layer_steps"] for a in args)
    return 100.0 * sum(a["experts_touched"] for a in args) / steps \
        / int(run.cell.config["num_experts"])
