"""How uneven the routing is: the hottest expert's assignments over
the mean expert's, over the window's decode layer-steps (the program's
own counts on ``engine.sync``: ``expert_load_max`` is the hottest
expert's load summed over layer-steps, ``expert_assignments`` all of
them; the mean expert gets ``assignments / num_experts``). 1 is even;
with 64 assignments over 64 experts a uniform router reads about 4."""

import program_spans


def read(run):
    bursts = program_spans.bursts(run)
    args = [s["args"] for _, s, _ in bursts or []
            if s["args"].get("expert_assignments")]
    if not args:
        return None
    return sum(a["expert_load_max"] for a in args) \
        * int(run.cell.config["num_experts"]) \
        / sum(a["expert_assignments"] for a in args)
