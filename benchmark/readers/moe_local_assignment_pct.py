"""Share of the router's assignments that fall on experts this chip
holds: ``expert_assignments`` (live rows' assignments to held experts)
over ``expert_assignments_routed`` (live rows x experts a token, held
or not), both summed over the window's ``engine.sync`` spans (decode
layer-steps, the program's own counts). Where a chip holds an eighth of
the experts and the routing is even it reads 12.5: how near each held
expert's load is to what it would see in the deployment the
configuration states. A program that does not count the routed
assignments has nothing to read."""

import program_spans


def read(run):
    bursts = program_spans.bursts(run)
    args = [s["args"] for _, s, _ in bursts or []
            if s["args"].get("expert_assignments_routed")]
    if not args:
        return None
    return 100.0 * sum(a["expert_assignments"] for a in args) \
        / sum(a["expert_assignments_routed"] for a in args)
