"""The engine thread's own time per decode step: every engine span's
self time except the two that wait for the device. A burst's time less
its ``engine.sync`` (dispatching the chunks, uploading the slots' state,
handing the tokens out) plus an admission's time less its
``engine.prefill``, summed over the window's bursts and admissions and
divided by the bursts' decode steps (``program_spans.py``). Planning
between bursts lies in no span and is not in it."""

import program_spans


def read(run):
    bursts = program_spans.bursts(run)
    if not bursts:
        return None
    steps = sum(s["args"]["steps"] for _, s, _ in bursts)
    if not steps:
        return None
    host_us = sum(b["dur"] - s["dur"] for b, s, _ in bursts)
    host_us += sum(a["dur"] - p["dur"]
                   for a, p in program_spans.admissions(run) or [])
    run.say(f"engine_host_ms_per_step: {len(bursts)} bursts, {steps} steps, "
            f"host {host_us / 1e3:.1f} ms of which dispatch "
            f"{sum(d['dur'] for _, _, ds in bursts for d in ds) / 1e3:.1f}")
    return host_us / 1e3 / steps
