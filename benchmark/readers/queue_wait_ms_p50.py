"""Median time a request waited between ``submit`` and the start of its
admission: the program's ``request.queue_wait`` records that ended
inside the window (``program_spans.py``). The sample count is printed:
a window of a dozen requests gives a median and no tail."""

import program_spans


def read(run):
    waits = program_spans.ended_in_window(run, "request.queue_wait")
    if not waits:
        return None
    run.say(f"queue_wait_ms_p50: {len(waits)} samples (ms, the first 32): "
            + " ".join(f"{e['dur'] / 1e3:.0f}" for e in waits[:32]))
    return program_spans.median_ms(waits)
