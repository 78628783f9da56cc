"""Median duration of the window's ``engine.prefill`` spans: from the
prefill's dispatch to the host's read of the last position's logits,
the wait for the device included (``program_spans.py``)."""

import program_spans


def read(run):
    prefills = program_spans.ended_in_window(run, "engine.prefill")
    if not prefills:
        return None
    run.say(f"prefill_span_ms_p50: {len(prefills)} samples (ms:bucket, the "
            "first 32): " + " ".join(
                f"{e['dur'] / 1e3:.0f}:{e['args'].get('bucket')}"
                for e in prefills[:32]))
    return program_spans.median_ms(prefills)
