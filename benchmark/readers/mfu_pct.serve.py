"""Whole-step share of the chip's bf16 peak while serving: the model
operations (``flops/<family>.py``) of every prompt prefilled and every
token delivered inside the window, over the window, over the peak."""


def read(run):
    flops = run.counters.get("model_flops")
    if not flops or not run.window_s or not run.cell.peaks:
        return None
    return 100.0 * flops / run.window_s / (
        run.cell.peaks["bf16_flops_per_s"] * len(run.devices))
