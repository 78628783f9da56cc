"""Whole-step share of the chip's bf16 peak while training: operations
forward and backward need per predicted position (causal half of
attention, ``flops/<family>.py``) x ``train_tok_s`` / peak."""


def read(run):
    rate = run.e2e.get("train_tok_s")
    if not rate or not run.cell.peaks:
        return None
    per = run.cell.flops.train_flops_per_position(
        run.cell.config, run.counters["positions"])
    return 100.0 * per * rate / (
        run.cell.peaks["bf16_flops_per_s"] * len(run.devices))
