"""Char-RNN LSTM training-throughput self-baseline (BASELINE.md row:
"Char-RNN / seq2seq LSTM — correctness + throughput self-baseline";
reference config: zoo TextGenerationLSTM, the CudnnLSTMHelper role).

The workload lives in bench_common.run_char_lstm — the SAME loop
bench.py's driver metric times, so CLI sweeps and the driver line
cannot diverge. Methodology matches bench.py v3.

Usage: python bench_lstm.py [--batch 256] [--seq 200] [--hidden 256]
"""

from __future__ import annotations

import argparse
import json

from bench_common import peak_flops, pipeline_ab_lstm, run_char_lstm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=200)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=77)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dtype", default="bf16",
                    choices=["bf16", "f32", "fp16"])
    ap.add_argument("--precision", default=None,
                    choices=[None, "float32", "mixed_bfloat16",
                             "mixed_float16"],
                    help="mixed-precision policy (fp32 master weights; "
                         "overrides --dtype)")
    ap.add_argument("--precision-ab", action="store_true",
                    help="run the precision A/B/C (f32 vs "
                         "mixed_bfloat16 policy vs naive full-bf16) "
                         "and report mixed/naive speedups vs f32")
    ap.add_argument("--pipeline-ab", action="store_true",
                    help="also run the device input-pipeline A/B on a "
                         "ragged stream (bucketing + async prefetch "
                         "vs raw): reports pipeline_speedup and "
                         "per-side compile counts")
    ap.add_argument("--zero-ab", action="store_true",
                    help="interleaved A/B of the data-parallel sharing "
                         "step: replicated vs ZeRO-style update "
                         "sharding (step time + per-device master/opt "
                         "byte gauges; recorded into MULTICHIP rounds)")
    args = ap.parse_args()

    if args.zero_ab:
        from bench_common import zero_ab

        print(json.dumps(zero_ab(
            "lstm", steps=args.steps, batch=args.batch,
            hidden=args.hidden, seq=args.seq,
            precision=args.precision)))
        return

    if args.precision_ab:
        from bench_common import precision_ab

        print(json.dumps(precision_ab(
            "lstm", steps=args.steps, batch=args.batch, seq=args.seq,
            hidden=args.hidden, vocab=args.vocab)))
        return

    # roofline registry on for this run: the compiled step registers
    # under "bench_lstm_step" so the aggregate line carries its
    # roofline-verdict row (memory- vs compute-bound + achieved rates)
    from deeplearning4j_tpu.profiler import programs

    programs.set_enabled(True)
    programs.get_default().reset()
    r = run_char_lstm(batch=args.batch, seq=args.seq,
                      hidden=args.hidden, vocab=args.vocab,
                      steps=args.steps, dtype=args.dtype,
                      precision=args.precision,
                      site="bench_lstm_step")
    tok_s = r["tokens_per_sec"]
    out = {"metric": "char_lstm_train", "value": round(tok_s, 1),
           "unit": "tokens/sec/chip", "batch": args.batch,
           "seq": args.seq, "hidden": args.hidden, "dtype": args.dtype}
    if r["flops_per_step"]:
        flops_tok = r["flops_per_step"] / r["tokens_per_step"]
        out["tflops"] = round(tok_s * flops_tok / 1e12, 2)
        out["flops_src"] = "cost_analysis"
        # MFU denominator matches the COMPUTE dtype (mixed policies
        # compute in bf16 even though params are f32) — resolved by
        # the policy itself, not a hand map
        if args.precision is not None:
            from deeplearning4j_tpu.nn.precision import PrecisionPolicy

            compute_dt = PrecisionPolicy.of(args.precision).compute_dtype
        else:
            compute_dt = args.dtype
        peak = peak_flops(compute_dt)
        if peak:
            out["mfu"] = round(tok_s * flops_tok / peak, 4)
    else:
        # analytic fallback: 2 LSTM layers, 8*h*(in+h) MACs fwd each,
        # x3 for bwd, + the vocab softmax head
        h, v = args.hidden, args.vocab
        fwd_tok = 8 * h * (v + h) + 8 * h * (h + h) + 2 * h * v
        out["tflops_est"] = round(tok_s * 3 * fwd_tok / 1e12, 2)
    # feed the measured window back into the registry so the row
    # carries achieved FLOP/s / GB/s, not just the static verdict
    from bench_common import roofline_row

    row = roofline_row("bench_lstm_step",
                       seconds_per_step=r["tokens_per_step"]
                       / max(tok_s, 1e-9),
                       steps=args.steps)
    if row:
        out["roofline"] = row
    if args.pipeline_ab:
        out.update(pipeline_ab_lstm(hidden=args.hidden,
                                    vocab=args.vocab))
    print(json.dumps(out))


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
