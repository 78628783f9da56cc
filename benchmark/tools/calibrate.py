"""Readings for the limits of ``correct``, many seeds in one process:

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 15 [--control] [--faults]

For each seed it runs the cell through ``run.measure`` (the timed path,
at the cell's own size and load, a short window) and prints the numbers
compared: the LOWER readings. With ``--control`` it also reads the
reference put in the program's place in float8 on the same prompts and
tokens, or the same batches: the UPPER readings. With ``--faults``
(training) it reads the half-batch fault planted in the reference. A
benchmark run never calls this; ``PERF.md`` quotes what it printed.
"""

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run as bench_run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--rehearse", default=None)
    args = ap.parse_args()
    out_dir = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"calibrate.{args.workload}.jsonl"), "a")
    for seed in [int(s) for s in args.seeds.split(",")]:
        argv = ["--workload", args.workload, "--seed", str(seed),
                "--seconds", str(args.seconds), "--trace", "0"]
        if args.rehearse:
            argv += ["--rehearse", args.rehearse]
        run, line = bench_run.measure(argv)
        cfg, ref, mix = run.cell.config, run.cell.reference, run.cell.traffic
        row = {"workload": args.workload, "seed": seed,
               "device": line["device"], "correct": line["correct"],
               "program": run.samples.get("numbers") or {
                   k: c["value"] for k, c in line["checks"].items()},
               "metrics": {k: m["value"] for k, m in line["metrics"].items()}}
        if "checked" in run.samples:
            if args.control:
                got = ref.check_served(
                    cfg, seed, run.samples["checked"], precision="fp8",
                    max_tokens=int(mix["output_len"].get("max", 0)) or None)
                row["control_fp8"] = {
                    "served_gap": got["widest_gap"],
                    "served_gap_mean": got["mean_gap"],
                    "served_mismatch_share": got["mismatch_share"],
                    "compared": got["compared"]}
        elif "reference" in run.samples:
            s = run.samples
            tr = cfg["deployment"]["trainer"]
            blk = int(mix["reference_rows_per_block"])
            strip = lambda d: {k: v for k, v in d.items() if k != "_at"}
            if args.control:
                ctl = ref.train_reference(cfg, seed, s["batches"], tr, "fp8", blk)
                row["control_fp8"] = strip(ref.compare_training(ctl, s["reference"]))
            if args.faults:
                rows = int(cfg["deployment"]["rows"])
                half = ref.train_reference(cfg, seed, s["batches"], tr, "f32",
                                           blk, keep_rows=rows // 2)
                row["fault_half_batch"] = strip(
                    ref.compare_training(half, s["reference"]))
        print("CALIBRATE " + json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
        out.flush()
        del run
        gc.collect()
    out.close()


if __name__ == "__main__":
    main()
