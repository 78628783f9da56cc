"""One cell, once:
``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``

Warms up, measures for ``--seconds``, checks the timed path's output
against the plain reference, and prints one JSON object as the last line
of standard output. Exits non-zero, with no result, where JAX finds no
TPU listed in ``peaks.json`` or fewer chips than the cell asks for.
``--rehearse <file>`` is the benchmark's own rehearsal: sizes from the
file, no look for the chip, for tests and for debugging on a CPU; its
line names the platform it ran on and is no measurement.
"""

import time

_T0 = time.perf_counter()          # process start, as near as Python gets

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _harness():
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    import harness

    return harness


def measure(argv=None, t0=None):
    """Run the cell once; -> (harness.Run, result line). Raises
    ``harness.Refused`` where the run cannot be measured."""
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None,
                    help="a file of rehearsal sizes; no measurement")
    ap.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    h = _harness()
    cell = h.Cell(args.root, args.workload, args.rehearse)
    if args.root not in sys.path:
        sys.path.insert(0, args.root)    # the program, by its package
    try:
        from deeplearning4j_tpu.common.environment import \
            configure_compile_cache
    except ImportError as e:
        raise h.Refused(f"the program is not in this checkout: {e}")
    cache = configure_compile_cache()
    device, devices = h.look_for_chip(cell, args.rehearse)
    run = h.Run(cell, args, t0, device, devices)
    run.say(f"cell {cell.name} seed {run.seed} seconds {run.seconds} "
            f"trace {int(run.traced)} compile cache {cache}"
            + (" REHEARSAL, no measurement" if run.rehearsal else ""))
    run.phase("import")
    cell.driver.run(run)
    line = run.result()
    os.makedirs(run.out_dir, exist_ok=True)
    with open(os.path.join(run.out_dir, f"{cell.name}.last.json"), "w") as f:
        json.dump({"phases": run.phases, "line": line}, f)
    return run, line


def main(argv=None):
    try:
        run, line = measure(argv, _T0)
    except _harness().Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    for name, c in run.checks.items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
