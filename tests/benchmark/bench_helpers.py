"""Shared by the benchmark's tests: find ``benchmark/`` and load its
files by path, the way ``run.py`` does (no package import, so nothing
here depends on how pytest lays out ``sys.path``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
TINY = os.path.join(BENCH, "rehearse_tiny.json")

if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run as bench_run  # noqa: E402


def load(rel):
    return harness.load_module(os.path.join(BENCH, rel))


def tiny_cfg():
    cfg = harness.load_json(os.path.join(BENCH, "configs", "gpt2-medium.json"))
    return harness.merge(cfg, harness.load_json(TINY)["configs"]["gpt2-medium"])


def rehearse(workload, seed=7, seconds=1.5, trace=0):
    """One rehearsal run in this process -> (Run, line)."""
    return bench_run.measure(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace), "--rehearse", TINY])
