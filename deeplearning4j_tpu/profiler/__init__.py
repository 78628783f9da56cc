"""Profiling / numerics-panic instrumentation.

Reference: org/nd4j/linalg/profiler/{OpProfiler,ProfilerConfig} — per-op
timing histograms and NAN_PANIC/INF_PANIC/ANY_PANIC modes that assert-
check every op output (SURVEY.md §5); libnd4j graph/profiling/
{GraphProfile,NodeProfile}.

TPU redesign: the reference times each op at its JNI dispatch; here the
whole step is ONE XLA executable, so "per-op wall time" lives in the
XLA profile, not Python. The split is:

- `start_trace`/`stop_trace` — jax.profiler traces (XLA per-op/HLO
  timing; open in TensorBoard/xprof). This is the NodeProfile analog.
- `OpProfiler` — graph-dispatch census (how many times each registered
  op is EMITTED/traced; invocation counts + host wall-clock of eager
  registry calls) plus step-level timings via `ProfilerListener`.
- panic modes — `check_numerics(tree)` host assertion used by the
  training front-ends each iteration when enabled (the score is always
  checked; ANY_PANIC also sweeps params — documented cost).
"""

from __future__ import annotations

import collections
import contextlib
import enum
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.profiler import flight_recorder, telemetry, tracing
from deeplearning4j_tpu.profiler.model_health import HealthMonitor


def __getattr__(name):
    # slo/programs/timeseries are LAZY attributes (PEP 562): the fit
    # loops and serving engines import this package for telemetry, and
    # the off-mode contract is that they never pull in the SLO engine,
    # the program registry, or the time-series store
    if name in ("slo", "programs", "timeseries"):
        import importlib

        return importlib.import_module(
            f"deeplearning4j_tpu.profiler.{name}")
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


class ProfilerMode(enum.Enum):
    DISABLED = "disabled"
    OPERATIONS = "operations"     # count + time registry dispatches
    NAN_PANIC = "nan_panic"
    INF_PANIC = "inf_panic"
    ANY_PANIC = "any_panic"       # NaN or Inf, sweep params too


class ProfilerConfig:
    def __init__(self, mode: ProfilerMode = ProfilerMode.DISABLED,
                 check_params: bool = False):
        self.mode = mode
        self.check_params = check_params or mode is ProfilerMode.ANY_PANIC


class OpProfiler:
    """Singleton (reference: OpProfiler.getInstance())."""

    _instance: Optional["OpProfiler"] = None

    def __init__(self):
        self.config = ProfilerConfig()
        # honor the DL4J_TPU_PANIC env default (reference: ND4J panic
        # modes via system properties — see common/environment.py)
        from deeplearning4j_tpu.common.environment import Environment

        panic = Environment.getInstance().panicMode()
        if panic:
            mode = {"nan": ProfilerMode.NAN_PANIC,
                    "inf": ProfilerMode.INF_PANIC,
                    "any": ProfilerMode.ANY_PANIC}.get(panic)
            if mode is not None:
                self.config = ProfilerConfig(mode=mode)
        self.invocations: Dict[str, int] = collections.Counter()
        self.total_time: Dict[str, float] = collections.defaultdict(float)
        self._orig_get_op = None

    @classmethod
    def getInstance(cls) -> "OpProfiler":
        if cls._instance is None:
            cls._instance = OpProfiler()
        return cls._instance

    # -- registry instrumentation --------------------------------------
    def applyConfig(self, config: ProfilerConfig) -> None:
        self.config = config
        if config.mode is ProfilerMode.OPERATIONS:
            self._install()
        else:
            self._uninstall()

    def _install(self) -> None:
        if self._orig_get_op is not None:
            return
        # imported here: the op library (and Pallas behind it) takes a
        # second to load, and everything that only wants telemetry
        # imports this package
        from deeplearning4j_tpu.ops import registry as _registry

        self._orig_get_op = _registry.get_op
        prof = self

        def profiled_get_op(name):
            fn = prof._orig_get_op(name)

            def wrapped(*a, **k):
                t0 = time.perf_counter()
                out = fn(*a, **k)
                prof.invocations[name] += 1
                prof.total_time[name] += time.perf_counter() - t0
                return out

            return wrapped

        _registry.get_op = profiled_get_op

    def _uninstall(self) -> None:
        if self._orig_get_op is not None:
            from deeplearning4j_tpu.ops import registry as _registry

            _registry.get_op = self._orig_get_op
            self._orig_get_op = None

    def reset(self) -> None:
        self.invocations.clear()
        self.total_time.clear()

    def printOutDashboard(self) -> str:
        lines = ["OpProfiler (dispatch census — XLA fuses execution; "
                 "use start_trace for device timing):"]
        for name, n in sorted(self.invocations.items(),
                              key=lambda kv: -self.total_time[kv[0]]):
            lines.append(f"  {name:<30} calls={n:<8} "
                         f"host_time={self.total_time[name]*1e3:.2f}ms")
        return "\n".join(lines)


# ---------------------------------------------------------------- panics
class NumericsException(ArithmeticError):
    pass


def check_numerics(tree, mode: ProfilerMode, context: str = "") -> None:
    """Host-side NaN/Inf assertion over a pytree (reference: the panic
    modes' per-op output checks, applied per-step here).

    Panic-mode cost is ONE device->host transfer per call: floating
    leaves are fetched together via a single ``jax.device_get`` (a
    per-leaf ``np.asarray`` would sync the pipeline once per leaf), and
    the NaN/Inf flags are reduced across all leaves before raising."""
    if mode in (ProfilerMode.DISABLED, ProfilerMode.OPERATIONS):
        return
    float_leaves = []
    for leaf in jax.tree_util.tree_leaves(tree):
        dt = getattr(leaf, "dtype", None)
        if dt is None:
            leaf = np.asarray(leaf)
            dt = leaf.dtype
        # jnp.issubdtype, not np: bfloat16 (ml_dtypes) must be swept too
        if jnp.issubdtype(dt, jnp.floating):
            float_leaves.append(leaf)
    if not float_leaves:
        return
    host = jax.device_get(float_leaves)
    check_nan = mode in (ProfilerMode.NAN_PANIC, ProfilerMode.ANY_PANIC)
    check_inf = mode in (ProfilerMode.INF_PANIC, ProfilerMode.ANY_PANIC)
    has_nan = has_inf = False
    for a in host:
        a = np.asarray(a)
        if a.dtype not in (np.float16, np.float32, np.float64):
            a = a.astype(np.float32)   # extended dtypes lack isnan ufuncs
        if check_nan and np.isnan(a).any():
            has_nan = True
        if check_inf and np.isinf(a).any():
            has_inf = True
    if has_nan:
        raise NumericsException(f"NaN detected {context}")
    if has_inf:
        raise NumericsException(f"Inf detected {context}")


# ------------------------------------------------------------ XLA traces
def start_trace(log_dir: str) -> bool:
    """Start a jax.profiler trace (per-op HLO timing — the reference's
    NodeProfile equivalent, viewable in xprof/TensorBoard).

    Routed through ``programs.ProfileSession`` — the process has ONE
    jax.profiler trace slot shared with managed ``capture()`` bundles,
    and a second ``jax.profiler.start_trace`` raises RuntimeError from
    inside XLA. Idempotent-with-warning: returns False (no-op) when a
    trace or managed capture is already active, True when this call
    started the trace."""
    from deeplearning4j_tpu.profiler import programs

    return programs.profile_session().start_manual(log_dir)


def stop_trace() -> bool:
    """Stop an ad-hoc trace started by :func:`start_trace`. No-op
    (False) when none is active — a managed capture in flight is never
    stopped from here."""
    from deeplearning4j_tpu.profiler import programs

    return programs.profile_session().stop_manual()


@contextlib.contextmanager
def trace(log_dir: str):
    """Context-managed trace, exception-safe against a FAILED start: a
    start that did not take the trace slot (already active, or
    jax.profiler refused) is not stopped on exit, so an outer trace or
    capture keeps running."""
    started = start_trace(log_dir)
    try:
        yield
    finally:
        if started:
            stop_trace()


__all__ = ["OpProfiler", "ProfilerConfig", "ProfilerMode",
           "NumericsException", "check_numerics", "start_trace",
           "stop_trace", "trace", "telemetry", "HealthMonitor",
           "tracing", "flight_recorder", "slo", "programs",
           "timeseries"]
