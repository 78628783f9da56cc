"""Global environment/flag singleton.

Reference: libnd4j ``sd::Environment`` (verbose/debug flags mirrored
across JNI), ``org/nd4j/config/ND4JSystemProperties`` /
``ND4JEnvironmentVars`` (env-var configuration), and
``Nd4jEnvironment.getEnvironmentInformation()`` (runtime/hardware
report used by PerformanceListener) — SURVEY.md §5 config/flag system.

Env vars (the DL4J_TPU_* namespace replaces ND4J_*):
- ``DL4J_TPU_PANIC=nan|inf|any`` — default numerics panic mode; WIRED:
  OpProfiler reads it at first use, so training steps panic-check
  without any code change.
- ``DL4J_TPU_VERBOSE=1`` / ``DL4J_TPU_DEBUG=1`` — flag accessors for
  user code and listeners (``Environment.isVerbose()``); the framework
  core does not condition on them yet.
- ``DL4J_TPU_MAX_THREADS=N`` — exposed via ``Environment.maxThreads()``
  for host-side worker pools user code spins up; the bundled native
  codec sizes its own std::thread pool internally.

``configure_compile_cache()`` places JAX's persistent compilation cache;
every process entry point calls it before its first compile.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional


#: programs that took at least this long to compile are stored; JAX's
#: default (1 s) would skip the smaller serving warm-pool programs
_CACHE_MIN_COMPILE_SECS = 0.1


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this does nothing. Otherwise the cache is ``<checkout>/.jax_cache``,
    derived from this file's location: a cache that moves between runs
    is never hit, so the path must not come from a temp dir, a pid or
    the clock. Call it first thing in a process entry point
    (``chip_smoke.py``, ``bench*.py``, ``prof*.py``,
    ``control/worker.py``, ``examples/``) — before the first compile,
    never at package import."""
    # every compilation and every load from this cache is a record in
    # the telemetry ring from here on (jit.compile / jit.cache_load)
    from deeplearning4j_tpu.profiler import telemetry

    telemetry.watch_compilations()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _CACHE_MIN_COMPILE_SECS)
    return path


class Environment:
    """Singleton (reference: sd::Environment::getInstance())."""

    _instance: Optional["Environment"] = None

    def __init__(self):
        self._verbose = os.environ.get("DL4J_TPU_VERBOSE", "0") == "1"
        self._debug = os.environ.get("DL4J_TPU_DEBUG", "0") == "1"
        self._panic = os.environ.get("DL4J_TPU_PANIC", "").lower() or None
        try:
            self._max_threads = int(
                os.environ.get("DL4J_TPU_MAX_THREADS", "0")) or None
        except ValueError:
            self._max_threads = None

    @classmethod
    def getInstance(cls) -> "Environment":
        if cls._instance is None:
            cls._instance = Environment()
        return cls._instance

    # -- flags (reference naming) --------------------------------------
    def isVerbose(self) -> bool:
        return self._verbose or self._debug

    def setVerbose(self, v: bool) -> None:
        self._verbose = bool(v)

    def isDebug(self) -> bool:
        return self._debug

    def setDebug(self, v: bool) -> None:
        self._debug = bool(v)

    def panicMode(self) -> Optional[str]:
        """'nan' | 'inf' | 'any' | None — default for profiler panic."""
        return self._panic

    def setPanicMode(self, mode: Optional[str]) -> None:
        self._panic = mode

    def maxThreads(self) -> int:
        if self._max_threads:
            return self._max_threads
        return os.cpu_count() or 1

    def setMaxThreads(self, n: int) -> None:
        self._max_threads = int(n)


class Nd4jEnvironment:
    """Runtime/hardware report (reference:
    org/nd4j/linalg/api/environment/Nd4jEnvironment — feeds
    PerformanceListener's system-info lines)."""

    @staticmethod
    def getEnvironmentInformation() -> Dict[str, Any]:
        import platform as _platform

        import jax

        devs = jax.devices()
        info: Dict[str, Any] = {
            "backend": devs[0].platform if devs else "none",
            "blas.vendor": "XLA",   # matmuls lower to the MXU, not BLAS
            "device.count": len(devs),
            "device.kind": devs[0].device_kind if devs else "none",
            "host.cpu.count": os.cpu_count(),
            "host.name": _platform.node(),
            "jax.version": jax.__version__,
            "os": f"{_platform.system()} {_platform.release()}",
            "python.version": _platform.python_version(),
        }
        try:
            stats = devs[0].memory_stats()
            if stats:
                info["device.memory.bytes.limit"] = stats.get(
                    "bytes_limit")
                info["device.memory.bytes.in.use"] = stats.get(
                    "bytes_in_use")
        except Exception:
            pass  # CPU backend has no memory_stats
        return info
