"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's "distributed-without-cluster" test philosophy
(SURVEY.md §4): libnd4j/Spark/Aeron tests all run in one process; here
multi-chip sharding logic runs against 8 virtual CPU devices so tests
never need real TPU hardware. Must run before jax is imported anywhere.
"""

import os
import tempfile

# Op-execution accounting (reference: OpValidation, SURVEY.md §4): the
# registry records every dispatched op; subprocesses spawned by tests
# inherit this env var and append their sets at exit, so the
# end-of-suite executional gate (test_zzz_op_execution_gate.py) sees
# multi-process drives too. Pid-keyed so parallel sessions don't mix;
# removed up front in case of pid reuse.
_trace = os.path.join(tempfile.gettempdir(),
                      f"dl4j_op_trace_{os.getpid()}.txt")
if "DL4J_TPU_OP_TRACE_FILE" not in os.environ:
    os.environ["DL4J_TPU_OP_TRACE_FILE"] = _trace
    if os.path.exists(_trace):
        os.remove(_trace)

# Same accounting for import MAPPERS (TF/ONNX/Keras dispatches record
# into modelimport/trace.py; gate: test_zzz_mapper_execution_gate.py).
_mtrace = os.path.join(tempfile.gettempdir(),
                       f"dl4j_mapper_trace_{os.getpid()}.txt")
if "DL4J_TPU_MAPPER_TRACE_FILE" not in os.environ:
    os.environ["DL4J_TPU_MAPPER_TRACE_FILE"] = _mtrace
    if os.path.exists(_mtrace):
        os.remove(_mtrace)

# Tests run on the virtual 8-device CPU mesh and never on an
# accelerator; both settings are read at the first ``import jax``.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu" and len(jax.devices()) == 8, \
    f"test mesh wrong: {jax.devices()}"

jax.config.update("jax_enable_x64", False)
# Correctness tests pin full f32 accumulation; production configs choose
# their own precision policy (bf16 on MXU) via nn/conf dtype settings.
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_sessionfinish(session, exitstatus):
    """Thread-leak gate: fail the run if non-daemon threads (or any
    device-prefetch worker — that subsystem must always join its
    threads) survive the suite. A leaked non-daemon thread hangs the
    interpreter at exit; a leaked prefetch worker means a fit loop or
    test skipped shutdown()."""
    import threading
    import time

    def leaked():
        return [
            t for t in threading.enumerate()
            if t.is_alive() and t is not threading.main_thread()
            and (not t.daemon
                 or t.name.startswith(("DevicePrefetch",
                                       "AsyncDataSet-ETL",
                                       "ServingEngine",
                                       "ServingFleetRouter",
                                       "ServingPrefillLane",
                                       "JobScheduler",
                                       "JobRunner",
                                       "SLOEvaluator",
                                       "WorkerSupervisor",
                                       "WorkerHeartbeat",
                                       "NoticePoller",
                                       "TSDBSampler")))
        ]

    deadline = time.time() + 2.0
    survivors = leaked()
    while survivors and time.time() < deadline:
        time.sleep(0.1)   # grace: threads mid-exit
        survivors = leaked()
    if survivors:
        print("\nTHREAD-LEAK GATE: threads survived the suite: "
              + ", ".join(f"{t.name} (daemon={t.daemon})"
                          for t in survivors))
        session.exitstatus = 3
