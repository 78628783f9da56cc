"""Control plane phase 2: workers as supervised OS processes.

PR 13's ``JobScheduler`` made survival a first-class behavior, but a
"worker" was still an in-process failure domain: a hard host death was
only emulated (``inject_fault``), a preemption was something tests
requested, and every bundle lived on the dying process's own disk.
This module closes those three gaps:

- **Real processes.** ``WorkerSupervisor`` spawns one OS process per
  worker (``python -m deeplearning4j_tpu.control.worker``), each
  heartbeating over a file lease in a shared control directory. A
  process that exits — or whose lease goes stale — is DEAD the way a
  host is dead: nothing in it gets to clean up. The supervisor maps
  that death onto the scheduler's existing verdict path
  (``lose_worker`` + ``DeviceLostError`` → recover-newest-bundle-and-
  migrate) and, when the restart budget allows, respawns the worker —
  whose first heartbeat restores its capacity to the fleet
  (``restore_worker``).
- **Notices that arrive.** ``supervisor.preempt(worker, deadline_s)``
  delivers a GCE/Borg-style maintenance event: a ``notice.json`` the
  worker's ``NoticePoller`` converts into
  ``FaultTolerance.request_preemption(deadline_s, kind="metadata")``,
  so the task checkpoints and drains BEFORE the kill. At the deadline
  the supervisor enforces the platform contract — a worker still
  running its task is SIGKILLed, and recovery degrades to the newest
  periodic bundle.
- **Tasks that migrate.** ``submit_task`` queues work (an ``entry``
  of the form ``"module:function"`` called with a ``WorkerTaskContext``)
  onto any alive worker. A task whose worker died is re-assigned to a
  survivor; with its bundles in a ``SharedFSBundleStore`` the
  survivor's ``auto_resume`` finds the dead host's checkpoint and the
  run continues bit-identically.

The control directory is the entire protocol (no sockets, no pickles —
any host that mounts it can participate)::

    <control_dir>/<worker>/
        heartbeat.json        worker -> supervisor, every heartbeat_s
        task.json             supervisor -> worker (the assignment)
        notice.json           supervisor -> worker (maintenance event)
        result-<task>.json    worker -> supervisor (outcome)
        metrics.json          worker -> supervisor (federated registry
                              capture, when DL4J_TPU_TSDB=1 — ingested
                              into the coordinator's time-series store
                              under worker=/host= labels; see
                              profiler/timeseries.py)
        worker.log            the process's stdout+stderr

Multi-host meshes ride the existing ``jax.distributed`` seam: a
supervisor constructed with ``coordinator=`` injects the
``DL4J_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID`` env vars
(``parallel.mesh.worker_env``) so spawned workers join one mesh via
``maybe_init_distributed()``.

Supervisor-off identity: nothing here is imported by the scheduler,
the fit loops, or the serving engine unless a supervisor is
constructed — the in-process control plane is byte-for-byte the PR 13
code path.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Sequence

from deeplearning4j_tpu.profiler import flight_recorder as _flight
from deeplearning4j_tpu.profiler import telemetry as _telemetry

log = logging.getLogger("deeplearning4j_tpu")

HEARTBEAT = "heartbeat.json"
TASK = "task.json"
NOTICE = "notice.json"
METRICS = "metrics.json"

#: task outcomes a worker reports
OUTCOMES = ("completed", "preempted", "failed")


def _write_json_atomic(path: str, obj: Dict[str, Any]) -> None:
    """tmp + fsync + rename via the resume-bundle helpers
    (util/model_serializer): a reader never sees a torn JSON file,
    and the rename is made durable (atomic_replace fsyncs the parent
    directory — a power cut can't un-publish a result/notice)."""
    from deeplearning4j_tpu.util.model_serializer import (
        atomic_replace, unique_tmp_path,
    )

    tmp = unique_tmp_path(path)
    with open(tmp, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())
    atomic_replace(tmp, path)


def _read_json(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


# ======================================================================
# the worker process (runs via ``python -m ...control.worker``)
# ======================================================================
class WorkerTaskContext:
    """What a task entry function receives: its parameters, the
    worker-configured FaultTolerance policy (preemption notices land
    on it — a fit MUST pass it to ``fit(..., fault_tolerance=...)``),
    and a ``progress(step)`` hook that feeds the heartbeat so the
    supervisor (and its liveness gauges) see live step counts."""

    def __init__(self, worker: str, task_id: str,
                 params: Dict[str, Any], attempt: int,
                 fault_tolerance, report: Callable[[int], None]):
        self.worker = worker
        self.task_id = task_id
        self.params = dict(params or {})
        self.attempt = int(attempt)
        self.fault_tolerance = fault_tolerance
        self._report = report
        #: a task that exits EARLY because of a preemption notice
        #: (without writing a checkpoint — e.g. a cooperative loop)
        #: sets this so the supervisor re-queues it; fits don't need
        #: it (their preemption checkpoint is the drain signal), and
        #: a task that ran to completion leaves it False even if a
        #: notice landed after its last boundary
        self.drained = False

    def progress(self, step: int) -> None:
        self._report(int(step))

    @property
    def preemption_requested(self) -> bool:
        ft = self.fault_tolerance
        return bool(ft is not None and ft.preemption_requested)


def _resolve_entry(entry: str) -> Callable:
    """``"module:function"`` -> the callable (module importable on the
    worker's sys.path; the supervisor puts the control dir there so
    drills can drop task modules next to the protocol files)."""
    import importlib

    mod_name, _, fn_name = entry.partition(":")
    if not mod_name or not fn_name:
        raise ValueError(
            f"task entry {entry!r} is not 'module:function'")
    return getattr(importlib.import_module(mod_name), fn_name)


def _build_ft(spec: Optional[Dict[str, Any]]):
    """FaultTolerance from the task's JSON ``ft`` spec. A
    ``shared_root`` (+ optional ``namespace``) becomes a
    SharedFSBundleStore — the cross-host discovery that lets a
    survivor resume a dead worker's run; every other key passes
    through to the policy constructor."""
    from deeplearning4j_tpu.util.resilience import (
        FaultTolerance, SharedFSBundleStore,
    )

    spec = dict(spec or {})
    store = None
    root = spec.pop("shared_root", None)
    namespace = spec.pop("namespace", "default")
    if root:
        store = SharedFSBundleStore(root, namespace)
    return FaultTolerance(bundle_store=store, **spec)


def echo_task(ctx: WorkerTaskContext) -> Dict[str, Any]:
    """Built-in smoke task: round-trips its params (proves the spawn/
    assign/run/result protocol without touching jax)."""
    return {"echo": ctx.params, "worker": ctx.worker,
            "attempt": ctx.attempt}


def spin_task(ctx: WorkerTaskContext) -> Dict[str, Any]:
    """Built-in drill task: spins for ``seconds`` (default: forever),
    draining early on a preemption notice — the no-jax way to exercise
    notices, SIGKILL-mid-task, and migration. Each step also ticks a
    counter in THIS process's registry, so federation drills have a
    worker-side series to watch arrive coordinator-side."""
    deadline = (time.monotonic() + float(ctx.params["seconds"])
                if "seconds" in ctx.params else None)
    step = 0
    drill = _telemetry.MetricsRegistry.get_default().counter(
        "dl4j_tpu_worker_drill_steps_total",
        "spin_task steps (metric-federation drill)")
    while deadline is None or time.monotonic() < deadline:
        if ctx.preemption_requested:
            ctx.drained = True
            return {"drained_at_step": step}
        step += 1
        ctx.progress(step)
        drill.inc()
        time.sleep(0.02)
    return {"steps": step}


class _WorkerMain:
    """The worker process body: heartbeat thread + task/notice loop."""

    def __init__(self, control_dir: str, name: str,
                 heartbeat_s: float = 0.2, metrics_s: float = 0.5):
        self.dir = os.path.join(control_dir, name)
        os.makedirs(self.dir, exist_ok=True)
        self.name = name
        self.heartbeat_s = float(heartbeat_s)
        self.metrics_s = float(metrics_s)
        self._lock = threading.Lock()
        self._state = {"state": "idle", "task": None, "step": 0}
        self._seq = 0
        self._stop = threading.Event()
        self._ft = None           # the running task's policy
        self._done_tasks: set = set()
        #: metric federation rides the heartbeat loop, gated on the
        #: inherited DL4J_TPU_TSDB opt-in (checked once here so an
        #: off-mode worker never imports the timeseries module)
        self._metrics_on = os.environ.get(
            "DL4J_TPU_TSDB", "0") not in ("0", "", "false")

    # -------------------------------------------------------- heartbeat
    def _beat_once(self) -> None:
        with self._lock:
            self._seq += 1
            payload = dict(self._state, t=time.time(), pid=os.getpid(),
                           seq=self._seq, worker=self.name)
        _write_json_atomic(os.path.join(self.dir, HEARTBEAT), payload)

    def _beat_loop(self) -> None:
        next_metrics = 0.0
        while not self._stop.is_set():
            try:
                self._beat_once()
            except OSError:
                pass              # control dir hiccup: next beat retries
            if self._metrics_on \
                    and time.monotonic() >= next_metrics:
                next_metrics = time.monotonic() + self.metrics_s
                self._publish_metrics()
            self._stop.wait(self.heartbeat_s)

    def _publish_metrics(self) -> None:
        """Federate this process's registry: an encoded capture next
        to the heartbeat, atomically replaced each cadence — the
        supervisor ingests it into the coordinator's time-series
        store under ``worker=``/``host=`` labels. Never raises (a
        full control volume must not kill the heartbeat loop)."""
        try:
            import socket

            from deeplearning4j_tpu.profiler import timeseries as _ts

            if not _ts.enabled():
                return
            cap = _telemetry.MetricsRegistry.get_default().capture()
            if not cap:
                return
            _write_json_atomic(
                os.path.join(self.dir, METRICS),
                {"worker": self.name, "host": socket.gethostname(),
                 "t": time.time(),
                 "capture": _ts.encode_capture(cap)})
        except Exception:
            log.debug("worker %s: metrics publish failed", self.name,
                      exc_info=True)

    def _set(self, **kw) -> None:
        with self._lock:
            self._state.update(kw)

    # ----------------------------------------------------------- signals
    def _install_signals(self) -> None:
        def _sigterm(signum, frame):
            ft = self._ft
            if ft is not None:
                # mid-task: behave like a platform grace period — the
                # policy checkpoints at the next boundary and the task
                # returns "preempted"
                ft.request_preemption(kind="signal")
            else:
                raise SystemExit(0)

        try:
            signal.signal(signal.SIGTERM, _sigterm)
        except (ValueError, OSError):
            pass

    # -------------------------------------------------------------- task
    def _run_task(self, spec: Dict[str, Any]) -> None:
        task_id = spec["task_id"]
        self._set(state="running", task=task_id, step=0)
        from deeplearning4j_tpu.util.resilience import NoticePoller

        ft = _build_ft(spec.get("ft"))
        self._ft = ft
        poller = NoticePoller(ft, file=os.path.join(self.dir, NOTICE),
                              poll_s=min(self.heartbeat_s, 0.1))
        poller.start()
        before = ft.preemptions_checkpointed
        result: Dict[str, Any] = {"task_id": task_id,
                                  "worker": self.name,
                                  "attempt": spec.get("attempt", 1)}
        try:
            fn = _resolve_entry(spec["entry"])
            ctx = WorkerTaskContext(
                self.name, task_id, spec.get("params"),
                spec.get("attempt", 1), ft,
                report=lambda s: self._set(step=s))
            value = fn(ctx)
            # drained = a preemption CHECKPOINT was written (a fit
            # honored the notice) or the entry declared a cooperative
            # early exit (ctx.drained). A raw still-set flag is NOT
            # enough: a notice landing after the fit's last boundary
            # must not re-queue a task that actually finished.
            preempted = (ft.preemptions_checkpointed > before
                         or ctx.drained)
            result["outcome"] = "preempted" if preempted else "completed"
            try:
                json.dumps(value)
            except (TypeError, ValueError):
                value = repr(value)
            result["result"] = value
            store = ft.store()
            if preempted and store is not None:
                result["bundle"] = store.latest_valid()
        except BaseException as e:   # the result file IS the report
            result["outcome"] = "failed"
            result["error"] = f"{type(e).__name__}: {e}"
            log.exception("worker %s: task %s failed", self.name,
                          task_id)
        finally:
            poller.stop()
            self._ft = None
        _write_json_atomic(
            os.path.join(self.dir, f"result-{task_id}.json"), result)
        self._done_tasks.add(task_id)
        if result["outcome"] == "preempted":
            # the platform is about to take this host: report, then
            # leave. The supervisor respawns us when the window passes.
            self._set(state="drained", task=None)
            self._beat_once()
            raise SystemExit(0)
        self._set(state="idle", task=None, step=0)

    # -------------------------------------------------------------- loop
    def run(self) -> int:
        self._install_signals()
        beat = threading.Thread(target=self._beat_loop, daemon=True,
                                name="WorkerHeartbeat")
        beat.start()
        log.warning("worker %s up (pid %d, control dir %s)", self.name,
                    os.getpid(), self.dir)
        try:
            while True:
                notice = _read_json(os.path.join(self.dir, NOTICE))
                if notice is not None and self._ft is None:
                    # idle worker noticed: nothing to checkpoint —
                    # drain immediately
                    self._set(state="drained")
                    self._beat_once()
                    return 0
                spec = _read_json(os.path.join(self.dir, TASK))
                if spec is not None \
                        and spec.get("task_id") not in self._done_tasks:
                    self._run_task(spec)
                time.sleep(0.05)
        except SystemExit:
            return 0
        finally:
            self._stop.set()


def main(argv: Sequence[str]) -> int:
    import argparse

    p = argparse.ArgumentParser(
        description="deeplearning4j_tpu supervised worker process")
    p.add_argument("control_dir")
    p.add_argument("name")
    p.add_argument("--heartbeat-s", type=float, default=0.2)
    args = p.parse_args(list(argv))
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    if os.environ.get("TPU_VISIBLE_CHIPS"):
        # the supervisor assigned this process a chip: take it now, so
        # that a worker which cannot have it ends here, non-zero, with
        # JAX's reason in worker.log
        import jax

        jax.devices()
    return _WorkerMain(args.control_dir, args.name,
                       args.heartbeat_s).run()


# ======================================================================
# the supervisor
# ======================================================================
class WorkerTask:
    """Supervisor-side task record + client handle."""

    def __init__(self, entry: str, params: Optional[Dict[str, Any]],
                 ft: Optional[Dict[str, Any]], *,
                 task_id: Optional[str] = None,
                 worker: Optional[str] = None,
                 resume: bool = True, max_migrations: int = 3):
        self.task_id = task_id or f"task-{uuid.uuid4().hex[:8]}"
        self.entry = str(entry)
        self.params = dict(params or {})
        self.ft = dict(ft or {})
        self.pinned = worker       # explicit placement, or None = any
        self.resume = bool(resume)
        self.max_migrations = int(max_migrations)
        self.state = "queued"      # queued|running|completed|preempted|
        #                            failed|cancelled
        self.worker: Optional[str] = None
        self.attempts = 0
        self.migrations = 0
        self.excluded: set = set()
        self.result: Any = None
        self.bundle: Optional[str] = None
        self.error: Optional[str] = None
        self._finished = threading.Event()

    @property
    def done(self) -> bool:
        return self._finished.is_set()

    def wait(self, timeout: Optional[float] = None) -> "WorkerTask":
        if not self._finished.wait(timeout):
            raise TimeoutError(
                f"task {self.task_id} still {self.state} after "
                f"{timeout}s")
        return self

    def status(self) -> Dict[str, Any]:
        return {"task_id": self.task_id, "entry": self.entry,
                "state": self.state, "worker": self.worker,
                "attempts": self.attempts,
                "migrations": self.migrations, "error": self.error,
                "bundle": self.bundle}


class _WorkerHandle:
    """Supervisor-side per-worker-process record."""

    def __init__(self, name: str, directory: str):
        self.name = name
        self.dir = directory
        self.proc: Optional[subprocess.Popen] = None
        self.state = "stopped"    # starting|alive|dead|drained|stopped
        self.restarts = 0
        self.task: Optional[WorkerTask] = None
        self.last_seq = -1
        self.last_seen = time.monotonic()
        self.last_beat: Dict[str, Any] = {}
        self.not_before = 0.0     # respawn backoff gate
        self.notice_deadline: Optional[float] = None
        #: next respawn is a maintenance-window return, not a crash
        #: recovery — it must not consume the restart budget
        self.respawn_free = False
        #: the worker was down (crash OR drain) since its last alive —
        #: the next first-heartbeat must restore fleet capacity
        self.was_down = False
        #: newest federated metrics.json timestamp already ingested
        self.last_metrics_t = 0.0

    def beat_age(self) -> float:
        return time.monotonic() - self.last_seen


class WorkerSupervisor:
    """Spawn, lease-monitor, preempt, and restart worker processes
    (module docstring). Construct, ``start()``, then ``submit_task``
    — or attach to a ``JobScheduler`` (``scheduler=`` here, or
    ``JobScheduler(supervisor=...)``) so process death and recovery
    drive the fleet's ``lose_worker``/``restore_worker`` capacity.

    ``lease_s`` is the liveness contract: a worker whose heartbeat
    file goes stale that long is presumed dead and hard-killed (a
    half-dead process must not keep writing to shared state after the
    fleet moved on — the same fencing reason real leases exist)."""

    def __init__(self, workers: Sequence[str] = ("w0", "w1"), *,
                 control_dir: Optional[str] = None,
                 heartbeat_s: float = 0.2, lease_s: float = 3.0,
                 poll_s: float = 0.1,
                 restart_workers: bool = True, max_restarts: int = 3,
                 restart_delay_s: float = 0.25,
                 scheduler=None, env: Optional[Dict[str, str]] = None,
                 python: Optional[str] = None,
                 coordinator: Optional[str] = None,
                 make_default: bool = True):
        self.control_dir = control_dir or tempfile.mkdtemp(
            prefix="dl4j_workers_")
        self.heartbeat_s = float(heartbeat_s)
        self.lease_s = float(lease_s)
        self.poll_s = float(poll_s)
        self.restart_workers = bool(restart_workers)
        self.max_restarts = int(max_restarts)
        self.restart_delay_s = float(restart_delay_s)
        self.scheduler = scheduler
        self.env = dict(env or {})
        self.python = python or sys.executable
        self.coordinator = coordinator
        self._names = [str(w) for w in workers]
        self._handles: Dict[str, _WorkerHandle] = {
            n: _WorkerHandle(
                n, os.path.join(self.control_dir, n))
            for n in self._names}
        self._tasks: Dict[str, WorkerTask] = {}
        self._queue: List[str] = []
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_gauges = 0.0
        if scheduler is not None and hasattr(scheduler,
                                             "attach_supervisor"):
            scheduler.attach_supervisor(self)
        if make_default:
            set_default_supervisor(self)

    # -------------------------------------------------------- lifecycle
    def start(self) -> "WorkerSupervisor":
        with self._lock:
            if self._thread is not None:
                return self
            for name in self._names:
                self._spawn(name)
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="WorkerSupervisor")
            self._thread.start()
        return self

    def shutdown(self, timeout: float = 10.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        deadline = time.monotonic() + timeout
        for h in self._handles.values():
            p = h.proc
            if p is None or p.poll() is not None:
                continue
            try:
                p.terminate()
            except OSError:
                pass
        for h in self._handles.values():
            p = h.proc
            if p is None:
                continue
            try:
                p.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(5)
            h.state = "stopped"
        with self._lock:
            for task in self._tasks.values():
                if not task.done:
                    task.state = "cancelled"
                    task._finished.set()
        if default_supervisor() is self:
            set_default_supervisor(None)

    def __enter__(self) -> "WorkerSupervisor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # ------------------------------------------------------------ spawn
    def _worker_env(self, name: str) -> Dict[str, str]:
        env = dict(os.environ)
        env.update(self.env)
        # the control dir rides the worker's sys.path so drills can
        # drop task modules right next to the protocol files; the
        # package root rides along so the spawned interpreter resolves
        # deeplearning4j_tpu regardless of its cwd
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        extra = self.control_dir + os.pathsep + pkg_root
        env["PYTHONPATH"] = (extra + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else extra)
        if self.coordinator:
            from deeplearning4j_tpu.parallel.mesh import worker_env

            env.update(worker_env(self.coordinator, len(self._names),
                                  self._names.index(name)))
        elif env.get("JAX_PLATFORMS", "").split(",")[0] == "tpu":
            # One process per chip. On a TPU host the first process
            # that touches JAX takes every chip and the next one fails,
            # so worker i is given chip i and nothing else (libtpu's
            # own variables; brought up on a v5e 2x2 host, PR 21). The
            # worker's JAX_PLATFORMS names the TPU alone: a worker that
            # cannot have its chip must not run on the CPU instead.
            i = self._names.index(name)
            env.update({
                "JAX_PLATFORMS": "tpu",
                "TPU_VISIBLE_CHIPS": str(i),
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1",
                "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{8476 + i}",
                "TPU_MESH_CONTROLLER_PORT": str(8476 + i)})
        return env

    def _spawn(self, name: str) -> None:
        h = self._handles[name]
        os.makedirs(h.dir, exist_ok=True)
        # never let a new incarnation act on the previous one's inputs
        for fname in (TASK, NOTICE, HEARTBEAT, METRICS):
            try:
                os.remove(os.path.join(h.dir, fname))
            except OSError:
                pass
        logf = open(os.path.join(h.dir, "worker.log"), "ab")
        try:
            h.proc = subprocess.Popen(
                [self.python, "-m",
                 "deeplearning4j_tpu.control.worker", self.control_dir,
                 name, "--heartbeat-s", str(self.heartbeat_s)],
                stdout=logf, stderr=subprocess.STDOUT,
                env=self._worker_env(name))
        finally:
            logf.close()
        h.state = "starting"
        h.last_seq = -1
        h.last_seen = time.monotonic()
        h.last_beat = {}         # never read a dead incarnation's beat
        h.notice_deadline = None
        _flight.record("worker_process_spawn", worker=name,
                       pid=h.proc.pid, restarts=h.restarts)
        log.warning("supervisor: spawned worker %s (pid %d)", name,
                    h.proc.pid)

    # ------------------------------------------------------------ client
    def submit_task(self, entry: str,
                    params: Optional[Dict[str, Any]] = None, *,
                    ft: Optional[Dict[str, Any]] = None,
                    worker: Optional[str] = None,
                    resume: bool = True,
                    max_migrations: int = 3) -> WorkerTask:
        task = WorkerTask(entry, params, ft, worker=worker,
                          resume=resume, max_migrations=max_migrations)
        with self._lock:
            self._tasks[task.task_id] = task
            self._queue.append(task.task_id)
        _flight.record("worker_task_submit", task=task.task_id,
                       entry=entry, worker=worker)
        self.start()
        return task

    def task(self, task_id: str) -> WorkerTask:
        with self._lock:
            return self._tasks[task_id]

    def preempt(self, worker: str, deadline_s: float = 30.0,
                kind: str = "notice") -> None:
        """Deliver a maintenance notice: the worker checkpoints and
        drains within ``deadline_s``; at the deadline a worker still
        running its task is SIGKILLed (the platform doesn't wait) and
        recovery degrades to the newest periodic bundle."""
        h = self._handles[str(worker)]
        _write_json_atomic(
            os.path.join(h.dir, NOTICE),
            {"deadline_s": float(deadline_s), "t": time.time(),
             "kind": kind})
        h.notice_deadline = time.monotonic() + float(deadline_s)
        _flight.record("worker_preempt_notice", worker=str(worker),
                       deadline_s=deadline_s, notice_kind=kind)
        log.warning("supervisor: maintenance notice for worker %s "
                    "(deadline %.1fs)", worker, deadline_s)

    def kill(self, worker: str) -> None:
        """SIGKILL a worker process — the chaos drill's hard host
        death (no notice, no grace, no cleanup)."""
        h = self._handles[str(worker)]
        p = h.proc
        _flight.record("worker_process_kill", worker=str(worker))
        if p is not None and p.poll() is None:
            p.kill()

    # ------------------------------------------------------------ status
    def workers_status(self) -> Dict[str, Dict[str, Any]]:
        out = {}
        with self._lock:
            for name, h in self._handles.items():
                out[name] = {
                    "state": h.state,
                    "pid": h.proc.pid if h.proc else None,
                    "restarts": h.restarts,
                    "heartbeat_age_s": round(h.beat_age(), 3),
                    "step": h.last_beat.get("step"),
                    "task": h.task.task_id if h.task else None,
                }
        return out

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            tasks = [t.status() for t in self._tasks.values()]
        return {"workers": self.workers_status(), "tasks": tasks,
                "control_dir": self.control_dir}

    def alive(self) -> List[str]:
        with self._lock:
            return [n for n, h in self._handles.items()
                    if h.state == "alive"]

    # ---------------------------------------------------------- monitor
    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self._poll_workers()
                self._assign_tasks()
                self._publish_gauges()
            except Exception:
                log.exception("supervisor: monitor pass failed")
            self._stop.wait(self.poll_s)

    def _poll_workers(self) -> None:
        now = time.monotonic()
        for name, h in self._handles.items():
            if h.proc is None:
                if h.state == "dead" and self.restart_workers \
                        and now >= h.not_before \
                        and (h.respawn_free
                             or h.restarts < self.max_restarts):
                    if h.respawn_free:
                        # maintenance-window return: planned, budget
                        # untouched — only crashes spend max_restarts
                        h.respawn_free = False
                    else:
                        h.restarts += 1
                    self._spawn(name)
                continue
            beat = _read_json(os.path.join(h.dir, HEARTBEAT))
            if beat is not None and beat.get("seq", -1) != h.last_seq:
                h.last_seq = beat.get("seq", -1)
                h.last_seen = now
                h.last_beat = beat
                if h.state == "starting":
                    self._on_worker_alive(h)
            self._ingest_worker_metrics(h)
            self._collect_result(h)
            rc = h.proc.poll()
            if rc is not None:
                drained = (h.last_beat.get("state") == "drained"
                           or (h.task is None and rc == 0))
                h.proc = None
                if drained and h.task is None:
                    h.state = "drained" if h.notice_deadline else "dead"
                    if h.state == "drained":
                        _flight.record("worker_process_drained",
                                       worker=name)
                        # respawn when the maintenance window passes
                        # — a planned return, free of restart budget
                        h.state = "dead"
                        h.was_down = True
                        h.respawn_free = True
                        h.not_before = h.notice_deadline or now
                        h.notice_deadline = None
                        continue
                self._on_worker_dead(h, f"process exited rc={rc}")
            elif h.state == "alive" and h.beat_age() > self.lease_s:
                # stale lease: fence the half-dead process, then treat
                # it exactly like a host death
                try:
                    h.proc.kill()
                    h.proc.wait(5)
                except OSError:
                    pass
                h.proc = None
                self._on_worker_dead(
                    h, f"heartbeat lease expired "
                       f"({h.beat_age():.1f}s > {self.lease_s}s)")
            elif h.notice_deadline is not None \
                    and now > h.notice_deadline:
                # the maintenance window closed and the worker is
                # still up: the platform kill lands NOW
                h.notice_deadline = None
                log.warning("supervisor: worker %s missed its notice "
                            "deadline — killing", name)
                try:
                    h.proc.kill()
                except OSError:
                    pass

    def _ingest_worker_metrics(self, h: _WorkerHandle) -> None:
        """Hand a fresh worker ``metrics.json`` to the coordinator's
        time-series sampler (``Sampler.ingest_remote``), which merges
        it into each tick under ``worker=``/``host=`` labels so range
        queries and SLO rules see the whole cluster. sys.modules-
        guarded: a supervisor in a TSDB-off process never imports
        (let alone feeds) the store."""
        _ts = sys.modules.get(
            "deeplearning4j_tpu.profiler.timeseries")
        if _ts is None:
            return
        sampler = _ts.default_sampler()
        if sampler is None:
            return
        obj = _read_json(os.path.join(h.dir, METRICS))
        if not obj:
            return
        try:
            t = float(obj.get("t", 0.0))
        except (TypeError, ValueError):
            return
        if t <= h.last_metrics_t:
            return                 # already ingested this capture
        cap = _ts.decode_capture(obj.get("capture") or {})
        if not cap:
            return
        h.last_metrics_t = t
        sampler.ingest_remote(cap, worker=h.name,
                              host=obj.get("host"), t=t)

    def _collect_result(self, h: _WorkerHandle) -> None:
        task = h.task
        if task is None:
            return
        res = _read_json(
            os.path.join(h.dir, f"result-{task.task_id}.json"))
        if res is None:
            return
        h.task = None
        outcome = res.get("outcome", "failed")
        task.worker = h.name
        task.result = res.get("result")
        task.bundle = res.get("bundle")
        task.error = res.get("error")
        if outcome == "preempted" and task.resume \
                and task.migrations < task.max_migrations:
            # checkpointed clean drain: the task itself continues on
            # another worker (the bundle store is how it finds its
            # own state)
            task.state = "preempted"
            task.migrations += 1
            task.excluded.add(h.name)
            with self._lock:
                self._queue.append(task.task_id)
            _flight.record("worker_task_migrated", task=task.task_id,
                           frm=h.name, reason="preempt_notice")
            return
        task.state = outcome
        task._finished.set()
        _flight.record("worker_task_finished", task=task.task_id,
                       worker=h.name, outcome=outcome)

    def _on_worker_alive(self, h: _WorkerHandle) -> None:
        h.state = "alive"
        _flight.record("worker_process_alive", worker=h.name,
                       pid=h.last_beat.get("pid"),
                       restarts=h.restarts)
        log.warning("supervisor: worker %s alive (pid %s)", h.name,
                    h.last_beat.get("pid"))
        sched = self.scheduler
        if sched is not None and h.was_down:
            # every return from a down period restores capacity —
            # crash respawns AND maintenance-window returns (the
            # latter never touch the restart budget)
            try:
                sched.on_worker_process_alive(h.name)
            except Exception:
                log.exception("supervisor: scheduler restore hook "
                              "failed for %s", h.name)
        h.was_down = False

    def _on_worker_dead(self, h: _WorkerHandle, why: str) -> None:
        h.state = "dead"
        h.was_down = True
        h.not_before = time.monotonic() + self.restart_delay_s
        _flight.record("worker_process_dead", worker=h.name, why=why)
        log.warning("supervisor: worker %s DEAD (%s)", h.name, why)
        task = h.task
        if task is not None:
            h.task = None
            task.excluded.add(h.name)
            if task.resume and task.migrations < task.max_migrations:
                task.state = "queued"
                task.migrations += 1
                with self._lock:
                    self._queue.append(task.task_id)
                _flight.record("worker_task_migrated",
                               task=task.task_id, frm=h.name,
                               reason="worker_dead")
                log.warning("supervisor: task %s migrates off dead "
                            "worker %s", task.task_id, h.name)
            else:
                task.state = "failed"
                task.error = f"worker {h.name} died: {why}"
                task._finished.set()
        sched = self.scheduler
        if sched is not None:
            try:
                sched.on_worker_process_dead(h.name, why)
            except Exception:
                log.exception("supervisor: scheduler verdict hook "
                              "failed for %s", h.name)

    def _assign_tasks(self) -> None:
        with self._lock:
            queue = list(self._queue)
        for task_id in queue:
            task = self._tasks.get(task_id)
            if task is None or task.done:
                with self._lock:
                    if task_id in self._queue:
                        self._queue.remove(task_id)
                continue
            target = None
            blocked_only_by_exclusion = False
            for name, h in self._handles.items():
                if h.state != "alive" or h.task is not None:
                    continue
                if task.pinned is not None and name != task.pinned:
                    continue
                if name in task.excluded:
                    blocked_only_by_exclusion = True
                    continue
                target = h
                break
            if target is None:
                if blocked_only_by_exclusion:
                    # every schedulable worker is excluded — but an
                    # exclusion only means "not the incarnation that
                    # just died/drained"; an ALIVE worker is a fresh
                    # incarnation, so stale exclusions are lifted
                    # rather than leaving the task queued forever
                    task.excluded.clear()
                continue          # no capacity yet: stays queued
            task.attempts += 1
            task.state = "running"
            task.worker = target.name
            target.task = task
            _write_json_atomic(
                os.path.join(target.dir, TASK),
                {"task_id": task.task_id, "entry": task.entry,
                 "params": task.params, "ft": task.ft,
                 "attempt": task.attempts})
            with self._lock:
                self._queue.remove(task_id)
            _flight.record("worker_task_assign", task=task.task_id,
                           worker=target.name, attempt=task.attempts)

    # ----------------------------------------------------------- gauges
    def _publish_gauges(self, force: bool = False) -> None:
        if not _telemetry.enabled():
            return
        now = time.monotonic()
        if not force and now - self._last_gauges < 0.5:
            return
        self._last_gauges = now
        reg = _telemetry.MetricsRegistry.get_default()
        counts: Dict[str, int] = {}
        with self._lock:
            for h in self._handles.values():
                counts[h.state] = counts.get(h.state, 0) + 1
            # EVERY worker publishes an age: a dead/unspawned
            # worker's age keeps CLIMBING (last_seen froze at its
            # final beat) instead of the series freezing at a small
            # healthy-looking value — the operator's "age climbing
            # toward lease_s / beyond it" read stays truthful
            ages = {n: h.beat_age() for n, h in self._handles.items()}
        g = reg.gauge(_telemetry.WORKER_PROCESSES,
                      "supervised worker processes by state")
        for state in ("starting", "alive", "dead", "drained",
                      "stopped"):
            g.set(counts.get(state, 0), state=state)
        ga = reg.gauge(_telemetry.WORKER_HEARTBEAT_AGE,
                       "seconds since each worker's last heartbeat "
                       "(climbs unbounded while a worker is down)")
        for name, age in ages.items():
            ga.set(round(age, 3), worker=name)


# ======================================================================
# default-supervisor registry (HTTP surface parity with the scheduler)
# ======================================================================
_default_sup: Optional[WorkerSupervisor] = None
_sup_lock = threading.Lock()


def set_default_supervisor(sup: Optional[WorkerSupervisor]) -> None:
    global _default_sup
    with _sup_lock:
        _default_sup = sup


def default_supervisor() -> Optional[WorkerSupervisor]:
    return _default_sup


def workers_snapshot() -> Dict[str, Any]:
    """Peek-style snapshot for telemetry embedding ({} without a live
    supervisor)."""
    s = _default_sup
    return s.snapshot() if s is not None else {}


__all__ = ["WorkerSupervisor", "WorkerTask", "WorkerTaskContext",
           "echo_task", "spin_task", "main",
           "set_default_supervisor", "default_supervisor",
           "workers_snapshot"]


if __name__ == "__main__":       # pragma: no cover - subprocess entry
    sys.exit(main(sys.argv[1:]))
