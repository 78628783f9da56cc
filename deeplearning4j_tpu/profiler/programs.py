"""Roofline program registry + managed device profiling.

The rest of the profiler records *how fast* each site is (step
latencies, one cost_analysis FLOPs number for MFU); this module records
*where the time goes*. Two layers:

**ProgramRegistry** — a process-wide table of every compiled XLA
executable the stack runs: site label, input shape/dtype signature,
compile wall time, HLO digest, ``cost_analysis()`` (flops, bytes
accessed, transcendentals) and ``memory_analysis()`` (temp / argument /
output / generated-code bytes). Per-dispatch accounting (count + host
wall time) turns the static numbers into achieved FLOP/s, achieved
GB/s, arithmetic intensity and a **roofline verdict** per program:

- ``compute_bound``  — AI ≥ ridge point (peak_flops / peak_bandwidth)
- ``memory_bound``   — AI below the ridge: fuse or re-layout, don't
  look for a faster MXU schedule
- ``dispatch_bound`` — the program is too small for the device: its
  roofline-model runtime is under ``DISPATCH_FLOOR_S``, or (when real
  peaks are known) measured dispatch wall time exceeds
  ``DISPATCH_FACTOR``× the roofline time — launch/host overhead
  dominates and kernel tuning is pointless
- ``unknown``        — XLA reported no flops/bytes for the program

Peaks come from ``profiler/flops.py`` (``PEAK_FLOPS`` +
``PEAK_HBM_GBPS``). On the CPU backend, which has no entry,
classification uses the NOMINAL_* v5e-class ratios (labeled
``"nominal"`` in snapshots) so verdicts stay available in CPU test runs
without publishing utilization numbers; an accelerator whose kind is
not in the table raises.

Populated from ``telemetry.instrument_jit`` (training/eval step sites)
and the serving engines' AOT warm pools (decode/prefill/adopt sites).
The registry is OFF by default (``DL4J_TPU_PROGRAMS=1`` env or
``set_enabled(True)``): registration re-lowers the jitted call once at
compile time (abstract trace, hits the executable cache — no second
XLA compile) and per-dispatch accounting is one enabled-check when off,
so off-mode hot paths are bit-identical.

**ProfileSession** — makes device capture a managed artifact instead of
a bare ``jax.profiler`` wrapper. One session per process interlocks
ad-hoc traces (``profiler.start_trace``/``trace()``) and bounded
``capture()`` runs, since jax.profiler supports exactly one active
trace. ``capture()`` writes a digest-valid bundle::

    profile-<utc-stamp>-<trigger>-<pid>-<nonce>/
        trace/...            # raw jax.profiler output (xplane/trace.json)
        programs.json        # registry snapshot at capture time
        manifest.json        # format + trigger + per-file sha256 digests

written atomically (tmp dir → fsync → rename, same recipe as flight
incident dumps), pruned keep-newest (``KEEP_CAPTURES``). Triggers:
``POST /v1/profile`` on the ui/remote servers, the SLO engine's
firing-page hook (``slo.SLOEngine`` — rate-limited via
``maybe_capture``), and ``bench.py --profile``. Every capture emits a
``profile_capture`` flight event and bumps
``dl4j_tpu_profile_captures_total{trigger}``.

Host-wall caveat: dispatch seconds are measured on the host around the
executable call; with async device dispatch they are an upper bound on
launch cost and a lower bound on device occupancy. The training fronts
block per step (score fetch), so step sites are accurate there; decode
sites include queueing slack. The trace bundle is the ground truth.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import shutil
import tempfile
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from deeplearning4j_tpu.profiler import flight_recorder as _flight
from deeplearning4j_tpu.profiler import telemetry as _telemetry
from deeplearning4j_tpu.profiler.flops import (
    PEAK_FLOPS, PEAK_HBM_GBPS, peak_flops, peak_hbm_gbps,
)

log = logging.getLogger("deeplearning4j_tpu")

_FORMAT = "dl4j-tpu-profile-1"

#: classification ratios for the CPU backend, which has no peak-table
#: entry (v5e-class bf16 ratios); used for the VERDICT only, never for
#: published utilization numbers.
NOMINAL_PEAK_FLOPS = 197e12
NOMINAL_PEAK_HBM_GBPS = 819.0

#: roofline-model runtime below this is launch overhead territory on
#: any real accelerator (grid launch + host sync ~O(100µs)).
DISPATCH_FLOOR_S = 1e-4
#: measured avg dispatch this many times over the roofline-model time
#: (known peaks only) also reads dispatch_bound.
DISPATCH_FACTOR = 10.0

VERDICTS = ("compute_bound", "memory_bound", "dispatch_bound", "unknown")

_ENABLED = os.environ.get("DL4J_TPU_PROGRAMS", "0") == "1"


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


# ------------------------------------------------------------- verdicts
def roofline_verdict(flops: Optional[float],
                     bytes_accessed: Optional[float],
                     avg_dispatch_s: Optional[float] = None,
                     peak_fl: Optional[float] = None,
                     peak_bw_gbps: Optional[float] = None) -> str:
    """Classify a program against the roofline. ``peak_fl`` /
    ``peak_bw_gbps`` None → nominal ratios (classification only; the
    measured-dispatch test is skipped because comparing CPU wall time
    against a TPU roofline would mislabel everything dispatch_bound)."""
    if not flops or not bytes_accessed:
        return "unknown"
    nominal = peak_fl is None or peak_bw_gbps is None
    pf = peak_fl if peak_fl else NOMINAL_PEAK_FLOPS
    bw = (peak_bw_gbps if peak_bw_gbps else NOMINAL_PEAK_HBM_GBPS) * 1e9
    roofline_s = max(flops / pf, bytes_accessed / bw)
    if roofline_s < DISPATCH_FLOOR_S:
        return "dispatch_bound"
    if (not nominal and avg_dispatch_s is not None
            and avg_dispatch_s > DISPATCH_FACTOR * roofline_s):
        return "dispatch_bound"
    if flops / bytes_accessed < pf / bw:
        return "memory_bound"
    return "compute_bound"


# ------------------------------------------------------------- registry
class _Program:
    __slots__ = ("site", "signature", "source", "engine",
                 "compile_seconds", "hlo_digest", "flops",
                 "bytes_accessed", "transcendentals", "memory",
                 "dispatches", "timed_dispatches", "dispatch_seconds")

    def __init__(self, site, signature, source, engine, compile_seconds):
        self.site = site
        self.signature = signature
        self.source = source
        self.engine = engine
        self.compile_seconds = compile_seconds
        self.hlo_digest = None
        self.flops = None
        self.bytes_accessed = None
        self.transcendentals = None
        self.memory: Dict[str, int] = {}
        self.dispatches = 0
        self.timed_dispatches = 0
        self.dispatch_seconds = 0.0


def _extract(prog: _Program, compiled) -> None:
    """Pull cost/memory analysis + HLO digest off a compiled
    executable; every probe is individually guarded — a backend that
    can't answer one question must not lose the others."""
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        if ca:
            f = ca.get("flops")
            b = ca.get("bytes accessed")
            t = ca.get("transcendentals")
            prog.flops = float(f) if f else None
            prog.bytes_accessed = float(b) if b else None
            prog.transcendentals = float(t) if t else None
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            prog.memory = {
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "argument_bytes": int(
                    getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(
                    getattr(ma, "output_size_in_bytes", 0)),
                "generated_code_bytes": int(
                    getattr(ma, "generated_code_size_in_bytes", 0)),
            }
    except Exception:
        pass
    try:
        prog.hlo_digest = hashlib.sha256(
            compiled.as_text().encode()).hexdigest()[:16]
    except Exception:
        pass


class ProgramRegistry:
    """Process-wide table of compiled executables, keyed
    ``(site, signature)`` — a site that recompiles per shape (TBPTT
    tails, serving batch tiers) gets one row per signature."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[Tuple[str, str], _Program] = {}

    def register(self, site: str, signature: str, compiled, *,
                 source: str = "jit", engine: Optional[str] = None,
                 compile_seconds: Optional[float] = None) -> None:
        """Record (or refresh) one compiled executable. Never raises."""
        try:
            prog = _Program(site, signature, source, engine,
                            compile_seconds)
            _extract(prog, compiled)
            with self._lock:
                old = self._programs.get((site, signature))
                if old is not None:
                    # recompile of a known shape: keep the dispatch
                    # history, refresh the analysis
                    prog.dispatches = old.dispatches
                    prog.timed_dispatches = old.timed_dispatches
                    prog.dispatch_seconds = old.dispatch_seconds
                self._programs[(site, signature)] = prog
        except Exception:
            log.exception("program registry: register(%s) failed", site)

    def record_dispatch(self, site: str, signature: Optional[str],
                        seconds: Optional[float]) -> None:
        """Count one dispatch. ``seconds=None`` counts without timing
        (the compile call's wall time is compile, not execution).
        Unknown (site, signature) dispatches are dropped — a program
        must register before it is accounted."""
        if signature is None:
            return
        with self._lock:
            prog = self._programs.get((site, signature))
            if prog is None:
                return
            prog.dispatches += 1
            if seconds is not None:
                prog.timed_dispatches += 1
                prog.dispatch_seconds += seconds

    def size(self) -> int:
        with self._lock:
            return len(self._programs)

    def reset(self) -> None:
        with self._lock:
            self._programs.clear()

    # ------------------------------------------------------- snapshot
    @staticmethod
    def _device_peaks() -> Dict[str, Any]:
        """Device-0 peak entries. The CPU has none and is classified
        against the nominal ratios (labelled ``"nominal"``); any other
        device missing from the peaks table is an error, not a default.
        """
        import jax

        d0 = jax.devices()[0]
        dev: Dict[str, Any] = {"kind": d0.device_kind}
        if d0.platform != "cpu" and d0.device_kind not in PEAK_FLOPS:
            raise RuntimeError(
                f"no peak-FLOPs entry for {d0.platform} device kind "
                f"{d0.device_kind!r}: add it to profiler.flops.PEAK_FLOPS"
                " (roofline verdicts are not computed against another "
                "chip's ratios)")
        fl = PEAK_FLOPS.get(dev["kind"])
        if fl is not None:
            dev["peak_flops"] = dict(fl)
        bw = peak_hbm_gbps()
        if bw is not None:
            dev["peak_hbm_gbps"] = bw
        return dev

    @staticmethod
    def _program_dict(p: _Program, peak_fl, peak_bw) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "site": p.site, "signature": p.signature,
            "source": p.source, "engine": p.engine,
            "compile_seconds": p.compile_seconds,
            "hlo_digest": p.hlo_digest,
            "flops": p.flops, "bytes_accessed": p.bytes_accessed,
            "transcendentals": p.transcendentals,
            "memory": dict(p.memory),
            "dispatches": p.dispatches,
            "dispatch_seconds": round(p.dispatch_seconds, 6),
        }
        if p.flops and p.bytes_accessed:
            d["arithmetic_intensity"] = p.flops / p.bytes_accessed
        avg = (p.dispatch_seconds / p.timed_dispatches
               if p.timed_dispatches else None)
        if avg and p.flops:
            d["achieved_flops_per_s"] = p.flops / avg
            if peak_fl:
                d["mfu"] = round(p.flops / avg / peak_fl, 4)
        if avg and p.bytes_accessed:
            d["achieved_gbps"] = p.bytes_accessed / avg / 1e9
            if peak_bw:
                d["hbm_utilization"] = round(
                    p.bytes_accessed / avg / 1e9 / peak_bw, 4)
        d["verdict"] = roofline_verdict(
            p.flops, p.bytes_accessed, avg_dispatch_s=avg,
            peak_fl=peak_fl, peak_bw_gbps=peak_bw)
        return d

    def snapshot(self, top_n: Optional[int] = None) -> Dict[str, Any]:
        """JSON-ready registry view: per-program rows (sorted by total
        dispatch wall time, descending — "top N by device time") plus
        per-site aggregates with their own roofline verdict."""
        with self._lock:
            progs = list(self._programs.values())
        dev = self._device_peaks()
        kind = dev.get("kind")
        peak_bw = PEAK_HBM_GBPS.get(kind) if kind else None

        def _peak_fl(p: _Program) -> Optional[float]:
            entry = PEAK_FLOPS.get(kind) if kind else None
            if entry is None:
                return None
            key = ("bf16" if p.signature and "bfloat16" in p.signature
                   else "f32")
            return entry.get(key)

        rows = [self._program_dict(p, _peak_fl(p), peak_bw)
                for p in progs]
        rows.sort(key=lambda d: (-d["dispatch_seconds"], d["site"]))

        sites: Dict[str, Dict[str, Any]] = {}
        for d in rows:
            s = sites.setdefault(d["site"], {
                "programs": 0, "dispatches": 0, "dispatch_seconds": 0.0,
                "flops": 0.0, "bytes_accessed": 0.0, "verdict": "unknown",
            })
            s["programs"] += 1
            s["dispatches"] += d["dispatches"]
            s["dispatch_seconds"] = round(
                s["dispatch_seconds"] + d["dispatch_seconds"], 6)
            s["flops"] += (d["flops"] or 0.0) * d["dispatches"]
            s["bytes_accessed"] += \
                (d["bytes_accessed"] or 0.0) * d["dispatches"]
            if s["verdict"] == "unknown":
                # rows arrive sorted by device time: the first program
                # with a verdict is the site's dominant one
                s["verdict"] = d["verdict"]
        for s in sites.values():
            if s["flops"] and s["bytes_accessed"]:
                s["arithmetic_intensity"] = \
                    s["flops"] / s["bytes_accessed"]

        if top_n is not None:
            rows = rows[:max(0, int(top_n))]
        out: Dict[str, Any] = {
            "enabled": _ENABLED,
            "peak_source": ("table" if kind in PEAK_FLOPS
                            else "nominal"),
            "programs": rows,
            "sites": sites,
        }
        if dev:
            out["device"] = dev
        return out


_default: Optional[ProgramRegistry] = None
_dlock = threading.Lock()


def get_default() -> ProgramRegistry:
    global _default
    if _default is None:
        with _dlock:
            if _default is None:
                _default = ProgramRegistry()
    return _default


def snapshot(top_n: Optional[int] = None) -> Dict[str, Any]:
    """Peek-style snapshot for ``telemetry.snapshot()`` embedding: {}
    unless at least one program has registered (so off-mode snapshots
    are unchanged)."""
    r = _default
    if r is None or not r.size():
        return {}
    return r.snapshot(top_n)


def on_jit_compile(site: str, fn, args, kwargs, signature: str,
                   compile_seconds: float) -> None:
    """``telemetry.instrument_jit`` hook, called on a detected compile.
    Re-lowers the call abstractly — this hits the executable cache the
    compile just populated, so there is no second XLA compile. Never
    raises."""
    if not _ENABLED:
        return
    try:
        compiled = fn.lower(*args, **kwargs).compile()
    except Exception:
        log.debug("program registry: lower(%s) failed", site,
                  exc_info=True)
        return
    get_default().register(site, signature, compiled, source="jit",
                           compile_seconds=compile_seconds)


def record_dispatch(site: str, signature: Optional[str],
                    seconds: Optional[float]) -> None:
    if not _ENABLED:
        return
    r = _default
    if r is not None:
        r.record_dispatch(site, signature, seconds)


# ------------------------------------------------------ profile session
def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _slug(s: str) -> str:
    return "".join(c if c.isalnum() or c in "-_" else "-"
                   for c in str(s))[:48] or "manual"


def list_captures(root: str) -> List[str]:
    """Capture bundle dirs under ``root``, oldest first (the UTC stamp
    prefix makes name order time order)."""
    try:
        names = sorted(n for n in os.listdir(root)
                       if n.startswith("profile-")
                       and not n.startswith(".")
                       and os.path.isfile(
                           os.path.join(root, n, "manifest.json")))
    except OSError:
        return []
    return [os.path.join(root, n) for n in names]


class ProfileSession:
    """Single owner of the process's jax.profiler trace slot.

    jax.profiler supports ONE active trace per process (a second
    ``start_trace`` raises RuntimeError from inside XLA); this class is
    the interlock between ad-hoc traces (``profiler.start_trace`` /
    ``trace()``) and managed bounded ``capture()`` bundles, so they can
    never interleave. All entry points are no-op-with-warning rather
    than raising when the slot is busy."""

    KEEP_CAPTURES = 8
    MAX_DURATION_S = 60.0

    def __init__(self, directory: Optional[str] = None):
        self._lock = threading.Lock()
        self._owner: Optional[str] = None      # "manual" | "capture"
        self.directory = directory
        self.last_bundle: Optional[str] = None
        self._last_capture_mono: Optional[float] = None

    def active(self) -> Optional[str]:
        """Current owner ("manual"/"capture") or None when idle."""
        with self._lock:
            return self._owner

    def _resolve_dir(self, directory: Optional[str]) -> str:
        return (directory or self.directory
                or os.environ.get("DL4J_TPU_PROFILE_DIR")
                or os.path.join(tempfile.gettempdir(),
                                "dl4j_tpu_profiles"))

    # ------------------------------------------------- ad-hoc traces
    def start_manual(self, log_dir: str) -> bool:
        """Idempotent-with-warning start. False when a trace or capture
        is already active (the old code called jax.profiler.start_trace
        again and got RuntimeError)."""
        import jax

        with self._lock:
            if self._owner is not None:
                log.warning(
                    "profiler: a %s trace is already active — ignoring "
                    "start_trace(%r)", self._owner, log_dir)
                return False
            # failure (bad dir, backend refusal) propagates and leaves
            # the slot free — trace() then knows not to stop
            jax.profiler.start_trace(log_dir)
            self._owner = "manual"
            return True

    def stop_manual(self) -> bool:
        """Stop an ad-hoc trace; False (no-op) unless one is active —
        a managed capture in flight is never stopped from here."""
        import jax

        with self._lock:
            if self._owner != "manual":
                return False
            try:
                jax.profiler.stop_trace()
            finally:
                self._owner = None
            return True

    # ---------------------------------------------- managed captures
    def capture(self, duration_s: float = 0.5, *,
                trigger: str = "manual",
                directory: Optional[str] = None,
                work=None) -> Optional[str]:
        """Bounded jax.profiler capture → digest-valid bundle path, or
        None (slot busy / capture failed). Never raises. ``work`` (a
        nullary callable, e.g. one training step) runs inside the trace
        before the residual ``duration_s`` sleep, so there is always
        device activity on the timeline."""
        import jax

        try:
            duration_s = min(max(float(duration_s), 0.0),
                             self.MAX_DURATION_S)
        except (TypeError, ValueError):
            return None
        with self._lock:
            if self._owner is not None:
                log.warning(
                    "profiler: capture(%s) skipped — a %s trace is "
                    "already active", trigger, self._owner)
                return None
            self._owner = "capture"
        root = self._resolve_dir(directory)
        name = (f"profile-{time.strftime('%Y%m%dT%H%M%S', time.gmtime())}"
                f"-{_slug(trigger)}-{os.getpid()}-{uuid.uuid4().hex[:6]}")
        final = os.path.join(root, name)
        tmp = os.path.join(root, f".{name}.tmp")
        try:
            os.makedirs(os.path.join(tmp, "trace"), exist_ok=True)
            t0 = time.perf_counter()
            jax.profiler.start_trace(os.path.join(tmp, "trace"))
            try:
                if work is not None:
                    work()
                if duration_s > 0:
                    time.sleep(duration_s)
            finally:
                jax.profiler.stop_trace()
            path = self._write_bundle(
                tmp, final, root, trigger,
                measured_s=time.perf_counter() - t0)
        except Exception:
            log.exception("profiler: capture(%s) failed", trigger)
            shutil.rmtree(tmp, ignore_errors=True)
            return None
        finally:
            with self._lock:
                self._owner = None
        self.last_bundle = path
        _flight.record("profile_capture", trigger=trigger, bundle=path)
        _telemetry.MetricsRegistry.get_default().counter(
            _telemetry.PROFILE_CAPTURES,
            "managed device-profile captures written").inc(
            trigger=trigger)
        log.info("profiler: capture(%s) wrote %s", trigger, path)
        return path

    def maybe_capture(self, *, trigger: str, duration_s: float = 0.25,
                      min_interval_s: float = 120.0,
                      directory: Optional[str] = None,
                      work=None) -> Optional[str]:
        """Rate-limited capture for automated triggers (the SLO page
        hook): at most one bundle per ``min_interval_s`` across ALL
        automated triggers, None when inside the window or the slot is
        busy. Manual ``capture()`` calls do NOT advance the limiter —
        an operator forcing a bundle must not suppress the next
        alert-triggered diagnostic. Never raises."""
        with self._lock:
            last = self._last_capture_mono
        if last is not None and \
                time.monotonic() - last < min_interval_s:
            log.info("profiler: capture(%s) rate-limited "
                     "(min_interval_s=%s)", trigger, min_interval_s)
            return None
        path = self.capture(duration_s, trigger=trigger,
                            directory=directory, work=work)
        if path is not None:
            self._last_capture_mono = time.monotonic()
        return path

    # ------------------------------------------------------- bundles
    def _write_bundle(self, tmp: str, final: str, root: str,
                      trigger: str, measured_s: float) -> str:
        from deeplearning4j_tpu.util.model_serializer import \
            fsync_directory

        with open(os.path.join(tmp, "programs.json"), "w") as f:
            f.write(json.dumps(get_default().snapshot()))
        digests = {}
        for dirpath, _dirnames, filenames in os.walk(tmp):
            for fn in filenames:
                p = os.path.join(dirpath, fn)
                rel = os.path.relpath(p, tmp)
                digests[rel] = _sha256(p)
                with open(p, "rb") as f:
                    os.fsync(f.fileno())
        manifest = {"format": _FORMAT, "trigger": trigger,
                    "created_unix": time.time(),
                    "duration_s": round(measured_s, 6),
                    "pid": os.getpid(), "digests": digests}
        mpath = os.path.join(tmp, "manifest.json")
        with open(mpath, "w") as f:
            f.write(json.dumps(manifest, indent=1, sort_keys=True))
            f.flush()
            os.fsync(f.fileno())
        fsync_directory(tmp)
        os.replace(tmp, final)
        fsync_directory(root)
        for old in list_captures(root)[:-self.KEEP_CAPTURES]:
            shutil.rmtree(old, ignore_errors=True)
        return final


_session: Optional[ProfileSession] = None


def profile_session() -> ProfileSession:
    global _session
    if _session is None:
        with _dlock:
            if _session is None:
                _session = ProfileSession()
    return _session


def load_capture(path: str) -> Dict[str, Any]:
    """Read a capture bundle back, verifying every manifest digest.
    Returns {"path", "valid", "manifest", "programs"}; ``valid`` False
    on format mismatch, a missing member, or a digest mismatch."""
    out: Dict[str, Any] = {"path": path, "valid": False,
                           "manifest": None, "programs": None}
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        return out
    out["manifest"] = manifest
    if manifest.get("format") != _FORMAT:
        return out
    ok = True
    for rel, want in (manifest.get("digests") or {}).items():
        p = os.path.join(path, rel)
        try:
            ok = ok and _sha256(p) == want
        except OSError:
            ok = False
    out["valid"] = ok
    if "programs.json" in (manifest.get("digests") or {}):
        try:
            with open(os.path.join(path, "programs.json")) as f:
                out["programs"] = json.load(f)
        except (OSError, ValueError):
            out["valid"] = False
    return out


# ----------------------------------------------------------------- http
def http_programs(query: str = "") -> Tuple[Dict[str, Any], int]:
    """GET /v1/programs handler shared by the ui and remote servers:
    registry snapshot, top-N programs by device time (?n=, default 50).
    Returns (json-ready object, http status)."""
    import urllib.parse

    n = 50
    try:
        q = urllib.parse.parse_qs(query or "")
        if "n" in q:
            n = max(1, min(500, int(q["n"][0])))
    except ValueError:
        return {"error": "n must be an integer"}, 400
    return get_default().snapshot(top_n=n), 200


def http_profile(payload: Any) -> Tuple[Dict[str, Any], int]:
    """POST /v1/profile handler: forced (non-rate-limited) capture.
    Body: {"duration_s": 0.5, "trigger": "...", "directory": "..."} —
    all optional. 409 when a trace/capture is already active, 500 when
    the capture itself failed."""
    if not isinstance(payload, dict):
        return {"error": "body must be a JSON object"}, 400
    try:
        duration = float(payload.get("duration_s", 0.5))
    except (TypeError, ValueError):
        return {"error": "duration_s must be a number"}, 400
    if not 0.0 <= duration <= ProfileSession.MAX_DURATION_S:
        return {"error": "duration_s must be in "
                         f"[0, {ProfileSession.MAX_DURATION_S}]"}, 400
    sess = profile_session()
    if sess.active() is not None:
        return {"error": "a profiler trace/capture is already "
                         "active"}, 409
    path = sess.capture(duration,
                        trigger=_slug(payload.get("trigger") or "http"),
                        directory=payload.get("directory"))
    if path is None:
        return {"error": "profile capture failed (see logs)"}, 500
    return {"bundle": path,
            "programs": get_default().size()}, 200


def reset() -> None:
    """Fresh default registry + session rate-limit state (tests /
    between bench rounds). An ACTIVE session is left untouched."""
    global _default, _session
    with _dlock:
        _default = None
        s = _session
        if s is not None and s.active() is None:
            _session = None


__all__ = [
    "ProgramRegistry", "ProfileSession", "roofline_verdict", "VERDICTS",
    "NOMINAL_PEAK_FLOPS", "NOMINAL_PEAK_HBM_GBPS", "DISPATCH_FLOOR_S",
    "DISPATCH_FACTOR", "enabled", "set_enabled", "get_default",
    "snapshot", "on_jit_compile", "record_dispatch", "profile_session",
    "load_capture", "list_captures", "http_programs", "http_profile",
    "reset", "PEAK_FLOPS", "PEAK_HBM_GBPS", "peak_flops",
    "peak_hbm_gbps",
]
