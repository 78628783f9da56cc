"""Request-scoped distributed tracing.

The telemetry spine (telemetry.py) answers "how is the PROCESS doing"
— histograms, gauges, a Chrome-trace buffer of host spans. What it
cannot answer is "why was REQUEST 1743 slow" or "which HOST is the
straggler": spans carry no identity that survives aggregation. This
module adds that identity layer:

- ``TraceContext`` — a lightweight per-request (or per-fit-site) trace:
  ``trace_id``, optional ``request_id``, host/process id, and a bounded
  event list. An event is ALSO emitted into the telemetry span buffer
  tagged ``trace``/``request``/``host``, so one Chrome-trace export
  carries both the process story and the per-request story, unless
  the interval is one the program has already spanned: the timeline
  then carries that span's id, and the buffer holds it once.
- The serving engine threads a context through a request's whole life:
  ``submit -> queue_wait -> prefill -> decode_burst* -> finish``. The
  finished timeline lands in a bounded registry served at
  ``GET /v1/serving/requests/<id>`` (remote/server.py) and summarized
  on ``/telemetry``.
- The fit loops record one ``train_step`` event per step under a
  long-lived per-site context (``record_train_step``), so a training
  incident dump carries the same timeline shape a serving request does.
- Multi-host: every span is tagged with ``jax.process_index()``. Worker
  hosts ``push_spans(coordinator_url)`` their per-span-name aggregates;
  the coordinator's UI server ingests them at ``POST /telemetry/spans``
  and ``/telemetry`` then shows per-host step totals side by side — a
  straggler host is a visibly fatter ``device_step`` row, not a guess.

Off-mode contract (mirrors PR 5's HealthMonitor): tracing defaults OFF
(``DL4J_TPU_TRACING=1`` or ``set_enabled(True)`` to opt in) and every
hook's disabled path is one module-attribute read — serving and fit
paths are bit-identical with tracing off, and tracing is host-side
only, so enabling it never changes numerics either.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
import urllib.request
import uuid
from typing import Any, Dict, List, Optional

from deeplearning4j_tpu.profiler import telemetry as _telemetry

_ENABLED = os.environ.get("DL4J_TPU_TRACING", "0") not in ("0", "")

#: bounded registries: finished timelines kept for /v1/serving/requests
#: lookups, live contexts kept for incident dumps (flight_recorder.py)
_RECENT_MAX = 256
_LIVE_MAX = 1024
#: events retained per context (a request's decode bursts are bounded
#: by max_new_tokens / chunking anyway; train contexts wrap)
_EVENTS_PER_TRACE = 512

_lock = threading.Lock()
_live: "collections.OrderedDict[str, TraceContext]" = \
    collections.OrderedDict()
_recent: "collections.OrderedDict[str, Dict[str, Any]]" = \
    collections.OrderedDict()
_train: Dict[str, "TraceContext"] = {}
#: span aggregates pushed by OTHER hosts (coordinator side)
_remote_hosts: Dict[str, Dict[str, Any]] = {}

_host: Optional[int] = None


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def host_id() -> int:
    """This process's id in the mesh (``jax.process_index()``), cached;
    0 when jax is unavailable or uninitialized."""
    global _host
    if _host is None:
        try:
            import jax

            _host = int(jax.process_index())
        except Exception:
            _host = 0
    return _host


def _key(request_id, trace_id: str) -> str:
    return str(request_id) if request_id is not None else trace_id


class TraceContext:
    """One trace: identity + a bounded, thread-safe event list. Events
    are relative-timestamped (ms since the trace started) so a timeline
    is readable without correlating perf_counter epochs."""

    def __init__(self, kind: str, request_id=None, **attrs):
        self.trace_id = uuid.uuid4().hex[:16]
        self.kind = kind
        self.request_id = request_id
        self.host = host_id()
        self.pid = os.getpid()
        self.started_wall = time.time()
        self._t0 = time.perf_counter()
        self.attrs = {k: v for k, v in attrs.items() if v is not None}
        self.finish_reason: Optional[str] = None
        self._events: collections.deque = collections.deque(
            maxlen=_EVENTS_PER_TRACE)
        self._elock = threading.Lock()

    def event(self, name: str, t0: float, t1: Optional[float] = None,
              span: Optional[int] = None, **attrs) -> None:
        """Record one completed interval in this trace's timeline.
        Without ``span`` it ALSO goes into the process Chrome-trace
        buffer, tagged with the trace / request / host identity. With
        ``span=<id>`` the interval is one the caller has already spanned
        (``telemetry.span`` / ``record_span``): the timeline takes the
        event with that record's id, and the ring is not written a
        second time."""
        if t1 is None:
            t1 = time.perf_counter()
        ev: Dict[str, Any] = {
            "name": name,
            "ts_ms": round((t0 - self._t0) * 1e3, 3),
            "dur_ms": round(max(t1 - t0, 0.0) * 1e3, 3),
        }
        if span is not None:
            ev["span"] = span
        if attrs:
            ev.update(attrs)
        with self._elock:
            self._events.append(ev)
        if span is not None:
            return
        tags = dict(attrs)
        tags["trace"] = self.trace_id
        tags["host"] = self.host
        if self.request_id is not None:
            tags["request"] = self.request_id
        _telemetry.record_span(name, t0, t1, **tags)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.event(name, t0, **attrs)

    def to_dict(self) -> Dict[str, Any]:
        with self._elock:
            events = list(self._events)
        return {
            "trace_id": self.trace_id,
            "kind": self.kind,
            "request_id": self.request_id,
            "host": self.host,
            "pid": self.pid,
            "started_wall": self.started_wall,
            "attrs": dict(self.attrs),
            "finish_reason": self.finish_reason,
            "events": events,
        }


def new_trace(kind: str, request_id=None, **attrs) \
        -> Optional[TraceContext]:
    """Open a trace, or None when tracing is off (callers guard on the
    context, so the disabled path costs one attribute read)."""
    if not _ENABLED:
        return None
    ctx = TraceContext(kind, request_id=request_id, **attrs)
    with _lock:
        _live[_key(request_id, ctx.trace_id)] = ctx
        while len(_live) > _LIVE_MAX:
            _live.popitem(last=False)
    return ctx


def finish_trace(ctx: Optional[TraceContext], reason: Optional[str] = None,
                 **attrs) -> None:
    """Close a trace: out of the live registry, timeline retained in the
    bounded recent registry for /v1/serving/requests/<id> lookups."""
    if ctx is None:
        return
    ctx.finish_reason = reason
    if attrs:
        ctx.attrs.update(attrs)
    d = ctx.to_dict()
    key = _key(ctx.request_id, ctx.trace_id)
    with _lock:
        _live.pop(key, None)
        _recent[key] = d
        _recent.move_to_end(key)
        while len(_recent) > _RECENT_MAX:
            _recent.popitem(last=False)


def timeline(request_id) -> Optional[Dict[str, Any]]:
    """One request's (or train site's) timeline — live or finished —
    or None when unknown (or tracing was off when it ran)."""
    key = str(request_id)
    with _lock:
        ctx = _live.get(key)
        if ctx is not None:
            return ctx.to_dict()
        d = _recent.get(key)
        return dict(d) if d is not None else None


def _summarize(d: Dict[str, Any]) -> Dict[str, Any]:
    phases: Dict[str, List[float]] = {}
    end = 0.0
    for ev in d["events"]:
        p = phases.setdefault(ev["name"], [0, 0.0])
        p[0] += 1
        p[1] += ev["dur_ms"]
        end = max(end, ev["ts_ms"] + ev["dur_ms"])

    def total(name: str) -> float:
        return round(phases.get(name, (0, 0.0))[1], 3)

    return {
        "request_id": d["request_id"],
        "trace_id": d["trace_id"],
        "kind": d["kind"],
        "host": d["host"],
        # per-replica tag (serving fleet: which engine served it)
        "engine": d["attrs"].get("engine"),
        "finish_reason": d["finish_reason"],
        "total_ms": round(end, 3),
        "queue_ms": total("queue_wait"),
        "prefix_lookup_ms": total("prefix_lookup"),
        "prefill_ms": total("prefill"),
        # fleet spans: routing decision + disaggregated-prefill lane
        "route_ms": total("route"),
        "lane_prefill_ms": total("lane_prefill"),
        "decode_ms": total("decode_burst"),
        "events": sum(c for c, _ in phases.values()),
        "spans": {name: {"count": c, "total_ms": round(t, 3)}
                  for name, (c, t) in sorted(phases.items())},
    }


def recent_summaries(n: int = 32) -> List[Dict[str, Any]]:
    with _lock:
        ds = list(_recent.values())[-n:]
    return [_summarize(d) for d in reversed(ds)]


def live_summaries() -> List[Dict[str, Any]]:
    with _lock:
        ctxs = list(_live.values())
    return [_summarize(c.to_dict()) for c in ctxs]


def snapshot_requests() -> Dict[str, Any]:
    """Full timelines, live and recent — what a flight-recorder
    incident dump embeds so the post-mortem shows exactly where every
    in-flight request was when the process went down."""
    with _lock:
        live = [c.to_dict() for c in _live.values()]
        recent = [dict(d) for d in list(_recent.values())[-32:]]
    return {"live": live, "recent": recent}


# ------------------------------------------------------- training steps
def record_train_step(site: str, iteration: int, t0: float,
                      t1: Optional[float] = None, **attrs) -> None:
    """One training step under the long-lived per-site train trace.
    Disabled cost: one module-attribute read."""
    if not _ENABLED:
        return
    with _lock:
        ctx = _train.get(site)
        if ctx is None:
            ctx = _train[site] = TraceContext("train",
                                              request_id=f"train:{site}",
                                              site=site)
        # (re-)insert newest every step: the _live registry evicts
        # oldest-first under request floods, and a never-finishing
        # train context must not be the permanent first casualty
        key = _key(ctx.request_id, ctx.trace_id)
        _live[key] = ctx
        _live.move_to_end(key)
    ctx.event("train_step", t0, t1, iteration=iteration, **attrs)


# --------------------------------------------------- host aggregation
def host_spans(max_events: int = 20_000) -> Dict[str, Any]:
    """Aggregate this host's span buffer per span name (count /
    total_ms / max_ms) — the compact unit that ships to a coordinator.
    Only the newest ``max_events`` trace events are copied and folded,
    bounding the cost of a /telemetry poll on a long-lived process."""
    events = _telemetry.recent_trace_events(max_events)
    agg: Dict[str, List[float]] = {}
    for e in events:
        a = agg.setdefault(e["name"], [0, 0.0, 0.0])
        dur = e.get("dur", 0.0) / 1e3
        a[0] += 1
        a[1] += dur
        a[2] = max(a[2], dur)
    return {
        "host": host_id(),
        "pid": os.getpid(),
        "wall_time": time.time(),
        "spans": {name: {"count": int(c), "total_ms": round(t, 3),
                         "max_ms": round(m, 3)}
                  for name, (c, t, m) in sorted(agg.items())},
    }


#: distinct remote hosts retained (bounded like every other registry
#: here — a restarting worker that changes ids must not grow the
#: coordinator forever)
_REMOTE_MAX = 64


def ingest_host_spans(summary: Dict[str, Any]) -> None:
    """Coordinator side of the aggregation path: store a worker host's
    span summary (POST /telemetry/spans on ui/server.py lands here).
    Oldest-ingested hosts are evicted past ``_REMOTE_MAX``."""
    if not isinstance(summary, dict) or "host" not in summary:
        raise ValueError("span summary must carry a 'host' id")
    key = str(summary["host"])
    with _lock:
        _remote_hosts.pop(key, None)
        _remote_hosts[key] = summary
        while len(_remote_hosts) > _REMOTE_MAX:
            _remote_hosts.pop(next(iter(_remote_hosts)))


def aggregate_hosts() -> Dict[str, Dict[str, Any]]:
    """Local + every ingested remote host, keyed by host id — the
    straggler view: compare each host's device_step / train_step
    totals in one table."""
    out = {str(host_id()): host_spans()}
    with _lock:
        for h, s in _remote_hosts.items():
            out.setdefault(h, s)
    return out


def push_spans(coordinator_url: str, host: Optional[int] = None,
               timeout: float = 10.0) -> None:
    """Worker-side push: POST this host's span aggregate to the
    coordinator's UI server (``/telemetry/spans``)."""
    summary = host_spans()
    if host is not None:
        summary["host"] = int(host)
    body = json.dumps(summary).encode()
    req = urllib.request.Request(
        coordinator_url.rstrip("/") + "/telemetry/spans", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        r.read()


# ------------------------------------------------------------ snapshot
def snapshot() -> Dict[str, Any]:
    """/telemetry + bench embedding: {} unless tracing is on or a
    remote host has pushed spans (peek-style — an untraced process
    pays nothing and shows nothing)."""
    with _lock:
        has_remote = bool(_remote_hosts)
    if not _ENABLED and not has_remote:
        return {}
    return {
        "enabled": _ENABLED,
        "host": host_id(),
        "live_requests": live_summaries(),
        "recent_requests": recent_summaries(16),
        "hosts": aggregate_hosts(),
    }


def reset() -> None:
    """Drop every registry (tests / between bench rounds). Leaves the
    enabled flag as configured."""
    with _lock:
        _live.clear()
        _recent.clear()
        _train.clear()
        _remote_hosts.clear()


__all__ = ["TraceContext", "new_trace", "finish_trace", "timeline",
           "recent_summaries", "live_summaries", "snapshot_requests",
           "record_train_step", "host_spans", "ingest_host_spans",
           "aggregate_hosts", "push_spans", "snapshot", "reset",
           "enabled", "set_enabled", "host_id"]
