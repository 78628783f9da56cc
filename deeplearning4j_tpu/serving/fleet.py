"""Serving fleet: replicated decode engines behind one KV-aware
router, with disaggregated prefill.

PRs 7-9 built a production single-device serving core — continuous
batching, paged KV, prefix cache, sticky sessions. "Millions of users"
needs the fleet around it, and the same fuse-and-overlap playbook the
repo applied to kernels applies one level up:

- **One admission queue, N replicas.** ``ServingFleet`` owns N
  ``DecodeEngine`` replicas (one per device, or N same-device CPU
  replicas for tests) behind ONE queue and a router thread
  ("ServingFleetRouter"). Clients get a ``FleetRequest`` handle with
  the same ``result()``/``stream()`` API as a ``ServingRequest``.
- **KV-aware routing.** A request is scored onto the replica where it
  will run best: free KV pages and free slots (capacity), plus each
  replica's OWN prefix-cache hit hint for this prompt (locality — the
  replica already holding the prompt's prefix pages wins, so shared
  system prompts concentrate instead of re-prefilling everywhere).
- **Session affinity.** A ``session_id`` whose pages are pinned on
  replica k routes back to k (warm resume, zero history re-prefill),
  falling back to a cold prefill elsewhere only when k is saturated or
  dead.
- **Disaggregated prefill.** Prompts >= ``prefill_threshold`` tokens
  run on a dedicated prefill lane — its own AOT-compiled executables
  and its own submission thread ("ServingPrefillLane") — and the
  computed K/V is handed to the decode replica through
  ``DecodeEngine.submit_prepared``: the replica commits it with one
  page scatter (``kv_pages.handoff_commit``) between decode bursts, so
  a 2048-token bucket-padded prefill never stalls anyone's decode
  bursts. The lane's output is bit-identical to the replica's own
  prefill (same forward, same bucket padding), so greedy outputs stay
  token-identical to a solo engine.
- **One AOT compile.** Same-device replicas adopt replica 0's
  warm-pool executables (``_WarmPool.adopt``): fleet startup lowers
  and compiles each program once, not once per replica. Distinct
  devices compile per device (executables are device-bound).
- **Replica death and elastic resize.** A replica whose scheduler dies
  fails its in-flight work; the fleet re-routes every such request to
  a survivor and REPLAYS it, suppressing tokens the client already
  received (greedy and seeded sampling replay exactly; unseeded
  sampling may change distribution at the failover point — documented,
  not hidden). Sessions pinned on the dead replica are gone; their
  next turn re-admits cold elsewhere. ``add_replica`` /
  ``remove_replica`` grow and shrink the fleet at runtime (the
  scheduler's alert-driven elasticity path), ``drain_replica`` /
  ``restart_replica`` give in-place resize, and ``kill_replica`` is
  the chaos hook the CI drill uses. Replica identity is a stable id
  (``_Replica.rid``), never a list position, so affinity and
  drain/kill targets survive the list shrinking underneath them.

Everything is observable: ``SERVING_*`` metrics are labelled
``engine=<id>`` per replica, the fleet adds routed/reroute counters and
a live-replica gauge, traces carry per-replica ``engine`` tags plus
``route``/``lane_prefill`` spans, and the flight recorder sees
``fleet_replica_dead`` / ``fleet_reroute`` events.
"""

from __future__ import annotations

import itertools
import queue as _queue
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.profiler import flight_recorder as _flight
from deeplearning4j_tpu.profiler import telemetry as _telemetry
from deeplearning4j_tpu.serving.engine import (
    CapacityRejected, DecodeEngine, ServingRequest, device_sds,
)

#: process-wide fleet ordinals — the ``fleet=<id>`` label on the
#: queue-pressure gauge (and the key the control plane's SLO-driven
#: scale-up matches alerts back to a ServeJob with)
_FLEET_IDS = itertools.count()


# ---------------------------------------------------------------- client
class FleetRequest:
    """Client handle for one fleet request: the ``ServingRequest`` API
    (``result``/``stream``/``done``/timings) over whatever replica —
    or sequence of replicas, under failover — actually serves it.

    Token replay on failover: the proxy counts tokens per attempt and
    suppresses the first ``len(tokens)`` of a replayed attempt, so the
    client-visible stream never duplicates or loses a token."""

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 temperature: float, eos_id, sample_seed, session_id,
                 spec_decode: Optional[bool] = None):
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.sample_seed = sample_seed
        self.session_id = session_id
        #: per-request speculative-decoding opt-in/out (None follows
        #: the replica engines' spec_decode config) — replayed verbatim
        #: on failover so a rerouted request keeps its draft behavior
        self.spec_decode = spec_decode
        self.tokens: List[int] = []
        self.finish_reason: Optional[str] = None
        self.ttft_s: Optional[float] = None
        self.latency_s: Optional[float] = None
        self.cache_hit_tokens = 0
        #: draft-token tally of the FINAL attempt (set at finish):
        #: front-ends echo these as the response's ``spec`` stats
        self.spec_proposed = 0
        self.spec_accepted = 0
        #: routing facts front-ends echo: replica, reason, lane, attempts
        self.routing: Dict[str, Any] = {}
        self.engine_id: Optional[str] = None
        self.attempts = 0
        self._fleet: Optional["ServingFleet"] = None
        self._inner: Optional[ServingRequest] = None
        self._engine: Optional[DecodeEngine] = None
        self._replica_index: Optional[int] = None
        self._lane_result = None     # cached (handoff, lane_span)
        self._route_span = None      # id of the fleet.route that placed it
        self._no_lane = False        # lane failed once: go direct
        self._cancelled = False      # cancel(): never reroute/re-run
        self._skip = 0               # replayed tokens to suppress
        self._seen = 0               # tokens seen from current attempt
        self._t_submit = time.perf_counter()
        self._stream: "_queue.Queue" = _queue.Queue()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        self._lock = threading.RLock()

    # -- engine-side hooks (see ServingRequest._sink) -------------------
    def _attach(self, inner: ServingRequest, engine: DecodeEngine) \
            -> None:
        """Called synchronously inside engine.submit BEFORE the request
        becomes visible to the scheduler — no token can race this."""
        with self._lock:
            self._inner = inner
            self._engine = engine
            inner.caused_by = self._route_span
            self._seen = 0
            self._skip = len(self.tokens)
            self.engine_id = engine.engine_id
            self.routing.update(replica=engine.engine_id,
                                attempts=self.attempts)

    def _on_token(self, inner: ServingRequest, token: int) -> None:
        with self._lock:
            if inner is not self._inner or self._done.is_set():
                return
            self._seen += 1
            if self._seen <= self._skip:
                return               # replayed token the client has
            if self.ttft_s is None:
                self.ttft_s = time.perf_counter() - self._t_submit
            self.tokens.append(token)
        self._stream.put(token)

    def _on_finish(self, inner: ServingRequest, reason: str,
                   error: Optional[BaseException]) -> None:
        with self._lock:
            if inner is not self._inner or self._done.is_set():
                return
            cancelled = self._cancelled
        fleet = self._fleet
        if cancelled:
            # the client asked for this teardown: however the inner
            # request actually ended (clean evict, replica death, or
            # engine shutdown racing the abort pass), the contract is
            # reason=cancelled + partial tokens, never a raised error
            self._finalize("cancelled", None, inner)
            return
        if error is not None and fleet is not None \
                and fleet._maybe_reroute(self, inner, error):
            return                   # re-queued onto a survivor
        self._finalize(reason, error, inner)

    def _finalize(self, reason: str, error: Optional[BaseException],
                  inner: Optional[ServingRequest] = None) -> None:
        with self._lock:
            if self._done.is_set():
                return
            self.finish_reason = reason
            self._error = error
            self.latency_s = time.perf_counter() - self._t_submit
            if inner is not None:
                self.cache_hit_tokens = inner.cache_hit_tokens
                self.spec_proposed = inner.spec_proposed
                self.spec_accepted = inner.spec_accepted
            self._stream.put(None)
            self._done.set()
        fleet = self._fleet
        if fleet is not None:
            fleet._on_request_done(self)

    def _fail(self, error: BaseException) -> None:
        self._finalize("error", error)

    # -- client side ----------------------------------------------------
    @property
    def request_id(self):
        inner = self._inner
        return inner.request_id if inner is not None else None

    @property
    def trace_id(self):
        inner = self._inner
        return inner.trace_id if inner is not None else None

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"fleet request not done within {timeout}s")
        if self._error is not None:
            raise self._error
        return np.asarray(self.tokens, np.int32)

    def cancel(self) -> bool:
        """Cancel this request wherever it is right now: queued at the
        fleet router, waiting at the prefill lane, or decoding in a
        replica slot (the engine frees the slot and drains its pages
        to rc0). The stream ends, ``result()`` returns the tokens
        received so far, ``finish_reason`` becomes ``"cancelled"`` and
        the trace closes with the same reason. A ``result(timeout=)``
        that timed out should call this — otherwise the request keeps
        decoding (and holding KV pages) to completion. False when the
        request already finished."""
        with self._lock:
            if self._done.is_set():
                return False
            self._cancelled = True
            inner, eng = self._inner, self._engine
        if inner is not None and eng is not None and not inner.done \
                and eng.abort(inner):
            return True      # engine-side teardown flows back via sink
        # not routed (or raced completion/death): finalize here — the
        # router and lane skip finished requests
        self._finalize("cancelled", None, inner)
        return True

    def stream(self):
        """Yield tokens as they decode — across failovers; raises the
        request's error (if any) after the stream ends."""
        while True:
            tok = self._stream.get()
            if tok is None:
                break
            yield tok
        if self._error is not None:
            raise self._error


# ---------------------------------------------------------------- lane
class _PrefillLane:
    """Disaggregated prefill: a dedicated submission thread and its own
    AOT-compiled executables run long prompts' prefill forward, then
    hand the K/V stacks to the target replica via submit_prepared. The
    arrays are immutable jax values, so the handoff needs no
    cross-thread synchronization beyond the queue."""

    def __init__(self, fleet: "ServingFleet", model, params,
                 buckets: List[int], threshold: int, device=None):
        self.fleet = fleet
        self.model = model
        self.params = params          # replica-0's device-put tree
        #: replica 0's device: the lane compiles and runs THERE (its
        #: params already live there); handoff to another replica is a
        #: device_put inside the target's _admit
        self._device = device
        self.buckets = sorted(buckets)
        self.threshold = int(threshold)
        self._exec: Dict[int, Any] = {}
        self._queue: "_queue.Queue" = _queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.n_prefills = 0
        self.n_fallbacks = 0

    def _build_fn(self):
        m = self.model

        def lane_prefill(params, prompt, t0):
            # the model's ONE prefill, the engine's own: lane-served
            # prompts are bit-identical to engine-served ones by
            # construction, not by parallel maintenance
            ks, vs, _, last, _ = m.prefill(params, prompt, t0)
            return ks, vs, last.astype(jnp.float32)

        return lane_prefill

    def _sds(self, shape, dtype):
        return device_sds(shape, dtype, self._device)

    def start(self) -> None:
        fn = self._build_fn()
        i32 = jnp.int32
        with _telemetry.span("serving_lane_warmup",
                             buckets=len(self.buckets)):
            abs_params = jax.tree_util.tree_map(
                lambda a: self._sds(a.shape, a.dtype), self.params)
            for b in self.buckets:
                # one AOT executable per long bucket — the lane never
                # compiles after startup (its dispatch is the compiled
                # executable directly, like the engines' warm pool)
                self._exec[b] = jax.jit(fn).lower(
                    abs_params, self._sds((1, b), i32),
                    self._sds((), i32)).compile()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="ServingPrefillLane")
        self._thread.start()

    def enqueue(self, freq: FleetRequest, replica: "_Replica") -> None:
        self._queue.put((freq, replica))

    def shutdown(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._queue.put(None)
        t = self._thread
        if t is not None:
            t.join(timeout)
        # strand no one: requests still queued at the lane (the loop
        # fails everything it dequeues after _stop, but a racing
        # enqueue can land behind the sentinel) fail here
        while True:
            try:
                item = self._queue.get_nowait()
            except _queue.Empty:
                break
            if item is not None:
                item[0]._fail(RuntimeError("fleet has been shut down"))

    def stats(self) -> Dict[str, Any]:
        return {"threshold": self.threshold,
                "buckets": list(self.buckets),
                "prefills": self.n_prefills,
                "fallbacks": self.n_fallbacks,
                "queued": self._queue.qsize()}

    def _loop(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                break
            freq, replica = item
            if self._stop.is_set():
                # keep draining up to the sentinel: every queued
                # request gets a clean error, never a silent hang
                freq._fail(RuntimeError("fleet has been shut down"))
                continue
            try:
                self._serve(freq, replica)
            except BaseException as e:     # lane must not die silently
                freq._no_lane = True
                self.n_fallbacks += 1
                _flight.record("lane_fallback", error=repr(e)[:200])
                self.fleet._requeue(freq, "lane_error")

    def _serve(self, freq: FleetRequest, replica: "_Replica") -> None:
        if freq.done:
            return                   # cancelled while lane-queued
        t0 = int(freq.prompt.size)
        bucket = next((b for b in self.buckets if b >= t0), None)
        if bucket is None:
            # longer than every compiled lane bucket: cold prefill on
            # the replica (its own out-of-bucket fallback handles it)
            freq._no_lane = True
            self.n_fallbacks += 1
            self.fleet._submit_to(replica, freq)
            return
        if freq._lane_result is None:
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :t0] = freq.prompt
            t_a = time.perf_counter()
            ks, vs, last = self._exec[bucket](
                self.params, jnp.asarray(padded),
                jnp.asarray(t0, jnp.int32))
            logits = np.asarray(last)
            t_b = time.perf_counter()
            self.n_prefills += 1
            _telemetry.record_span(
                "serving_lane_prefill", t_a, t_b,
                metric=_telemetry.SERVING_LANE_SECONDS, bucket=bucket)
            if _telemetry.enabled():
                _telemetry.MetricsRegistry.get_default().counter(
                    _telemetry.SERVING_LANE_PREFILLS,
                    "long-prompt prefills run on the disaggregated "
                    "lane instead of a decode replica").inc(
                    bucket=bucket)
            freq._lane_result = ((ks, vs, bucket, logits),
                                 (t_a, t_b, bucket))
        self.fleet._submit_to(replica, freq,
                              handoff=freq._lane_result)


# ------------------------------------------------------------- replicas
class _Replica:
    """One fleet member. Identity is the stable ``rid`` — allocated
    once, never reused — NOT the replica's position in the fleet's
    list: after a ``remove_replica`` shrinks the list, router
    affinity, ``restart_replica``, kill/drain targets and the
    capacity-listener callback all still name the engine they meant.
    ``index`` is a read-only alias for ``rid`` kept for callers (the
    scheduler's poll/rebalance paths, tests) that predate elastic
    resize."""

    __slots__ = ("rid", "engine", "alive", "draining",
                 "needs_cleanup")

    def __init__(self, rid: int, engine: DecodeEngine):
        self.rid = rid
        self.engine = engine
        self.alive = True
        self.draining = False
        self.needs_cleanup = False

    @property
    def index(self) -> int:
        return self.rid


# ---------------------------------------------------------------- fleet
class ServingFleet:
    """N decode-engine replicas, one admission queue, a KV-aware
    router, and an optional disaggregated prefill lane (module doc).

    Duck-types the ``DecodeEngine`` front-end surface (``submit`` /
    ``generate`` / ``stats`` / ``prefix_stats`` / ``release_session`` /
    ``shutdown``), so ``JsonModelServer(engine=fleet)`` and
    ``GenerativeInference`` work unchanged.

    Parameters
    ----------
    replicas : engine count (ignored when ``devices`` is given).
    devices : one jax device per replica; None places every replica on
        the default device (the N-CPU-replicas test topology), which
        also lets them share one AOT compile. On a TPU backend with
        more than one device, ``replicas > 1`` without ``devices``
        raises instead of piling onto device 0.
    prefill_threshold : prompts with at least this many tokens prefill
        on the dedicated lane; None disables disaggregation (and then
        a 1-replica fleet is greedy token-identical to a solo engine).
    max_queue : fleet admission queue bound — beyond it ``submit``
        raises the structured ``CapacityRejected``.
    engine_kwargs : forwarded to every ``DecodeEngine`` (slots,
        page_size, prefix_cache, session_capacity, kv_dtype,
        attn_mode, ...) — kept verbatim, so restarted replicas keep
        e.g. fp8 KV pools and the paged-attention kernel choice. The
        disaggregated prefill lane is unaffected by ``kv_dtype``: it
        hands off COMPUTE-dtype K/V stacks and each replica's adopt
        scatter quantizes into its own pool.
    """

    #: failed-over requests get this many total attempts before the
    #: error surfaces to the client
    MAX_ATTEMPTS_EXTRA = 1

    def __init__(self, model, params, *, replicas: int = 2,
                 devices: Optional[List[Any]] = None,
                 prefill_threshold: Optional[int] = None,
                 max_queue: int = 1024,
                 **engine_kwargs):
        if devices is not None:
            replicas = len(devices)
        if replicas < 1:
            raise ValueError("need at least one replica")
        if (devices is None and replicas > 1
                and jax.default_backend() == "tpu"
                and jax.device_count() > 1):
            # the CPU test mesh shares one default device (and one AOT
            # compile) across replicas on purpose; on chips that would
            # pile every replica onto chip 0 while the others idle
            raise ValueError(
                f"ServingFleet(replicas={replicas}) on a TPU host with "
                f"{jax.device_count()} devices needs devices= (one per "
                "replica, e.g. jax.devices()[:replicas]): without it "
                "every replica would land on device 0")
        engine_kwargs.setdefault("max_queue", max(256, max_queue))
        self.model = model
        self.fleet_id = f"fleet-{next(_FLEET_IDS)}"
        self.prefill_threshold = prefill_threshold
        #: the exact per-engine config, kept verbatim so
        #: restart_replica builds an identical engine (reverse-
        #: engineering kwargs from a live engine silently drops any
        #: newly-added knob)
        self._engine_kwargs = dict(engine_kwargs)
        #: replica-id allocator — ids are stable for the fleet's
        #: lifetime and never reused, so a removed replica's id can
        #: never silently re-target a later engine
        self._rids = itertools.count()
        self._replicas: List[_Replica] = []
        self._by_rid: Dict[int, _Replica] = {}
        first: Optional[DecodeEngine] = None
        for i in range(replicas):
            dev = devices[i] if devices is not None else None
            eng = DecodeEngine(
                model, params, device=dev,
                handoff_threshold=prefill_threshold,
                warm_source=first, **engine_kwargs)
            if first is None:
                first = eng
            r = _Replica(next(self._rids), eng)
            self._replicas.append(r)
            self._by_rid[r.rid] = r
        self._lane: Optional[_PrefillLane] = None
        if prefill_threshold is not None:
            self._lane = _PrefillLane(
                self, model, first.params,
                first.handoff_buckets, prefill_threshold,
                device=first._device)
        self._queue: "_queue.Queue" = _queue.Queue(maxsize=max_queue)
        #: live request handles (weak — finished requests fall out with
        #: their client references): what cancel_pending() sweeps
        self._live: "weakref.WeakSet" = weakref.WeakSet()
        self._affinity: Dict[str, int] = {}
        self._aff_lock = threading.Lock()
        #: serializes dead-replica cleanup (router _health_check) vs
        #: restart_replica's engine swap — without it the router can
        #: shut down a freshly-restarted engine it mistook for the
        #: dead one
        self._cleanup_lock = threading.Lock()
        self._router: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._start_lock = threading.Lock()
        self._rr = itertools.count()       # score tie-break rotation
        #: callable(replica_index, device, reason) invoked when a
        #: replica's capacity leaves the fleet (drained or dead) — how
        #: a JobScheduler gets its chip back for rebalancing. reason is
        #: "drained" | "dead".
        self.capacity_listener = None
        # fleet stats
        self.n_requests = 0
        self.n_completed = 0
        self.n_reroutes = 0
        #: elastic-resize operations currently in flight (+1 per
        #: add_replica, -1 per remove_replica) — published as the
        #: pending-scale gauge so dashboards can tell "small fleet"
        #: from "fleet mid-resize"
        self._pending_scale = 0
        self._routed: Dict[str, int] = {}
        self._stats_lock = threading.Lock()
        self._last_pressure_t = 0.0     # gauge-publish throttle

    # ------------------------------------------------------- lifecycle
    def start(self) -> "ServingFleet":
        with self._start_lock:
            if self._router is not None:
                return self
            if self._stop.is_set():
                raise RuntimeError("fleet has been shut down")
            with _telemetry.span("serving_fleet_start",
                                 replicas=len(self._replicas)):
                # replica 0 first: it compiles the shared warm pool the
                # others adopt
                for r in self._replicas:
                    r.engine.start()
                if self._lane is not None:
                    self._lane.start()
            self._gauge_replicas()
            self._router = threading.Thread(
                target=self._route_loop, daemon=True,
                name="ServingFleetRouter")
            self._router.start()
        return self

    def shutdown(self, timeout: float = 30.0) -> None:
        self._stop.set()
        self._queue.put(None)              # router sentinel
        if self._lane is not None:
            self._lane.shutdown(timeout)
        t = self._router
        if t is not None:
            t.join(timeout)
        # anything still queued at the fleet fails now, explicitly
        while True:
            try:
                item = self._queue.get_nowait()
            except _queue.Empty:
                break
            if isinstance(item, FleetRequest):
                item._fail(RuntimeError("fleet has been shut down"))
        for r in list(self._replicas):
            r.engine.shutdown(timeout)
        self._gauge_replicas()
        # the pressure gauge is only meaningful for a LIVE fleet —
        # same stale-series discipline as the per-engine gauges
        _telemetry.MetricsRegistry.get_default().remove_matching(
            "fleet", self.fleet_id, kinds=("gauge",))

    def __enter__(self) -> "ServingFleet":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # ---------------------------------------------------------- client
    def submit(self, prompt_ids, max_new_tokens: int,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               sample_seed: Optional[int] = None,
               session_id: Optional[str] = None,
               spec_decode: Optional[bool] = None) -> FleetRequest:
        if self._stop.is_set():
            raise RuntimeError("fleet has been shut down")
        # validate synchronously (every replica has the same config)
        prompt = self._replicas[0].engine._validate(prompt_ids,
                                                    max_new_tokens)
        if self._router is None:
            self.start()
        freq = FleetRequest(prompt, max_new_tokens, temperature,
                            eos_id, sample_seed, session_id,
                            spec_decode=spec_decode)
        freq._fleet = self
        try:
            self._queue.put_nowait(freq)
        except _queue.Full:
            hints = [r.engine.retry_after_hint()
                     for r in list(self._replicas) if r.alive]
            hint = min(hints) if hints else 1.0
            if _telemetry.enabled():
                _telemetry.MetricsRegistry.get_default().counter(
                    _telemetry.SERVING_REJECTS,
                    "submissions rejected because the admission "
                    "queue was full (429 at the HTTP front-end)").inc(
                    engine="fleet")
            raise CapacityRejected(
                f"fleet admission queue full ({self._queue.maxsize}); "
                f"retry after ~{hint}s", retry_after_s=hint)
        # close the submit/shutdown race (same contract as the engine's
        # _enqueue): if shutdown's final drain ran before our put, we
        # must fail the stranded request ourselves — seeing _stop clear
        # here proves shutdown will drain after us
        if self._stop.is_set():
            while True:
                try:
                    item = self._queue.get_nowait()
                except _queue.Empty:
                    break
                if isinstance(item, FleetRequest):
                    item._fail(RuntimeError("fleet has been shut "
                                            "down"))
        self._live.add(freq)
        self.n_requests += 1
        return freq

    def cancel_pending(self) -> int:
        """Cancel every live request (queued, laned, or decoding) —
        the scheduler's job-cancel path: the fleet stops cleanly
        without failing anyone with an opaque shutdown error. Returns
        the number cancelled."""
        n = 0
        for freq in list(self._live):
            if not freq.done and freq.cancel():
                n += 1
        return n

    def generate(self, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None) -> np.ndarray:
        return self.submit(prompt_ids, max_new_tokens, temperature,
                           eos_id).result(timeout)

    def release_session(self, session_id: str) -> bool:
        with self._aff_lock:
            self._affinity.pop(session_id, None)
        # every alive replica, not just the current affinity target: an
        # affinity_fallback may have left an older pin on a replica the
        # session was served from earlier — an explicit release must
        # free those pages too, not wait out their TTL
        hit = False
        for r in list(self._replicas):
            if r.alive:
                hit = r.engine.release_session(session_id) or hit
        return hit

    # ----------------------------------------------------------- stats
    @property
    def n_dispatches(self) -> int:
        return sum(r.engine.n_dispatches for r in list(self._replicas))

    def alive_replicas(self) -> int:
        return sum(1 for r in list(self._replicas) if r.alive)

    def queue_pressure(self) -> float:
        """Admission pressure normalized by live capacity: (fleet queue
        + per-replica queue depth) / live decode slots. ~0 = idle,
        >= 1 = a full slot-generation of work waiting. The train-vs-
        serve rebalancing signal: a scheduler hands chips from a fleet
        sitting near 0 to a starved train job, and back when pressure
        climbs. inf when nothing is alive."""
        alive = [r for r in list(self._replicas)
                 if r.alive and not r.draining]
        if not alive:
            return float("inf")
        depth = self._queue.qsize() + sum(
            r.engine.queue_depth() for r in alive)
        return depth / max(1, sum(r.engine.slots for r in alive))

    def stats(self) -> Dict[str, Any]:
        reps = list(self._replicas)
        e0 = reps[0].engine
        with self._stats_lock:
            routed = dict(self._routed)
        return {
            "fleet": True,
            "fleet_id": self.fleet_id,
            "replicas": [dict(r.engine.stats(), id=r.rid,
                              alive=r.alive, draining=r.draining)
                         for r in reps],
            "alive_replicas": self.alive_replicas(),
            "slots": sum(r.engine.slots for r in reps if r.alive),
            "pending_scale": self._pending_scale,
            "page_size": e0.page_size,
            "max_context": e0.max_context,
            "quantization": e0.quantization,
            "prefill_buckets": list(e0.prefill_buckets),
            "requests": self.n_requests,
            "completed": self.n_completed,
            "reroutes": self.n_reroutes,
            "router": {"queue_depth": self._queue.qsize(),
                       "routed": routed,
                       "affinity_entries": len(self._affinity)},
            **({"prefill_lane": self._lane.stats()}
               if self._lane is not None else {}),
        }

    def prefix_stats(self) -> Dict[str, Any]:
        return {
            "fleet": True,
            "replicas": {r.engine.engine_id: r.engine.prefix_stats()
                         for r in list(self._replicas)},
        }

    # --------------------------------------------------- elastic resize
    def _resolve(self, index: int) -> _Replica:
        """Replica lookup by stable id. Raises IndexError (what list
        indexing raised before ids existed) when no replica carries
        that id — including ids retired by ``remove_replica``."""
        r = self._by_rid.get(index)
        if r is None:
            raise IndexError(
                f"no replica with id {index} "
                f"(current ids: {sorted(self._by_rid)})")
        return r

    def add_replica(self, device: Any = None) -> int:
        """Grow the fleet by one replica at runtime and return its
        stable id.

        The new engine is built on ``device`` (None = wherever a live
        replica already runs, the shared-compile test topology),
        adopting a live same-device replica's AOT warm pool so it
        serves its first request with zero compiles. Compilation for a
        DISTINCT device happens here, on the caller's thread — the
        router never blocks on it. Registration is atomic: the replica
        list only ever shows fully-started engines, so in-flight
        routing cannot pick a half-built replica. On a started fleet
        the engine starts before registration; on an unstarted fleet
        it is registered cold and ``start()`` brings it up with the
        rest."""
        if self._stop.is_set():
            raise RuntimeError("fleet has been shut down")
        donor = next((x for x in list(self._replicas)
                      if x.alive and not x.draining), None)
        if device is None and donor is not None:
            device = donor.engine._device
        warm = donor.engine if donor is not None and \
            (donor.engine._device is device
             or donor.engine._device == device) else None
        params = donor.engine.params if donor is not None \
            else self._replicas[0].engine.params
        self._pending_scale += 1
        self._gauge_replicas()
        t0 = time.perf_counter()
        try:
            eng = DecodeEngine(
                self.model, params, device=device,
                handoff_threshold=self.prefill_threshold,
                warm_source=warm, **self._engine_kwargs)
            with self._start_lock:
                # under the start lock: either start() already ran (we
                # must start the engine ourselves, off the router's
                # critical path — this thread) or it hasn't (we
                # register cold and start() starts every replica)
                if self._router is not None:
                    try:
                        eng.start()
                    except BaseException:
                        # never leak a half-built engine's threads or
                        # gauge series into a fleet that rejected it
                        try:
                            eng.shutdown(timeout=5.0)
                        except Exception:
                            pass
                        raise
                with self._cleanup_lock:
                    rid = next(self._rids)
                    r = _Replica(rid, eng)
                    self._by_rid[rid] = r
                    self._replicas.append(r)
        finally:
            self._pending_scale -= 1
        self._gauge_replicas()
        _flight.record("fleet_replica_added",
                       engine=eng.engine_id, rid=rid,
                       adopted=eng._warm.adopted,
                       startup_s=round(time.perf_counter() - t0, 3))
        return rid

    def remove_replica(self, index: int,
                       timeout: Optional[float] = 60.0) -> bool:
        """Shrink the fleet: drain replica ``index`` (stop routing,
        finish in-flight work, hand its pinned sessions off — they
        re-admit cold on survivors and re-pin there), shut the engine
        down (retiring its engine-labelled gauge series), then retire
        the id. The capacity listener hears ``"drained"`` so a
        scheduler reclaims the chip. True when the drain was clean."""
        r = self._resolve(index)
        live = [x for x in list(self._replicas)
                if x.alive and not x.draining]
        if r.alive and not r.draining and len(live) <= 1:
            raise ValueError(
                "cannot remove the last live replica "
                f"(id {r.rid}) — shut the fleet down instead")
        self._pending_scale -= 1
        self._gauge_replicas()
        try:
            ok = True
            if r.alive:
                ok = self.drain_replica(r.rid, timeout)
            else:
                # dead replica: its scheduler thread already exited;
                # finish the cleanup the router would have done
                with self._cleanup_lock:
                    pending = r.needs_cleanup
                    r.needs_cleanup = False
                if pending:
                    try:
                        r.engine.shutdown(timeout=5.0)
                    except Exception:
                        pass
            with self._cleanup_lock:
                self._by_rid.pop(r.rid, None)
                try:
                    self._replicas.remove(r)
                except ValueError:
                    pass
        finally:
            self._pending_scale += 1
        self._gauge_replicas()
        _flight.record("fleet_replica_removed",
                       engine=r.engine.engine_id, rid=r.rid,
                       clean=ok)
        return ok

    def drain_replica(self, index: int,
                      timeout: Optional[float] = 60.0) -> bool:
        """Stop routing to replica ``index``, wait for its queued and
        in-flight requests to finish, then shut it down. Sessions
        pinned there are released (their next turn re-admits cold
        elsewhere). True when fully drained."""
        r = self._resolve(index)
        r.draining = True
        self._drop_affinity(r.rid)
        ok = r.engine.drain(timeout)
        r.engine.shutdown()
        r.alive = False
        self._gauge_replicas()
        _flight.record("fleet_replica_drained",
                       engine=r.engine.engine_id, clean=ok)
        self._notify_capacity(r, "drained")
        return ok

    def restart_replica(self, index: int) -> None:
        """Bring a drained/dead replica back: a fresh engine (adopting
        a live same-device replica's warm pool when possible) starts
        and rejoins routing."""
        r = self._resolve(index)
        if r.alive:
            raise ValueError(f"replica {index} is still alive")
        old = r.engine
        # finish the dead engine's cleanup HERE (under the same lock
        # the router's pass takes) before the new engine becomes
        # visible — otherwise a concurrent _health_check could shut
        # down the fresh engine it mistakes for the dead one
        with self._cleanup_lock:
            pending = r.needs_cleanup
            r.needs_cleanup = False
        if pending:
            try:
                old.shutdown(timeout=5.0)
            except Exception:
                pass
        donor = next((x.engine for x in list(self._replicas)
                      if x.alive and x.engine._device == old._device),
                     None)
        eng = DecodeEngine(
            self.model, old.params, device=old._device,
            handoff_threshold=self.prefill_threshold,
            warm_source=donor, **self._engine_kwargs)
        eng.start()
        with self._cleanup_lock:
            r.engine = eng
            r.alive = True
            r.draining = False
        self._gauge_replicas()
        _flight.record("fleet_replica_restarted",
                       engine=eng.engine_id, index=r.rid)

    def kill_replica(self, index: int,
                     error: Optional[BaseException] = None) -> None:
        """Chaos hook: poison replica ``index``'s scheduler so it dies
        the way a real fault would — evictions, incident dump,
        re-routing. The CI kill-a-replica drill calls this."""
        self._resolve(index).engine._die(
            error or RuntimeError(f"replica {index} killed by chaos "
                                  "hook"))

    # ----------------------------------------------------------- router
    def _gauge_pressure(self) -> None:
        """Publish queue_pressure() as a gauge (throttled): the
        continuous signal the SLO engine's ``serving_queue_pressure``
        rule windows — sustained pressure (not one busy poll) is what
        fires the scheduler's scale-up hook."""
        if not _telemetry.enabled():
            return
        now = time.monotonic()
        if now - self._last_pressure_t < 0.25:
            return
        self._last_pressure_t = now
        _telemetry.MetricsRegistry.get_default().gauge(
            _telemetry.SERVING_FLEET_PRESSURE,
            "fleet admission pressure: queued work per live decode "
            "slot (~0 idle, >=1 a full slot-generation waiting)").set(
            self.queue_pressure(), fleet=self.fleet_id)

    def _route_loop(self) -> None:
        while True:
            self._gauge_pressure()
            try:
                item = self._queue.get(timeout=0.05)
            except _queue.Empty:
                self._health_check()
                continue
            if item is None or self._stop.is_set():
                if isinstance(item, FleetRequest):
                    item._fail(RuntimeError("fleet has been shut "
                                            "down"))
                break
            self._health_check()
            try:
                self._route(item)
            except BaseException as e:
                item._fail(e)

    def _health_check(self) -> None:
        for r in list(self._replicas):
            if r.alive and r.engine._dead is not None:
                self._mark_dead(r, r.engine._dead)
            if r.needs_cleanup:
                # scheduler thread already exited; shutdown() joins it
                # and releases sessions/prefix references so the dead
                # pool's accounting drains (router thread only — the
                # dying thread must never join itself). The lock keeps
                # this from racing restart_replica's engine swap.
                with self._cleanup_lock:
                    if not r.needs_cleanup:
                        continue
                    r.needs_cleanup = False
                    dead_engine = r.engine
                try:
                    dead_engine.shutdown(timeout=5.0)
                except Exception:
                    pass

    def _mark_dead(self, r: _Replica, err: BaseException) -> None:
        if not r.alive:
            return
        r.alive = False
        r.needs_cleanup = True
        self._drop_affinity(r.rid)
        self._gauge_replicas()
        _flight.record("fleet_replica_dead",
                       engine=r.engine.engine_id,
                       error=repr(err)[:200])
        self._notify_capacity(r, "dead")

    def _notify_capacity(self, r: _Replica, reason: str) -> None:
        cb = self.capacity_listener
        if cb is not None:
            try:
                cb(r.rid, r.engine._device, reason)
            except Exception:
                pass   # a broken listener must not break routing

    def _drop_affinity(self, index: int) -> None:
        with self._aff_lock:
            for sid in [s for s, i in self._affinity.items()
                        if i == index]:
                del self._affinity[sid]

    def _gauge_replicas(self) -> None:
        if not _telemetry.enabled():
            return
        reg = _telemetry.MetricsRegistry.get_default()
        reg.gauge(
            _telemetry.SERVING_FLEET_REPLICAS,
            "decode replicas currently alive and routable").set(
            self.alive_replicas())
        reg.gauge(
            _telemetry.SERVING_FLEET_SIZE,
            "replicas registered with the fleet router (alive or "
            "not) — the elastic-resize size signal").set(
            len(self._replicas), fleet=self.fleet_id)
        reg.gauge(
            _telemetry.SERVING_FLEET_PENDING_SCALE,
            "elastic-resize operations in flight (+1 per add_replica,"
            " -1 per remove_replica; 0 = fleet at rest)").set(
            self._pending_scale, fleet=self.fleet_id)

    def _saturated(self, r: _Replica) -> bool:
        eng = r.engine
        depth = eng._queue.qsize() + len(eng._waiting)
        # hard-full admission counts as saturated even with a free
        # slot (a page-blocked head-of-line request can idle a slot
        # while the queue is at max_queue — routing there would only
        # bounce off CapacityRejected)
        if depth >= eng.max_queue:
            return True
        return bool(eng._active.all()) and depth >= eng.slots

    def _route(self, freq: FleetRequest) -> None:
        if freq.done:
            return                   # cancelled while queued
        t_r0 = time.perf_counter()
        cands = [r for r in list(self._replicas)
                 if r.alive and not r.draining]
        if not cands:
            freq._fail(RuntimeError("no live replicas"))
            return
        target: Optional[_Replica] = None
        reason = "score"
        if freq.session_id is not None:
            with self._aff_lock:
                idx = self._affinity.get(freq.session_id)
            if idx is not None:
                # affinity pins the stable replica id — a removed
                # replica's id resolves to None (cold fallback), never
                # to whatever engine now occupies its old list slot
                aff = self._by_rid.get(idx)
                if aff is not None and aff.alive \
                        and not aff.draining \
                        and not self._saturated(aff):
                    target, reason = aff, "affinity"
                else:
                    # pinned replica saturated or gone: cold elsewhere
                    reason = "affinity_fallback"
        if target is None:
            target = self._pick(freq, cands)
        if freq.session_id is not None:
            with self._aff_lock:
                self._affinity[freq.session_id] = target.rid
        freq.routing.update(reason=reason)
        if _telemetry.enabled():
            _telemetry.MetricsRegistry.get_default().counter(
                _telemetry.SERVING_FLEET_ROUTED,
                "requests routed to a replica (labels: reason, "
                "target engine)").inc(
                reason=reason, engine=target.engine.engine_id)
        with self._stats_lock:
            self._routed[reason] = self._routed.get(reason, 0) + 1
        lane_ok = (self._lane is not None and not freq._no_lane
                   and reason != "affinity"
                   and freq.prompt.size >= self._lane.threshold)
        freq.routing["lane"] = bool(lane_ok)
        freq.routing["route_ms"] = round(
            (time.perf_counter() - t_r0) * 1e3, 3)
        if lane_ok:
            self._lane.enqueue(freq, target)
        else:
            self._submit_to(target, freq, handoff=freq._lane_result)

    def _pick(self, freq: FleetRequest,
              cands: List[_Replica]) -> _Replica:
        """KV-aware score: free pages + free slots (capacity), the
        replica's own prefix-cache hit hint for this prompt
        (locality), minus queue depth. Ties rotate round-robin."""
        off = next(self._rr)
        best, best_score = None, None
        n = len(cands)
        for j in range(n):
            r = cands[(j + off) % n]
            eng = r.engine
            free_pages = eng.pool.free_pages / max(eng.pool.capacity, 1)
            free_slots = (eng.slots - int(eng._active.sum())) \
                / eng.slots
            depth = eng._queue.qsize() + len(eng._waiting)
            hit = 0.0
            if eng._prefix is not None:
                hit = eng._prefix.hit_tokens_hint(freq.prompt) \
                    / max(int(freq.prompt.size), 1)
            score = 2.0 * hit + free_pages + free_slots \
                - 0.5 * depth / eng.slots
            if best_score is None or score > best_score:
                best, best_score = r, score
        return best

    def _submit_to(self, target: _Replica, freq: FleetRequest,
                   handoff=None) -> None:
        """Hand a routed request to a replica engine (router or lane
        thread). Replica trouble re-queues instead of failing."""
        if freq.done:
            return                   # cancelled while in flight
        eng = target.engine
        freq.attempts += 1
        freq._replica_index = target.rid
        try:
            # the replica's engine.admit names this span as its parent:
            # the cause runs here, on the router's (or the lane's) thread
            with _telemetry.span(
                    "fleet.route", replica=eng.engine_id,
                    reason=freq.routing.get("reason"),
                    lane=freq.routing.get("lane", False),
                    attempts=freq.attempts) as sp:
                freq._route_span = sp.id
                if handoff is not None:
                    ho, lane_span = handoff
                    inner = eng.submit_prepared(
                        freq.prompt, freq.max_new_tokens,
                        freq.temperature, freq.eos_id, freq.sample_seed,
                        session_id=freq.session_id,
                        spec_decode=freq.spec_decode, handoff=ho,
                        lane_span=lane_span, _sink=freq)
                    freq._lane_result = None
                else:
                    inner = eng.submit(
                        freq.prompt, freq.max_new_tokens,
                        freq.temperature, freq.eos_id, freq.sample_seed,
                        session_id=freq.session_id,
                        spec_decode=freq.spec_decode, _sink=freq)
                sp.set(request=inner.request_id)
        except CapacityRejected:
            # replica queue full (rare: fleet sizes replica queues
            # generously) — try again through the router
            self._requeue(freq, "replica_full")
            return
        except RuntimeError as e:
            # engine died/shut down between health checks
            self._mark_dead(target, e)
            self._requeue(freq, "dead_on_submit")
            return
        if inner._trace is not None:
            inner._trace.event("route", sp.t0, sp.t1, span=sp.id,
                               replica=eng.engine_id,
                               reason=freq.routing.get("reason"),
                               lane=freq.routing.get("lane", False),
                               attempts=freq.attempts)

    def _requeue(self, freq: FleetRequest, why: str) -> None:
        if self._stop.is_set():
            freq._fail(RuntimeError("fleet has been shut down"))
            return
        if freq.attempts > len(self._replicas) \
                + self.MAX_ATTEMPTS_EXTRA:
            if why == "replica_full":
                # every replica's admission queue rejected us: this IS
                # the capacity case — keep the structured 429 contract
                # (retry_after_s) instead of an opaque error
                hints = [r.engine.retry_after_hint()
                         for r in list(self._replicas) if r.alive]
                freq._fail(CapacityRejected(
                    f"every replica at capacity after {freq.attempts} "
                    "attempts",
                    retry_after_s=min(hints) if hints else 1.0))
                return
            freq._fail(RuntimeError(
                f"request failed after {freq.attempts} attempts "
                f"({why})"))
            return
        _flight.record("fleet_requeue", why=why,
                       attempts=freq.attempts,
                       tokens_done=len(freq.tokens))
        try:
            self._queue.put_nowait(freq)
        except _queue.Full:
            freq._fail(CapacityRejected(
                "fleet queue full during re-route", 1.0))

    # ------------------------------------------------------- failover
    def _maybe_reroute(self, freq: FleetRequest,
                       inner: ServingRequest,
                       error: BaseException) -> bool:
        """Engine-death failover (called from the dying engine's
        scheduler thread via the sink hook). True when the request was
        re-queued onto a survivor; False surfaces the error."""
        if self._stop.is_set():
            return False
        idx = getattr(freq, "_replica_index", None)
        if idx is None:
            return False
        r = self._by_rid.get(idx)
        if r is None:
            return False    # replica already removed from the fleet
        eng = r.engine
        if eng._dead is None and not eng._stop.is_set():
            return False        # genuine per-request error: surface it
        self._mark_dead(r, error)
        if freq.attempts > len(self._replicas) \
                + self.MAX_ATTEMPTS_EXTRA:
            return False
        self.n_reroutes += 1
        if _telemetry.enabled():
            _telemetry.MetricsRegistry.get_default().counter(
                _telemetry.SERVING_FLEET_REROUTES,
                "requests replayed on a survivor after their replica "
                "died").inc(engine=eng.engine_id)
        _flight.record("fleet_reroute",
                       request_id=inner.request_id,
                       from_engine=eng.engine_id,
                       tokens_done=len(freq.tokens),
                       attempts=freq.attempts)
        try:
            self._queue.put_nowait(freq)
        except _queue.Full:
            return False
        return True

    def _on_request_done(self, freq: FleetRequest) -> None:
        self.n_completed += 1


__all__ = ["ServingFleet", "FleetRequest"]
