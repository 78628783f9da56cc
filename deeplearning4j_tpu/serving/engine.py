"""Continuous-batching decode engine over any model that brings
``cache_spec()`` / ``prefill`` / ``decode_step`` (docs/SERVING.md,
"What a served model brings"): ``models/gpt.py`` CausalLM,
``models/lfm2_moe.py``. This file schedules; it holds no model math
and reads no parameter by name.

The problem with ``generate()`` as a serving path: it compiles one
program per ``(batch, prompt, new_tokens)`` shape, runs the whole batch
in lockstep until the SLOWEST request finishes, and pays a trace +
XLA compile on the first request of every new shape. This module
replaces it with a server-grade engine:

- **Slot-based fixed-shape decode step, jitted ONCE.** A static batch
  of ``slots`` decode lanes; each slot carries its own KV pages,
  position and sampling state. Throughput is set by slot OCCUPANCY,
  not by the longest request: a finished request's slot is refilled
  from the queue between steps while its neighbors keep decoding.
- **Paged KV cache** (kv_pages.py): one page pool allocated at
  startup; per-slot page tables. No per-shape cache allocations, no
  per-shape executables.
- **AOT warm pool**: startup ``jit(...).lower(...).compile()``s the
  decode step and every prefill bucket (the same lower/compile
  workflow the V5E16_AOT.json projection used), and the engine calls
  the compiled executables directly — the first request never pays a
  trace (``jax.jit``'s call cache is NOT populated by AOT compilation,
  so the warm pool bypasses the jit call path entirely; the
  recompile-detector counters at the ``serving_*`` sites stay 0).
- **int8 weight-only decode** (``quantization="int8"``): decode is
  HBM-bandwidth-bound; the decode step reads int8 weights with
  per-channel scales (nn/precision.py) and dequantizes inside the
  matmul. Prefill (compute-bound) keeps the full-precision weights.

Greedy parity contract (tested): with temperature 0 and no
quantization, every request decoded through the engine — joining and
leaving mid-flight next to arbitrary other requests — produces
token-identical output to a solo ``CausalLM.generate()`` call. The
per-slot math is row-independent and the paged attention masks exactly
the positions the dense cache masks.

Telemetry: request p50/p99 latency + time-to-first-token histograms,
queue-depth / slot-occupancy / KV-page-utilization gauges, warm-pool
hit/miss counters (profiler/telemetry.py ``SERVING_*`` names), all on
``/metrics`` and ``/telemetry``.
"""

from __future__ import annotations

import collections
import itertools
import logging
import queue as _queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.nn.precision import (int8_matmul, quantize_int8,
                                             resolve_kv_dtype)
from deeplearning4j_tpu.ops.paged_attention_pallas import (
    paged_attention, paged_attention_mode)
from deeplearning4j_tpu.profiler import flight_recorder as _flight
from deeplearning4j_tpu.profiler import telemetry as _telemetry
from deeplearning4j_tpu.profiler import tracing as _tracing
from deeplearning4j_tpu.serving import kv_pages
from deeplearning4j_tpu.serving.prefix_cache import PrefixCache
from deeplearning4j_tpu.serving.sessions import SessionStore
from deeplearning4j_tpu.serving.spec_decode import (SpecConfig,
                                                    accept_tokens)

log = logging.getLogger("deeplearning4j_tpu")


# ------------------------------------------------------------ requests
#: PROCESS-wide request ids: the tracing registries and the
#: /v1/serving/requests/<id> lookups key on request_id, so two engines
#: in one process (or an engine restart) must not both mint id 0
_REQUEST_IDS = itertools.count()

#: PROCESS-wide engine ordinals: every SERVING_* metric is labelled
#: ``engine=<id>`` so N engines in one process (a serving fleet, or a
#: test constructing engines back to back) stay distinguishable series
#: instead of merging into one
_ENGINE_IDS = itertools.count()


class CapacityRejected(RuntimeError):
    """Hard capacity reject: the admission queue is full. Carries a
    ``retry_after_s`` hint (derived from recent request latency and
    queue depth) so front-ends can answer with a structured
    429-with-Retry-After instead of an opaque error, and clients can
    back off for a meaningful interval."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)


class ServingRequest:
    """Handle for one submitted generation request.

    ``result()`` blocks until completion and returns the generated
    tokens (np.int32, length <= max_new_tokens — shorter on EOS).
    ``stream()`` yields tokens as the engine emits them. ``ttft_s`` /
    ``latency_s`` are populated as the request progresses."""

    def __init__(self, request_id: int, prompt: np.ndarray,
                 max_new_tokens: int, temperature: float,
                 eos_id: Optional[int], keydata: np.ndarray,
                 session_id: Optional[str] = None):
        self.request_id = request_id
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = eos_id
        self.session_id = session_id
        #: prompt tokens whose K/V came from the prefix cache or a
        #: sticky session instead of prefill compute (0 = cold)
        self.cache_hit_tokens = 0
        #: speculative decoding: per-request opt-in/out (None follows
        #: the engine's spec_decode config) and the request's own
        #: draft-token acceptance tally — front-ends echo these as the
        #: response's ``spec`` stats
        self.spec_enabled: Optional[bool] = None
        self.spec_proposed = 0
        self.spec_accepted = 0
        #: conversation turn this request will pin as (resume bumps it)
        self._session_turns = 1
        self._keydata = keydata
        self.tokens: List[int] = []
        self.finish_reason: Optional[str] = None   # length | eos | error
        self.ttft_s: Optional[float] = None
        self.latency_s: Optional[float] = None
        #: id of the engine serving this request (set at submit) — the
        #: per-replica tag front-ends echo in responses and traces
        self.engine_id: Optional[str] = None
        #: fleet-side mirror (serving/fleet.py FleetRequest): receives
        #: _on_token/_on_finish callbacks. None outside a fleet — the
        #: solo-engine hot path pays one attribute read per token.
        self._sink = None
        #: disaggregated-prefill handoff (ks, vs, bucket, logits) from
        #: the fleet's prefill lane; None for every normal request
        self._handoff = None
        #: id of the span that caused this request on another thread
        #: (the fleet's ``fleet.route``): ``engine.admit`` records it as
        #: its parent. None outside a fleet.
        self.caused_by: Optional[int] = None
        #: per-request trace (profiler/tracing.py) — None with tracing
        #: off; the timeline is served at /v1/serving/requests/<id>
        self._trace = None
        #: the engine serving this request (set at submit) — what makes
        #: ``cancel()`` routable without the caller holding the engine
        self._engine = None
        self._t_submit = time.perf_counter()
        self._stream: "_queue.Queue" = _queue.Queue()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None

    # -- engine side ----------------------------------------------------
    def _push(self, token: int) -> None:
        if self.ttft_s is None:
            self.ttft_s = time.perf_counter() - self._t_submit
        self.tokens.append(token)
        self._stream.put(token)
        sink = self._sink
        if sink is not None:
            sink._on_token(self, token)

    def _finish(self, reason: str,
                error: Optional[BaseException] = None) -> None:
        self.finish_reason = reason
        self._error = error
        self.latency_s = time.perf_counter() - self._t_submit
        if self._trace is not None:
            tn = time.perf_counter()
            self._trace.event("finish", tn, tn, reason=reason,
                              tokens=len(self.tokens))
            _tracing.finish_trace(self._trace, reason=reason)
        self._stream.put(None)            # stream sentinel
        self._done.set()
        sink = self._sink
        if sink is not None:
            sink._on_finish(self, reason, error)

    # -- client side ----------------------------------------------------
    @property
    def trace_id(self) -> Optional[str]:
        return self._trace.trace_id if self._trace is not None else None

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not done within {timeout}s")
        if self._error is not None:
            raise self._error
        return np.asarray(self.tokens, np.int32)

    def cancel(self) -> bool:
        """Abort this request (client-callable, any thread): queued, it
        never runs; decoding, its slot is freed and its KV pages drain
        back to the pool at the scheduler's next pass. The request
        finishes with ``finish_reason="cancelled"`` (trace closed with
        the same reason) and ``result()`` returns the tokens generated
        so far. False when the request already finished — a ``result``
        / ``stream`` timeout no longer leaves the request holding its
        slot and pages forever."""
        eng = self._engine
        if eng is None:
            return False
        return eng.abort(self)

    def stream(self):
        """Yield tokens as they are generated; raises the request's
        error (if any) after the stream ends."""
        while True:
            tok = self._stream.get()
            if tok is None:
                break
            yield tok
        if self._error is not None:
            raise self._error


class _Burst:
    """A decode burst under way: the slots it decodes for and their
    requests, the budget to the nearest request's end, the roster and
    the running pos / tok / keys as device arrays, and the tokens of
    the chunks sent so far, not yet read."""

    __slots__ = ("idx", "reqs", "rem", "has_eos", "pos_live", "after_end",
                 "roster", "pos", "tok", "kd", "chunks", "counts", "steps",
                 "gen", "span")


# ----------------------------------------------------------- warm pool
class _WarmPool:
    """AOT-compiled executables keyed by program name. ``run`` calls
    the warm executable when present (zero trace); otherwise falls back
    to the instrumented jit path, which counts the compile."""

    def __init__(self, engine_id: str = "solo"):
        self._exec: Dict[Any, Any] = {}
        self.engine_id = engine_id
        self.hits = 0
        self.misses = 0
        #: executables adopted from another engine's warm pool (the
        #: fleet's shared-AOT startup) rather than compiled here
        self.adopted = 0
        #: program-registry signature per key (profiler/programs.py) —
        #: populated at compile/adopt time only when the registry was
        #: enabled, so run() attributes dispatches with one dict.get
        self._prog_sig: Dict[Any, str] = {}

    @staticmethod
    def _prog_site(key) -> str:
        return f"serving_{key[0]}"

    def _prog_register(self, key, ex, compile_seconds: float,
                       source: str) -> None:
        """Roofline registry registration (no-op when the registry is
        off; never raises — serving startup must not depend on it)."""
        from deeplearning4j_tpu.profiler import programs as _programs

        if not _programs.enabled():
            return
        sig = f"{key[0]}[{key[1]}]"
        self._prog_sig[key] = sig
        _programs.get_default().register(
            self._prog_site(key), sig, ex, source=source,
            engine=self.engine_id, compile_seconds=compile_seconds)

    def compile(self, key, jitted, *abstract_args) -> None:
        t0 = time.perf_counter()
        ex = self._exec[key] = jitted.lower(*abstract_args).compile()
        self._prog_register(key, ex, time.perf_counter() - t0,
                            "warm_pool")

    def adopt(self, source: "_WarmPool") -> int:
        """Share another engine's AOT executables (same shapes, same
        device): fleet replicas lower+compile ONCE and every further
        same-device replica adopts, so fleet startup does not pay N x
        the warm-pool cost. Returns the number adopted."""
        fresh = {k: v for k, v in source._exec.items()
                 if k not in self._exec}
        self._exec.update(fresh)
        self.adopted += len(fresh)
        for k, ex in fresh.items():
            self._prog_register(k, ex, 0.0, "adopted")
        return len(fresh)

    def __contains__(self, key) -> bool:
        return key in self._exec

    def run(self, key, fallback, *args):
        ex = self._exec.get(key)
        reg = (_telemetry.MetricsRegistry.get_default()
               if _telemetry.enabled() else None)
        if ex is not None:
            self.hits += 1
            if reg:
                reg.counter(_telemetry.SERVING_WARM_HITS,
                            "decode/prefill dispatches served by AOT-"
                            "compiled warm-pool executables").inc(
                    program=str(key[0]), engine=self.engine_id)
            sig = self._prog_sig.get(key)
            if sig is not None:
                # registry was on at compile time: per-dispatch
                # roofline accounting (host wall — decode queueing
                # slack included, see programs.py caveat)
                from deeplearning4j_tpu.profiler import \
                    programs as _programs

                t0 = time.perf_counter()
                out = ex(*args)
                _programs.record_dispatch(
                    self._prog_site(key), sig,
                    time.perf_counter() - t0)
                return out
            return ex(*args)
        self.misses += 1
        if reg:
            reg.counter(_telemetry.SERVING_WARM_MISSES,
                        "dispatches that missed the warm pool and "
                        "took the (compiling) jit path").inc(
                program=str(key[0]), engine=self.engine_id)
        return fallback(*args)


# --------------------------------------------------------- the engine
def device_sds(shape, dtype, device=None) -> jax.ShapeDtypeStruct:
    """Abstract value for AOT lowering, pinned to ``device`` when one
    is given — lowering from unpinned abstracts compiles for the
    process default device and fails placement on any replica living
    elsewhere. Shared by the engine warm pool and the fleet's lane."""
    if device is not None:
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=jax.sharding.SingleDeviceSharding(device))
    return jax.ShapeDtypeStruct(shape, dtype)


class DecodeEngine:
    """Continuous-batching generation server over a served model.

    Parameters
    ----------
    model, params : the model and its parameter tree.
    slots : static decode-batch width (requests in flight per step).
    page_size : KV-cache page length in positions.
    max_context : per-request position budget (prompt + generated);
        defaults to (and is capped at) ``model.cfg.max_len``.
    n_pages : total KV pool pages (incl. the null page). Default sizes
        the pool so every slot can hold ``max_context`` positions.
    prefill_buckets : prompt padding widths to AOT-compile; default
        powers of two (times page_size granularity) up to max_context.
    quantization : None | "int8" — int8 weight-only decode weights
        (per-channel scales, dequant-in-matmul); prefill stays full
        precision.
    kv_dtype : None | "fp8_e4m3" — quantize the KV-cache PAGES to
        float8_e4m3fn with per-page-per-head fp32 scale planes
        (kv_pages.py): half the page bytes of bf16, so ~2x effective
        KV capacity and half the cache traffic per decode step.
        Quantize-on-commit / dequantize-in-attention; greedy outputs
        agree with the float engine to quantization error (CI gates
        >= 0.99 token agreement), not bit-identically.
    attn_mode : None | "pallas" | "interpret" | "xla" — attention
        implementation for the decode core and the prefix-prefill
        program (ops/paged_attention_pallas.py). None follows
        ``DL4J_TPU_PAGED_ATTN`` / backend auto-detection: the fused
        online-softmax kernel on TPU, the reference einsum pair
        elsewhere. "xla" is op-for-op the pre-kernel engine.
    spec_decode : None | int k | "ngram" | dict | SpecConfig —
        speculative decoding (serving/spec_decode.py): a host-side
        draft proposes up to ``k`` tokens per slot per burst and one
        AOT-warmed fixed-shape VERIFY program scores all ``k+1``
        positions through the target model in a single weight read;
        the accepted prefix plus one correction/bonus token is
        emitted (greedy: exactly the plain rollout, token for token;
        temperature > 0: the target distribution is preserved by
        rejection sampling). None (the default) builds no verify
        program — the engine stays program-for-program identical to
        the spec-less path. Requests opt out per-submit with
        ``spec_decode=False``.
    prefix_cache : index committed prompt pages by chained page hash
        (serving/prefix_cache.py) and serve later prompts' shared
        prefixes from the SAME refcounted pages — copy-on-write on
        mid-page divergence, LRU leaf eviction under page pressure,
        prefill restricted to the uncached suffix. Off (the default)
        keeps the engine bit-identical to the cache-less path.
    session_capacity / session_ttl : > 0 enables sticky sessions
        (serving/sessions.py): a finished request submitted with a
        ``session_id`` pins its pages + token history, and the next
        turn whose prompt extends that history resumes decode after
        prefilling only the new tokens. Bounded by capacity (LRU),
        ttl seconds idle, explicit ``release_session``, and
        admission pressure.
    max_chunk : upper bound (a power of two) on decode steps fused
        into ONE dispatch via lax.scan. The scheduler picks the
        largest power-of-two chunk that cannot overshoot the nearest
        request completion, so join/evict granularity is preserved
        exactly while host dispatch overhead and slot-state transfers
        amortize over up to ``max_chunk`` tokens. 1 disables chunking.
    warm_start : AOT-compile the decode + prefill executables in
        ``start()`` so no request ever pays a trace.

    What a model has to bring, and which option needs which of its
    methods, is docs/SERVING.md, "What a served model brings". Where
    its ``cache_spec()`` names a per-slot ``state`` (conv windows),
    that array ``[state layers, slots, ...]`` lives beside the pool:
    written by prefill at admission (a reused slot never sees its
    predecessor's), carried through the chunk's scan and donated
    across dispatches like the pool; options that key on pages alone
    are then refused by name. Where it names ``stores`` (a latent row,
    an indexer's keys), the pool is those arrays under one page table
    a slot, and the options written for K and V pools are refused.
    """

    def __init__(self, model, params, *, slots: int = 8,
                 page_size: int = 16, max_context: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 quantization: Optional[str] = None,
                 max_chunk: int = 8,
                 max_queue: int = 512, seed: int = 0,
                 warm_start: bool = True,
                 prefix_cache: bool = False,
                 session_capacity: int = 0,
                 session_ttl: float = 600.0,
                 engine_id: Optional[str] = None,
                 device=None,
                 handoff_threshold: Optional[int] = None,
                 warm_source: Optional["DecodeEngine"] = None,
                 kv_dtype: Optional[str] = None,
                 attn_mode: Optional[str] = None,
                 spec_decode=None):
        cfg = model.cfg
        self.model = model
        #: what the model caches (docs/SERVING.md, "What a served
        #: model brings"); which options it can be given follows from
        #: that and from the methods it has, decided here and nowhere
        #: else
        spec = model.cache_spec()

        def refuse(asked, why):
            for name, wanted in asked.items():
                if wanted:
                    raise ValueError(f"{name} cannot be honoured for "
                                     f"{type(model).__name__}: {why}")

        if spec["state"] is not None:
            refuse({"prefix_cache": prefix_cache,
                    "session_capacity": session_capacity > 0,
                    "spec_decode": spec_decode is not None,
                    "quantization": quantization is not None,
                    "kv_dtype": kv_dtype is not None,
                    "handoff_threshold": handoff_threshold is not None},
                   "the model keeps a per-slot state beside its pages (a "
                   "recurrent state, or window layers' rings), and this "
                   "option keys on pages alone")
        #: the pool's stores where the model names its own (a latent
        #: row, an indexer's keys: ``{name: (layers, row width)}``),
        #: else None: K and V pools of ``kv_heads x head_dim``
        self._stores = spec.get("stores")
        if self._stores is not None:
            refuse({"kv_dtype": kv_dtype is not None,
                    "handoff_threshold": handoff_threshold is not None},
                   f"its pool is stores of its own "
                   f"({', '.join(self._stores)}), not K and V, and this "
                   "option is written for those")
        needs = {"prefix_cache": (prefix_cache, "paged_rows"),
                 "session_capacity": (session_capacity > 0, "paged_rows"),
                 "spec_decode": (spec_decode is not None, "paged_rows"),
                 "quantization": (quantization is not None,
                                  "quantize_decode_params")}
        for name, (asked, method) in needs.items():
            if asked and not hasattr(model, method):
                raise ValueError(
                    f"{name} cannot be honoured for "
                    f"{type(model).__name__}: it brings no {method}")
        #: metric/trace label for this engine (``engine=<id>`` on every
        #: SERVING_* series); auto-minted process-wide when not given
        self.engine_id = (str(engine_id) if engine_id is not None
                          else f"e{next(_ENGINE_IDS)}")
        #: placement for params + KV pools (None = default device, the
        #: pre-fleet path byte-for-byte); a fleet passes one device per
        #: replica
        self._device = device
        #: fleet replica mode: prompts >= this many tokens may arrive
        #: PRE-FILLED from the disaggregated prefill lane
        #: (submit_prepared) — the adopt scatter programs for the
        #: corresponding buckets are built and AOT-warmed. None (the
        #: default) builds none of it.
        self.handoff_threshold = handoff_threshold
        self._warm_source = warm_source
        self.slots = int(slots)
        self.page_size = int(page_size)
        self.max_context = int(min(max_context or cfg.max_len,
                                   cfg.max_len))
        if self.slots < 1:
            raise ValueError("need at least one slot")
        if self.max_context < self.page_size:
            raise ValueError(
                f"max_context {self.max_context} < page_size "
                f"{self.page_size}")
        self.pages_per_slot = kv_pages.pages_needed(self.max_context,
                                                    self.page_size)
        if n_pages is None:
            n_pages = 1 + self.slots * self.pages_per_slot
        self.quantization = quantization
        if quantization not in (None, "int8"):
            raise ValueError(f"unknown quantization {quantization!r} "
                             "(expected None or 'int8')")
        given = (jax.device_put(params, device)
                 if device is not None else jax.device_put(params))
        #: the trees the programs take, at rest as the model serves
        #: them (``serving_params``, where it brings one: a float32
        #: GPT-2 becomes a copy in its compute dtype, made once here
        #: and not inside every dispatch). The tree as given is not
        #: kept: it lives on only if the caller holds it. int8 codes
        #: and scales come from the tree as given.
        at_rest = getattr(model, "serving_params", lambda tree: tree)
        self.params = at_rest(given)
        self._decode_params = (
            at_rest(model.quantize_decode_params(given))
            if quantization == "int8" else self.params)
        del given
        #: bytes of every array those trees hold, one shared by both
        #: counted once (``stats()["weight_bytes"]``)
        leaves = jax.tree_util.tree_leaves(
            (self.params, self._decode_params))
        self._weight_bytes = sum(
            int(a.nbytes) for a in {id(a): a for a in leaves}.values())
        #: canonical kv_dtype (None = pool in the compute dtype) and
        #: the attention implementation, both resolved ONCE here and
        #: baked statically into the step builders — every executable
        #: of this engine uses one consistent path
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        self._attn_mode = (str(attn_mode) if attn_mode is not None
                           else paged_attention_mode())
        if self._attn_mode not in ("pallas", "interpret", "xla"):
            raise ValueError(
                f"unknown attn_mode {attn_mode!r} (expected None, "
                "'pallas', 'interpret' or 'xla')")
        self.pool = kv_pages.PagePool(
            spec.get("kv_layers"), spec.get("kv_heads"), self.page_size,
            spec.get("head_dim"), n_pages, dtype=model._cdtype,
            engine_id=self.engine_id, device=device,
            kv_dtype=self.kv_dtype, stores=self._stores)
        #: the per-slot state beside the pool: an array ``[state
        #: layers, slots, ...]`` in the compute dtype, or a dict of such
        #: (a cache's second kind: window layers' rings of K and of V),
        #: or None where the model has none
        self._state = None
        if spec["state"] is not None:
            self._state = jax.tree_util.tree_map(
                lambda shape: jnp.zeros(
                    (shape[0], self.slots, *shape[1:]), model._cdtype,
                    device=device),
                spec["state"], is_leaf=lambda x: isinstance(x, tuple))
        #: positions a window layer attends (None: the model has none);
        #: such a model's K/V of those layers is the state above
        self._window = spec.get("window")
        #: the most positions an attention layer reads of a context
        #: (None: all of it); such a model's indexer scores them all
        self._selected = spec.get("selected")
        #: the most slots ever live at once: what of the window layers'
        #: store was ever in use
        self._slots_high_water = 0
        self.prefill_buckets = self._resolve_buckets(prefill_buckets)
        # sampling-key width follows the process PRNG impl (threefry=2,
        # rbg=4) so keydata shapes match whatever jax.config says
        self._kd_width = int(
            jax.random.key_data(jax.random.key(0)).shape[-1])
        self._base_key = jax.random.key(seed)
        self._req_counter = _REQUEST_IDS   # process-wide, see above
        # sampling keys fold a PER-ENGINE ordinal (not the global
        # request id): two engines built with the same seed must
        # sample identically regardless of process-wide submission
        # history
        self._sample_counter = itertools.count()
        # the keys are made KEY_BLOCK ordinals at a time and taken from
        # the host's copy: a submit must not wait for the device, which
        # works through its calls in order and may have a chunk of
        # tens of milliseconds before the key's fold
        base = self._base_key
        self._key_block_fn = jax.jit(jax.vmap(
            lambda n: jax.random.key_data(jax.random.fold_in(base, n))))
        self._key_lock = threading.Lock()
        self._key_blocks: Dict[int, np.ndarray] = {}
        self._sample_keydata(0)
        # host-side slot state (the jitted step's small inputs)
        S, P = self.slots, self.pages_per_slot
        self._tables = np.zeros((S, P), np.int32)
        self._pos = np.zeros((S,), np.int32)
        self._tok = np.zeros((S,), np.int32)
        self._temps = np.zeros((S,), np.float32)
        self._keydata = np.zeros((S, self._kd_width), np.uint32)
        self._active = np.zeros((S,), bool)
        self._slot_req: List[Optional[ServingRequest]] = [None] * S
        self._slot_pages: List[List[int]] = [[] for _ in range(S)]
        self._slot_emitted = np.zeros((S,), np.int64)
        # device-side mirrors of the slot state that only changes on
        # join/evict (tables/active/temps): re-uploaded only when dirty
        self._dev_static = None
        #: counts the roster's changes (a join, an eviction), so that a
        #: burst begun ahead knows whether its roster still stands
        self._roster_gen = 0
        #: the burst whose first chunk went out before the last one's
        #: tokens were handed out (``_run_ahead``), or None
        self._ahead: Optional[_Burst] = None
        # programs: one chunk executable per power-of-two step count
        if max_chunk < 1 or (max_chunk & (max_chunk - 1)):
            raise ValueError(
                f"max_chunk must be a power of two >= 1, got "
                f"{max_chunk}")
        self.max_chunk = int(max_chunk)
        self._chunks = []
        k = 1
        while k <= self.max_chunk:
            self._chunks.append(k)
            k *= 2
        core = self._build_step_core()
        # donate the cache (pools, any scale planes, any per-slot
        # state): the engine rebinds it from every call's outputs, and
        # without donation XLA must copy the whole cache at every
        # dispatch boundary (the scan inside a chunk already aliases;
        # donation extends that across dispatches)
        self._decode_jits = {
            k: jax.jit(self._make_chunk(core, k), donate_argnums=(1,))
            for k in self._chunks}
        self._decode_fallbacks = {
            k: _telemetry.instrument_jit("serving_decode", fn)
            for k, fn in self._decode_jits.items()}
        self._prefill_jit = jax.jit(self._build_prefill_fn(),
                                    donate_argnums=(1,))
        self._prefill_fallback = _telemetry.instrument_jit(
            "serving_prefill", self._prefill_jit)
        # fleet replica mode: the adopt scatter that commits a prefill
        # lane's handed-off K/V into this engine's pages. Buckets are
        # the prefill buckets a lane-eligible prompt can land in
        # (smallest bucket >= a threshold-sized prompt is itself >=
        # threshold). None of this exists outside a fleet.
        self.handoff_buckets: List[int] = []
        if handoff_threshold is not None:
            if handoff_threshold < 1:
                raise ValueError("handoff_threshold must be >= 1")
            self.handoff_buckets = [
                b for b in self.prefill_buckets
                if b >= int(handoff_threshold)]
            self._adopt_jit = jax.jit(self._build_adopt_fn(),
                                      donate_argnums=(0,))
            self._adopt_fallback = _telemetry.instrument_jit(
                "serving_adopt", self._adopt_jit)
        # cross-request KV reuse (prefix_cache.py / sessions.py). Both
        # ride on the same two extra programs: a SUFFIX prefill that
        # attends through the slot's page table (so cached prefix
        # pages are read, only new positions are computed/written) and
        # the copy-on-write page copy. Neither exists when reuse is
        # off — the cache-less engine stays program-for-program
        # identical to the pre-reuse path.
        self._prefix = (PrefixCache(self.page_size,
                                    engine_id=self.engine_id)
                        if prefix_cache else None)
        self._sessions = (SessionStore(session_capacity, session_ttl,
                                       engine_id=self.engine_id)
                          if session_capacity > 0 else None)
        self._reuse = (self._prefix is not None
                       or self._sessions is not None)
        if self._reuse:
            self._prefix_prefill_jit = jax.jit(
                self._build_prefix_prefill_fn(), donate_argnums=(1,))
            self._prefix_prefill_fallback = _telemetry.instrument_jit(
                "serving_prefix_prefill", self._prefix_prefill_jit)
            self._copy_jit = jax.jit(kv_pages.copy_page,
                                     donate_argnums=(0,))
            self._copy_fallback = _telemetry.instrument_jit(
                "serving_cow_copy", self._copy_jit)
        # speculative decoding (serving/spec_decode.py): a host-side
        # draft proposes k tokens per slot and ONE fixed-shape verify
        # program scores all k+1 positions per weight read. Off (the
        # default) builds NOTHING — the engine's program set stays
        # byte-identical to the spec-less path, same gating discipline
        # as self._reuse above.
        self._spec = SpecConfig.resolve(spec_decode)
        if self._spec is not None:
            self._spec_draft = self._spec.make_draft()
            self._verify_jit = jax.jit(self._build_verify_fn(),
                                       donate_argnums=(1,))
            self._verify_fallback = _telemetry.instrument_jit(
                "serving_verify", self._verify_jit)
        self.n_spec_proposed = 0
        self.n_spec_accepted = 0
        #: verify dispatches summed over the ACTIVE lanes they served —
        #: the denominator of tokens-per-weight-read (a plain decode
        #: lane-step scores exactly 1 token per weight read)
        self.n_verify_lane_steps = 0
        self.n_verify_dispatches = 0
        self._warm = _WarmPool(engine_id=self.engine_id)
        self._warm_start = bool(warm_start)
        # scheduler. max_queue bounds queued + head-of-line-waiting
        # requests together (the scheduler drains the Queue into
        # _waiting between bursts, so the Queue's own maxsize alone
        # would not be a real admission bound).
        self.max_queue = int(max_queue)
        self._queue: "_queue.Queue" = _queue.Queue(maxsize=max_queue)
        self._waiting: "collections.deque" = collections.deque()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._start_lock = threading.Lock()
        self._dead: Optional[BaseException] = None
        #: chaos/drill hook (fleet.kill_replica): the scheduler raises
        #: this at its next loop iteration, exercising the real
        #: engine-death path (evictions, flight incident, re-routing)
        self._poison: Optional[BaseException] = None
        #: chaos hook (profiler/chaos.hang_replica): the scheduler
        #: sleeps this long at its next pass — hung, not dead
        self._hang_s: float = 0.0
        #: requests to cancel at the scheduler's next pass (abort());
        #: client threads add, the scheduler thread drains
        self._aborts: set = set()
        self._abort_lock = threading.Lock()
        #: monotonic clock of the last scheduler progress (admit or
        #: decode burst) — the control plane's stalled-replica signal
        self.last_progress = time.monotonic()
        # stats
        self.n_requests = 0
        self.n_completed = 0
        self.n_steps = 0         # decode steps (tokens per slot-lane)
        self.n_dispatches = 0    # chunked device calls
        #: counted where the spans open (engine thread only, no lock):
        #: K/V positions the paged kernel's calls had to read, a layer
        #: (a decode step: what each live slot holds; a verify dispatch
        #: or a suffix prefill: the lane's context once, its queries
        #: share the read), and prompt tokens prefilled against the
        #: padded buckets they ran in; and the pages those positions
        #: occupy, call by call: what the kernel's walk has to visit
        self.n_attended_tokens = 0
        self.n_attended_pages = 0
        self.n_prefill_tokens = 0
        self.n_prefill_bucket_tokens = 0
        self.n_tokens = 0
        #: what the expert layers did, summed over decode steps and
        #: prefills from the small arrays the programs return (live
        #: lanes and real prompt positions only): assignments, distinct
        #: experts touched, layer-steps, and the hottest expert's load
        self._expert_totals = dict.fromkeys(
            ("expert_assignments", "experts_touched",
             "expert_layer_steps", "expert_load_max",
             "expert_assignments_routed"), 0)
        self._occupancy_sum = 0.0
        # newest finished requests (id + finish reason + timings), so
        # client logs can join against server traces via stats()
        self._recent: "collections.deque" = collections.deque(maxlen=32)

    # ------------------------------------------------------ construction
    def _resolve_buckets(self, buckets) -> List[int]:
        ps, mc = self.page_size, self.max_context
        if buckets is None:
            buckets, b = [], ps
            while b < mc:
                buckets.append(b)
                b *= 2
            buckets.append(kv_pages.pages_needed(mc, ps) * ps)
        out = sorted({int(b) for b in buckets})
        for b in out:
            if b % ps or b < ps:
                raise ValueError(
                    f"prefill bucket {b} is not a multiple of "
                    f"page_size {ps}")
        return out

    # --------------------------------------------------- jitted programs
    @staticmethod
    def _sample_next(logits, keydata, temps):
        """Every slot's next token from its float32 logits (greedy at
        temperature 0, else a draw from its own key) and the keys
        advanced: the tail every decode core shares."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys = jax.random.wrap_key_data(keydata)
        nk = jax.vmap(jax.random.split)(keys)      # [S, 2] keys
        safe_t = jnp.where(temps > 0, temps, 1.0)
        sampled = jax.vmap(jax.random.categorical)(
            nk[:, 1], logits / safe_t[:, None]).astype(jnp.int32)
        nxt = jnp.where(temps > 0, sampled, greedy)
        return nxt, jax.random.key_data(nk[:, 0])

    def _build_step_core(self):
        """One fixed-shape decode step for all S slots: the model's
        ``decode_step`` over the cache ``(kv tree, per-slot state)``,
        then the shared sampling tail. Beside the tokens it returns
        the model's small per-step counts (an expert model's
        assignments, distinct experts, hottest load, of live lanes;
        None where the model counts nothing)."""
        m, ps, attn = self.model, self.page_size, self._attn_mode

        def step(params, cache, tables, pos, tok, keydata, temps, active):
            kv, state = cache
            kv, state, logits, counts = m.decode_step(
                params, kv, state, tables, pos, tok, active, ps, mode=attn)
            nxt, nkd = self._sample_next(logits, keydata, temps)
            return (kv, state), nxt, nkd, counts

        return step

    @staticmethod
    def _make_chunk(core, n_steps: int):
        """``n_steps`` decode steps fused into one lax.scan program.
        The scheduler guarantees no active request completes mid-chunk
        (chunk <= min remaining), so the slot roster (tables / active /
        temps) is loop-invariant and only the per-token state (pos /
        tok / keys / cache) carries. A chunk of 1 is the plain step.
        The model's per-step counts come back stacked ``[n_steps,
        ...]``."""

        def chunk(params, cache, tables, pos, active, tok,
                  keydata, temps):
            def body(carry, _):
                cache, pos, tok, kd = carry
                cache, nxt, nkd, counts = core(
                    params, cache, tables, pos, tok, kd, temps, active)
                pos = pos + active.astype(pos.dtype)
                tok = jnp.where(active, nxt, tok)
                return (cache, pos, tok, nkd), (nxt, counts)

            (cache, pos, tok, kd), (toks, counts) = lax.scan(
                body, (cache, pos, tok, keydata), None,
                length=n_steps)
            return cache, toks.T, pos, tok, kd, counts

        return chunk

    def _build_prefill_fn(self):
        """Parallel prefill of one request: the model's ``prefill``
        over the padded prompt gives every caching layer's K/V, written
        into the slot's pages, the per-slot state at the last REAL
        position (written into row ``slot`` of the state array, so a
        reused slot starts from its own prompt), the last real
        position's logits (the first generated token's distribution)
        and the prompt's counts. Positions >= t0 see padding but are
        causally invisible to positions < t0, so the committed K/V and
        returned logits are exact."""
        m, ps, attn = self.model, self.page_size, self._attn_mode

        def prefill(params, cache, prompt, page_row, t0, slot):
            kv, state = cache
            ks, vs, mine, last, counts = m.prefill(params, prompt, t0,
                                                   mode=attn)
            if self._stores is not None:
                # the model's own stores: ``ks`` is a row a position a
                # store, ``vs`` nothing
                kv = kv_pages.commit_rows(kv, ks, page_row, ps)
            else:
                # t0 bounds the REAL positions: an fp8 pool's page
                # scales are minted from them only, never from padding
                kv = kv_pages.commit_prefill(kv, ks, vs, page_row, ps,
                                             n_valid=t0)
            if state is not None:
                state = jax.tree_util.tree_map(
                    lambda all_, one: lax.dynamic_update_slice_in_dim(
                        all_, one[:, None].astype(all_.dtype), slot,
                        axis=1), state, mine)
            return (kv, state), last.astype(jnp.float32), counts

        return prefill

    def _build_prefix_prefill_fn(self):
        """SUFFIX prefill for a warm-prefix admission: the model's
        ``paged_rows`` with one lane, over the padded new tokens
        (bucket width ``B``) at absolute positions ``t_start..``,
        attending through the slot's WHOLE page table — the cached
        prefix pages are read in place, and only the suffix positions'
        K/V are computed and written (positions past the real prompt
        write to the null page). ``t_start`` may sit mid-page
        (copy-on-write divergence, session resume), which the
        per-position (page, offset) write handles for free. The
        attention is the decode step's own paged_attention, so warm
        greedy outputs stay token-identical to a cold prefill."""
        m, ps, attn = self.model, self.page_size, self._attn_mode

        def prefill(params, cache, tokens, table, t_start, t0):
            kv, state = cache
            B = tokens.shape[0]
            real = (t_start + jnp.arange(B, dtype=jnp.int32)) < t0
            kv, logits = m.paged_rows(
                params, kv, table[None],
                jnp.reshape(t_start, (1,)).astype(jnp.int32),
                tokens[None], real[None], ps, mode=attn)
            last = lax.dynamic_index_in_dim(
                logits[0], t0 - 1 - t_start, axis=0, keepdims=False)
            return (kv, state), last

        return prefill

    def _build_adopt_fn(self):
        """Fleet handoff commit: scatter the prefill lane's K/V stacks
        (computed on the lane's own executable stream) into this
        engine's pages. One scatter program per handoff bucket — the
        decode replica pays a page write, never the bucket-padded
        prefill forward itself. ``t0``, the true prompt length, keeps
        an fp8 pool's minted page scales off the padded tail; a float
        pool does not read it."""
        ps = self.page_size

        def adopt(cache, ks, vs, page_row, t0):
            kv, state = cache
            return kv_pages.handoff_commit(kv, ks, vs, page_row, ps,
                                           n_valid=t0), state

        return adopt

    def _build_verify_fn(self):
        """Speculative VERIFY: one fixed-shape dispatch scores the
        pending token plus ``K`` draft tokens per slot — ``W = K + 1``
        consecutive positions ``pos[s]..pos[s]+K`` — through the
        model's ``paged_rows`` with the DECODE params (the int8 weight
        read this whole feature exists to amortize happens ONCE for
        all W positions), then accepts the longest draft prefix the
        target agrees with (spec_decode.accept_tokens).

        Rollback is positional only: lanes past the accepted prefix
        wrote K/V at positions ``>= new_pos``, which the flat-position
        mask hides and the next dispatch overwrites in place
        (kv_pages.spec_rewind). Greedy rows are token-identical to the
        decode core's by row independence — the same batched-vs-single
        argument the prefix-prefill identity gate already rests on."""
        m, ps, attn = self.model, self.page_size, self._attn_mode
        W = self._spec.k + 1

        def verify(params, cache, tables, pos, active, tok, drafts,
                   n_draft, keydata, temps):
            kv, state = cache
            toks = jnp.concatenate([tok[:, None], drafts], axis=1)
            # lane 0 is the pending token (always real while the slot
            # is live); lane i >= 1 is draft i, real up to n_draft.
            # Padded/inactive lanes write to the null page.
            real = (jnp.arange(W, dtype=jnp.int32)[None, :]
                    <= n_draft[:, None]) & active[:, None]
            kv, logits = m.paged_rows(params, kv, tables, pos, toks, real,
                                      ps, mode=attn)
            out, n_acc, nkd = accept_tokens(logits, drafts, n_draft,
                                            keydata, temps)
            adv = jnp.where(active, n_acc, 0)
            new_pos = kv_pages.spec_rewind(pos, adv)
            corr = jnp.take_along_axis(
                out, (n_acc - 1)[:, None], axis=1)[:, 0]
            new_tok = jnp.where(active, corr, tok)
            return (kv, state), out, adv, new_pos, new_tok, nkd

        return verify

    # -------------------------------------------- the cache as one tree
    def _cache(self):
        """What every program of the model carries and donates: the
        pool's tree paired with the per-slot state (None where the
        model has none)."""
        return (self.pool.tree(), self._state)

    def _rebind(self, cache) -> None:
        kv, self._state = cache
        self.pool.rebind(kv)

    def _count_experts(self, counts: np.ndarray) -> Dict[str, int]:
        """Add one program's counts ``[..., expert layers, 3 or 4]``
        (assignments to experts held here, distinct ones touched,
        hottest load per layer-step; a model that holds a share of its
        experts adds the assignments routed, held or not) to the
        cumulative forms; -> the same sums as span attributes."""
        c = counts.reshape(-1, counts.shape[-1]).astype(np.int64)
        routed = c[:, 3] if c.shape[1] > 3 else c[:, 0]
        # a layer-step that routed nothing (no live lane) is no work
        c = c[routed > 0]
        got = {"expert_assignments": int(c[:, 0].sum()),
               "experts_touched": int(c[:, 1].sum()),
               "expert_layer_steps": int(len(c)),
               "expert_load_max": int(c[:, 2].sum()),
               "expert_assignments_routed": int(routed.sum())}
        for name, n in got.items():
            self._expert_totals[name] += n
        return got

    # ---------------------------------------------------------- startup
    def start(self) -> "DecodeEngine":
        with self._start_lock:
            if self._thread is not None:
                return self
            if self._dead is not None:
                raise RuntimeError("engine has been shut down")
            # black-box coverage: a crash that kills the process leaves
            # an incident dump with the scheduler's last decisions
            _flight.install_excepthook()
            if self._warm_start:
                self._aot_warmup()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="ServingEngine")
            self._thread.start()
        return self

    def _sds(self, shape, dtype) -> jax.ShapeDtypeStruct:
        return device_sds(shape, dtype, self._device)

    def _abstract(self, tree):
        return jax.tree_util.tree_map(
            lambda a: self._sds(a.shape, a.dtype), tree)

    def _aot_warmup(self) -> None:
        """lower+compile every executable the steady state needs, so
        the first request is served entirely from the warm pool.

        Fleet replicas share one AOT compile: when a same-device
        ``warm_source`` engine was given, its executables are ADOPTED
        (shapes and device identical by construction) and only the
        programs it lacks are compiled here — fleet startup pays the
        warm-pool cost once, not once per replica."""
        src = self._warm_source
        if src is not None and (src._device is self._device
                                or src._device == self._device) \
                and (src.slots, src.page_size, src.max_context,
                     src.quantization, tuple(src.prefill_buckets),
                     src.max_chunk, src._reuse, src.kv_dtype,
                     src._attn_mode,
                     src._spec.k if src._spec else None) \
                == (self.slots, self.page_size, self.max_context,
                    self.quantization, tuple(self.prefill_buckets),
                    self.max_chunk, self._reuse, self.kv_dtype,
                    self._attn_mode,
                    self._spec.k if self._spec else None):
            self._warm.adopt(src._warm)
        S, P, kw = self.slots, self.pages_per_slot, self._kd_width
        i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
        sds, _abs = self._sds, self._abstract
        cd = self.model._cdtype
        with _telemetry.span("serving_aot_warmup",
                             buckets=len(self.prefill_buckets),
                             chunks=len(self._chunks),
                             engine=self.engine_id,
                             adopted=self._warm.adopted):
            cache_abs = _abs(self._cache())
            for k in self._chunks:
                if ("decode", k) in self._warm:
                    continue
                self._warm.compile(
                    ("decode", k), self._decode_jits[k],
                    _abs(self._decode_params), cache_abs,
                    sds((S, P), i32), sds((S,), i32), sds((S,), bool),
                    sds((S,), i32), sds((S, kw), u32), sds((S,), f32))
            for b in self.prefill_buckets:
                if ("prefill", b) in self._warm:
                    continue
                self._warm.compile(
                    ("prefill", b), self._prefill_jit,
                    _abs(self.params), cache_abs, sds((1, b), i32),
                    sds((b // self.page_size,), i32), sds((), i32),
                    sds((), i32))
            spec = self.model.cache_spec()
            for b in self.handoff_buckets:
                if ("adopt", b) in self._warm:
                    continue
                # the lane's K/V stacks, one bucket of the pool's layers
                kv_sds = sds((spec["kv_layers"], 1, spec["kv_heads"], b,
                              spec["head_dim"]), cd)
                self._warm.compile(
                    ("adopt", b), self._adopt_jit,
                    cache_abs, kv_sds, kv_sds,
                    sds((b // self.page_size,), i32), sds((), i32))
            if self._spec is not None:
                K = self._spec.k
                if ("verify", K) not in self._warm:
                    self._warm.compile(
                        ("verify", K), self._verify_jit,
                        _abs(self._decode_params), cache_abs,
                        sds((S, P), i32), sds((S,), i32),
                        sds((S,), bool), sds((S,), i32),
                        sds((S, K), i32), sds((S,), i32),
                        sds((S, kw), u32), sds((S,), f32))
            if self._reuse:
                if ("cow_copy", 0) not in self._warm:
                    self._warm.compile(
                        ("cow_copy", 0), self._copy_jit,
                        _abs(self.pool.tree()), sds((), i32),
                        sds((), i32))
                for b in self.prefill_buckets:
                    if ("prefix_prefill", b) in self._warm:
                        continue
                    self._warm.compile(
                        ("prefix_prefill", b), self._prefix_prefill_jit,
                        _abs(self.params), cache_abs, sds((b,), i32),
                        sds((P,), i32), sds((), i32), sds((), i32))

    # ----------------------------------------------------------- client
    def _validate(self, prompt_ids, max_new_tokens: int) -> np.ndarray:
        """Shape/budget validation shared by submit(),
        submit_prepared() and the fleet front-end (which must reject
        bad requests synchronously, before routing)."""
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]          # [1, t0] convenience
        if prompt.ndim != 1:
            raise ValueError(
                f"submit() takes ONE sequence per call (got shape "
                f"{prompt.shape}); submit each row — the engine "
                "batches across requests")
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = prompt.size + int(max_new_tokens)
        if total > self.max_context:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_context "
                f"({self.max_context})")
        # hard physical bound: every page-table row is a DISTINCT
        # resident page, shared or not — a request whose total
        # footprint exceeds the pool can never be admitted. Pages the
        # request will merely SHARE are accounted at admission instead
        # (_plan_admission allocates only the uncached suffix), so a
        # long-shared-prefix request queues only for the pages it
        # actually consumes.
        if kv_pages.pages_needed(total, self.page_size) \
                > self.pool.capacity:
            raise ValueError(
                f"request needs more KV pages than the pool holds "
                f"({self.pool.capacity}); raise n_pages")
        return prompt

    def retry_after_hint(self) -> float:
        """Seconds a rejected client should wait before retrying:
        median recent request latency scaled by how many queue 'turns'
        are ahead of it — a measured hint, not a constant."""
        lats = [r["latency_ms"] for r in self._recent.copy()
                if r.get("latency_ms")]
        p50_s = (sorted(lats)[len(lats) // 2] / 1e3) if lats else 1.0
        depth = self._queue.qsize() + len(self._waiting)
        return round(min(30.0, max(
            0.05, p50_s * max(1.0, depth / max(self.slots, 1)))), 3)

    def submit(self, prompt_ids, max_new_tokens: int,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               sample_seed: Optional[int] = None,
               session_id: Optional[str] = None,
               spec_decode: Optional[bool] = None,
               _sink=None) -> ServingRequest:
        prompt = self._validate(prompt_ids, max_new_tokens)
        req = self._make_request(prompt, max_new_tokens, temperature,
                                 eos_id, sample_seed, session_id,
                                 _sink, spec_decode=spec_decode)
        self._enqueue(req)
        return req

    def submit_prepared(self, prompt_ids, max_new_tokens: int,
                        temperature: float = 0.0,
                        eos_id: Optional[int] = None,
                        sample_seed: Optional[int] = None,
                        session_id: Optional[str] = None,
                        spec_decode: Optional[bool] = None,
                        handoff=None, lane_span=None,
                        _sink=None) -> ServingRequest:
        """Fleet replica mode: submit a request whose prompt K/V was
        already computed by the disaggregated prefill lane. ``handoff``
        is ``(ks, vs, bucket, last_logits)`` — immutable device arrays
        from the lane's executable plus the host logits of the last
        real position; admission commits them with the AOT adopt
        scatter instead of running prefill. ``lane_span`` carries the
        lane's (t0, t1, bucket) timing for the request's trace."""
        if not self.handoff_buckets:
            raise ValueError(
                "engine built without handoff support (pass "
                "handoff_threshold=)")
        prompt = self._validate(prompt_ids, max_new_tokens)
        req = self._make_request(prompt, max_new_tokens, temperature,
                                 eos_id, sample_seed, session_id,
                                 _sink, spec_decode=spec_decode)
        req._handoff = handoff
        if req._trace is not None and lane_span is not None:
            t0, t1, bucket = lane_span
            req._trace.event("lane_prefill", t0, t1, bucket=bucket)
        self._enqueue(req)
        return req

    def _make_request(self, prompt: np.ndarray, max_new_tokens: int,
                      temperature: float, eos_id, sample_seed,
                      session_id, sink,
                      spec_decode: Optional[bool] = None) \
            -> ServingRequest:
        if self._dead is not None or self._stop.is_set():
            raise RuntimeError("engine has been shut down")
        rid = next(self._req_counter)
        keydata = (np.asarray(jax.random.key_data(
            jax.random.key(sample_seed))) if sample_seed is not None
            else self._sample_keydata(next(self._sample_counter)))
        req = ServingRequest(rid, prompt, max_new_tokens, temperature,
                             eos_id, keydata, session_id=session_id)
        req.engine_id = self.engine_id
        req._engine = self
        req.spec_enabled = spec_decode
        if sink is not None:
            # attach BEFORE the queue put: the scheduler may admit and
            # emit tokens the instant the request is visible, and the
            # sink must already know its inner request by then
            req._sink = sink
            sink._attach(req, self)
        req._trace = _tracing.new_trace(
            "serving_request", request_id=rid,
            prompt_tokens=int(prompt.size),
            max_new_tokens=int(max_new_tokens),
            engine=self.engine_id)
        return req

    #: sampling keys made in one device call
    KEY_BLOCK = 1024

    def _sample_keydata(self, n: int) -> np.ndarray:
        """The key data of this engine's ``n``-th request,
        ``fold_in(key(seed), n)``, from the host's copy of its block of
        ordinals. The device is asked once a block (the first at
        construction); the block before stays, for a caller that drew
        its ordinal just before the boundary."""
        first = n - n % self.KEY_BLOCK
        with self._key_lock:
            block = self._key_blocks.get(first)
            if block is None:
                block = np.asarray(self._key_block_fn(jnp.arange(
                    first, first + self.KEY_BLOCK, dtype=jnp.uint32)))
                self._key_blocks = {
                    f: b for f, b in self._key_blocks.items()
                    if f == first - self.KEY_BLOCK}
                self._key_blocks[first] = block
        return block[n - first].copy()

    def _enqueue(self, req: ServingRequest) -> None:
        _flight.record("serving_submit", request_id=req.request_id,
                       engine=self.engine_id,
                       prompt_tokens=int(req.prompt.size),
                       max_new_tokens=int(req.max_new_tokens))
        if self._thread is None:
            self.start()
        # hard capacity bound over queued + head-of-line-waiting (the
        # Queue alone drains into _waiting, so its maxsize is not the
        # real admission depth)
        if self._queue.qsize() + len(self._waiting) >= self.max_queue:
            self._reject(req)
        try:
            self._queue.put_nowait(req)
        except _queue.Full:
            self._reject(req)
        self.n_requests += 1
        if _telemetry.enabled():
            reg = _telemetry.MetricsRegistry.get_default()
            reg.counter(_telemetry.SERVING_REQUESTS,
                        "generation requests submitted").inc(
                engine=self.engine_id)
        # close the submit/shutdown race: if shutdown's final queue
        # drain happened before our put, _stop was set before it — so
        # seeing _stop clear here proves shutdown will drain AFTER us
        if self._stop.is_set():
            err = self._dead or RuntimeError(
                "engine has been shut down")
            while True:
                try:
                    r = self._queue.get_nowait()
                except _queue.Empty:
                    break
                r._finish("error", err)
        self._gauge_queue_depth()

    def _reject(self, req: ServingRequest) -> None:
        """Structured hard capacity reject — with a measured
        retry-after, so front-ends answer 429 instead of stalling the
        client thread on a blocking put."""
        hint = self.retry_after_hint()
        if _telemetry.enabled():
            _telemetry.MetricsRegistry.get_default().counter(
                _telemetry.SERVING_REJECTS,
                "submissions rejected because the admission "
                "queue was full (429 at the HTTP front-end)").inc(
                engine=self.engine_id)
        _flight.record("serving_reject",
                       request_id=req.request_id,
                       engine=self.engine_id,
                       retry_after_s=hint)
        if req._trace is not None:
            _tracing.finish_trace(req._trace, reason="rejected")
        raise CapacityRejected(
            f"admission queue full ({self.max_queue} requests "
            f"waiting); retry after ~{hint}s", retry_after_s=hint)

    def generate(self, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None) -> np.ndarray:
        """Blocking single-request convenience over submit()."""
        return self.submit(prompt_ids, max_new_tokens, temperature,
                           eos_id).result(timeout)

    def prefix_stats(self) -> Dict[str, Any]:
        """Cross-request KV-reuse stats: prefix-cache index counters,
        sharing gauges, sticky-session table (the /v1/serving/
        prefix_cache endpoint body)."""
        out: Dict[str, Any] = {
            "enabled": self._prefix is not None,
            "sessions_enabled": self._sessions is not None,
            "page_size": self.page_size,
        }
        if self._prefix is not None:
            out.update(self._prefix.stats())
            out["shared_pages"] = self.pool.shared_pages()
        if self._sessions is not None:
            out["sessions"] = self._sessions.stats()
        return out

    def release_session(self, session_id: str) -> bool:
        """Explicitly free a sticky session's pinned pages (client-
        callable; thread-safe against the scheduler). True if the id
        was pinned."""
        if self._sessions is None:
            return False
        return self._sessions.release(session_id, self.pool)

    def _pages_held(self, positions) -> int:
        """Pages the given counts of positions occupy, summed: what the
        paged kernel's calls visit (``ctx_pages`` beside ``ctx_tokens``)."""
        return int((-(-np.asarray(positions) // self.page_size)).sum())

    def _state_bytes(self) -> int:
        return sum(int(a.nbytes)
                   for a in jax.tree_util.tree_leaves(self._state))

    def _window_store(self) -> Dict[str, int]:
        """The cache's second kind beside the pool's numbers: the bytes
        the window layers' per-slot rings take, and what of them the
        most slots ever live at once used. Nothing where the model has
        no window layer."""
        if self._window is None:
            return {}
        total = self._state_bytes()
        return {"window_bytes": total,
                "window_high_water_bytes":
                    total // self.slots * self._slots_high_water}

    def stats(self) -> Dict[str, Any]:
        return {
            "engine_id": self.engine_id,
            "slots": self.slots,
            "page_size": self.page_size,
            "max_context": self.max_context,
            "quantization": self.quantization,
            "kv_dtype": self.pool.dtype_label,
            "attn_mode": self._attn_mode,
            "prefill_buckets": list(self.prefill_buckets),
            "handoff_buckets": list(self.handoff_buckets),
            "max_chunk": self.max_chunk,
            "requests": self.n_requests,
            "completed": self.n_completed,
            "decode_steps": self.n_steps,
            "dispatches": self.n_dispatches,
            "attended_tokens": self.n_attended_tokens,
            "attended_pages": self.n_attended_pages,
            "prefill_tokens": self.n_prefill_tokens,
            "prefill_bucket_tokens": self.n_prefill_bucket_tokens,
            "tokens": self.n_tokens,
            **self._expert_totals,
            "state_bytes": self._state_bytes(),
            "weight_bytes": self._weight_bytes,
            "active_slots": int(self._active.sum()),
            "queued": self._queue.qsize() + len(self._waiting),
            "avg_occupancy": (self._occupancy_sum / self.n_steps
                              if self.n_steps else 0.0),
            "kv_pages": {"capacity": self.pool.capacity,
                         "allocated": self.pool.allocated,
                         "high_water": self.pool.high_water,
                         "shared": self.pool.shared_pages(),
                         "page_bytes": self.pool.bytes_per_page(),
                         "store_bytes": self.pool.store_bytes(),
                         **self._window_store()},
            "warm_pool": {"hits": self._warm.hits,
                          "misses": self._warm.misses,
                          "adopted": self._warm.adopted},
            **({"spec": {
                "k": self._spec.k,
                "verify_dispatches": self.n_verify_dispatches,
                "proposed": self.n_spec_proposed,
                "accepted": self.n_spec_accepted,
                "acceptance": (self.n_spec_accepted
                               / self.n_spec_proposed
                               if self.n_spec_proposed else 0.0),
                # tokens emitted per weight read per decode lane;
                # the plain chunked burst is 1.0 by construction
                "tokens_per_dispatch": (
                    (self.n_spec_accepted + self.n_verify_lane_steps)
                    / self.n_verify_lane_steps
                    if self.n_verify_lane_steps else 0.0),
            }} if self._spec is not None else {}),
            **({"prefix_cache": self.prefix_stats()}
               if self._reuse else {}),
            # newest-first: client logs join on request_id, per-request
            # timelines at /v1/serving/requests/<id> (tracing on).
            # .copy() is one C call (atomic under the GIL) — iterating
            # the live deque would race the scheduler thread's appends
            "recent_requests": list(reversed(self._recent.copy())),
        }

    # ------------------------------------------------------------ abort
    def queue_depth(self) -> int:
        """Requests admitted but not yet in a slot (queued + head-of-
        line waiting) — the engine-level rebalancing signal."""
        return self._queue.qsize() + len(self._waiting)

    def abort(self, request: ServingRequest) -> bool:
        """Cancel ``request`` from any thread (see
        ``ServingRequest.cancel``). The actual teardown — slot freed,
        pages drained to rc0, trace closed ``finish{reason=cancelled}``
        — happens on the scheduler thread at its next pass, so no lock
        is ever taken against a decode dispatch. Returns False when the
        request already finished (or belongs to a dead engine, whose
        teardown already failed it)."""
        if request.done:
            return False
        with self._abort_lock:
            self._aborts.add(request)
        if self._dead is not None:
            # scheduler gone: _fail_pending already (or will have)
            # finished everything — nothing will drain the abort set
            with self._abort_lock:
                self._aborts.discard(request)
            return False
        return True

    def _process_aborts(self) -> None:
        """Scheduler-thread half of ``abort``: evict cancelled slot
        residents, drop cancelled queued/waiting requests."""
        with self._abort_lock:
            if not self._aborts:
                return
            aborts, self._aborts = self._aborts, set()
        # queued requests must be visible in _waiting to be dropped
        while True:
            try:
                self._waiting.append(self._queue.get_nowait())
            except _queue.Empty:
                break
        for req in aborts:
            if req.done:
                continue
            for s in range(self.slots):
                if self._slot_req[s] is req:
                    self._evict(s, "cancelled")
                    break
            else:
                if req in self._waiting:
                    self._waiting.remove(req)
                    _flight.record("serving_cancel",
                                   request_id=req.request_id,
                                   engine=self.engine_id, queued=True)
                    if _telemetry.enabled():
                        _telemetry.MetricsRegistry.get_default() \
                            .histogram(
                                _telemetry.SERVING_REQUEST_LATENCY,
                                "submit -> completion per request"
                            ).observe(
                                time.perf_counter() - req._t_submit,
                                reason="cancelled",
                                engine=self.engine_id)
                    req._finish("cancelled")
        self._gauge_queue_depth()

    # ---------------------------------------------- drain / chaos hooks
    @property
    def idle(self) -> bool:
        """No request in a slot, none queued — drained. Polled by the
        fleet's drain_replica before it shuts a replica down for an
        elastic resize."""
        return (not self._active.any() and self._queue.empty()
                and not self._waiting)

    def drain(self, timeout: Optional[float] = None,
              poll_s: float = 0.01) -> bool:
        """Wait for every queued + in-flight request to finish (the
        caller must have stopped submitting). True when drained; False
        on timeout or engine death."""
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        while not self.idle:
            if self._dead is not None or self._stop.is_set():
                return False
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(poll_s)
        return True

    def _die(self, error: BaseException) -> None:
        """Chaos hook (fleet kill-a-replica drill): make the scheduler
        raise ``error`` at its next iteration, driving the REAL death
        path — slot evictions, flight-recorder incident, fleet
        re-routing."""
        self._poison = error

    def shutdown(self, timeout: float = 30.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
        if self._dead is None:
            self._dead = RuntimeError("engine has been shut down")
        # the scheduler thread is gone (joined above), so running its
        # abort pass here is single-threaded-safe: a cancel that raced
        # shutdown must finish as reason=cancelled with its partial
        # tokens, not as the opaque shutdown error below
        try:
            self._process_aborts()
        except Exception:
            log.exception("abort pass during shutdown failed")
        # safe to fail whatever remains
        self._fail_pending(self._dead)
        # drain contract: with every slot failed, releasing the
        # session pins and the cache's own references brings every
        # refcount to 0 — the pool leaves fully free
        if self._sessions is not None:
            self._sessions.clear(self.pool)
        if self._prefix is not None:
            self._prefix.clear(self.pool)
        # stale-series expiry: this engine's gauges (queue depth, slot
        # occupancy, KV utilization, shared/pinned pages) would stay
        # frozen at their last value forever — drop them so
        # serving_snapshot(), /metrics, and SLO rules never evaluate a
        # ghost engine. Counters/histograms stay: cumulative history
        # keeps fleet aggregates correct. LAST in shutdown — the abort
        # pass above still updates the queue-depth gauge.
        _telemetry.retire_engine_series(self.engine_id)

    def __enter__(self) -> "DecodeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -------------------------------------------------------- scheduler
    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                if self._poison is not None:   # chaos/drill hook
                    raise self._poison
                hang = self._hang_s
                if hang:                       # chaos.hang_replica
                    self._hang_s = 0.0
                    _flight.record("chaos_hang", engine=self.engine_id,
                                   seconds=hang)
                    time.sleep(hang)
                self._process_aborts()
                if self._ahead is not None:
                    self._await_join()
                self._admit_waiting()
                if not self._active.any():
                    try:
                        self._waiting.append(
                            self._queue.get(timeout=0.02))
                    except _queue.Empty:
                        pass
                    continue
                self._decode_step()
        except BaseException as e:       # engine died: strand no one
            self._dead = e
            _flight.incident("serving_engine_died",
                             engine=self.engine_id,
                             error=repr(e)[:400])
            self._fail_pending(e)
        finally:
            if self._dead is None:
                self._dead = RuntimeError("engine has been shut down")

    def _fail_pending(self, err: BaseException) -> None:
        self._ahead = None
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is not None:
                self._evict(s, "error", err)
        pend = list(self._waiting)
        self._waiting.clear()
        while True:
            try:
                pend.append(self._queue.get_nowait())
            except _queue.Empty:
                break
        for req in pend:
            req._finish("error", RuntimeError(
                f"engine stopped before request {req.request_id} "
                f"ran: {err}"))

    def _admit_waiting(self) -> None:
        while True:
            try:
                self._waiting.append(self._queue.get_nowait())
            except _queue.Empty:
                break
        if self._sessions is not None:
            self._sessions.expire(self.pool)     # TTL sweep
        while self._waiting and not self._active.all():
            req = self._waiting[0]
            plan = self._plan_admission(req)
            if plan is None:
                break        # head-of-line waits for evictions
            self._waiting.popleft()
            slot = int(np.flatnonzero(~self._active)[0])
            try:
                with _telemetry.span(
                        "engine.admit", parent=req.caused_by,
                        request=req.request_id, engine=self.engine_id,
                        slot=slot, reuse=plan["kind"],
                        pages=len(plan["rows"])):
                    self._admit(req, plan, slot)
            except BaseException as e:
                self._release_plan(plan)
                req._finish("error", e)
        self._gauge_queue_depth()

    # ----------------------------------------------- admission planning
    def _shared_pages_hint(self, prompt: np.ndarray,
                           session_id: Optional[str]) -> int:
        """Pages this request would REUSE (pinned session pages,
        cached full-prefix pages) rather than newly allocate — the
        read-only budget hint admission re-resolves authoritatively.
        Exposed for capacity planning ("would this prompt fit right
        now?"): a request consumes only ``pages_needed(total) - hint``
        free pages."""
        if not self._reuse:
            return 0
        if self._sessions is not None and session_id is not None \
                and self._sessions.match_pos(session_id,
                                             prompt) is not None:
            return self._sessions.pages_hint(session_id)
        if self._prefix is not None:
            full = self._prefix.hit_tokens_hint(prompt) // self.page_size
            return min(full, (int(prompt.size) - 1) // self.page_size)
        return 0

    def _alloc_with_evict(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` fresh pages, evicting cold prefix-cache
        entries (LRU leaves with no live readers) and, as a last
        resort, the oldest pinned session — cached/pinned pages must
        never starve a live request. None when the pool is genuinely
        full of live readers (caller keeps the request queued)."""
        if n <= 0:
            return []
        while True:
            pages = self.pool.alloc(n)
            if pages is not None:
                return pages
            freed = 0
            if self._prefix is not None:
                freed += self._prefix.evict(
                    self.pool, n - self.pool.free_pages)
            if self.pool.free_pages < n and self._sessions is not None:
                freed += self._sessions.evict_oldest(self.pool)
                # a session's history pages may double as cache
                # entries; with the session's reference gone another
                # cache pass can reclaim them
                if self._prefix is not None \
                        and self.pool.free_pages < n:
                    freed += self._prefix.evict(
                        self.pool, n - self.pool.free_pages)
            if freed == 0:
                return None

    def _plan_admission(self, req: ServingRequest) \
            -> Optional[Dict[str, Any]]:
        """Resolve where this request's pages come from:

        - ``session``: its ``session_id`` pins a history the prompt
          extends — map the pinned pages, prefill only the new tokens;
        - ``prefix``: the prefix cache holds full (and possibly one
          divergent, copy-on-write) pages of the prompt — share them,
          prefill the suffix;
        - ``cold``: allocate everything, full prefill (the pre-reuse
          path, byte-for-byte).

        Returns None when pages cannot be found even after eviction
        (request stays head-of-line). Every page referenced by the
        returned plan holds a reference for this request;
        ``_release_plan`` undoes that on admission failure."""
        t0 = int(req.prompt.size)
        ps = self.page_size
        total_pages = kv_pages.pages_needed(
            t0 + req.max_new_tokens, ps)
        if req._handoff is not None:
            # disaggregated prefill: the lane already computed the
            # prompt's K/V — allocate the full footprint and commit via
            # the adopt scatter in _admit. Bypasses session/prefix
            # resolution by construction (the router never lanes a
            # session-affine resume).
            pages = self._alloc_with_evict(total_pages)
            if pages is None:
                return None
            return {"kind": "handoff", "rows": pages, "copies": [],
                    "drop_after_copy": [], "t_start": 0,
                    "session": None}
        t_l0 = time.perf_counter()
        plan: Optional[Dict[str, Any]] = None
        if self._sessions is not None and req.session_id is not None:
            plan = self._plan_session(req, t0, total_pages)
            if plan == "retry":
                return None
        if plan is None and self._prefix is not None:
            hit = self._prefix.lookup_acquire(req.prompt, self.pool)
            new = self._alloc_with_evict(total_pages - len(hit.pages))
            if new is None:
                hit.release(self.pool)
                return None
            self._prefix.record(hit)
            copies, drop = [], []
            if hit.cow_src is not None:
                # mid-page divergence: private copy of exactly that
                # page; our acquire-reference on the source drops once
                # the copy is dispatched
                copies = [(hit.cow_src, new[0])]
                drop = [hit.cow_src]
            plan = {"kind": "prefix" if hit.tokens else "cold",
                    "rows": hit.pages + new,
                    "copies": copies, "drop_after_copy": drop,
                    "t_start": hit.tokens, "session": None}
        if plan is None:
            pages = self._alloc_with_evict(total_pages)
            if pages is None:
                return None
            plan = {"kind": "cold", "rows": pages, "copies": [],
                    "drop_after_copy": [], "t_start": 0,
                    "session": None}
        if req._trace is not None and self._reuse:
            req._trace.event("prefix_lookup", t_l0,
                             hit_tokens=plan["t_start"],
                             kind=plan["kind"])
        return plan

    def _plan_session(self, req: ServingRequest, t0: int,
                      total_pages: int):
        """Sticky-session leg of the planner: None = no resumable
        session (fall through to the prefix cache, releasing a
        contradicted pin), "retry" = resumable but pages are short
        (stay head-of-line), else the session plan."""
        sid = req.session_id
        pos = self._sessions.match_pos(sid, req.prompt)
        if pos is None:
            if self._sessions.pages_hint(sid):
                # pinned history contradicts the prompt: the
                # conversation restarted — release the stale pin (its
                # full pages stay reachable through the prefix cache)
                self._sessions.release(sid, self.pool)
            return None
        peeked = self._sessions.peek(sid)
        if peeked is None:         # raced a TTL/capacity eviction
            return None
        peek_pages, peek_pos, _turns = peeked
        ps = self.page_size
        t_start = min(peek_pos, t0 - 1)
        extra_n = total_pages - len(peek_pages)
        idx = t_start // ps
        cow_src = (peek_pages[idx] if idx < len(peek_pages)
                   and self.pool.refcount(peek_pages[idx]) > 1
                   else None)
        # size the allocation BEFORE take(): a head-of-line request
        # stalled on pages must not churn take/pin (and their
        # counters/flight events) every scheduler pass
        new = self._alloc_with_evict(extra_n
                                     + (1 if cow_src is not None else 0))
        if new is None:
            return "retry"         # session stays pinned untouched
        sess = self._sessions.take(sid)
        if sess is None:           # raced an eviction/release
            if new:
                self.pool.free(new)
            return None
        copies, drop = [], []
        rows = list(sess.pages)
        if cow_src is not None:
            # the resume point sits mid-page in a page other readers
            # still map (e.g. it is also a cached full prompt page):
            # write into a private copy instead
            rows[idx] = new[0]
            copies = [(cow_src, new[0])]
            drop = [cow_src]
            new = new[1:]
        rows += new
        # a resume is a reuse hit like any other: the hit-rate and
        # hit-token metrics describe ALL cross-request KV reuse
        if self._prefix is not None:
            self._prefix.record_session(t_start)
        if _telemetry.enabled():
            reg = _telemetry.MetricsRegistry.get_default()
            reg.counter(
                _telemetry.SERVING_PREFIX_HITS,
                "prefix-cache lookups that reused >= 1 committed "
                "page").inc(kind="session", engine=self.engine_id)
            reg.counter(
                _telemetry.SERVING_PREFIX_HIT_TOKENS,
                "prompt tokens served from cached KV pages instead "
                "of prefill compute").inc(t_start,
                                          engine=self.engine_id)
        req._session_turns = sess.turns + 1
        _flight.record("session_resume", session_id=str(sid),
                       engine=self.engine_id,
                       request_id=req.request_id, pos=int(sess.pos),
                       new_tokens=t0 - t_start, turns=sess.turns)
        return {"kind": "session", "rows": rows, "copies": copies,
                "drop_after_copy": drop, "t_start": t_start,
                "session": sess}

    def _release_plan(self, plan: Dict[str, Any]) -> None:
        self.pool.free(plan["rows"] + plan["drop_after_copy"])

    # ---------------------------------------------------------- admit
    def _admit(self, req: ServingRequest, plan: Dict[str, Any],
               s: int) -> None:
        t0 = int(req.prompt.size)
        ps = self.page_size
        rows: List[int] = plan["rows"]
        t_start: int = plan["t_start"]
        for src, dst in plan["copies"]:
            # copy-on-write BEFORE any write can land in the shared
            # page: concurrent readers of src never see our tokens
            self.pool.rebind(self._warm.run(
                ("cow_copy", 0), self._copy_fallback, self.pool.tree(),
                jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32)))
        if plan["drop_after_copy"]:
            self.pool.free(plan["drop_after_copy"])
            plan["drop_after_copy"] = []
        handoff = plan["kind"] == "handoff"
        sl = t0 - t_start            # prompt tokens this prefill computes
        if handoff:
            bucket = req._handoff[2]
        else:
            bucket = next((b for b in self.prefill_buckets if b >= sl),
                          kv_pages.pages_needed(sl, ps) * ps)
        t_pre = time.perf_counter()
        wait_id = _telemetry.record_span(
            "request.queue_wait", req._t_submit, t_pre,
            request=req.request_id, engine=self.engine_id,
            prompt_tokens=t0)
        if not handoff:
            self.n_prefill_tokens += sl
            self.n_prefill_bucket_tokens += bucket
        # from the dispatch to the host's read of the last logits: the
        # engine waits for the device here
        with _telemetry.span(
                "engine.prefill",
                metric=(_telemetry.SERVING_HANDOFF_SECONDS if handoff
                        else _telemetry.SERVING_PREFILL_SECONDS),
                request=req.request_id, bucket=bucket,
                engine=self.engine_id) as sp:
            # set(), not entry attributes: those label the histogram
            sp.set(prompt_tokens=t0, hit_tokens=t_start)
            if handoff:
                # fleet handoff: the prefill lane computed ks/vs/logits
                # on its own executable stream; commit is one page
                # scatter
                ks, vs, _, last = req._handoff
                req._handoff = None
                if self._device is not None:
                    # cross-device fleet: the lane computed on the
                    # default device; land the stacks on this replica's
                    ks = jax.device_put(ks, self._device)
                    vs = jax.device_put(vs, self._device)
                page_row = np.zeros((bucket // ps,), np.int32)
                n_real = min(len(rows), bucket // ps)
                page_row[:n_real] = rows[:n_real]
                kvt = self._warm.run(
                    ("adopt", bucket), self._adopt_fallback,
                    self._cache(), ks, vs, jnp.asarray(page_row),
                    jnp.asarray(t0, jnp.int32))
            elif t_start == 0:
                prompt = np.zeros((1, bucket), np.int32)
                prompt[0, :t0] = req.prompt
                page_row = np.zeros((bucket // ps,), np.int32)
                n_real = min(len(rows), bucket // ps)
                page_row[:n_real] = rows[:n_real]
                # the prefill is told its slot (whose state row it
                # writes, where there is a state) and returns the
                # model's counts
                kvt, last, counts = self._warm.run(
                    ("prefill", bucket), self._prefill_fallback,
                    self.params, self._cache(), jnp.asarray(prompt),
                    jnp.asarray(page_row), jnp.asarray(t0, jnp.int32),
                    jnp.asarray(s, jnp.int32))
                if counts is not None:
                    sp.set(**self._count_experts(np.asarray(counts)))
            else:
                # warm path: prefill ONLY the uncached suffix, mid-page
                # starts included — attention reads the shared prefix
                # pages through the full table: one call of the paged
                # kernel a layer, whose queries share one read of the
                # t0 positions the slot then holds
                self.n_attended_tokens += t0
                pages = self._pages_held(t0)
                self.n_attended_pages += pages
                sp.set(ctx_tokens=t0, ctx_pages=pages)
                suffix = np.zeros((bucket,), np.int32)
                suffix[:sl] = req.prompt[t_start:]
                table = np.zeros((self.pages_per_slot,), np.int32)
                table[:len(rows)] = rows
                kvt, last = self._warm.run(
                    ("prefix_prefill", bucket),
                    self._prefix_prefill_fallback, self.params,
                    self._cache(), jnp.asarray(suffix),
                    jnp.asarray(table), jnp.asarray(t_start, jnp.int32),
                    jnp.asarray(t0, jnp.int32))
            logits = np.asarray(last)
        self._rebind(kvt)
        first = self._sample_first(req, logits)
        req.cache_hit_tokens = t_start
        if req._trace is not None:
            req._trace.event("queue_wait", req._t_submit, t_pre,
                             span=wait_id)
            req._trace.event("prefill", sp.t0, sp.t1, span=sp.id,
                             bucket=bucket, slot=s, hit_tokens=t_start,
                             handoff=handoff)
        _flight.record("serving_admit", request_id=req.request_id,
                       engine=self.engine_id,
                       slot=s, bucket=bucket, pages=len(rows),
                       reuse=plan["kind"], hit_tokens=t_start,
                       queue_ms=round((t_pre - req._t_submit) * 1e3, 3))
        self._slot_req[s] = req
        self._slot_pages[s] = rows
        self._slot_emitted[s] = 0
        self._tables[s] = 0
        self._tables[s, :len(rows)] = rows
        self._pos[s] = t0
        self._tok[s] = first
        self._temps[s] = req.temperature
        self._keydata[s] = req._keydata
        self._active[s] = True
        self._slots_high_water = max(self._slots_high_water,
                                     int(self._active.sum()))
        self._dev_static = None      # roster changed: re-upload
        self._roster_gen += 1
        if self._prefix is not None:
            # index this prompt's full pages (freshly prefilled ones
            # AND, for a session resume, committed history pages) for
            # the next shared-prefix request
            self._prefix.insert(req.prompt, rows, self.pool)
        self._emit(s, first)
        self.last_progress = time.monotonic()
        if _telemetry.enabled():
            _telemetry.MetricsRegistry.get_default().counter(
                _telemetry.SERVING_TOKENS,
                "tokens generated across all requests").inc(
                engine=self.engine_id)

    def _sample_first(self, req: ServingRequest,
                      logits: np.ndarray) -> int:
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        key = jax.random.wrap_key_data(jnp.asarray(req._keydata))
        key, sub = jax.random.split(key)
        req._keydata = np.asarray(jax.random.key_data(key))
        return int(jax.random.categorical(
            sub, jnp.asarray(logits) / req.temperature))

    def _dev_slot_state(self):
        """tables/active/temps change only on join/evict: upload once
        per roster change, not once per dispatch."""
        if self._dev_static is None:
            # copies: a burst may be in flight when the next join or
            # eviction writes these arrays in place
            self._dev_static = (jnp.asarray(self._tables.copy()),
                                jnp.asarray(self._active.copy()),
                                jnp.asarray(self._temps.copy()))
        return self._dev_static

    #: dispatches chained device-to-device per burst before tokens are
    #: fetched and emitted (bounds streaming latency; joins/evictions
    #: can only happen at roster boundaries anyway)
    MAX_BURST_DISPATCHES = 4

    def _spec_burst(self) -> bool:
        """One speculative draft -> verify burst: the host drafts up to
        ``k`` tokens per eligible slot (from the slot's own emitted
        history — the draft must see the newest accepted tokens, which
        is why a verify burst is exactly one dispatch), ONE warm
        verify call scores every slot's ``k+1`` positions through the
        decode weights, and the accepted prefix + correction is
        emitted. Slots whose request opted out (``spec_decode=False``)
        or that have a single token of budget left ride along with
        ``n_draft = 0`` — their lane is op-for-op a plain decode step.

        Returns False when NO active slot is spec-eligible this pass,
        and the caller falls through to the plain chunked burst — a
        fully opted-out roster never pays the wider program."""
        active_idx = np.flatnonzero(self._active)
        K = self._spec.k
        S = self.slots
        drafts = np.zeros((S, K), np.int32)
        n_draft = np.zeros((S,), np.int32)
        for s in active_idx:
            req = self._slot_req[int(s)]
            if req.spec_enabled is False:
                continue
            # never draft past the request budget: the correction
            # token always emits, so at most rem - 1 drafts can land
            rem = req.max_new_tokens - int(self._slot_emitted[s])
            nd = min(K, rem - 1)
            if nd <= 0:
                continue
            history = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])
            prop = np.asarray(self._spec_draft.propose(history, nd),
                              np.int32).ravel()[:nd]
            drafts[s, :prop.size] = prop
            n_draft[s] = prop.size
        if not n_draft.any():
            return False
        lanes = int(len(active_idx))
        occupancy = float(lanes) / S
        # a lane scores its n_draft + 1 positions in the one dispatch:
        # one call of the paged kernel a layer, whose queries share one
        # read of the pos + n_draft + 1 positions the lane then holds
        held = self._pos[active_idx].astype(np.int64) \
            + n_draft[active_idx] + 1
        ctx, pages = int(held.sum()), self._pages_held(held)
        self.n_attended_tokens += ctx
        self.n_attended_pages += pages
        with _telemetry.span(
                "engine.burst", metric=_telemetry.SERVING_VERIFY_SECONDS,
                engine=self.engine_id) as burst:
            tables, active, temps = self._dev_slot_state()
            with _telemetry.span("engine.dispatch", k=1, live=lanes,
                                 ctx_tokens=ctx, ctx_pages=pages,
                                 verify=K):
                (kvt, out, adv, pos, tok, kd) = self._warm.run(
                    ("verify", K), self._verify_fallback,
                    self._decode_params, self._cache(), tables,
                    jnp.asarray(self._pos), active,
                    jnp.asarray(self._tok), jnp.asarray(drafts),
                    jnp.asarray(n_draft), jnp.asarray(self._keydata),
                    temps)
            self._rebind(kvt)
            self.n_dispatches += 1
            self.n_verify_dispatches += 1
            # ONE host sync for the whole burst (np.array copies: _admit
            # writes joined slots' state into these buffers in place)
            with _telemetry.span("engine.sync", steps=1,
                                 dispatches=1) as sync:
                out = np.asarray(out)
                nacc = np.array(adv)
                self._pos = np.array(pos)
                self._tok = np.array(tok)
                self._keydata = np.array(kd)
            self.n_steps += 1
            self._occupancy_sum += occupancy
            self.n_verify_lane_steps += lanes
            proposed = int(n_draft[active_idx].sum())
            accepted = int((nacc[active_idx] - 1).sum())
            self.n_spec_proposed += proposed
            self.n_spec_accepted += accepted
            _flight.record("serving_verify", engine=self.engine_id,
                           k=K, lanes=lanes, proposed=proposed,
                           accepted=accepted,
                           occupancy=round(occupancy, 4))
            if _tracing.enabled():
                for s in active_idx:
                    r = self._slot_req[int(s)]
                    if r is not None and r._trace is not None:
                        r._trace.event("verify", burst.t0, sync.t1,
                                       span=burst.id, slot=int(s),
                                       proposed=int(n_draft[s]),
                                       accepted=int(nacc[s] - 1))
            if _telemetry.enabled():
                reg = _telemetry.MetricsRegistry.get_default()
                reg.gauge(_telemetry.SERVING_SLOT_OCCUPANCY,
                          "fraction of decode slots occupied by live "
                          "requests this step").set(
                    occupancy, engine=self.engine_id)
                reg.counter(_telemetry.SERVING_DECODE_STEPS,
                            "fixed-shape decode steps executed").inc(
                    engine=self.engine_id)
                if proposed:
                    reg.counter(
                        _telemetry.SERVING_SPEC_PROPOSED,
                        "draft tokens proposed to the verify "
                        "program").inc(proposed, engine=self.engine_id)
                if accepted:
                    reg.counter(
                        _telemetry.SERVING_SPEC_ACCEPTED,
                        "draft tokens the target model accepted").inc(
                        accepted, engine=self.engine_id)
                if self.n_spec_proposed:
                    reg.gauge(
                        _telemetry.SERVING_SPEC_ACCEPTANCE,
                        "cumulative accepted / proposed draft "
                        "tokens").set(
                        self.n_spec_accepted / self.n_spec_proposed,
                        engine=self.engine_id)
                if self.n_verify_lane_steps:
                    reg.gauge(
                        _telemetry.SERVING_TOKENS_PER_DISPATCH,
                        "tokens emitted per weight read per decode lane "
                        "(plain decode = 1.0)").set(
                        (self.n_spec_accepted + self.n_verify_lane_steps)
                        / self.n_verify_lane_steps,
                        engine=self.engine_id)
            emitted0 = self.n_tokens
            with _telemetry.span("engine.emit") as emit:
                for s in active_idx:
                    req = self._slot_req[int(s)]
                    if req is not None:
                        req.spec_proposed += int(n_draft[s])
                        req.spec_accepted += int(nacc[s] - 1)
                    for i in range(int(nacc[s])):
                        if not self._active[s]:
                            break      # finished on eos mid-acceptance
                        self._emit(int(s), int(out[s, i]))
                emit.set(tokens=self.n_tokens - emitted0)
        self.last_progress = time.monotonic()
        if _telemetry.enabled() and self.n_tokens > emitted0:
            _telemetry.MetricsRegistry.get_default().counter(
                _telemetry.SERVING_TOKENS,
                "tokens generated across all requests").inc(
                self.n_tokens - emitted0, engine=self.engine_id)
        return True

    def _open_burst(self, read: Optional["_Burst"] = None,
                    on_device: bool = False) -> "_Burst":
        """The roster, budget and slot state of the next decode burst.
        ``read`` is the burst before it, whose tokens are not handed
        out yet: the slots it brings to their budget's end are left
        out as ``_evict`` will leave them (an all-null table row, not
        active), so the burst can be on the device while the tokens go
        out. The running pos / tok / keys come from the host's copies
        or, ``on_device``, straight from ``read``'s own outputs, before
        the host has read them (``read`` decoded for every live slot
        then). Uploads are copies: ``_admit`` and ``_evict`` write
        these arrays in place, and a burst may be in flight by then."""
        emitted = self._slot_emitted
        if read is not None:
            emitted = emitted.copy()
            emitted[read.idx] += read.steps
        idx = np.flatnonzero(self._active)
        left = np.asarray([self._slot_req[s].max_new_tokens
                           for s in idx], np.int64) - emitted[idx]
        going = left > 0
        ending, idx = idx[~going], idx[going]
        b = _Burst()
        b.idx = idx
        b.reqs = [self._slot_req[s] for s in idx]
        b.rem = int(left[going].min()) if len(idx) else 0
        b.has_eos = any(r.eos_id is not None for r in b.reqs)
        b.after_end = bool(len(ending))
        if len(ending):
            tables, active, temps = (self._tables.copy(),
                                     self._active.copy(),
                                     self._temps.copy())
            tables[ending], active[ending], temps[ending] = 0, False, 0.0
            b.roster = (jnp.asarray(tables), jnp.asarray(active),
                        jnp.asarray(temps))
        else:
            b.roster = self._dev_slot_state()
        if on_device:
            b.pos_live = (read.pos_live + read.steps)[going]
            b.pos, b.tok, b.kd = read.pos, read.tok, read.kd
        else:
            b.pos_live = self._pos[idx].astype(np.int64)
            b.pos = jnp.asarray(self._pos.copy())
            b.tok = jnp.asarray(self._tok.copy())
            b.kd = jnp.asarray(self._keydata.copy())
        b.chunks, b.counts, b.steps = [], [], 0
        b.gen = self._roster_gen
        # entered when the scheduler takes the burst up; a chunk sent
        # ahead of that is under it all the same, by its id
        b.span = _telemetry.span(
            "engine.burst", metric=_telemetry.SERVING_DECODE_STEP_SECONDS,
            engine=self.engine_id)
        return b

    def _dispatch_chunk(self, b: "_Burst") -> None:
        """The burst's next chunk: the largest power of two of steps
        that cannot pass the nearest request's end. The chunk sent
        ahead around a slot just freed is JOIN_CHUNK steps at most: the
        caller's next request is admitted behind it (``_await_join``),
        and the slot stands empty for as long as it runs."""
        most = self.max_chunk
        if b.after_end and not b.chunks:
            most = min(most, self.JOIN_CHUNK)
        k = 1
        while k * 2 <= min(b.rem - b.steps, most):
            k *= 2
        live, steps = len(b.idx), b.steps
        # step j of the chunk attends the pos + steps + j + 1
        # positions each live slot holds by then
        ctx = k * (int(b.pos_live.sum()) + live * steps) \
            + live * (k * (k + 1) // 2)
        held = b.pos_live[:, None] + steps + np.arange(1, k + 1)
        pages = self._pages_held(held)
        self.n_attended_tokens += ctx
        self.n_attended_pages += pages
        attrs = {}
        if self._window is not None:
            # what a window layer's calls had to read of it
            attrs["ctx_window_tokens"] = int(
                np.minimum(held, self._window).sum())
        if self._selected is not None:
            # what an attention layer's calls read of the contexts, and
            # what an indexer layer's scored to choose it
            attrs["ctx_selected_tokens"] = int(
                np.minimum(held, self._selected).sum())
            attrs["ctx_index_tokens"] = ctx
        tables, active, temps = b.roster
        with _telemetry.span("engine.dispatch", parent=b.span.id, k=k,
                             live=live, ctx_tokens=ctx, ctx_pages=pages,
                             **attrs):
            (kvt, toks, b.pos, b.tok, b.kd, counts) = self._warm.run(
                ("decode", k), self._decode_fallbacks[k],
                self._decode_params, self._cache(), tables,
                b.pos, active, b.tok, b.kd, temps)
        self._rebind(kvt)
        b.chunks.append(toks)
        if counts is not None:
            b.counts.append(counts)
        b.steps += k
        self.n_dispatches += 1

    def _run_ahead(self, read: "_Burst",
                   on_device: bool) -> Optional["_Burst"]:
        """The next burst's first chunk, sent to the device BEFORE the
        tokens of ``read`` are handed out, so that handing them out
        (every caller's thread wakes) happens while the device works;
        ``on_device``, before the host has even read them, so that the
        device goes from one burst to the next with no wait between.
        Only where the roster after ``read`` is known without its
        tokens (no request ends on an ``eos_id``, none is being
        cancelled) and nobody could join first (a free slot and a
        queued request: the admission goes first, as before)."""
        if (self._spec is not None or read.has_eos or self._aborts
                or self._stop.is_set()):
            return None
        b = self._open_burst(read, on_device)
        if not len(b.idx) or (len(b.idx) < self.slots and (
                self._waiting or not self._queue.empty())):
            return None
        self._dispatch_chunk(b)
        return b

    #: steps of the chunk sent ahead around a slot just freed: long
    #: enough for a caller's thread to wake and submit its next request
    #: (a step is milliseconds), short enough that the slot is not
    #: empty for long
    JOIN_CHUNK = 2

    def _await_join(self) -> None:
        """A request has just ended and a chunk is on the device: a
        caller that waits for each reply sends its next request now, so
        the scheduler waits for it here, for as long as the device is
        busy anyway, instead of finding the queue empty by microseconds
        and decoding a further chunk around the free slot."""
        b = self._ahead
        if not b.after_end or self._active.all() or self._waiting:
            return
        while not (b.chunks[-1].is_ready() or self._stop.is_set()):
            try:
                self._waiting.append(self._queue.get(timeout=0.001))
                return
            except _queue.Empty:
                pass

    def _decode_step(self) -> None:
        """One decode BURST: chain chunk dispatches device-to-device —
        pos/tok/keys flow from one executable's output straight into
        the next call, tokens accumulate as device arrays — and sync
        to the host only when the roster can change: the nearest
        request completion, an active eos_id (completion unpredictable
        -> single chunk), or a queued request that could join a free
        slot. The next burst's first chunk goes out before this
        burst's tokens are read and handed to the callers
        (``_run_ahead``); the next call takes that burst up."""
        if self._spec is not None and self._spec_burst():
            return
        b, self._ahead = self._ahead, None
        if b is None:
            b = self._open_burst()
        with b.span as burst:
            # a burst begun ahead whose roster has changed since (a
            # request joined, one was cancelled) is read at once
            stale = b.gen != self._roster_gen
            free_slots = not self._active.all()
            occupancy = float(len(b.idx)) / self.slots
            while not b.chunks or not (
                    stale or b.has_eos or b.steps >= b.rem
                    or len(b.chunks) >= self.MAX_BURST_DISPATCHES
                    # a waiting request can join a free slot
                    or (free_slots and not self._queue.empty())):
                self._dispatch_chunk(b)
            steps = b.steps
            # the slots that still serve the request they decoded for:
            # one cancelled meanwhile has its tokens dropped, and a
            # request that joined meanwhile keeps the state _admit
            # gave its slot
            keep = [i for i, s in enumerate(b.idx)
                    if self._slot_req[s] is b.reqs[i]]
            b.idx, b.pos_live = b.idx[keep], b.pos_live[keep]
            active_idx = b.idx
            # the roster as the burst knew it: the next one goes out
            # before this one is read. Else it waits for the read,
            # which gives the host's copies the slots' new state
            ahead = None if stale else self._run_ahead(b, True)
            # ONE host sync for the whole burst
            with _telemetry.span("engine.sync", steps=steps,
                                 dispatches=len(b.chunks)) as sync:
                # every copy to the host started before the first is
                # waited for
                chunks, pos, tok, kd, counts = jax.device_get(
                    (b.chunks, b.pos, b.tok, b.kd, b.counts))
                toks = np.concatenate(chunks, axis=1)
                self._pos[active_idx] = pos[active_idx]
                self._tok[active_idx] = tok[active_idx]
                self._keydata[active_idx] = kd[active_idx]
                if counts:
                    sync.set(**self._count_experts(
                        np.concatenate(counts)))
            self.n_steps += steps
            self._occupancy_sum += occupancy * steps
            _flight.record("serving_burst", engine=self.engine_id,
                           steps=steps, dispatches=len(b.chunks),
                           occupancy=round(occupancy, 4))
            if _tracing.enabled():
                for s in active_idx:
                    r = self._slot_req[int(s)]
                    if r is not None and r._trace is not None:
                        r._trace.event("decode_burst", burst.t0, sync.t1,
                                       span=burst.id, tokens=steps,
                                       slot=int(s))
            if _telemetry.enabled():
                reg = _telemetry.MetricsRegistry.get_default()
                reg.gauge(_telemetry.SERVING_SLOT_OCCUPANCY,
                          "fraction of decode slots occupied by live "
                          "requests this step").set(
                    occupancy, engine=self.engine_id)
                reg.counter(_telemetry.SERVING_DECODE_STEPS,
                            "fixed-shape decode steps executed").inc(
                    steps, engine=self.engine_id)
            if stale:
                ahead = self._run_ahead(b, False)
            emitted0 = self.n_tokens
            with _telemetry.span("engine.emit") as emit:
                for s in active_idx:
                    for k in range(steps):
                        if not self._active[s]:
                            break          # finished on eos mid-chunk
                        self._emit(int(s), int(toks[s, k]))
                emit.set(tokens=self.n_tokens - emitted0)
            if ahead is not None:
                # the roster it was given is the one the hand-out left
                ahead.gen = self._roster_gen
                self._ahead = ahead
        self.last_progress = time.monotonic()
        if _telemetry.enabled() and self.n_tokens > emitted0:
            _telemetry.MetricsRegistry.get_default().counter(
                _telemetry.SERVING_TOKENS,
                "tokens generated across all requests").inc(
                self.n_tokens - emitted0, engine=self.engine_id)

    def _emit(self, s: int, token: int) -> None:
        """Hot loop (up to burst_steps x slots calls between
        dispatches): no registry lookups here except the rare
        first-token TTFT sample — the token counter is bulk-inc'd once
        per burst/admit by the callers."""
        req = self._slot_req[s]
        req._push(token)
        self._slot_emitted[s] += 1
        self.n_tokens += 1
        if self._slot_emitted[s] == 1 and _telemetry.enabled():
            reg = _telemetry.MetricsRegistry.get_default()
            reg.histogram(
                _telemetry.SERVING_TTFT,
                "submit -> first generated token").observe(
                req.ttft_s, engine=self.engine_id)
            if req.cache_hit_tokens:
                reg.histogram(
                    _telemetry.SERVING_WARM_TTFT,
                    "submit -> first token for requests whose prompt "
                    "reused cached KV (prefix-cache or session "
                    "hit)").observe(req.ttft_s, engine=self.engine_id)
        if self._slot_emitted[s] >= req.max_new_tokens:
            self._evict(s, "length")
        elif req.eos_id is not None and token == req.eos_id:
            self._evict(s, "eos")

    def _evict(self, s: int, reason: str,
               error: Optional[BaseException] = None) -> None:
        req = self._slot_req[s]
        if not self._maybe_pin_session(s, req, reason, error):
            self.pool.free(self._slot_pages[s])
        self._slot_req[s] = None
        self._slot_pages[s] = []
        self._slot_emitted[s] = 0
        self._tables[s] = 0      # all-null row: decode writes -> page 0
        self._pos[s] = 0
        self._tok[s] = 0
        self._temps[s] = 0.0
        self._active[s] = False
        self._dev_static = None      # roster changed: re-upload
        self._roster_gen += 1
        self.n_completed += 1
        req._finish(reason, error)
        _flight.record("serving_evict", request_id=req.request_id,
                       engine=self.engine_id,
                       reason=reason, tokens=len(req.tokens))
        self._recent.append({
            "request_id": req.request_id,
            "finish_reason": reason,
            "tokens": len(req.tokens),
            "latency_ms": round(req.latency_s * 1e3, 3)
            if req.latency_s is not None else None,
            "ttft_ms": round(req.ttft_s * 1e3, 3)
            if req.ttft_s is not None else None,
        })
        if _telemetry.enabled():
            _telemetry.MetricsRegistry.get_default().histogram(
                _telemetry.SERVING_REQUEST_LATENCY,
                "submit -> completion per request").observe(
                req.latency_s, reason=reason, engine=self.engine_id)

    def _maybe_pin_session(self, s: int, req: ServingRequest,
                           reason: str,
                           error: Optional[BaseException]) -> bool:
        """On a clean finish of a ``session_id`` request, pin the
        COMMITTED state under that id instead of freeing it: the token
        history whose K/V actually landed in the pool (prompt + all
        generated tokens but the last — the final token was emitted,
        never fed back through the decode step) and the pages holding
        it. Pages past the committed extent are freed now."""
        if (error is not None or reason not in ("length", "eos")
                or req.session_id is None or self._sessions is None):
            return False
        pages = self._slot_pages[s]
        history = np.concatenate(
            [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
        keep = kv_pages.pages_needed(history.size, self.page_size)
        if not 0 < keep <= len(pages):
            return False
        if len(pages) > keep:
            self.pool.free(pages[keep:])
        self._sessions.pin(req.session_id, pages[:keep], history,
                           self.pool, turns=req._session_turns)
        return True

    def _gauge_queue_depth(self) -> None:
        if _telemetry.enabled():
            _telemetry.MetricsRegistry.get_default().gauge(
                _telemetry.SERVING_QUEUE_DEPTH,
                "requests waiting for a free decode slot").set(
                len(self._waiting) + self._queue.qsize(),
                engine=self.engine_id)


__all__ = ["DecodeEngine", "ServingRequest", "CapacityRejected"]
