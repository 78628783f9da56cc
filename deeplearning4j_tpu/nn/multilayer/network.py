"""MultiLayerNetwork — sequential network front-end.

Reference: org/deeplearning4j/nn/multilayer/MultiLayerNetwork.java
(~4k LoC) + the training driver stack (Solver, BaseOptimizer,
StochasticGradientDescent, MultiLayerUpdater — SURVEY.md §2.19, §2.22,
§3.1).

The reference's fit() runs a per-layer, per-op eager loop crossing JNI
thousands of times per iteration, with params/gradients living in flat
mutable view arrays. The TPU-native design compiles the ENTIRE training
iteration — forward, loss, backward, updater, param update — into ONE
XLA executable (`jax.jit` with donated buffers), executed per minibatch.
That single design decision replaces: LayerWorkspaceMgr arenas (XLA
buffer assignment), the updater loop (fused into the step), gradient
views (pytree + donation), and the flow-controller sync machinery
(XLA's dataflow schedule).

Parity surface kept from the reference: init()/fit()/output()/score()/
params()/setParams()/numParams()/evaluate()/summary(), listener
callbacks, per-layer updater overrides (incl. NoOp freezing),
l1/l2 regularization, gradient clipping modes.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import DataSetIterator
from deeplearning4j_tpu.learning.schedules import ISchedule, ScheduleType
from deeplearning4j_tpu.learning.updaters import IUpdater, apply_updater
from deeplearning4j_tpu.profiler import flight_recorder as _flight
from deeplearning4j_tpu.profiler import model_health as _model_health
from deeplearning4j_tpu.profiler import telemetry as _telemetry
from deeplearning4j_tpu.profiler import tracing as _tracing


def _eval_mask(ds):
    """Label mask for evaluation, with the evalTimeSeries convention:
    per-timestep labels + a features mask and no explicit label mask
    means the features mask IS the label mask (reference: RNN masking)."""
    if ds.labels_mask is None and ds.features_mask is not None \
            and np.asarray(ds.labels).ndim == 3:
        return ds.features_mask
    return ds.labels_mask


def _uses_epoch_schedule(upd) -> bool:
    """True if the updater's LR schedule counts epochs, not iterations
    (reference: ScheduleType.EPOCH resolved in BaseMultiLayerUpdater)."""
    lr = getattr(upd, "learning_rate", None)
    return isinstance(lr, ISchedule) and lr.schedule_type is ScheduleType.EPOCH
from deeplearning4j_tpu.ndarray.dtypes import DataType
from deeplearning4j_tpu.ndarray.ndarray import NDArray, _unwrap
from deeplearning4j_tpu.nn import precision as _precision
from deeplearning4j_tpu.nn.conf.builder import (
    MultiLayerConfiguration, apply_preprocessor,
)
from deeplearning4j_tpu.nn.conf.constraint import apply_constraints
from deeplearning4j_tpu.nn.conf.layers import LossLayer, OutputLayer

#: param keys subject to l1/l2 (weights, not biases/scales — reference
#: regularizes weights by default, bias via separate l2Bias we omit)
_REGULARIZED_KEYS = {"W", "RW", "dW", "pW", "Wq", "Wk", "Wv", "Wo"}


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.params_list: Optional[List[dict]] = None   # per-layer param dicts
        self.states_list: Optional[List[dict]] = None   # per-layer non-trainable state
        self.opt_states: Optional[List[Any]] = None     # per-layer updater state
        self._updaters: List[IUpdater] = []
        self._iteration = 0
        self._epoch = 0
        self._score = float("nan")
        self._listeners: List[Any] = []
        self._rng_key = None
        self._step_cache: dict = {}
        self._fwd_cache: dict = {}
        self._pretrain_cache: dict = {}
        self._rnn_carries = None    # stateful rnnTimeStep hidden state
        self._rnn_batch = 0
        # mixed-precision policy (nn/precision.py): identity policies
        # (precision=None / single-dtype) keep the legacy code paths
        # bit-for-bit; mixed policies split param vs compute dtype
        self._policy = _precision.PrecisionPolicy.resolve(
            getattr(conf, "precision", None), conf.dtype)
        self._mixed = not self._policy.is_identity
        #: MASTER param dtype (fp32 under mixed policies)
        self._dtype = DataType.from_any(self._policy.param_dtype).jax
        #: dtype inputs are staged in (compute dtype — halves transfer
        #: bytes under mixed policies)
        self._input_dtype = DataType.from_any(
            self._policy.compute_dtype).jax
        #: dtype output()/feedForward() return
        self._out_dtype = DataType.from_any(
            self._policy.output_dtype).jax
        self._compute_dtypes: List[Any] = []
        self._loss_scale_state = None
        self._ls_seen = (0, 0)
        # in-step model-health monitor (profiler/model_health.py);
        # None keeps every step builder on its legacy code path
        self._health = None

    # ------------------------------------------------------------------
    # initialization (reference: MultiLayerNetwork#init + ParamInitializer)
    # ------------------------------------------------------------------
    def init(self) -> "MultiLayerNetwork":
        conf = self.conf
        key = jax.random.key(conf.seed)
        it = conf.input_type
        if it is None:
            # manual-n_in path (reference allows omitting setInputType when
            # every layer's nIn is explicit); derive the input type from
            # the first parameterized layer
            it = self._infer_input_type()
        self.params_list, self.states_list, self._updaters = [], [], []
        self.opt_states = []
        for i, layer in enumerate(conf.layers):
            tag = conf.preprocessors.get(i)
            if tag == "flatten":
                from deeplearning4j_tpu.nn.conf.inputs import InputType
                it = InputType.feedForward(it.flat_size())
            elif tag and tag.startswith("to_conv:"):
                from deeplearning4j_tpu.nn.conf.inputs import InputType
                h, w, c = (int(v) for v in tag.split(":", 1)[1].split(","))
                it = InputType.convolutional(h, w, c)
            elif it.kind == "convolutionalFlat":
                from deeplearning4j_tpu.nn.conf.inputs import InputType
                it = InputType.feedForward(it.flat_size())
            key, sub = jax.random.split(key)
            p = layer.init_params(sub, it, self._dtype)
            s = layer.init_state(it, self._dtype)
            upd = layer.updater if layer.updater is not None else conf.updater
            self.params_list.append(p)
            self.states_list.append(s)
            self._updaters.append(upd)
            self.opt_states.append(upd.init_state(p))
            it = layer.output_type(it)
        self._output_type = it
        self._rng_key = jax.random.key(conf.seed ^ 0x5EED)
        # per-layer compute dtypes (fp32 islands for loss heads /
        # normalization stay fp32 under mixed policies)
        self._compute_dtypes = [
            self._policy.layer_compute_dtype(l, i)
            for i, l in enumerate(conf.layers)]
        self._loss_scale_state = _precision.init_loss_scale(self._policy)
        self._ls_seen = (0, 0)
        if self._mixed:
            _precision.record_cast_count("mln", sum(
                _precision.count_casts(p, self._compute_dtypes[i])
                for i, p in enumerate(self.params_list)))
        return self

    def _infer_input_type(self):
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import (
            ConvolutionLayer, LSTM, SimpleRnn, SubsamplingLayer,
        )

        first = self.conf.layers[0]
        if isinstance(first, (ConvolutionLayer, SubsamplingLayer)):
            raise ValueError(
                "Image networks need setInputType(InputType.convolutional"
                "(h, w, c)) — channel count alone does not fix the geometry")
        n_in = getattr(first, "n_in", 0)
        if not n_in:
            raise ValueError(
                "Without setInputType, the first layer must declare n_in")
        if isinstance(first, (LSTM, SimpleRnn)):
            return InputType.recurrent(n_in)
        return InputType.feedForward(n_in)

    def _check_init(self):
        if self.params_list is None:
            raise RuntimeError("Call init() first")

    # ------------------------------------------------------------------
    # mixed-precision seams (identity policies: strict no-ops)
    # ------------------------------------------------------------------
    def _cd(self, i):
        """Compute dtype of layer i under the active policy."""
        return self._compute_dtypes[i] if self._mixed else self._dtype

    def _cast_p(self, p, i):
        """Cast one layer's MASTER params to its compute dtype. Inside
        the jitted step this happens once per step, and its vjp casts
        the gradients straight back to the master dtype (fp32)."""
        return _precision.cast_tree(p, self._compute_dtypes[i]) \
            if self._mixed else p

    def _cast_a(self, a, i):
        """Cast the activation entering layer i (fp32 islands cast up,
        and back down at the next reduced-precision layer)."""
        return _precision.cast_leaf(a, self._compute_dtypes[i]) \
            if self._mixed else a

    # ------------------------------------------------------------------
    # forward (reference: feedForward / ffToLayerActivationsInWs)
    # ------------------------------------------------------------------
    def _forward(self, params_list, states_list, x, train: bool, rng,
                 fmask=None):
        """Pure forward through all layers. Returns (out, new_states)."""
        from deeplearning4j_tpu.nn.conf.layers import GlobalPoolingLayer

        conf = self.conf
        a = x
        if fmask is not None:
            a = a * fmask[..., None].astype(a.dtype)
        new_states = []
        keys = (jax.random.split(rng, len(conf.layers))
                if rng is not None else [None] * len(conf.layers))
        for i, layer in enumerate(conf.layers):
            tag = conf.preprocessors.get(i)
            if tag:
                a = apply_preprocessor(tag, a)
            a = self._cast_a(a, i)
            p_i = self._cast_p(params_list[i], i)
            if fmask is not None and isinstance(layer, GlobalPoolingLayer) \
                    and a.ndim == 3 and a.shape[1] == fmask.shape[1]:
                a, ns = layer.apply_masked(p_i, states_list[i],
                                           a, fmask, train, keys[i])
            else:
                a, ns = layer.apply(p_i, states_list[i], a,
                                    train, keys[i])
            new_states.append(ns)
        if self._mixed:
            a = _precision.cast_leaf(a, self._out_dtype)
        return a, new_states

    def _loss(self, params_list, states_list, x, y, mask, rng, fmask=None,
              collect_acts=False):
        """Forward to the loss head; fused stable loss on pre-activations.
        ``collect_acts=True`` (the HealthMonitor step path) extends the
        aux with per-layer non-finite activation flags."""
        loss, (new_states, data_loss, _, act_bad) = self._loss_carries(
            params_list, states_list, None, x, y, mask, rng, fmask,
            collect_acts=collect_acts)
        if collect_acts:
            return loss, (new_states, data_loss, act_bad)
        return loss, (new_states, data_loss)

    def _loss_carries(self, params_list, states_list, carries, x, y, mask,
                      rng, fmask=None, collect_acts=False):
        """Loss forward threading recurrent hidden state (tBPTT path:
        reference MultiLayerNetwork#doTruncatedBPTT keeps each layer's
        rnnTimeStep state across segments; gradient truncation falls out
        of the carries entering the jitted segment step as inputs)."""
        from deeplearning4j_tpu.nn.conf.layers import GlobalPoolingLayer

        conf = self.conf
        a = x
        # features mask: zero padded timesteps at the input (reference:
        # setLayerMaskArrays; padded inputs contribute nothing) — masked
        # pooling below handles the reduction side
        if fmask is not None:
            a = a * fmask[..., None].astype(a.dtype)
        new_states = []
        new_carries = []
        # per-layer non-finite forward flags (model-health provenance)
        act_bad = [] if collect_acts else None
        keys = (jax.random.split(rng, len(conf.layers))
                if rng is not None else [None] * len(conf.layers))
        for i, layer in enumerate(conf.layers[:-1]):
            tag = conf.preprocessors.get(i)
            if tag:
                a = apply_preprocessor(tag, a)
            a = self._cast_a(a, i)
            p_i = self._cast_p(params_list[i], i)
            k_i = keys[i]
            # masked global pooling when the time axis still lines up
            if fmask is not None and isinstance(layer, GlobalPoolingLayer) \
                    and a.ndim == 3 and a.shape[1] == fmask.shape[1]:
                a, ns = layer.apply_masked(p_i, states_list[i], a, fmask,
                                           True, k_i)
                new_states.append(ns)
                new_carries.append(None)
                if collect_acts:
                    act_bad.append(_model_health.act_flag(a))
                continue
            # weight noise (reference: IWeightNoise applied per training
            # forward; DropConnect/WeightNoise in conf/weightnoise)
            if getattr(layer, "weight_noise", None) is not None \
                    and k_i is not None:
                k_i, k_wn = jax.random.split(k_i)
                p_i = layer.weight_noise.apply(p_i, k_wn)
            if carries is not None and layer.is_recurrent:
                a, ns, c = layer.apply_with_carry(
                    p_i, states_list[i], carries[i], a, True, k_i)
            else:
                a, ns = layer.apply(p_i, states_list[i], a, True, k_i)
                c = None
            new_states.append(ns)
            new_carries.append(c)
            if collect_acts:
                act_bad.append(_model_health.act_flag(a))
        new_carries.append(None)  # loss head is never recurrent
        last = conf.layers[-1]
        if not hasattr(last, "loss_value"):
            raise ValueError("Last layer must be an OutputLayer/LossLayer "
                             "(or another loss-bearing head, e.g. "
                             "OCNNOutputLayer) to fit()")
        tag = conf.preprocessors.get(len(conf.layers) - 1)
        if tag:
            a = apply_preprocessor(tag, a)
        # loss head: fp32 island under mixed policies — the activation
        # is cast UP so the logits, softmax and loss reduction all run
        # at full precision (the policy's fp32_loss_head default)
        a = self._cast_a(a, len(conf.layers) - 1)
        p_last = self._cast_p(params_list[-1], len(conf.layers) - 1)
        if getattr(last, "weight_noise", None) is not None \
                and keys[-1] is not None:
            p_last = last.weight_noise.apply(p_last, keys[-1])
        data_loss = last.loss_value(p_last, states_list[-1], a, y, mask)
        new_states.append(states_list[-1])
        if collect_acts:
            # the loss head's provenance bit is its loss value: a clean
            # prefix + non-finite loss localizes the blow-up to the head
            act_bad.append(_model_health.act_flag(data_loss))

        # l1/l2 regularization (reference: BaseLayer#calcRegularizationScore)
        reg = jnp.asarray(0.0, data_loss.dtype)
        for layer, p in zip(conf.layers, params_list):
            l1 = layer.l1 or 0.0
            l2 = layer.l2 or 0.0
            if l1 == 0.0 and l2 == 0.0:
                continue
            for k, v in p.items():
                if k in _REGULARIZED_KEYS:
                    if l1:
                        reg = reg + l1 * jnp.sum(jnp.abs(v))
                    if l2:
                        reg = reg + 0.5 * l2 * jnp.sum(v * v)
        return data_loss + reg, (new_states, data_loss, new_carries,
                                 act_bad)

    def _clip_grads(self, grads_list):
        mode = self.conf.gradient_normalization
        if not mode:
            return grads_list
        t = self.conf.gradient_normalization_threshold
        if mode == "ClipElementWiseAbsoluteValue":
            return jax.tree_util.tree_map(lambda g: jnp.clip(g, -t, t), grads_list)
        if mode == "ClipL2PerLayer":
            out = []
            for g in grads_list:
                leaves = jax.tree_util.tree_leaves(g)
                norm = jnp.sqrt(sum(jnp.sum(l * l) for l in leaves) + 1e-12)
                scale = jnp.minimum(1.0, t / norm)
                out.append(jax.tree_util.tree_map(lambda l: l * scale, g))
            return out
        if mode == "RenormalizeL2PerLayer":
            out = []
            for g in grads_list:
                leaves = jax.tree_util.tree_leaves(g)
                norm = jnp.sqrt(sum(jnp.sum(l * l) for l in leaves) + 1e-12)
                out.append(jax.tree_util.tree_map(lambda l: l / norm, g))
            return out
        raise ValueError(f"Unknown gradient normalization: {mode}")

    # ------------------------------------------------------------------
    # the compiled training step
    # ------------------------------------------------------------------
    def _apply_updates(self, params_list, opt_states, grads, it_step,
                       ep_step):
        """Master-precision weight update: grads arrive fp32 (the
        param-cast vjp), apply_updater keeps the math fp32, and
        ``p - u`` runs in the master dtype."""
        new_params, new_opt = [], []
        for i in range(len(params_list)):
            step = ep_step if _uses_epoch_schedule(self._updaters[i]) else it_step
            updates, no = apply_updater(self._updaters[i], opt_states[i],
                                        grads[i], params_list[i], step)
            np_i = jax.tree_util.tree_map(
                lambda p, u: p - u, params_list[i], updates)
            # post-update constraints (reference: BaseConstraint)
            new_params.append(apply_constraints(self.conf.layers[i], np_i))
            new_opt.append(no)
        return new_params, new_opt

    def _get_train_step(self, has_mask: bool, has_fmask: bool = False) -> Callable:
        # the health flag is STATIC: toggling a HealthMonitor on/off
        # costs exactly one extra compile per site, nothing per step
        health = self._health is not None
        key = (has_mask, has_fmask, health)
        if key in self._step_cache:
            return self._step_cache[key]
        policy = self._policy
        n_layers = len(self.conf.layers)

        if policy.loss_scaling:
            # mixed_float16: scaled loss, fp32 unscale, overflow ->
            # skip-step-and-halve — all inside the one compiled step
            def step_fn(params_list, states_list, opt_states, ls_state,
                        it_step, ep_step, x, y, mask, fmask, rng):
                loss_fn = lambda pl: self._loss(pl, states_list, x, y,
                                                mask, rng, fmask,
                                                collect_acts=health)
                ((loss, aux), grads,
                 finite) = _precision.scaled_value_and_grad(
                    loss_fn, ls_state, params_list)
                raw_grads = grads
                grads = self._clip_grads(grads)
                new_params, new_opt = self._apply_updates(
                    params_list, opt_states, grads, it_step, ep_step)
                (new_params, new_opt, new_states,
                 new_ls) = _precision.guard_scaled_step(
                    policy, ls_state, finite,
                    [(new_params, params_list), (new_opt, opt_states),
                     (aux[0], states_list)])
                if health:
                    # post-guard params: a handled overflow reads
                    # update_ratio 0, and the handled flag tells the
                    # host not to report it as model sickness
                    h = _model_health.device_stats(
                        range(n_layers), raw_grads, new_params,
                        params_list, aux[2],
                        handled=jnp.logical_not(finite))
                    return (new_params, new_states, new_opt, new_ls,
                            aux[1], h)
                return new_params, new_states, new_opt, new_ls, aux[1]

            jitted = _telemetry.instrument_jit(
                "mln_step", jax.jit(step_fn, donate_argnums=(0, 1, 2, 3)))
            self._step_cache[key] = jitted
            return jitted

        def step_fn(params_list, states_list, opt_states, it_step, ep_step,
                    x, y, mask, fmask, rng):
            loss_fn = lambda pl: self._loss(pl, states_list, x, y, mask, rng,
                                            fmask, collect_acts=health)
            (loss, aux), grads = \
                jax.value_and_grad(loss_fn, has_aux=True)(params_list)
            raw_grads = grads
            grads = self._clip_grads(grads)
            new_params, new_opt = self._apply_updates(
                params_list, opt_states, grads, it_step, ep_step)
            if health:
                h = _model_health.device_stats(
                    range(n_layers), raw_grads, new_params, params_list,
                    aux[2])
                return new_params, aux[0], new_opt, aux[1], h
            return new_params, aux[0], new_opt, aux[1]

        jitted = _telemetry.instrument_jit(
            "mln_step", jax.jit(step_fn, donate_argnums=(0, 1, 2)))
        self._step_cache[key] = jitted
        return jitted

    def _get_tbptt_step(self, has_mask: bool) -> Callable:
        """Compiled tBPTT segment step: one param update per segment,
        recurrent state carried between segments (reference:
        MultiLayerNetwork#doTruncatedBPTT). Gradients stop at segment
        boundaries because carries enter the jitted step as plain inputs
        (tbptt_back_length == tbptt_fwd_length by construction here)."""
        health = self._health is not None
        key = ("tbptt", has_mask, health)
        if key in self._step_cache:
            return self._step_cache[key]
        policy = self._policy
        n_layers = len(self.conf.layers)

        if policy.loss_scaling:
            def step_fn(params_list, states_list, opt_states, ls_state,
                        carries, it_step, ep_step, x, y, mask, rng):
                loss_fn = lambda pl: self._loss_carries(
                    pl, states_list, carries, x, y, mask, rng,
                    collect_acts=health)
                ((loss, (new_states, data_loss, new_carries, act_bad)),
                 grads, finite) = _precision.scaled_value_and_grad(
                    loss_fn, ls_state, params_list)
                raw_grads = grads
                grads = self._clip_grads(grads)
                new_params, new_opt = self._apply_updates(
                    params_list, opt_states, grads, it_step, ep_step)
                # carries deliberately NOT guarded: they are activations
                # not trainable state — the next segment re-enters from
                # whatever the forward produced, and non-finite carries
                # resolve on the minibatch reset
                (new_params, new_opt, new_states,
                 new_ls) = _precision.guard_scaled_step(
                    policy, ls_state, finite,
                    [(new_params, params_list), (new_opt, opt_states),
                     (new_states, states_list)])
                if health:
                    h = _model_health.device_stats(
                        range(n_layers), raw_grads, new_params,
                        params_list, act_bad,
                        handled=jnp.logical_not(finite))
                    return (new_params, new_states, new_opt, new_ls,
                            new_carries, data_loss, h)
                return (new_params, new_states, new_opt, new_ls,
                        new_carries, data_loss)

            jitted = _telemetry.instrument_jit(
                "mln_tbptt_step",
                jax.jit(step_fn, donate_argnums=(0, 1, 2, 3, 4)))
            self._step_cache[key] = jitted
            return jitted

        def step_fn(params_list, states_list, opt_states, carries, it_step,
                    ep_step, x, y, mask, rng):
            loss_fn = lambda pl: self._loss_carries(
                pl, states_list, carries, x, y, mask, rng,
                collect_acts=health)
            (loss, (new_states, data_loss, new_carries, act_bad)), grads = \
                jax.value_and_grad(loss_fn, has_aux=True)(params_list)
            raw_grads = grads
            grads = self._clip_grads(grads)
            new_params, new_opt = self._apply_updates(
                params_list, opt_states, grads, it_step, ep_step)
            if health:
                h = _model_health.device_stats(
                    range(n_layers), raw_grads, new_params, params_list,
                    act_bad)
                return (new_params, new_states, new_opt, new_carries,
                        data_loss, h)
            return new_params, new_states, new_opt, new_carries, data_loss

        jitted = _telemetry.instrument_jit(
            "mln_tbptt_step", jax.jit(step_fn, donate_argnums=(0, 1, 2, 3)))
        self._step_cache[key] = jitted
        return jitted

    def _get_forward(self, train: bool, has_fmask: bool = False) -> Callable:
        key = (train, has_fmask)
        if key in self._fwd_cache:
            return self._fwd_cache[key]
        fn = _telemetry.instrument_jit("mln_forward", jax.jit(
            lambda pl, sl, x, rng, fm: self._forward(pl, sl, x, train, rng,
                                                     fm)[0]))
        self._fwd_cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    # public training API (reference: fit(INDArray,INDArray) / fit(iter))
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1,
            fault_tolerance=None, auto_resume=None):
        self._check_init()
        if fault_tolerance is not None or auto_resume is not None:
            # fault-tolerant loop (util/resilience.py): preemption-safe
            # checkpointing, auto-resume, divergence rollback. Without a
            # policy the legacy path below runs bit-identically.
            from deeplearning4j_tpu.util import resilience as _resilience

            return _resilience.run_fit(self, fault_tolerance, data,
                                       labels, epochs,
                                       auto_resume=auto_resume)
        if isinstance(data, DataSetIterator):
            import time as _time

            for _ in range(epochs):
                it = iter(data)
                while True:
                    # time spent waiting on the iterator = ETL time
                    # (reference: PerformanceListener's ETL-time metric,
                    # surfaced in the training UI's system charts)
                    t0 = _time.perf_counter()
                    try:
                        ds = next(it)
                    except StopIteration:
                        break
                    self._last_etl_ms = (_time.perf_counter() - t0) * 1e3
                    _telemetry.record_phase("etl_wait", t0)
                    self._fit_batch(ds.features, ds.labels, ds.labels_mask,
                                    ds.features_mask)
                self._epoch += 1
                for l in self._listeners:
                    if hasattr(l, "onEpochEnd"):
                        l.onEpochEnd(self)
            return self
        # non-iterator paths have no ETL wait — clear any stale value a
        # previous iterator-based fit left behind (the UI would
        # otherwise chart a frozen constant)
        self._last_etl_ms = None
        if isinstance(data, DataSet):
            for _ in range(epochs):
                self._fit_batch(data.features, data.labels,
                                data.labels_mask, data.features_mask)
            return self
        if labels is None:
            raise ValueError("fit(x, y) requires labels")
        for _ in range(epochs):
            self._fit_batch(_unwrap(data), _unwrap(labels), None)
        return self

    @staticmethod
    def _validate_fmask(fm, x):
        from deeplearning4j_tpu.nn.masking import validate_features_mask

        return validate_features_mask(
            _unwrap(fm) if fm is not None else None, x)

    def _fit_batch(self, x, y, mask, features_mask=None):
        xin = _unwrap(x)
        if isinstance(xin, jax.Array) and xin.dtype == self._input_dtype:
            # already device-resident in the right dtype (device
            # prefetcher output): no host->device copy, no cast
            _telemetry.record_on_device_batch("mln")
            x = xin
        else:
            x = jnp.asarray(xin, self._input_dtype)
        y = jnp.asarray(_unwrap(y))
        fm = self._validate_fmask(features_mask, x)
        # per-timestep labels with a features mask and no explicit label
        # mask: the features mask IS the label mask (reference: RNN
        # masking conventions)
        if mask is None and fm is not None and y.ndim == 3 \
                and fm.ndim == 2 and y.shape[1] == fm.shape[1]:
            mask = fm
        m = jnp.asarray(_unwrap(mask)) if mask is not None else None
        k = self.conf.tbptt_fwd_length
        if (k and x.ndim == 3 and x.shape[1] > k
                and any(l.is_recurrent for l in self.conf.layers)):
            if fm is not None:
                raise NotImplementedError(
                    "features masks with truncated BPTT are not supported "
                    "yet — use standard BPTT")
            return self._fit_tbptt(x, y, m, k)
        self._rng_key, sub = jax.random.split(self._rng_key)
        hm = self._health
        step_fn = self._get_train_step(m is not None, fm is not None)
        t_step = time.perf_counter()
        if self._loss_scale_state is not None:
            res = step_fn(
                self.params_list, self.states_list, self.opt_states,
                self._loss_scale_state, jnp.asarray(self._iteration),
                jnp.asarray(self._epoch), x, y, m, fm, sub)
            res, health = _model_health.split_health(res, hm is not None)
            (self.params_list, self.states_list, self.opt_states,
             self._loss_scale_state, loss) = res
        else:
            res = step_fn(
                self.params_list, self.states_list, self.opt_states,
                jnp.asarray(self._iteration), jnp.asarray(self._epoch),
                x, y, m, fm, sub)
            res, health = _model_health.split_health(res, hm is not None)
            (self.params_list, self.states_list, self.opt_states,
             loss) = res
        # dispatch-side timing: the step is async, so this span is host
        # dispatch (+ compile on a cache miss), not device wall time —
        # deliberately so; blocking here would stall the pipeline
        _telemetry.record_phase("device_step", t_step)
        # keep the loss on-device: a float() here would force a host sync
        # every step and stall the dispatch pipeline; score() converts
        # lazily
        self._score = loss
        self._iteration += 1
        self._last_batch_size = int(x.shape[0])
        # black box + request-scoped tracing: host-side only (the
        # score stays on device), disabled cost = one attribute read
        _flight.record_step("mln", self._iteration, t_step,
                            etl_ms=self._last_etl_ms)
        _tracing.record_train_step("mln", self._iteration, t_step)
        # device-array references for listeners that recompute
        # gradients (StatsListener collect_gradients — the reference's
        # per-iteration gradient reports; free to keep, they alias the
        # arrays already on device)
        self._last_fit_batch = (x, y, m, fm, sub)
        _telemetry.sample_device_memory()
        if hm is not None:
            # records the device-side tree; syncs (one small transfer)
            # only on every `frequency`-th step
            hm.on_step(self, health, site="mln", jit_site="mln_step")
        if self._loss_scale_state is not None:
            # mirror loss-scale/overflow counters into telemetry (one
            # device->host sync per step — mixed_float16 only; disable
            # telemetry to trade observability for dispatch pipelining)
            self._ls_seen = _precision.record_loss_scale(
                "mln", self._loss_scale_state, self._ls_seen)
        self._panic_check()
        if self._listeners:
            t_l = time.perf_counter()
            for l in self._listeners:
                l.iterationDone(self, self._iteration, self._epoch)
            _telemetry.record_phase("listener_host", t_l)

    def _panic_check(self):
        """NaN/Inf panic hook (reference: OpProfiler NAN_PANIC et al. —
        per-op there, per-step here since the step is one executable)."""
        from deeplearning4j_tpu.profiler import (
            OpProfiler, ProfilerMode, check_numerics,
        )
        cfg = OpProfiler.getInstance().config
        if cfg.mode in (ProfilerMode.DISABLED, ProfilerMode.OPERATIONS):
            return
        # under dynamic loss scaling a non-finite LOSS can be a handled
        # overflow (step skipped, scale halved) — say so in the panic
        ls_ctx = _precision.loss_scale_context(self._loss_scale_state)
        check_numerics(self._score, cfg.mode,
                       f"in score at iteration {self._iteration}{ls_ctx}")
        if cfg.check_params:
            check_numerics(self.params_list, cfg.mode,
                           f"in params at iteration {self._iteration}"
                           f"{ls_ctx}")

    def _fit_tbptt(self, x, y, mask, k: int):
        """Truncated BPTT over the time axis (reference:
        MultiLayerNetwork#doTruncatedBPTT — split [N,T,*] into length-k
        segments, update params per segment, carry RNN state forward,
        reset state at the start of each minibatch)."""
        if y.ndim < 3:
            raise ValueError(
                "tBPTT requires per-timestep labels [N,T,C] "
                "(use RnnOutputLayer)")
        n, t = x.shape[0], x.shape[1]
        try:
            # carries are activations: compute dtype, not master dtype
            carries = [
                (l.init_carry(n, self._cd(i)) if l.is_recurrent else None)
                for i, l in enumerate(self.conf.layers)]
        except NotImplementedError:
            raise ValueError(
                "Truncated BPTT is not supported with Bidirectional layers "
                "(the backward direction needs the full sequence) — use "
                "standard BPTT") from None
        hm = self._health
        step_fn = self._get_tbptt_step(mask is not None)
        for t0 in range(0, t, k):
            xc = x[:, t0:t0 + k]
            yc = y[:, t0:t0 + k]
            mc = mask[:, t0:t0 + k] if mask is not None else None
            self._rng_key, sub = jax.random.split(self._rng_key)
            t_step = time.perf_counter()
            if self._loss_scale_state is not None:
                res = step_fn(
                    self.params_list, self.states_list, self.opt_states,
                    self._loss_scale_state, carries,
                    jnp.asarray(self._iteration), jnp.asarray(self._epoch),
                    xc, yc, mc, sub)
                res, health = _model_health.split_health(
                    res, hm is not None)
                (self.params_list, self.states_list, self.opt_states,
                 self._loss_scale_state, carries, loss) = res
            else:
                res = step_fn(
                    self.params_list, self.states_list, self.opt_states,
                    carries, jnp.asarray(self._iteration),
                    jnp.asarray(self._epoch), xc, yc, mc, sub)
                res, health = _model_health.split_health(
                    res, hm is not None)
                (self.params_list, self.states_list, self.opt_states,
                 carries, loss) = res
            _telemetry.record_phase("device_step", t_step)
            self._score = loss
            self._iteration += 1
            self._last_batch_size = int(xc.shape[0])
            _flight.record_step("mln_tbptt", self._iteration, t_step)
            _tracing.record_train_step("mln_tbptt", self._iteration,
                                       t_step)
            if hm is not None:
                hm.on_step(self, health, site="mln",
                           jit_site="mln_tbptt_step")
            if self._loss_scale_state is not None:
                self._ls_seen = _precision.record_loss_scale(
                    "mln", self._loss_scale_state, self._ls_seen)
            self._panic_check()
            if self._listeners:
                t_l = time.perf_counter()
                for l in self._listeners:
                    l.iterationDone(self, self._iteration, self._epoch)
                _telemetry.record_phase("listener_host", t_l)

    # ------------------------------------------------------------------
    # layerwise unsupervised pretraining (reference:
    # MultiLayerNetwork#pretrain / #pretrainLayer — SURVEY.md §2.19;
    # the VAE/AutoEncoder pretrain path)
    # ------------------------------------------------------------------
    def _prefix_activations(self, idx, params_list, states_list, a):
        """Inference-mode forward through layers [0, idx) plus layer
        idx's input preprocessor — the frozen feature extractor under
        pretrainLayer/reconstructionLogProbability. Pure: safe inside
        jit."""
        for j, lay in enumerate(self.conf.layers[:idx]):
            tag = self.conf.preprocessors.get(j)
            if tag:
                a = apply_preprocessor(tag, a)
            a = self._cast_a(a, j)
            a, _ = lay.apply(self._cast_p(params_list[j], j),
                             states_list[j], a, False, None)
        tag = self.conf.preprocessors.get(idx)
        if tag:
            a = apply_preprocessor(tag, a)
        return a

    def _get_pretrain_step(self, idx: int) -> Callable:
        if idx in self._pretrain_cache:
            return self._pretrain_cache[idx]
        layer = self.conf.layers[idx]

        def step_fn(p_i, prefix_params, states_list, opt_state, it_step,
                    x, rng):
            # frozen-prefix features, inference mode, inside the SAME
            # compiled program (no separate feature-extraction pass)
            a = self._prefix_activations(idx, prefix_params, states_list,
                                         x)

            def loss_fn(p):
                if layer.weight_noise is not None and rng is not None:
                    p = layer.weight_noise.apply(p, rng)
                loss = layer.unsupervised_loss(
                    self._cast_p(p, idx), self._cast_a(a, idx), rng)
                # same l1/l2 treatment fit() applies (reference:
                # pretraining includes regularization in the score);
                # regularization reads the MASTER params
                for k, v in p.items():
                    if k in _REGULARIZED_KEYS:
                        if layer.l1:
                            loss = loss + layer.l1 * jnp.sum(jnp.abs(v))
                        if layer.l2:
                            loss = loss + 0.5 * layer.l2 * jnp.sum(v * v)
                return loss

            loss, grads = jax.value_and_grad(loss_fn)(p_i)
            grads = self._clip_grads([grads])[0]
            updates, new_opt = apply_updater(self._updaters[idx],
                                             opt_state, grads, p_i,
                                             it_step)
            new_p = jax.tree_util.tree_map(lambda p, u: p - u, p_i,
                                           updates)
            return apply_constraints(layer, new_p), new_opt, loss

        jitted = _telemetry.instrument_jit("mln_pretrain",
                                           jax.jit(step_fn))
        self._pretrain_cache[idx] = jitted
        return jitted

    def pretrainLayer(self, idx: int, data, epochs: int = 1):
        """Unsupervised training of ONE layer (reference:
        MultiLayerNetwork#pretrainLayer(int, DataSetIterator)): lower
        layers act as a frozen feature extractor; only layer ``idx``'s
        params (and its updater state) change. ``data`` is features —
        an array, DataSet or DataSetIterator (labels ignored)."""
        self._check_init()
        layer = self.conf.layers[idx]
        if not hasattr(layer, "unsupervised_loss"):
            raise ValueError(
                f"layer {idx} ({type(layer).__name__}) is not "
                "pretrainable — only layers with an unsupervised loss "
                "(VariationalAutoencoder, AutoEncoder) support "
                "pretrainLayer")
        step = self._get_pretrain_step(idx)

        def batches():
            if isinstance(data, DataSetIterator):
                for ds in data:
                    yield ds.features
            elif isinstance(data, DataSet):
                yield data.features
            else:
                yield data

        for _ in range(epochs):
            for xb in batches():
                x = jnp.asarray(_unwrap(xb), self._input_dtype)
                self._rng_key, sub = jax.random.split(self._rng_key)
                (self.params_list[idx], self.opt_states[idx],
                 loss) = step(self.params_list[idx], self.params_list,
                              self.states_list, self.opt_states[idx],
                              jnp.asarray(self._iteration), x, sub)
                self._score = loss
                self._iteration += 1
        return self

    def pretrain(self, data, epochs: int = 1):
        """Layerwise pretrain of every pretrainable layer, bottom-up
        (reference: MultiLayerNetwork#pretrain(DataSetIterator))."""
        for idx, layer in enumerate(self.conf.layers):
            if hasattr(layer, "unsupervised_loss"):
                self.pretrainLayer(idx, data, epochs)
        return self

    def reconstructionLogProbability(self, idx: int, x,
                                     num_samples: int = 16) -> NDArray:
        """Importance-sampled log p(x) from the VAE at layer ``idx``
        (reference: VariationalAutoencoder#reconstructionLogProbability
        — the anomaly-detection score)."""
        self._check_init()
        layer = self.conf.layers[idx]
        if not hasattr(layer, "reconstruction_log_prob"):
            raise ValueError(f"layer {idx} is not a "
                             "VariationalAutoencoder")
        xj = jnp.asarray(_unwrap(x), self._input_dtype)
        a = self._prefix_activations(idx, self.params_list,
                                     self.states_list, xj)
        self._rng_key, sub = jax.random.split(self._rng_key)
        return NDArray(layer.reconstruction_log_prob(
            self.params_list[idx], a, sub, num_samples))

    # ------------------------------------------------------------------
    # inference / scoring
    # ------------------------------------------------------------------
    def output(self, x, train: bool = False, features_mask=None) -> NDArray:
        """Reference: MultiLayerNetwork#output(INDArray, train[, mask]).
        Compiled forward; train=True uses batch statistics + dropout.
        features_mask keeps inference consistent with masked training
        (zeroed padding + masked global pooling)."""
        self._check_init()
        xj = jnp.asarray(_unwrap(x), self._input_dtype)
        fm = self._validate_fmask(features_mask, xj)
        if train:
            self._rng_key, sub = jax.random.split(self._rng_key)
        else:
            sub = None
        out = self._get_forward(train, fm is not None)(
            self.params_list, self.states_list, xj, sub, fm)
        return NDArray(out)

    def feedForward(self, x) -> List[NDArray]:
        """Per-layer activations (reference returns the full list)."""
        self._check_init()
        a = jnp.asarray(_unwrap(x), self._input_dtype)
        acts = [NDArray(a)]
        for i, layer in enumerate(self.conf.layers):
            tag = self.conf.preprocessors.get(i)
            if tag:
                a = apply_preprocessor(tag, a)
            a = self._cast_a(a, i)
            a, _ = layer.apply(self._cast_p(self.params_list[i], i),
                               self.states_list[i], a, False, None)
            acts.append(NDArray(a))
        if self._mixed and acts:
            acts[-1] = NDArray(
                _precision.cast_leaf(acts[-1].jax, self._out_dtype))
        return acts

    # ------------------------------------------------------------------
    # stateful RNN stepping (reference: MultiLayerNetwork#rnnTimeStep,
    # rnnClearPreviousState, rnnGetPreviousState — SURVEY.md §5)
    # ------------------------------------------------------------------
    def _rnn_step_forward(self, params_list, states_list, carries, x):
        conf = self.conf
        a = x
        new_carries = []
        for i, layer in enumerate(conf.layers):
            tag = conf.preprocessors.get(i)
            if tag:
                a = apply_preprocessor(tag, a)
            a = self._cast_a(a, i)
            p_i = self._cast_p(params_list[i], i)
            if layer.is_recurrent:
                a, _, c = layer.apply_with_carry(
                    p_i, states_list[i], carries[i], a, False, None)
            else:
                a, _ = layer.apply(p_i, states_list[i], a, False, None)
                c = None
            new_carries.append(c)
        if self._mixed:
            a = _precision.cast_leaf(a, self._out_dtype)
        return a, new_carries

    def rnnTimeStep(self, x) -> NDArray:
        """One (or more) timesteps of stateful inference: hidden state is
        kept across calls so long sequences can be generated step by step
        without re-running history. 2-D input [N,F] means a single step
        and returns [N,out]; 3-D [N,T,F] steps T times, returns [N,T,out]."""
        self._check_init()
        xj = jnp.asarray(_unwrap(x), self._input_dtype)
        single = xj.ndim == 2
        if single:
            xj = xj[:, None, :]
        n = xj.shape[0]
        if self._rnn_carries is not None and self._rnn_batch != n:
            raise ValueError(
                f"rnnTimeStep batch size changed ({self._rnn_batch} -> {n}) "
                "with stored state — call rnnClearPreviousState() first "
                "(reference behavior: mini-batch mismatch is an error)")
        if self._rnn_carries is None:
            self._rnn_carries = [
                (l.init_carry(n, self._cd(i)) if l.is_recurrent else None)
                for i, l in enumerate(self.conf.layers)]
            self._rnn_batch = n
        if "rnn_step" not in self._fwd_cache:
            self._fwd_cache["rnn_step"] = _telemetry.instrument_jit(
                "mln_rnn_step", jax.jit(self._rnn_step_forward))
        out, self._rnn_carries = self._fwd_cache["rnn_step"](
            self.params_list, self.states_list, self._rnn_carries, xj)
        if single and out.ndim == 3:
            out = out[:, 0]
        return NDArray(out)

    rnn_time_step = rnnTimeStep

    def rnnClearPreviousState(self) -> None:
        self._rnn_carries = None
        self._rnn_batch = 0

    def rnnGetPreviousState(self, layer_idx: int):
        """Stored hidden state of one layer (LSTM: (h, c); SimpleRnn: h),
        or None if stateless / no step taken yet."""
        if self._rnn_carries is None:
            return None
        return self._rnn_carries[layer_idx]

    def rnnSetPreviousState(self, layer_idx: int, state) -> None:
        if self._rnn_carries is None:
            raise RuntimeError("No rnnTimeStep state yet — step once or "
                               "set all layers explicitly")
        self._rnn_carries[layer_idx] = state

    def score(self, dataset: Optional[DataSet] = None) -> float:
        """Last minibatch loss, or loss on a provided DataSet."""
        if dataset is None:
            return float(self._score)
        self._check_init()
        loss, _ = self._loss(self.params_list, self.states_list,
                             jnp.asarray(dataset.features,
                                         self._input_dtype),
                             jnp.asarray(dataset.labels),
                             dataset.labels_mask, None)
        return float(loss)

    def backpropGradient(self, x, external_errors, train: bool = True,
                         features_mask=None):
        """Backprop EXTERNAL errors through the whole net (reference:
        MultiLayerNetwork#backpropGradient(epsilon, workspaceMgr) — the
        embed-in-a-custom-training-loop workflow: the caller owns the
        loss, hands dL/dOutput here, and receives (parameter gradients,
        epsilon at the input)).

        TPU-first: one ``jax.vjp`` over the same compiled train-mode
        forward ``output(train=True)`` uses, so the whole
        forward+backward is XLA-fused; gradients come back in the
        ``params_list`` pytree layout (what ``updater.apply`` and
        ``computeGradientAndScore`` use)."""
        self._check_init()
        xj = jnp.asarray(_unwrap(x), self._input_dtype)
        err = jnp.asarray(_unwrap(external_errors), self._out_dtype)
        fm = self._validate_fmask(features_mask, xj)
        saved_key = self._rng_key
        if train:
            self._rng_key, sub = jax.random.split(self._rng_key)
        else:
            sub = None
        fwd = self._get_forward(train, fm is not None)
        out, vjp = jax.vjp(
            lambda pl, xx: fwd(pl, self.states_list, xx, sub, fm),
            self.params_list, xj)
        if err.shape != out.shape:
            self._rng_key = saved_key   # failed call must not advance
            #                             the dropout stream
            raise ValueError(
                f"external_errors shape {err.shape} must match the "
                f"network output shape {out.shape}")
        grads, eps = vjp(err)
        return grads, NDArray(eps)

    def computeGradientAndScore(self, x, y):
        """(gradients, score) — the seam gradient-check tests use
        (reference: MultiLayerNetwork#computeGradientAndScore)."""
        self._check_init()
        x = jnp.asarray(_unwrap(x), self._input_dtype)
        y = jnp.asarray(_unwrap(y))
        loss_fn = lambda pl: self._loss(pl, self.states_list, x, y, None, None)[0]
        loss, grads = jax.value_and_grad(loss_fn)(self.params_list)
        return grads, float(loss)

    def evaluate(self, iterator: DataSetIterator, batch_output=None):
        """Classification evaluation (reference: MultiLayerNetwork#evaluate)."""
        from deeplearning4j_tpu.evaluation import Evaluation

        ev = Evaluation()
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            ev.eval(ds.labels, out.jax, mask=_eval_mask(ds))
        return ev

    def evaluateROC(self, iterator: DataSetIterator, threshold_steps=0):
        """Binary ROC/AUC (reference: MultiLayerNetwork#evaluateROC;
        expects a 1- or 2-column probability output). threshold_steps
        is accepted for API parity but the sweep is always EXACT
        (thresholdSteps=0 mode — strictly more accurate)."""
        from deeplearning4j_tpu.evaluation import ROC

        roc = ROC()
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            roc.eval(ds.labels, out.jax, mask=_eval_mask(ds))
        return roc

    def evaluateROCMultiClass(self, iterator: DataSetIterator,
                              threshold_steps=0):
        """One-vs-all ROC per class (reference:
        MultiLayerNetwork#evaluateROCMultiClass; exact sweep)."""
        from deeplearning4j_tpu.evaluation import ROCMultiClass

        roc = ROCMultiClass()
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            roc.eval(ds.labels, out.jax, mask=_eval_mask(ds))
        return roc

    def evaluateRegression(self, iterator: DataSetIterator):
        from deeplearning4j_tpu.evaluation import RegressionEvaluation

        ev = RegressionEvaluation()
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            mask = ds.labels_mask
            if mask is None and ds.features_mask is not None \
                    and np.asarray(ds.labels).ndim == 3:
                mask = ds.features_mask
            ev.eval(ds.labels, out.jax, mask=mask)
        return ev

    # ------------------------------------------------------------------
    # parameter access (reference: params()/setParams() flat views)
    # ------------------------------------------------------------------
    def _flat_order(self):
        """Deterministic (layer, key) order for the flat param vector."""
        order = []
        for i, p in enumerate(self.params_list):
            for k in sorted(p):
                order.append((i, k))
        return order

    def params(self) -> NDArray:
        """Single flat param vector (reference's flat view — here a copy;
        mutation goes through setParams, not aliasing)."""
        self._check_init()
        parts = [self.params_list[i][k].ravel() for i, k in self._flat_order()]
        return NDArray(jnp.concatenate(parts)) if parts else NDArray(jnp.zeros(0))

    def setParams(self, flat) -> None:
        self._check_init()
        v = _unwrap(flat)
        off = 0
        for i, k in self._flat_order():
            cur = self.params_list[i][k]
            n = cur.size
            self.params_list[i][k] = v[off:off + n].reshape(cur.shape).astype(cur.dtype)
            off += n
        if off != v.size:
            raise ValueError(f"Param length mismatch: {off} vs {v.size}")

    def numParams(self) -> int:
        self._check_init()
        return sum(int(l.size) for p in self.params_list
                   for l in jax.tree_util.tree_leaves(p))

    def paramTable(self) -> dict:
        """{'0_W': array, ...} flat name map (reference: paramTable())."""
        self._check_init()
        return {f"{i}_{k}": NDArray(self.params_list[i][k])
                for i, k in self._flat_order()}

    # ------------------------------------------------------------------
    # listeners / misc (reference: setListeners, summary)
    # ------------------------------------------------------------------
    def setListeners(self, *listeners):
        self._listeners = list(listeners)
        return self

    def addListeners(self, *listeners):
        self._listeners.extend(listeners)
        return self

    def setHealthMonitor(self, monitor) -> "MultiLayerNetwork":
        """Attach (or with None, detach) an in-step HealthMonitor
        (profiler/model_health.py). Toggling costs exactly one extra
        compile per jit site; attached, every train step also emits
        per-layer grad/update/param stats + NaN provenance, fetched
        once every ``monitor.frequency`` steps."""
        self._health = monitor
        return self

    def getHealthMonitor(self):
        return self._health

    def getListeners(self):
        return list(self._listeners)

    def getIterationCount(self) -> int:
        return self._iteration

    def getEpochCount(self) -> int:
        return self._epoch

    def summary(self) -> str:
        self._check_init()
        lines = [f"{'idx':<4}{'layer':<28}{'params':>12}  out_type"]
        it = self.conf.input_type
        total = 0
        for i, layer in enumerate(self.conf.layers):
            n = sum(int(l.size) for l in jax.tree_util.tree_leaves(self.params_list[i]))
            total += n
            ot = layer.output_type(it) if it else None
            lines.append(f"{i:<4}{type(layer).__name__:<28}{n:>12,}  "
                         f"{(ot.kind + str(ot.example_shape())) if ot else '?'}")
            it = ot
        lines.append(f"Total params: {total:,}")
        return "\n".join(lines)

    def clone(self) -> "MultiLayerNetwork":
        m = MultiLayerNetwork(self.conf)
        if self.params_list is not None:
            m.init()
            m.params_list = jax.tree_util.tree_map(lambda a: a, self.params_list)
            m.states_list = jax.tree_util.tree_map(lambda a: a, self.states_list)
            m.opt_states = jax.tree_util.tree_map(lambda a: a, self.opt_states)
            if self._loss_scale_state is not None:
                m._loss_scale_state = jax.tree_util.tree_map(
                    lambda a: a, self._loss_scale_state)
                m._ls_seen = self._ls_seen
        return m
