"""Pipeline parallelism (GPipe-style fill/drain microbatch schedule).

The reference has NO pipeline parallelism (SURVEY.md §2 'Parallelism
strategies present in the reference': data parallelism only) — this is
a TPU-first extension: stages live on a 'pipe' mesh axis, and the whole
schedule is ONE compiled SPMD program:

- Layer params are stacked to leaves [n_stages, layers_per_stage, ...]
  and sharded over 'pipe' on the leading axis, so each device holds only
  its stage's weights (what makes models larger than one chip's HBM
  trainable).
- A `lax.scan` over `n_micro + n_stages - 1` ticks runs the fill/drain
  schedule; activations hop stage→stage+1 via `lax.ppermute` each tick.
- The BACKWARD pipeline is not hand-written: `jax.grad` differentiates
  through the scan and the ppermute (whose transpose is the reverse
  permute), yielding the mirrored drain/fill schedule automatically.
- Embeddings and the tied MLM head are replicated across 'pipe'
  (stage 0 consumes the embedding, the last stage the head); their
  gradient contributions are psum'd over ('data', 'pipe').

Loss math is EXACTLY the unpipelined model's (sum over masked tokens /
count), so pipelined and single-device training produce the same values
up to float reassociation — the equivalence test in
tests/test_pipeline.py asserts this.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import shard_map


def _tmap(f, *trees, **kw):
    return jax.tree_util.tree_map(f, *trees, **kw)


class PipelinedTransformer:
    """Wraps a TransformerEncoder with a GPipe schedule over mesh axes
    ('data', 'pipe')."""

    def __init__(self, encoder, n_stages: int):
        cfg = encoder.cfg
        if cfg.n_layers % n_stages != 0:
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible by "
                f"n_stages={n_stages}")
        self.enc = encoder
        self.n_stages = n_stages
        self.layers_per_stage = cfg.n_layers // n_stages
        self._eval_cache: Dict[Any, Any] = {}

    # ------------------------------------------------------------------
    # parameter layout
    # ------------------------------------------------------------------
    def stack_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """[{layer0}, {layer1}, ...] -> leaves [S, Lps, ...]."""
        stacked = _tmap(lambda *xs: jnp.stack(xs), *params["layers"])
        s, l = self.n_stages, self.layers_per_stage
        stacked = _tmap(
            lambda a: a.reshape((s, l) + a.shape[1:]), stacked)
        out = {k: v for k, v in params.items() if k != "layers"}
        out["stages"] = stacked
        return out

    def unstack_params(self, sp: Dict[str, Any]) -> Dict[str, Any]:
        flat = _tmap(
            lambda a: a.reshape((self.enc.cfg.n_layers,) + a.shape[2:]),
            sp["stages"])
        layers = [
            _tmap(lambda a: a[i], flat) for i in range(self.enc.cfg.n_layers)
        ]
        out = {k: v for k, v in sp.items() if k != "stages"}
        out["layers"] = layers
        return out

    def param_specs(self) -> Dict[str, Any]:
        """'stages' sharded over 'pipe' on the stage axis; everything
        else replicated (embeddings/head used at the pipeline ends).
        Derived from the encoder's own param tree so a new per-layer
        param never needs a second schema here."""
        template = jax.eval_shape(self.enc.init_params)
        out = {}
        for k, v in template.items():
            if k == "layers":
                out["stages"] = _tmap(lambda _: P("pipe"), v[0])
            else:
                out[k] = _tmap(lambda _: P(), v)
        return out

    def shard_params(self, params: Dict[str, Any], mesh: Mesh):
        sp = self.stack_params(params)
        specs = self.param_specs()
        return _tmap(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            sp, specs, is_leaf=lambda x: isinstance(x, P))

    # ------------------------------------------------------------------
    # the schedule
    # ------------------------------------------------------------------
    def _stage_apply(self, stage_params, x, train, rng, stage_id):
        """Run this device's layers_per_stage layers over x. Returns
        (out, aux_sum) — the MoE balance-loss sum over this stage's
        layers (0.0 for dense FFN configs)."""
        enc = self.enc

        def body(carry, inp):
            lp, li = inp
            x_c, aux_c = carry
            key = (jax.random.fold_in(rng, stage_id * self.layers_per_stage
                                      + li)
                   if (train and rng is not None) else None)
            y, aux = enc._block(x_c, lp, None, train, key, False)
            return (y, aux_c + aux), None

        lidx = jnp.arange(self.layers_per_stage)
        (out, aux), _ = lax.scan(body, (x, jnp.float32(0.0)),
                                 (stage_params, lidx))
        return out, aux

    def _local_loss_terms(self, params, ids, labels, mask_pos, train, rng):
        """Per-(data,pipe)-shard pipelined forward; returns local
        (masked log-prob sum, mask count, MoE aux sum) — psum'd by the
        caller (aux is 0.0 for dense configs).

        ids/labels/mask_pos: LOCAL [n_micro, mb, T].
        """
        enc = self.enc
        cfg = enc.cfg
        cd = enc._cdtype
        s = self.n_stages
        n_micro, mb, t = ids.shape
        stage = lax.axis_index("pipe")
        # each device's slice of the stacked stage tree has a leading
        # stage axis of size 1 inside shard_map — drop it
        stage_params = _tmap(lambda a: a[0], params["stages"])

        def embed(mi):
            mids = lax.dynamic_index_in_dim(ids, mi, keepdims=False)
            x = params["tok_emb"].astype(cd)[mids]
            x = x + params["pos_emb"].astype(cd)[None, :t]
            x = enc._ln(x, {k: v.astype(cd)
                            for k, v in params["emb_ln"].items()})
            return x

        def ce_terms(hidden, mi):
            mlab = lax.dynamic_index_in_dim(labels, mi, keepdims=False)
            mmask = lax.dynamic_index_in_dim(mask_pos, mi, keepdims=False)
            logits = enc.mlm_logits(params, hidden).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tok = jnp.take_along_axis(logits, mlab[..., None],
                                      axis=-1)[..., 0]
            return jnp.sum((tok - lse) * mmask), jnp.sum(mmask)

        def tick(carry, tk):
            x_recv, num, den, aux = carry
            # stage 0 ingests microbatch `tk` (clamped during drain);
            # later stages consume what arrived on the wire. lax.cond,
            # not jnp.where: only stage 0 should PAY for the embedding
            # lookup (and below, only the last stage for the V-wide
            # logits matmul) — where() would run both on every rank
            mi_in = jnp.clip(tk, 0, n_micro - 1)
            x_in = lax.cond(stage == 0, lambda: embed(mi_in),
                            lambda: x_recv)
            key = (jax.random.fold_in(rng, tk)
                   if (train and rng is not None) else None)
            h, aux_t = self._stage_apply(stage_params, x_in, train, key,
                                         stage)
            # MoE aux: count only ticks where THIS stage processed a
            # real microbatch (fill/drain ticks run on garbage)
            aux_real = jnp.logical_and(tk >= stage,
                                       tk < stage + n_micro)
            aux = aux + jnp.where(aux_real, aux_t, 0.0)
            # last stage scores microbatch tk-(S-1) once it's real
            mi_out = tk - (s - 1)
            valid = jnp.logical_and(stage == s - 1,
                                    jnp.logical_and(mi_out >= 0,
                                                    mi_out < n_micro))
            n_, d_ = lax.cond(
                valid,
                lambda: ce_terms(h, jnp.clip(mi_out, 0, n_micro - 1)),
                lambda: (jnp.float32(0.0), jnp.float32(0.0)))
            num = num + n_
            den = den + d_
            # hop to the next stage (ring closes the last->first link;
            # the drained value arriving at stage 0 is overwritten by
            # the embedding select above)
            perm = [(i, (i + 1) % s) for i in range(s)]
            x_send = lax.ppermute(h, "pipe", perm)
            return (x_send, num, den, aux), None

        zero_x = jnp.zeros((mb, t, cfg.d_model), cd)
        ticks = jnp.arange(n_micro + s - 1)
        (_, num, den, aux), _ = lax.scan(
            tick, (zero_x, 0.0, 0.0, jnp.float32(0.0)), ticks)
        return num, den, aux

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def make_train_step(self, updater, mesh: Mesh, n_micro: int):
        """Compiled DP x PP MLM train step over mesh ('data', 'pipe').

        Batch [N, T] is split into n_micro microbatches per data shard;
        gradients for replicated leaves psum over ('data','pipe'),
        stage-sharded leaves over 'data' only."""
        enc = self.enc
        specs = self.param_specs()

        def per_shard(params, ids, labels, mask_pos, rng):
            rng = jax.random.fold_in(rng, lax.axis_index("data"))
            dp = lax.axis_size("data")
            n_mb = ids.shape[0]
            # global mask count is params-independent — precompute so
            # the MoE aux term can be pre-scaled by it inside the local
            # objective (it gets divided back out with the grads below).
            # mask_pos is replicated across 'pipe' (sharded over 'data'
            # only), so reduce over 'data' alone.
            den_g = jnp.maximum(
                lax.psum(jnp.sum(mask_pos), "data"), 1.0)
            aux_w = getattr(enc.cfg, "aux_loss_weight", 0.0) \
                if getattr(enc.cfg, "n_experts", 0) else 0.0

            # Differentiate the LOCAL unnormalized objective (-num), NOT
            # an already-psum'd scalar: lax.psum's transpose is psum, so
            # grad-of-replicated-loss inflates every cotangent by the
            # mesh size. The ppermute transposes already route each
            # rank's cotangents back through the pipeline, so the local
            # grad of -num IS the global grad restricted to this rank's
            # data shard; normalize by the global mask count afterward.
            def local_obj(p):
                num, den, aux = self._local_loss_terms(
                    p, ids, labels, mask_pos, True, rng)
                obj = -num
                if aux_w:
                    # target global term: w * psum(aux) / (dp*n_micro);
                    # pre-multiply by den_g since grads are /den_g later
                    obj = obj + aux_w * aux * den_g / (dp * n_mb)
                return obj, (num, den, aux)

            (_, (num, den, aux)), grads = jax.value_and_grad(
                local_obj, has_aux=True)(params)
            num_g = lax.psum(num, ("data", "pipe"))
            loss = -num_g / den_g
            if aux_w:
                loss = loss + aux_w * lax.psum(
                    aux, ("data", "pipe")) / (dp * n_mb)
            # stage-sharded leaves: each pipe rank owns its stage's
            # grads (data-reduce only). Replicated leaves: partial
            # contributions live on the pipeline ends — sum them.
            grads = _tmap(
                lambda g, s: lax.psum(g, "data") if s == P("pipe")
                else lax.psum(g, ("data", "pipe")),
                grads, specs, is_leaf=lambda x: isinstance(x, P))
            grads = _tmap(lambda g: g / den_g, grads)
            return loss, grads

        in_specs = (specs, P("data"), P("data"), P("data"), P())
        out_specs = (P(), specs)
        smapped = shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)

        def step(params, opt_state, it_step, ids, labels, mask_pos, rng):
            sm = self._split_micro(mesh, n_micro)
            loss, grads = smapped(params, sm(ids), sm(labels),
                                  sm(mask_pos), rng)
            new_params, new_opt = enc._apply_updates(
                updater, params, opt_state, grads, it_step)
            return new_params, new_opt, loss

        # split_micro's reshape puts [dp*n_micro, mb, T]: shard_map's
        # P('data') splits the leading axis so each data shard sees
        # [n_micro, mb, T]
        return jax.jit(step, donate_argnums=(0, 1))

    @staticmethod
    def _split_micro(mesh: Mesh, n_micro: int):
        """[N, ...] -> [dp*n_micro, mb, ...] with a clear error on
        indivisible batches (shared by train and eval paths)."""
        dp = mesh.shape["data"]

        def split(a):
            n = a.shape[0]
            if n % (dp * n_micro) != 0:
                raise ValueError(
                    f"batch {n} not divisible by data_parallel*"
                    f"n_micro={dp * n_micro}")
            return a.reshape((dp * n_micro, n // (dp * n_micro))
                             + a.shape[1:])

        return split

    def make_eval_loss(self, mesh: Mesh, n_micro: int):
        """Compiled pipelined eval loss (train=False); cached per
        (mesh, n_micro) so repeated eval calls don't recompile."""
        key = (mesh, n_micro)
        cached = self._eval_cache.get(key)
        if cached is not None:
            return cached
        specs = self.param_specs()

        def per_shard(params, i, l, m):
            num, den, _aux = self._local_loss_terms(
                params, i, l, m, False, None)
            num = lax.psum(num, ("data", "pipe"))
            den = lax.psum(den, ("data", "pipe"))
            return -num / jnp.maximum(den, 1.0)

        smapped = shard_map(
            per_shard, mesh=mesh,
            in_specs=(specs, P("data"), P("data"), P("data")),
            out_specs=P(), check_vma=False)
        sm = self._split_micro(mesh, n_micro)
        fn = jax.jit(lambda p, i, l, m: smapped(p, sm(i), sm(l), sm(m)))
        self._eval_cache[key] = fn
        return fn

    def eval_loss(self, params_stacked, ids, labels, mask_pos, mesh: Mesh,
                  n_micro: int):
        """Pipelined eval loss (train=False) — for equivalence tests."""
        return self.make_eval_loss(mesh, n_micro)(
            params_stacked, ids, labels, mask_pos)
