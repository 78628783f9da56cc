"""TransformerEncoder — the flagship distributed model.

Reference parity target: BERT-base via SameDiff TF-import
(BASELINE.md; SURVEY.md §3.4). The reference executes the imported
graph node-by-node in a Java interpreter loop; here the model is a pure
jax function whose whole training step compiles to one XLA program, and
whose parallelism is declared as sharding specs over a
('data', 'model') mesh:

- DP: batch axis sharded over 'data'.
- TP (Megatron-style): QKV and MLP-in projections column-sharded over
  'model' (P(None, 'model')), attention-out and MLP-out row-sharded
  (P('model', None)) — XLA GSPMD inserts the all-reduces on ICI.
- SP (sequence parallelism): between blocks, activations are sharded
  over the token axis on 'model' (P('data', 'model', None)) so
  layernorm/residual/dropout work is divided rather than replicated —
  the reshard to/from head-sharded attention is GSPMD's all-to-all.
  This is what lets sequence length scale past one chip's HBM, the
  capability the reference entirely lacks (SURVEY.md §5 long-context).

The encoder trains masked-LM style (tied output head) or
classification; both heads are provided.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.common.serde import serializable


@serializable
@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 30522          # BERT-base vocab
    max_len: int = 512
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    dropout: float = 0.1
    type_vocab: int = 2
    eps: float = 1e-12
    dtype: str = "float32"           # params; compute may be bf16
    compute_dtype: str = "bfloat16"  # MXU-native
    seed: int = 0
    # Mixture-of-Experts (0 = dense FFN). When set, EVERY layer's FFN is
    # an expert-parallel MoE, sharded over the 'model' mesh axis
    # (models/moe.py). Composes with every engine: make_train_step
    # (GSPMD EP), make_ring_train_step (SP x EP: shard-local routing,
    # aux pmean'd over the mesh), and PipelinedTransformer (PP x EP:
    # per-stage aux sums counted on real microbatch ticks only).
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def bert_base() -> TransformerConfig:
    return TransformerConfig()


def tiny_config(vocab=128, max_len=64, d_model=64, n_layers=2, n_heads=4,
                d_ff=128) -> TransformerConfig:
    return TransformerConfig(vocab_size=vocab, max_len=max_len,
                             d_model=d_model, n_layers=n_layers,
                             n_heads=n_heads, d_ff=d_ff,
                             compute_dtype="float32")


class TransformerEncoder:
    def __init__(self, config: TransformerConfig, attn_impl: str = "default"):
        """attn_impl: 'default' (fused XLA softmax attention) or 'flash'
        (ops.flash_attention dispatcher: Pallas kernels on TPU,
        blockwise online-softmax elsewhere — O(T) memory)."""
        self.cfg = config
        self.attn_impl = attn_impl
        self._pdtype = jnp.dtype(config.dtype)
        self._cdtype = jnp.dtype(config.compute_dtype)

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------
    def init_params(self, key=None) -> Dict[str, Any]:
        cfg = self.cfg
        key = key if key is not None else jax.random.key(cfg.seed)
        d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
        std = 0.02

        def norm(k, shape):
            return std * jax.random.normal(k, shape, self._pdtype)

        keys = jax.random.split(key, 4 + cfg.n_layers)
        params = {
            "tok_emb": norm(keys[0], (v, d)),
            "pos_emb": norm(keys[1], (cfg.max_len, d)),
            "type_emb": norm(keys[2], (cfg.type_vocab, d)),
            "emb_ln": {"gamma": jnp.ones((d,), self._pdtype),
                       "beta": jnp.zeros((d,), self._pdtype)},
            "layers": [],
        }
        for li in range(cfg.n_layers):
            ks = jax.random.split(keys[4 + li], 6)
            lp = {
                "wqkv": norm(ks[0], (d, 3 * d)),
                "bqkv": jnp.zeros((3 * d,), self._pdtype),
                "wo": norm(ks[1], (d, d)),
                "bo": jnp.zeros((d,), self._pdtype),
                "ln1": {"gamma": jnp.ones((d,), self._pdtype),
                        "beta": jnp.zeros((d,), self._pdtype)},
                "ln2": {"gamma": jnp.ones((d,), self._pdtype),
                        "beta": jnp.zeros((d,), self._pdtype)},
            }
            if cfg.n_experts:
                e = cfg.n_experts
                lp.update({
                    "wr": norm(ks[4], (d, e)),
                    "we1": norm(ks[2], (e, d, f)),
                    "be1": jnp.zeros((e, f), self._pdtype),
                    "we2": norm(ks[3], (e, f, d)),
                    "be2": jnp.zeros((e, d), self._pdtype),
                })
            else:
                lp.update({
                    "w1": norm(ks[2], (d, f)),
                    "b1": jnp.zeros((f,), self._pdtype),
                    "w2": norm(ks[3], (f, d)),
                    "b2": jnp.zeros((d,), self._pdtype),
                })
            params["layers"].append(lp)
        params["mlm_bias"] = jnp.zeros((v,), self._pdtype)
        return params

    # ------------------------------------------------------------------
    # sharding specs (Megatron TP + SP between blocks)
    # ------------------------------------------------------------------
    def param_specs(self) -> Dict[str, Any]:
        rep = P()
        ln = {"gamma": rep, "beta": rep}
        layer = {
            "wqkv": P(None, "model"),   # column-parallel
            "bqkv": P("model"),
            "wo": P("model", None),     # row-parallel
            "bo": rep,
            "ln1": ln,
            "ln2": ln,
        }
        if self.cfg.n_experts:
            layer.update({
                # expert parallelism: expert stack over 'model'
                "wr": rep,
                "we1": P("model", None, None),
                "be1": P("model", None),
                "we2": P("model", None, None),
                "be2": P("model", None),
            })
        else:
            layer.update({
                "w1": P(None, "model"),
                "b1": P("model"),
                "w2": P("model", None),
                "b2": rep,
            })
        return {
            "tok_emb": P(None, "model"),
            "pos_emb": rep,
            "type_emb": rep,
            "emb_ln": ln,
            "layers": [dict(layer) for _ in range(self.cfg.n_layers)],
            "mlm_bias": rep,
        }

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _ln(self, x, p):
        m = jnp.mean(x, -1, keepdims=True)
        v = jnp.var(x, -1, keepdims=True)
        return (x - m) * lax.rsqrt(v + self.cfg.eps) * p["gamma"] + p["beta"]

    def _sp(self, x, sharded: bool):
        """Sequence-parallel constraint between blocks (token axis on
        'model'); no-op when running unsharded."""
        if not sharded:
            return x
        return lax.with_sharding_constraint(x, P("data", "model", None))

    def _attn_sp(self, x, sharded: bool):
        if not sharded:
            return x
        return lax.with_sharding_constraint(x, P("data", None, "model"))

    def encode(self, params, ids, type_ids=None, mask=None, train=False,
               rng=None, sharded=False, return_aux=False):
        """ids: [N, T] int32 -> hidden [N, T, D]."""
        cfg = self.cfg
        n, t = ids.shape
        cd = self._cdtype
        x = params["tok_emb"].astype(cd)[ids]
        x = x + params["pos_emb"].astype(cd)[None, :t]
        if type_ids is not None:
            x = x + params["type_emb"].astype(cd)[type_ids]
        x = self._ln(x, {k: v.astype(cd) for k, v in params["emb_ln"].items()})
        x = self._sp(x, sharded)

        att_mask = None
        if mask is not None:
            att_mask = mask[:, None, None, :]  # [N,1,1,T] key padding

        attn_fn = None
        if self.attn_impl == "flash":
            from deeplearning4j_tpu.ops.flash_attention import attention

            def attn_fn(q, k, v, m):
                key_mask = None if m is None else m[:, 0, 0, :]
                return attention(q, k, v, key_mask)

        keys = (jax.random.split(rng, cfg.n_layers)
                if (train and rng is not None) else [None] * cfg.n_layers)
        aux_total = jnp.float32(0.0)
        for li, lp in enumerate(params["layers"]):
            x, aux = self._block(x, lp, att_mask, train, keys[li], sharded,
                                 attn_fn=attn_fn)
            aux_total = aux_total + aux
        if return_aux:
            return x, aux_total
        return x

    def _block(self, x, lp, att_mask, train, rng, sharded, attn_fn=None):
        cfg = self.cfg
        cd = self._cdtype
        n, t, d = x.shape
        h, hd = cfg.n_heads, cfg.head_dim

        # attention (post-LN like BERT: LN after residual)
        qkv = x @ lp["wqkv"].astype(cd) + lp["bqkv"].astype(cd)
        qkv = self._attn_sp(qkv, sharded)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(y):
            return y.reshape(n, t, h, hd).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        if attn_fn is not None:
            # pluggable attention: ring / ulysses / pallas flash.
            # att_mask must go through the impl (which may need to
            # rotate it around the ring) — never drop it silently.
            ctx = attn_fn(q, k, v, att_mask)
        else:
            scale = 1.0 / jnp.sqrt(jnp.asarray(hd, cd))
            logits = jnp.einsum("nhqd,nhkd->nhqk", q, k) * scale
            if att_mask is not None:
                neg = jnp.asarray(jnp.finfo(logits.dtype).min, logits.dtype)
                logits = jnp.where(att_mask.astype(bool), logits, neg)
            w = jax.nn.softmax(logits, axis=-1)
            ctx = jnp.einsum("nhqk,nhkd->nhqd", w, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(n, t, d)
        att = ctx @ lp["wo"].astype(cd) + lp["bo"].astype(cd)
        if train and rng is not None and cfg.dropout > 0:
            rng, sub = jax.random.split(rng)
            keep = 1.0 - cfg.dropout
            att = att * jax.random.bernoulli(sub, keep, att.shape) / keep
        x = self._sp(x + att, sharded)
        x = self._ln(x, {k2: v2.astype(cd) for k2, v2 in lp["ln1"].items()})

        # MLP — dense FFN or expert-parallel MoE (models/moe.py)
        aux = jnp.float32(0.0)
        if "we1" in lp:
            from deeplearning4j_tpu.models.moe import moe_ffn

            out, aux = moe_ffn(
                x.reshape(n * t, d), lp["wr"], lp["we1"], lp["be1"],
                lp["we2"], lp["be2"], top_k=cfg.expert_top_k,
                capacity_factor=cfg.capacity_factor, sharded=sharded,
                group_size=t)  # per-sequence dispatch groups
            out = out.reshape(n, t, d)
        else:
            hmid = jax.nn.gelu(x @ lp["w1"].astype(cd) + lp["b1"].astype(cd))
            out = hmid @ lp["w2"].astype(cd) + lp["b2"].astype(cd)
        if train and rng is not None and cfg.dropout > 0:
            rng, sub = jax.random.split(rng)
            keep = 1.0 - cfg.dropout
            out = out * jax.random.bernoulli(sub, keep, out.shape) / keep
        x = self._sp(x + out, sharded)
        x = self._ln(x, {k2: v2.astype(cd) for k2, v2 in lp["ln2"].items()})
        return x, aux

    def mlm_logits(self, params, hidden):
        """Tied-embedding MLM head: hidden @ tok_emb^T + bias."""
        return (hidden @ params["tok_emb"].astype(hidden.dtype).T
                + params["mlm_bias"].astype(hidden.dtype))

    # ------------------------------------------------------------------
    # losses / training step
    # ------------------------------------------------------------------
    def mlm_loss(self, params, ids, labels, mask_positions, train=True,
                 rng=None, sharded=False, masked_capacity=None):
        """labels: [N,T] int32 with targets; mask_positions: [N,T] 1.0
        where the token was masked (loss only there).

        Memory/FLOPs design: the [N,T,V] log-probability tensor is never
        materialized — per-token CE is logit[label] - logsumexp(logits),
        which XLA fuses into the vocab matmul's epilogue. With
        `masked_capacity=K`, only the top-K masked positions per row are
        projected to the vocab at all (hidden gather before the V-wide
        matmul) — the standard MLM-head optimization: ~15% of positions
        carry loss, so the 768x30522 matmul shrinks ~6.7x. Positions
        beyond K are dropped from the loss (choose K >= max masked/row
        for exactness).
        """
        hidden, aux = self.encode(params, ids, train=train, rng=rng,
                                  sharded=sharded, return_aux=True)
        if masked_capacity is not None:
            k = int(masked_capacity)
            # indices of the K largest mask flags per row (masked first;
            # ties among zeros harmless — they get weight 0)
            w, idx = jax.lax.top_k(mask_positions, k)        # [N,K]
            hidden = jnp.take_along_axis(
                hidden, idx[..., None], axis=1)              # [N,K,D]
            labels = jnp.take_along_axis(labels, idx, axis=1)
            mask_positions = w
        logits = self.mlm_logits(params, hidden).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tok = jnp.take_along_axis(logits, labels[..., None],
                                  axis=-1)[..., 0]
        denom = jnp.maximum(jnp.sum(mask_positions), 1.0)
        ce = -jnp.sum((tok - lse) * mask_positions) / denom
        if self.cfg.n_experts:
            ce = ce + self.cfg.aux_loss_weight * aux
        return ce

    @staticmethod
    def _apply_updates(updater, params, opt_state, grads, it_step):
        """Single definition of update application — shared by the
        GSPMD and ring train steps so updater-policy changes can't
        drift between them."""
        from deeplearning4j_tpu.learning.updaters import apply_updater

        updates, new_opt = apply_updater(updater, opt_state, grads,
                                         params, it_step)
        new_params = jax.tree_util.tree_map(lambda p, u: p - u,
                                            params, updates)
        return new_params, new_opt

    def make_train_step(self, updater, mesh: Optional[Mesh] = None,
                        masked_capacity: Optional[int] = None):
        """Build the compiled train step; with a mesh, params/opt are
        sharded per param_specs and the batch over 'data'.
        masked_capacity: see mlm_loss (vocab-head gather optimization)."""
        sharded = mesh is not None

        def step(params, opt_state, it_step, ids, labels, mask_pos, rng):
            loss, grads = jax.value_and_grad(self.mlm_loss)(
                params, ids, labels, mask_pos, True, rng, sharded,
                masked_capacity)
            new_params, new_opt = self._apply_updates(
                updater, params, opt_state, grads, it_step)
            return new_params, new_opt, loss

        if not sharded:
            return jax.jit(step, donate_argnums=(0, 1))

        specs = self.param_specs()
        pspec = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

        dp = NamedSharding(mesh, P("data", None))
        rep = NamedSharding(mesh, P())
        return jax.jit(
            step,
            in_shardings=(pspec, None, rep, dp, dp, dp, rep),
            # pin the updated params to the SAME shardings as the input:
            # without this GSPMD may emit them re-sharded (observed with
            # MoE: pos_emb came back P('model')), and feeding them to the
            # next step then fails the in_shardings check
            out_shardings=(pspec, None, rep),
            donate_argnums=(0, 1),
        )

    # ------------------------------------------------------------------
    # context parallelism (ring / Ulysses) — DP x SP under shard_map
    # ------------------------------------------------------------------
    def _encode_local(self, params, ids, sp_axis, train, rng, attn,
                      pad_mask=None):
        """Per-shard encode for shard_map: ids is the LOCAL token shard
        [Nl, Tl]; position embeddings are offset by this shard's ring
        index; attention runs via ring/ulysses collectives over sp.
        pad_mask: LOCAL [Nl, Tl], 1.0 = real token (travels around the
        ring with its K/V block inside the attention impl)."""
        from deeplearning4j_tpu.parallel.ring_attention import (
            ring_attention, ulysses_attention,
        )

        cfg = self.cfg
        cd = self._cdtype
        n, t = ids.shape
        n_sp = lax.axis_size(sp_axis)  # static inside shard_map
        if t * n_sp > cfg.max_len:
            raise ValueError(
                f"global sequence {t}*{n_sp}={t * n_sp} exceeds "
                f"max_len={cfg.max_len}; dynamic_slice would clamp and "
                f"silently reuse positions")
        sp = lax.axis_index(sp_axis)
        x = params["tok_emb"].astype(cd)[ids]
        pos = lax.dynamic_slice_in_dim(params["pos_emb"].astype(cd),
                                       sp * t, t, axis=0)
        x = x + pos[None]
        x = self._ln(x, {k: v.astype(cd)
                         for k, v in params["emb_ln"].items()})

        base = (ring_attention if attn == "ring" else ulysses_attention)

        def attn_fn(q, k, v, att_mask):
            assert att_mask is None  # padding travels as kv_mask instead
            return base(q, k, v, axis_name=sp_axis, kv_mask=pad_mask)
        keys = (jax.random.split(rng, cfg.n_layers)
                if (train and rng is not None) else [None] * cfg.n_layers)
        aux_total = jnp.float32(0.0)
        for li, lp in enumerate(params["layers"]):
            # MoE under SP: each shard routes its LOCAL token block
            # (per-sequence-shard dispatch groups); aux is averaged
            # over shards by the caller
            x, aux = self._block(x, lp, None, train, keys[li], False,
                                 attn_fn=attn_fn)
            aux_total = aux_total + aux
        return x, aux_total

    def make_ring_train_step(self, updater, mesh: Mesh, attn: str = "ring"):
        """Compiled DP x SP (context-parallel) MLM train step.

        mesh must have axes ('data', 'sp'). Params are replicated; the
        batch is sharded over 'data' and the TOKEN axis over 'sp' —
        each device holds [N/dp, T/sp] and attention streams K/V blocks
        around the sp ring (or all-to-alls heads for attn='ulysses').
        The reference has no such capability (SURVEY.md §5); this is the
        long-context path. Gradients psum over both axes.
        """
        from deeplearning4j_tpu.parallel.mesh import shard_map

        if attn not in ("ring", "ulysses"):
            raise ValueError(f"attn must be ring|ulysses: {attn}")

        def per_shard_grads(params, ids, labels, mask_pos, pad_mask, rng):
            # distinct dropout streams per shard
            rng = jax.random.fold_in(rng, lax.axis_index("data"))
            rng = jax.random.fold_in(rng, lax.axis_index("sp"))

            def local_loss(p):
                hidden, aux = self._encode_local(
                    p, ids, "sp", True, rng, attn, pad_mask=pad_mask)
                logits = self.mlm_logits(p, hidden).astype(jnp.float32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                tok_lp = jnp.take_along_axis(
                    logp, labels[..., None], axis=-1)[..., 0]
                num = lax.psum(jnp.sum(tok_lp * mask_pos), ("data", "sp"))
                den = lax.psum(jnp.sum(mask_pos), ("data", "sp"))
                loss = -num / jnp.maximum(den, 1.0)
                if self.cfg.n_experts:
                    # shard-local routing: model-level balance loss is
                    # the mean over all (data, sp) shards
                    loss = loss + self.cfg.aux_loss_weight * lax.pmean(
                        aux, ("data", "sp"))
                return loss

            loss, grads = jax.value_and_grad(local_loss)(params)
            grads = lax.psum(grads, ("data", "sp"))
            return loss, grads

        dp_sp = P("data", "sp")
        rep = P()

        def step(params, opt_state, it_step, ids, labels, mask_pos, rng,
                 pad_mask=None):
            if pad_mask is None:  # static branch: None never traces
                smapped = shard_map(
                    lambda p, i, l, m, r: per_shard_grads(p, i, l, m,
                                                          None, r),
                    mesh=mesh,
                    in_specs=(rep, dp_sp, dp_sp, dp_sp, rep),
                    out_specs=(rep, rep), check_vma=False)
                loss, grads = smapped(params, ids, labels, mask_pos, rng)
            else:
                smapped = shard_map(
                    per_shard_grads, mesh=mesh,
                    in_specs=(rep, dp_sp, dp_sp, dp_sp, dp_sp, rep),
                    out_specs=(rep, rep), check_vma=False)
                loss, grads = smapped(params, ids, labels, mask_pos,
                                      pad_mask, rng)
            new_params, new_opt = self._apply_updates(
                updater, params, opt_state, grads, it_step)
            return new_params, new_opt, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def shard_params(self, params, mesh: Mesh):
        specs = self.param_specs()
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, specs,
            is_leaf=lambda x: isinstance(x, (jax.Array,)) or isinstance(x, P))

    def num_params(self, params) -> int:
        return sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
