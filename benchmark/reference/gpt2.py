"""Plain reference of the GPT-2 family: forward, loss, gradients and
Adam in straightforward ``jax.numpy``, float32 with matmuls at
``highest`` precision. No kernels, no cache, no batching tricks.

It imports nothing of the program and takes nothing the program made:
the weights come from ``make_params`` here, from the seed. The program
is handed the same weights in its own layout (``layout="program"``: a
list of per-layer dicts, as ``CausalLM.init_params`` lays them out);
the reference keeps them stacked ``[L, ...]`` and scans over layers, so
it compiles once whatever the depth.

Follows Radford et al. 2019 / the published ``modeling_gpt2``: learned
positions, pre-LN blocks, tanh GELU, LN eps from the configuration,
head tied to the token embedding, attention scaled by 1/sqrt(head).
Departures: none in the forward. Adam is Kingma & Ba's algorithm in the
form of their section 2's last paragraph (``alpha_t = lr sqrt(1-b2^t) /
(1-b1^t)``, ``theta -= alpha_t m / (sqrt(v) + eps)``), which is what
DL4J's Adam and the deployment in the configuration file use.

``precision`` selects the arithmetic: ``"f32"`` is the reference;
``"fp8"`` is the CONTROL, float8 as models are trained and served in it
(Micikevicius et al. 2022, "FP8 Formats for Deep Learning"): every
matmul operand rounded to float8_e4m3fn and, in the backward pass, every
matmul's incoming cotangent to float8_e5m2, each with a per-tensor
scale, accumulation in float32: the nearest precision below the bf16
the configurations state. The control has to come out as
not correct; it never runs inside a benchmark run.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
_LAYER_SHAPES = ("ln1.g", "ln1.b", "wqkv", "bqkv", "wo", "bo",
                 "ln2.g", "ln2.b", "w1", "b1", "w2", "b2")
#: the fused q/k/v leaves are compared third by third: the key's bias has
#: no gradient under softmax, and would hide inside the fused leaf
_THIRDS = ("wqkv", "bqkv")
_QKV = ("q", "k", "v")


def sizes(cfg):
    d = int(cfg["n_embd"])
    return (d, int(cfg.get("n_inner") or 4 * d), int(cfg["n_layer"]),
            int(cfg["n_head"]), int(cfg["vocab_size"]),
            int(cfg["n_positions"]))


def _frozen(cfg):
    """The configuration's numbers as a hashable key for the jit caches."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))))


def seed_key(seed):
    """A key from any whole number up to 2**63: the low 31 bits seed,
    the rest are folded in (``jax.random.key`` takes 32 signed bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


# ------------------------------------------------------------- weights
def _stacked(cfg, key):
    d, f, L, _, V, P = sizes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    ks = jax.random.split(key, 6)

    def n(k, shape, s):
        return jax.random.normal(k, shape, jnp.float32) * s

    one, zero = (lambda *s: jnp.ones(s, jnp.float32)), \
        (lambda *s: jnp.zeros(s, jnp.float32))
    res = std / math.sqrt(2 * L)       # GPT-2's scaled residual init
    return {
        "tok_emb": n(ks[0], (V, d), std),
        "pos_emb": n(ks[1], (P, d), std / 2),
        "ln_f": {"g": one(d), "b": zero(d)},
        "layers": {
            "ln1": {"g": one(L, d), "b": zero(L, d)},
            "wqkv": n(ks[2], (L, d, 3 * d), std), "bqkv": zero(L, 3 * d),
            "wo": n(ks[3], (L, d, d), res), "bo": zero(L, d),
            "ln2": {"g": one(L, d), "b": zero(L, d)},
            "w1": n(ks[4], (L, d, f), std), "b1": zero(L, f),
            "w2": n(ks[5], (L, f, d), res), "b2": zero(L, d),
        },
    }


def _unstack(p, L):
    layers = [jax.tree_util.tree_map(lambda a: a[i], p["layers"])
              for i in range(L)]
    return {"tok_emb": p["tok_emb"], "pos_emb": p["pos_emb"],
            "ln_f": p["ln_f"], "layers": layers}


def make_params(cfg, seed, layout="stacked"):
    """All weights on the device in ONE jitted call from the seed,
    float32. ``layout="program"`` gives the per-layer list the program's
    model takes; both layouts hold the same numbers."""
    L = sizes(cfg)[2]
    return _make_params(_frozen(cfg), layout == "program", L)(seed_key(seed))


@functools.lru_cache(maxsize=8)
def _make_params(frozen, program_layout, L):
    cfg = dict(frozen)

    def make(key):
        p = _stacked(cfg, key)
        return _unstack(p, L) if program_layout else p

    return jax.jit(make)


def _norm(a, axes=None):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)), axis=axes))


def _leaf_norm(path, a, stacked_axis):
    """One leaf's norm: per layer slice where stacked, and the fused
    q/k/v leaves third by third (a trailing axis of 3)."""
    keys = [getattr(p, "key", None) for p in path]
    lead = 1 if (stacked_axis and "layers" in keys) else 0
    if keys[-1] in _THIRDS:
        a = a.reshape(a.shape[:-1] + (3, a.shape[-1] // 3))
        axes = tuple(i for i in range(lead, a.ndim) if i != a.ndim - 2)
        return _norm(a, axes)
    return _norm(a, tuple(range(lead, a.ndim)) if lead else None)


def _flatten(norms, cfg, stacked):
    """{name: float} in one fixed order from a tree of leaf norms."""
    out = {"tok_emb": float(norms["tok_emb"]), "pos_emb": float(norms["pos_emb"]),
           "ln_f.g": float(norms["ln_f"]["g"]), "ln_f.b": float(norms["ln_f"]["b"])}
    for i in range(sizes(cfg)[2]):
        for k in _LAYER_SHAPES:
            v = (_get(norms["layers"], k)[i] if stacked
                 else _get(norms["layers"][i], k))
            if k in _THIRDS:
                for j, third in enumerate(_QKV):
                    out[f"layers.{i}.{k}.{third}"] = float(v[j])
            else:
                out[f"layers.{i}.{k}"] = float(v)
    return out


def _get(tree, dotted):
    for part in dotted.split("."):
        tree = tree[int(part)] if isinstance(tree, list) else tree[part]
    return tree


def leaf_norms_program(tree, cfg, other=None):
    """{name: l2 norm} of a program-layout tree (or of ``tree - other``)."""
    if other is not None:
        tree = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            tree, other)
    return _flatten(jax.device_get(_program_norms(tree)), cfg, False)


@jax.jit
def _program_norms(tree):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: _leaf_norm(path, a, False), tree)


def leaf_norms_stacked(tree, cfg, other=None):
    """The same dict from a stacked tree: one norm per layer slice."""
    if other is not None:
        tree = jax.tree_util.tree_map(lambda a, b: a - b, tree, other)
    return _flatten(jax.device_get(_stacked_norms(tree)), cfg, True)


@jax.jit
def _stacked_norms(tree):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: _leaf_norm(path, a, True), tree)


# ------------------------------------------------------------- forward
def _q8(x):
    """Round to float8_e4m3fn with a per-tensor scale (amax -> 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    # straight through: the rounding has no gradient of its own (a plain
    # cast would round the tangents to float8 too, and flush them to 0)
    return x + lax.stop_gradient(q - x)


@jax.custom_vjp
def _g8(y):
    """Identity whose cotangent is rounded to float8_e5m2 with a
    per-tensor scale: the gradient format of float8 training."""
    return y


def _g8_bwd(_, g):
    s = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / 57344.0
    return ((g / s).astype(jnp.float8_e5m2).astype(jnp.float32) * s,)


_g8.defvjp(lambda y: (y, None), _g8_bwd)


def _ein(spec, a, b, precision):
    if precision == "fp8":
        return _g8(jnp.einsum(spec, _q8(a), _q8(b), precision=HI))
    if precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HI)


def _ln(x, g, b, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden(params, ids, cfg, precision="f32", remat=False):
    """ids [B, T] -> final-LN hidden states [B, T, d] (stacked params)."""
    d, _, _, H, _, _ = sizes(cfg)
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    B, T = ids.shape
    hd = d // H
    x = params["tok_emb"][ids] + params["pos_emb"][:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]

    def block(x, lp):
        h = _ln(x, lp["ln1"]["g"], lp["ln1"]["b"], eps)
        qkv = _ein("btd,de->bte", h, lp["wqkv"], precision) + lp["bqkv"]
        q, k, v = (y.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
                   for y in jnp.split(qkv, 3, axis=-1))
        s = _ein("bhqd,bhkd->bhqk", q, k, precision) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = _ein("bhqk,bhkd->bhqd", w, v, precision)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, d)
        x = x + _ein("btd,de->bte", ctx, lp["wo"], precision) + lp["bo"]
        h = _ln(x, lp["ln2"]["g"], lp["ln2"]["b"], eps)
        mid = _gelu_tanh(_ein("btd,df->btf", h, lp["w1"], precision) + lp["b1"])
        x = x + _ein("btf,fd->btd", mid, lp["w2"], precision) + lp["b2"]
        return x, None

    if remat:
        block = jax.checkpoint(block)
    x, _ = lax.scan(block, x, params["layers"])
    return _ln(x, params["ln_f"]["g"], params["ln_f"]["b"], eps)


def logits(params, ids, cfg, precision="f32"):
    """ids [B, T] -> logits [B, T, V]."""
    return _ein("btd,vd->btv", hidden(params, ids, cfg, precision),
                params["tok_emb"], precision)


def loss(params, ids, cfg, precision="f32", remat=False):
    """Mean next-token cross entropy of ids[:, :-1] -> ids[:, 1:]."""
    h = hidden(params, ids[:, :-1], cfg, precision, remat)
    lg = _ein("btd,vd->btv", h, params["tok_emb"], precision)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


# -------------------------------------------------------------- serving
def served_gaps(params, ids, positions, tokens, cfg, precision="f32"):
    """For rows of prompt+served ids [B, T] and, per row, K logit
    positions with the K tokens served from them: the gap by which each
    served token's reference logit lies below the reference's best.
    With ``precision="fp8"`` the gap is read for the token the lower
    precision puts first instead (the control)."""
    h = hidden(params, ids, cfg, "f32")
    hp = jnp.take_along_axis(h, positions[..., None], axis=1)   # [B,K,d]
    lg = jnp.einsum("bkd,vd->bkv", hp, params["tok_emb"], precision=HI)
    if precision != "f32":
        hc = jnp.take_along_axis(hidden(params, ids, cfg, precision),
                                 positions[..., None], axis=1)
        tokens = jnp.argmax(_ein("bkd,vd->bkv", hc, params["tok_emb"],
                                 precision), axis=-1)
    at = jnp.take_along_axis(lg, tokens[..., None], axis=-1)[..., 0]
    return jnp.max(lg, axis=-1) - at


@functools.lru_cache(maxsize=8)
def _served_fn(frozen, precision):
    cfg = dict(frozen)
    return jax.jit(lambda p, ids, pos, tok: served_gaps(
        p, ids, pos, tok, cfg, precision))


def check_served(cfg, seed, requests, precision="f32", pad_to=None,
                 max_tokens=None):
    """``requests``: list of (prompt ids, served tokens). Runs the
    reference once over each prompt with its served tokens (one row at a
    time, padded to one length so one program serves all) and returns
    the gaps' widest, their mean and the share of tokens with a gap at
    all (a served token that is not the reference's best), with how
    many tokens were compared. The causal mask makes the padding
    invisible to the compared positions."""
    _, _, _, _, _, P = sizes(cfg)
    pad_to = pad_to or P
    kmax = max_tokens or max(len(t) for _, t in requests)
    fn = _served_fn(_frozen(cfg), precision)
    params = make_params(cfg, seed)
    worst, worst_at, every = 0.0, None, []
    for ri, (prompt, served) in enumerate(requests):
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        n = len(served)
        row = np.zeros((1, pad_to), np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        row[0, :len(seq)] = seq
        pos = np.zeros((1, kmax), np.int32)
        pos[0, :n] = len(prompt) - 1 + np.arange(n)
        tok = np.zeros((1, kmax), np.int32)
        tok[0, :n] = served
        gaps = np.asarray(fn(params, row, pos, tok))[0, :n]
        every.append(gaps)
        if n and float(gaps.max()) > worst:
            worst, worst_at = float(gaps.max()), (ri, int(gaps.argmax()))
    every = np.concatenate(every) if every else np.zeros(0)
    return {"widest_gap": worst, "at": worst_at, "compared": int(every.size),
            "mean_gap": float(every.mean()) if every.size else None,
            "mismatch_share": float((every > 0).mean()) if every.size else None}


# ------------------------------------------------------------- training
@functools.lru_cache(maxsize=8)
def _train_fns(frozen, precision):
    cfg = dict(frozen)

    @jax.jit
    def grad_block(params, ids):
        return jax.value_and_grad(
            lambda p: loss(p, ids, cfg, precision, remat=True))(params)

    @jax.jit
    def add(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    @functools.partial(jax.jit, static_argnames=("n",))
    def scale(a, n):
        return jax.tree_util.tree_map(lambda x: x / n, a)

    @jax.jit
    def adam(params, m, v, g, t, lr, b1, b2, eps):
        m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        alpha = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        params = jax.tree_util.tree_map(
            lambda p, m_, v_: p - alpha * m_ / (jnp.sqrt(v_) + eps),
            params, m, v)
        return params, m, v

    return grad_block, add, scale, adam


def train_reference(cfg, seed, batches, trainer, precision="f32",
                    rows_per_block=2, keep_rows=None):
    """Follow ``len(batches)`` Adam steps from the seed's weights. Each
    batch is ids [rows, T+1]; gradients are accumulated over blocks of
    ``rows_per_block`` rows (equal blocks, so the mean of block means is
    the batch mean) with every layer recomputed in the backward, so the
    float32 step fits beside nothing else. ``keep_rows`` plants the
    half-batch fault: the mean is taken over the first ``keep_rows``
    rows only. Returns the losses, the first gradient's per-leaf norms
    and the per-leaf norms of the parameters' change after all steps."""
    grad_block, add, scale, adam = _train_fns(_frozen(cfg), precision)
    p0 = make_params(cfg, seed)
    params = p0
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(p0), zeros(p0)
    losses, gnorm = [], None
    for t, ids in enumerate(batches, start=1):
        ids = np.asarray(ids, np.int32)
        if keep_rows is not None:
            ids = ids[:keep_rows]
        if ids.shape[0] % rows_per_block:
            raise ValueError("rows must divide into equal blocks")
        nb = ids.shape[0] // rows_per_block
        total, g = 0.0, None
        for b in range(nb):
            lb, gb = grad_block(params, ids[b * rows_per_block:
                                            (b + 1) * rows_per_block])
            total += float(lb)
            g = gb if g is None else add(g, gb)
        g = scale(g, n=nb)
        losses.append(total / nb)
        if t == 1:
            gnorm = leaf_norms_stacked(g, cfg)
        params, m, v = adam(params, m, v, g, float(t),
                            float(trainer["learning_rate"]),
                            float(trainer["beta1"]), float(trainer["beta2"]),
                            float(trainer["epsilon"]))
        del g
    dnorm = leaf_norms_stacked(params, cfg, other=p0)
    return {"losses": losses, "grad_norms": gnorm, "change_norms": dnorm}


# ----------------------------------------------------------- comparison
def _median(xs):
    return float(np.median(np.asarray(list(xs), np.float64)))


def leaf_gaps(prog, ref, leaves=None):
    """{leaf: gap between the program's norm and the reference's},
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    leaves = list(leaves if leaves is not None else ref)
    med = _median(ref[n] for n in leaves)
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
            for n in leaves}


def worst_leaf_gap(prog, ref, leaves=None):
    """The widest of ``leaf_gaps`` and the leaf it is at."""
    gaps = leaf_gaps(prog, ref, leaves)
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def compare_training(prog, ref):
    """The numbers the training cells compare. ``prog`` and ``ref`` have
    ``losses``, ``grad_norms`` and ``change_norms``. Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    under Adam by round-off alone and are left out of the change.
    ``*_gap`` is the worst leaf's; ``*_gap_median`` the median leaf's,
    which one small leaf's noise does not reach."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    g = leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    med_g = _median(ref["grad_norms"].values())
    moved = [n for n, v in ref["grad_norms"].items() if v >= 1e-3 * med_g]
    c = leaf_gaps(prog["change_norms"], ref["change_norms"], moved)
    return {"loss_gap": loss_gap,
            "loss1_gap": abs(prog["losses"][0] - ref["losses"][0])
            / abs(ref["losses"][0]),
            "grad_gap": max(g.values()), "grad_gap_median": _median(g.values()),
            "change_gap": max(c.values()),
            "change_gap_median": _median(c.values()),
            "_at": {"grad": max(g, key=g.get), "change": max(c, key=c.get),
                    "left_out": len(ref["grad_norms"]) - len(moved)}}
