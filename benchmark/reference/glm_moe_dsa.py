"""Plain reference of the GLM-MoE-DSA family (zai-org ``glm_moe_dsa``,
GLM-5.2): the forward pass in straightforward ``jax.numpy``, float32
with every product at ``highest`` precision. No kernel, no cache, no
batching. It imports nothing of the program and takes nothing the
program made: the weights come from ``make_params`` here, from the
seed, as bfloat16 VALUES (the dtype the configuration states and the
program holds them in); the reference reads the same values in float32.

The equations (``x`` is the residual stream, ``d`` its width):

    h_0 = E[ids]                                   (no position embedding)
    h  <- h + Attn_l(RMS(h; g_in));  h <- h + FF_l(RMS(h; g_post))
    logits = RMS(h_L; g_f) W_head                  (a head of its own, untied)
    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g     (eps = rms_norm_eps)

- multi-head latent attention (every layer), position ``t``, ``x =
  RMS(h; g_in)``, ``H`` heads:
  ``c_q = RMS(x W_qa; g_q)`` (``q_lora_rank``); ``q = c_q W_qb`` ->
  ``H x [q_nope (qk_nope_head_dim) | q_rope (qk_rope_head_dim)]``;
  ``[c_kv | k_r] = x W_kva`` (``kv_lora_rank + qk_rope_head_dim``),
  ``c_kv <- RMS(c_kv; g_kv)``; ``q_rope`` and ``k_r`` are rotated at
  ``t`` over INTERLEAVED pairs at ``rope_theta`` (``rope_interleave``),
  ``k_r`` is one head shared by all ``H``; ``[k_nope | v]_j = c_kv
  W_kvb_j`` (``qk_nope_head_dim + v_head_dim`` a head);
  ``a_{t,s,j} = (q_nope_j . k_nope_{s,j} + q_rope_j . k_r,s) /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)``; softmax over ``s in
  S_t`` ONLY; ``o_j = sum_s p v_{s,j}``; ``Attn = concat(o) W_o``.
- the indexer, on a layer whose ``indexer_types`` entry is ``"full"``:
  ``q^I_i = (c_q W^I_q)_i`` (``index_n_heads`` of ``index_head_dim``),
  ``k^I_s = LayerNorm(x_s W^I_k)`` (gain and bias), both rotated on
  their FIRST ``qk_rope_head_dim`` lanes (interleaved pairs), ``w_i =
  (x W^I_w)_i``; ``I_{t,s} = sum_i w_i relu(q^I_i . k^I_s)`` for ``s <=
  t``; ``S_t`` = the ``index_topk`` positions of largest ``I_{t,.}``,
  ties to the lower position (every ``s <= t`` while ``t <
  index_topk``). On a ``"shared"`` layer ``S_t`` is that of the nearest
  ``"full"`` layer before it; such a layer has no indexer parameters.
- dense FF (``mlp_layer_types`` ``dense``): ``W_2 (silu(W_1 x) * W_3 x)``.
- sparse FF: ``s = sigmoid(x W_g)`` over ALL the router's outputs
  (``n_routed_experts``, float32); ``idx = top_k(s + b)`` with ``b`` the
  per-expert selection bias (``noaux_tc``; ``n_group = topk_group = 1``:
  no group limit; the bias selects and does not weigh); ``w = s[idx] /
  (sum s[idx] + 1e-20) * routed_scaling_factor`` where
  ``norm_topk_prob``; ``FF = sum_{e in idx, e held} w_e W_2^e (silu(W_1^e
  x) * W_3^e x) + W_2^s (silu(W_1^s x) * W_3^s x)``: the shared expert
  is neither routed nor scaled. No capacity, no drop.

**The share.** A configuration may state that a chip holds
``num_experts`` of the router's ``n_routed_experts`` experts (from
``expert_offset``) and ``vocab_size`` rows of the embedding and of the
head (from ``vocab_offset``). The reference is given the same share:
the router keeps its width and its experts a token, what the absent
experts would have added is left out, and that partial result goes on
to the next layer. An expert's and a vocabulary row's values depend on
the seed, the layer and their GLOBAL index only, so the shares of one
seed are slices of one uncut model (``tests/test_glm_moe_dsa.py`` adds
sixteen expert shares up to the uncut layer and joins eight vocabulary
slices into the uncut logits).

Assumed, where the catalog row's ``config`` has no key (each follows
DeepSeek-V3.2's public block, whose keys the config uses; swapping one
is a few lines here and in the program alike): the indexer rotates the
FIRST ``qk_rope_head_dim`` of its ``index_head_dim`` lanes; its key
norm is a LayerNorm with gain and bias at eps 1e-6; its scores carry no
scale (V3.2 multiplies by ``index_n_heads^-1/2 index_head_dim^-1/2``, a
positive constant that leaves every top-k as it is); V3.2's Hadamard
rotation of the indexer's queries and keys and its fp8 are left out (a
rotation leaves ``q . k`` as it is; fp8 is no key of the config); the
selection bias of a sigmoid router and ``1e-20`` beside the weights'
sum. Departures: projections are stored input-major (``x W``; HF stores
``W^T``), ``W_kvb`` as its K part ``[kv_lora_rank, H x qk_nope]`` and
its V part ``[kv_lora_rank, H x v]``, the shared experts as one SwiGLU:
layouts, not mathematics. The multi-token prediction layer is not part
of the forward pass of the next token and is not held. The weights are
random (normal at ``initializer_range``, residual-side projections
scaled by ``1 / sqrt(2 layers)``, norm gains 1, the LayerNorm's bias 0,
the selection bias normal at ``expert_bias_range``).

At the published widths one sparse layer's share is 3.2 GB in float32,
so ``check_served`` makes and applies ONE LAYER AT A TIME over the
sampled requests, a request a call; indexer scores, the selection and
the attention are made a block of queries at a time (a 14,336-token
request's scores are 53 GB a layer at once); the routed experts are the
published loop over the experts hit, each over its own tokens only.

``precision`` selects the arithmetic: ``"f32"`` is the reference;
``"fp8"`` is the CONTROL (every matmul operand rounded to float8_e4m3fn
with a per-tensor scale, accumulation in float32: the nearest precision
below the bf16 the configuration states). The control has to come out
as not correct; it never runs inside a benchmark run.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
BF16 = jnp.bfloat16
FULL, SHARED = "full", "shared"

#: every leaf a layer can hold; a leaf's values depend on the seed, its
#: layer and its place in this list only, so a layer can be made alone
_LEAVES = ("in_norm", "post_norm", "wq_a", "q_norm", "wq_b", "wkv_a",
           "kv_norm", "wkv_k", "wkv_v", "wo", "iwq", "iwk", "ik_gain",
           "ik_bias", "iww", "w1", "w3", "w2", "router", "router_bias",
           "ew1", "ew3", "ew2", "sw1", "sw3", "sw2")
#: queries a block of the selection and of the attention
QUERY_BLOCK = 256
#: the sorted rows of the held (token, expert) pairs come in multiples
#: of this many
ROW_STEP = 4096


def sizes(cfg):
    E = int(cfg["num_experts"])
    return {"d": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
            "v0": int(cfg.get("vocab_offset", 0)),
            "L": int(cfg["num_hidden_layers"]),
            "H": int(cfg["num_attention_heads"]),
            "Rq": int(cfg["q_lora_rank"]), "C": int(cfg["kv_lora_rank"]),
            "dn": int(cfg["qk_nope_head_dim"]),
            "dr": int(cfg["qk_rope_head_dim"]),
            "dv": int(cfg["v_head_dim"]),
            "Hi": int(cfg["index_n_heads"]),
            "Di": int(cfg["index_head_dim"]),
            "top": int(cfg["index_topk"]),
            "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "E": E, "Er": int(cfg.get("n_routed_experts") or E),
            "e0": int(cfg.get("expert_offset", 0)),
            "k": int(cfg["num_experts_per_tok"]),
            "ns": int(cfg.get("n_shared_experts", 1)),
            "eps": float(cfg["rms_norm_eps"]),
            "ieps": float(cfg.get("index_norm_eps", 1e-6)),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "idx": tuple(cfg["indexer_types"]),
            "mlp": tuple(cfg["mlp_layer_types"]),
            "norm_topk": bool(cfg.get("norm_topk_prob", True)),
            "scale": float(cfg.get("routed_scaling_factor", 1.0))}


def indexer_pattern(n_layers, offset, freq):
    """``indexer_types`` as ``index_skip_topk_offset`` and
    ``index_topk_freq`` spell it: full where ``l < offset`` or ``(l -
    offset) % freq == freq - 1``."""
    return [FULL if l < offset or (l - offset) % freq == freq - 1
            else SHARED for l in range(n_layers)]


def layer_leaves(cfg, li):
    """{leaf: (shape, std, rows)} of layer ``li``; std None is a norm
    gain (ones), 0.0 a bias at rest (zeros); ``rows`` (first, count)
    where the leaf's leading dimension is a slice of a longer one (the
    experts held), whose members are made by their global index."""
    z = sizes(cfg)
    d, H, C = z["d"], z["H"], z["C"]
    std = float(cfg.get("initializer_range", 0.02))
    res = std / math.sqrt(2 * z["L"])
    out = {"in_norm": ((d,), None, None), "post_norm": ((d,), None, None),
           "wq_a": ((d, z["Rq"]), std, None),
           "q_norm": ((z["Rq"],), None, None),
           "wq_b": ((z["Rq"], H * (z["dn"] + z["dr"])), std, None),
           "wkv_a": ((d, C + z["dr"]), std, None),
           "kv_norm": ((C,), None, None),
           "wkv_k": ((C, H * z["dn"]), std, None),
           "wkv_v": ((C, H * z["dv"]), std, None),
           "wo": ((H * z["dv"], d), res, None)}
    if z["idx"][li] == FULL:
        out.update(iwq=((z["Rq"], z["Hi"] * z["Di"]), std, None),
                   iwk=((d, z["Di"]), std, None),
                   ik_gain=((z["Di"],), None, None),
                   ik_bias=((z["Di"],), 0.0, None),
                   iww=((d, z["Hi"]), std, None))
    if z["mlp"][li] == "dense":
        out.update(w1=((d, z["F"]), std, None), w3=((d, z["F"]), std, None),
                   w2=((z["F"], d), res, None))
    else:
        Fe, held = z["Fe"], (z["e0"], z["E"])
        out.update(router=((d, z["Er"]), std, None),
                   router_bias=((z["Er"],),
                                float(cfg.get("expert_bias_range", 0.02)),
                                None),
                   ew1=((d, Fe), std, held), ew3=((d, Fe), std, held),
                   ew2=((Fe, d), res, held),
                   sw1=((d, Fe * z["ns"]), std, None),
                   sw3=((d, Fe * z["ns"]), std, None),
                   sw2=((Fe * z["ns"], d), res, None))
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63: the low 31 bits seed,
    the rest are folded in (``jax.random.key`` takes 32 signed bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=64)
def _leaf_fn(shape, std, count):
    if std is None or std == 0.0:
        fill = jnp.ones if std is None else jnp.zeros
        return jax.jit(lambda key, first: fill(shape, BF16))
    one = lambda key: (jax.random.normal(key, shape, jnp.float32)
                       * std).astype(BF16)
    if count is None:
        return jax.jit(lambda key, first: one(key))
    # members of a longer leading dimension, each from its global index
    return jax.jit(lambda key, first: jax.vmap(
        lambda i: one(jax.random.fold_in(key, i)))(
            first + jnp.arange(count, dtype=jnp.int32)))


def _leaf(key, where, index, shape, std, rows=None):
    k = jax.random.fold_in(jax.random.fold_in(key, where), index)
    first, count = rows or (0, None)
    return _leaf_fn(tuple(shape), std, count)(k, jnp.int32(first))


def make_layer(cfg, seed, li):
    """Layer ``li``'s leaves, bfloat16, each made by its own call."""
    key = seed_key(seed)
    return {name: _leaf(key, li + 1, _LEAVES.index(name), shape, std, rows)
            for name, (shape, std, rows) in layer_leaves(cfg, li).items()}


def make_globals(cfg, seed):
    """The embedding ``[V, d]`` and the head ``[d, V]`` over the rows
    held (each row from its global index), and the final norm."""
    z = sizes(cfg)
    key = seed_key(seed)
    std = float(cfg.get("initializer_range", 0.02))
    rows = (z["v0"], z["V"])
    return {"tok_emb": _leaf(key, 0, 0, (z["d"],), std, rows),
            "final_norm": _leaf(key, 0, 1, (z["d"],), None),
            "head": _leaf(key, 0, 2, (z["d"],), std, rows).T}


def make_params(cfg, seed, layout="program"):
    """All weights on the device, leaf by leaf (so the 7.8 GB of the
    published widths are never held twice): bfloat16 VALUES, held in
    the dtype the configuration states for its parameters (bfloat16 as
    published; a rehearsal at a CPU size may state float32 and then
    runs the program on the very values the reference reads). One
    layout: the per-layer list the program's model takes."""
    del layout
    held = jnp.dtype(cfg.get("dtypes", {}).get("params", "bfloat16"))
    p = make_globals(cfg, seed)
    p["layers"] = [make_layer(cfg, seed, li)
                   for li in range(sizes(cfg)["L"])]
    if held != BF16:
        p = jax.tree_util.tree_map(lambda a: a.astype(held), p)
    return p


# ------------------------------------------------------------- forward
def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b, precision):
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _rope(x, theta):
    """RoPE over INTERLEAVED pairs of ``x [T, heads, hd]`` at positions
    0..T-1: lanes ``(2i, 2i + 1)`` turn by ``t theta^(-2i / hd)``."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _rope_first(x, n, theta):
    """``x [T, heads, hd]`` with its first ``n`` lanes rotated."""
    return jnp.concatenate([_rope(x[..., :n], theta), x[..., n:]], -1)


def _blocks(fn, T, *cut):
    """``fn`` over arrays cut along their leading (query) axis a block
    at a time, the results joined again."""
    b = QUERY_BLOCK
    if T <= b or T % b:
        return fn(*cut)
    out = lax.map(lambda a: fn(*a), tuple(
        c.reshape(T // b, b, *c.shape[1:]) for c in cut))
    return out.reshape(T, *out.shape[2:])


def select(lp, x, c_q, z, precision):
    """The indexer of a full layer over one sequence -> bool ``[T, T]``:
    query ``t`` reads key ``s``. A row's scores are ranked by a stable
    sort (falling score, ties to the lower position) and the first
    ``index_topk`` ranks are in."""
    T = x.shape[0]
    qi = _rope_first(_ein("tr,re->te", c_q, lp["iwq"], precision)
                     .reshape(T, z["Hi"], z["Di"]), z["dr"], z["theta"])
    ki = _layer_norm(_ein("td,de->te", x, lp["iwk"], precision),
                     lp["ik_gain"], lp["ik_bias"], z["ieps"])
    ki = _rope_first(ki[:, None, :], z["dr"], z["theta"])[:, 0]
    wi = _ein("td,dh->th", x, lp["iww"], precision)
    j = jnp.arange(T)

    def block(qb, wb, ib):
        s = _ein("qhd,kd->qhk", qb, ki, precision)
        I = jnp.sum(jax.nn.relu(s) * wb[:, :, None], axis=1)     # [b, T]
        I = jnp.where(j[None, :] <= ib[:, None], I, -jnp.inf)
        order = jnp.argsort(-I, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1)
        return (rank < z["top"]) & (j[None, :] <= ib[:, None])

    return _blocks(block, T, qi, wi, j)


def attn_op(lp, x, z, li, mask, precision):
    """-> (the attention's output ``[T, d]``, the selection in force:
    this layer's own where it is a full one, else ``mask`` as given)."""
    T = x.shape[0]
    H, dn, dr, dv, C = z["H"], z["dn"], z["dr"], z["dv"], z["C"]
    c_q = _rms(_ein("td,dr->tr", x, lp["wq_a"], precision), lp["q_norm"],
               z["eps"])
    q = _ein("tr,re->te", c_q, lp["wq_b"], precision) \
        .reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], z["theta"])], -1)
    kva = _ein("td,de->te", x, lp["wkv_a"], precision)
    c_kv = _rms(kva[:, :C], lp["kv_norm"], z["eps"])
    k_r = _rope(kva[:, None, C:], z["theta"])                 # [T, 1, dr]
    k = jnp.concatenate([
        _ein("tc,ce->te", c_kv, lp["wkv_k"], precision).reshape(T, H, dn),
        jnp.broadcast_to(k_r, (T, H, dr))], -1)
    v = _ein("tc,ce->te", c_kv, lp["wkv_v"], precision).reshape(T, H, dv)
    if z["idx"][li] == FULL:
        mask = select(lp, x, c_q, z, precision)

    def block(qb, mb):
        s = _ein("qhd,khd->hqk", qb, k, precision) / math.sqrt(dn + dr)
        s = jnp.where(mb[None], s, -jnp.inf)
        return _ein("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                    precision)

    ctx = _blocks(block, T, q, mask)
    return _ein("td,de->te", ctx.reshape(T, H * dv), lp["wo"],
                precision), mask


def _swiglu(x, w1, w3, w2, precision):
    mid = jax.nn.silu(_ein("td,df->tf", x, w1, precision)) \
        * _ein("td,df->tf", x, w3, precision)
    return _ein("tf,fd->td", mid, w2, precision)


def route(lp, x, z, precision="f32"):
    """-> (idx [T, k] among ALL the router's outputs, weights [T, k])
    of the tokens ``x [T, d]``."""
    s = jax.nn.sigmoid(_ein("td,de->te", x, lp["router"], precision))
    _, idx = lax.top_k(s + lp["router_bias"], z["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * z["scale"]


@functools.partial(jax.jit, static_argnames=("n", "precision"))
def _expert(ys, xs, lp, e, start, count, n, precision):
    """Held expert ``e`` over its block of the sorted rows: ``n`` rows
    from ``start`` (a few set lengths, so the block may run into the
    next expert's rows), of which its own ``count`` are written to
    ``ys``."""
    w1, w3, w2 = (lax.dynamic_index_in_dim(lp[k], e, keepdims=False)
                  for k in ("ew1", "ew3", "ew2"))
    y = _swiglu(lax.dynamic_slice_in_dim(xs, start, n), w1, w3, w2,
                precision)
    mine = jnp.arange(n)[:, None] < count
    y = jnp.where(mine, y, lax.dynamic_slice_in_dim(ys, start, n))
    return lax.dynamic_update_slice_in_dim(ys, y, start, axis=0)


def routed_ff(lp, x, idx, w, z, precision):
    """The routed experts HELD, over ``x [T, d]``: the (token, expert)
    pairs whose expert is held are sorted by expert on the host, each
    expert that got tokens computes its SwiGLU over its own block of
    rows only (as the published model's loop over the experts hit), and
    every token sums its held experts' outputs with their weights; a
    pair whose expert is held elsewhere adds nothing. A block is cut at
    64 times a power of two rows, so that a few programs serve every
    count, and the rows are padded to a few lengths. Gathers only: no
    scatter-add."""
    T, k = idx.shape
    local = np.asarray(idx).reshape(-1) - z["e0"]
    pairs = np.flatnonzero((local >= 0) & (local < z["E"]))
    if not pairs.size:
        return jnp.zeros_like(x)
    order = pairs[np.argsort(local[pairs], kind="stable")]
    counts = np.bincount(local[pairs], minlength=z["E"])
    starts = np.concatenate([[0], np.cumsum(counts)])
    block = lambda c: 64 * 2 ** max(0, math.ceil(math.log2(c / 64)))
    cap = -(-(order.size + block(counts.max())) // ROW_STEP) * ROW_STEP
    src = np.zeros(cap, np.int64)
    src[:order.size] = order // k
    xs = x[src]
    ys = jnp.zeros_like(xs)
    for e in np.flatnonzero(counts):
        ys = _expert(ys, xs, lp, jnp.int32(e), jnp.int32(starts[e]),
                     jnp.int32(counts[e]), block(counts[e]), precision)
    # back to (token, slot): a pair of an absent expert reads the last
    # row, which no expert writes
    back = np.full(T * k, cap - 1, np.int64)
    back[order] = np.arange(order.size)
    y = ys[back].reshape(T, k, -1)
    return jnp.sum(y * jnp.asarray(w)[..., None], axis=1)


@functools.lru_cache(maxsize=32)
def _pre_fn(frozen, li, precision):
    """Layer ``li`` up to its routed experts, the selection carried:
    -> (x after a dense layer, mask); for a sparse one (x after the
    attention and the shared expert, the feed-forward's input, its
    routing, mask)."""
    z = dict(frozen)

    def pre(lp, x, mask):
        a, mask = attn_op(lp, _rms(x, lp["in_norm"], z["eps"]), z, li,
                          mask, precision)
        x = x + a
        h = _rms(x, lp["post_norm"], z["eps"])
        if z["mlp"][li] == "dense":
            return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"],
                               precision), mask
        x = x + _swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"], precision)
        return (x, h) + route(lp, h, z, precision) + (mask,)

    return jax.jit(pre)


_to_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
    lambda a: a.astype(jnp.float32), t))


def _frozen(z):
    return tuple(sorted(z.items()))


def layer(lp, x, mask, li, z, precision="f32"):
    """One layer over one sequence ``x [T, d]`` (float32) -> ``(x,
    mask)``; ``lp`` the layer's leaves in float32, ``z`` =
    ``sizes(cfg)``, ``mask`` the selection in force before the layer
    (None before the first, which is a full one). Layers of one
    (indexer, feed-forward) kind share the program of the first of that
    kind."""
    mine = (z["idx"][li], z["mlp"][li])
    first = next(i for i in range(z["L"])
                 if (z["idx"][i], z["mlp"][i]) == mine)
    if mask is None:
        mask = jnp.zeros((x.shape[0],) * 2, bool)
    got = _pre_fn(_frozen(z), first, precision)(lp, x, mask)
    if z["mlp"][li] == "dense":
        return got
    x, h, idx, w, mask = got
    return x + routed_ff(lp, h, idx, w, z, precision), mask


def hidden_rows(cfg, seed, rows, precision="f32", params=None):
    """Final hidden states (after the final norm) of each sequence of
    ``rows`` ([T] ids each, one length), a layer at a time. With
    ``params`` (a whole tree) nothing is regenerated. -> (hidden states,
    the head ``[d, V]``)."""
    z = sizes(cfg)
    g = params or make_globals(cfg, seed)
    emb = g["tok_emb"]
    hs = [emb[jnp.asarray(r, jnp.int32)].astype(jnp.float32) for r in rows]
    masks = [None] * len(rows)
    for li in range(z["L"]):
        lp = params["layers"][li] if params else make_layer(cfg, seed, li)
        lp = _to_f32(lp)
        for ri in range(len(rows)):
            hs[ri], masks[ri] = layer(lp, hs[ri], masks[ri], li, z,
                                      precision)
        jax.block_until_ready(hs)
        del lp
    gn = g["final_norm"].astype(jnp.float32)
    return [_rms(h, gn, z["eps"]) for h in hs], g["head"]


def logits(cfg, seed, ids, precision="f32", params=None):
    """ids [T] -> logits [T, V] of one sequence (``V`` the rows held)."""
    (h,), head = hidden_rows(cfg, seed, [ids], precision, params)
    return _ein("td,dv->tv", h, head.astype(jnp.float32), precision)


def selections(cfg, seed, ids, params=None):
    """The selection in force at every layer: {layer: bool [T, T]} over
    one sequence (a shared layer's is the full layer's before it)."""
    z = sizes(cfg)
    g = params or make_globals(cfg, seed)
    h = g["tok_emb"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    out, mask = {}, None
    for li in range(z["L"]):
        lp = _to_f32(params["layers"][li] if params
                     else make_layer(cfg, seed, li))
        h, mask = layer(lp, h, mask, li, z)
        out[li] = np.asarray(mask)
    return out


# -------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("precision",))
def _gaps(h, hc, head, positions, tokens, precision):
    head = head.astype(jnp.float32)
    lg = jnp.einsum("kd,dv->kv", h[positions], head, precision=HI)
    if hc is not None:
        tokens = jnp.argmax(_ein("kd,dv->kv", hc[positions], head,
                                 precision), axis=-1)
    at = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - at


def check_served(cfg, seed, requests, precision="f32", pad_to=None,
                 max_tokens=None, params=None):
    """``requests``: list of (prompt ids, served tokens). Runs the
    reference over each prompt with its served tokens (padded to one of
    a few lengths so few programs serve all; the causal mask, the
    selection among causal keys and the per-token experts make the
    padding invisible to the compared positions) and returns the gap by
    which each served token's reference logit lies below the
    reference's best: the widest, the mean, and the share of tokens
    with a gap at all. With ``precision="fp8"`` the gap is read for the
    token the lower precision puts first instead (the control)."""
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)[:-1]])
            for p, t in requests]
    longest = max(len(s) for s in seqs)
    # few lengths, so that few programs are ever compiled
    step = 128 if longest <= 512 else 512 if longest <= 4096 else 2048
    pad_to = pad_to or -(-longest // step) * step
    kmax = max_tokens or max(len(t) for _, t in requests)
    rows = [np.pad(s, (0, pad_to - len(s))) for s in seqs]
    hs, head = hidden_rows(cfg, seed, rows, "f32", params)
    hcs = [None] * len(rows)
    if precision != "f32":
        hcs, _ = hidden_rows(cfg, seed, rows, precision, params)
    worst, worst_at, every = 0.0, None, []
    for ri, (prompt, served) in enumerate(requests):
        n = len(served)
        pos, tok = np.zeros((2, kmax), np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        tok[:n] = served
        gaps = np.asarray(_gaps(hs[ri], hcs[ri], head, pos, tok,
                                precision))[:n]
        every.append(gaps)
        if n and float(gaps.max()) > worst:
            worst, worst_at = float(gaps.max()), (ri, int(gaps.argmax()))
    every = np.concatenate(every) if every else np.zeros(0)
    return {"widest_gap": worst, "at": worst_at, "compared": int(every.size),
            "mean_gap": float(every.mean()) if every.size else None,
            "mismatch_share": float((every > 0).mean()) if every.size else None}
