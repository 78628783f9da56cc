"""The dropless routed expert product, shared by every served model
that has sparse experts (``models/lfm2_moe.py``, ``models/exaone_moe.py``,
``models/glm_moe_dsa.py``, ``models/mellum.py``), and the two routers
that choose for it (``route_sigmoid``, ``route_softmax``).

A layer is told which experts it HOLDS: the contiguous range ``[first,
first + E)`` of the router's outputs, ``E`` the leading dimension of
the expert matrices it is given. Routing is over all of the router's
outputs; an assignment to an expert that is not held goes to no group,
exactly as the assignments of dead slots and of a bucket's padding do,
and adds nothing to the result. What the absent experts would have
added is another chip's to compute (expert parallelism); nothing here
stands in for them. A model that holds every expert (``first`` 0 and
``E`` the router's width) runs the program it ran before the range
existed.

Assignments are sorted by expert and multiplied group by group
(``ops/grouped_matmul_pallas.py``), reading only experts that got rows;
the operations are named ``moe_experts`` in a device trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.grouped_matmul_pallas import (grouped_matmul,
                                                          row_tile)


def _scores(x, router):
    return jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def route_sigmoid(x, router, bias, k, normalise, scale):
    """``x [m, d]`` -> (the router's choices ``[m, k]`` among ALL its
    outputs, weights ``[m, k]`` float32): sigmoid scores in float32,
    the per-expert ``bias`` selects and does not weigh, the selected
    scores normalised (``1e-20`` beside their sum) where ``normalise``,
    then scaled by ``scale``."""
    s = jax.nn.sigmoid(_scores(x, router))
    _, idx = lax.top_k(s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if normalise:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * scale


def route_softmax(x, router, k, normalise):
    """``x [m, d]`` -> (choices ``[m, k]``, weights ``[m, k]`` float32):
    a softmax over all the router's outputs in float32, its top ``k``,
    renormalised to sum to 1 where ``normalise``."""
    w, idx = lax.top_k(jax.nn.softmax(_scores(x, router), axis=-1), k)
    if normalise:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w


def routed_experts(x, idx, w, ew1, ew3, ew2, *, first=0, routed=None,
                   live=None, mode=None):
    """``x [m, d]`` through the SwiGLU experts its rows were routed to
    -> ``([m, d], counts [E])``.

    ``idx [m, k]`` are the router's choices among its ``routed``
    outputs (default: the ``E`` experts held), ``w [m, k]`` their
    float32 weights; ``ew1`` / ``ew3`` ``[E, d, f]`` and ``ew2`` ``[E,
    f, d]`` are the experts held, the router's outputs ``[first, first
    + E)``. ``live [m]`` (bool) marks rows that are real: the others go
    to no expert and come back zero. ``counts`` are the live rows each
    held expert got (int32)."""
    E = ew1.shape[0]
    m, d = x.shape
    k = idx.shape[1]
    flat = idx.reshape(-1)
    if first or (routed is not None and routed != E):
        # the router's output -> the expert's place among those held;
        # one held elsewhere is no group here
        local = flat - first
        flat = jnp.where((local >= 0) & (local < E), local, E)
    if live is not None:
        flat = jnp.where(jnp.repeat(live, k), flat, E)       # no group
    counts = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0,
                     dtype=jnp.int32)
    order = jnp.argsort(flat, stable=True)
    a = m * k
    pad = -a % row_tile(a)
    rows = jnp.pad(x[order // k], ((0, pad), (0, 0)))
    up = grouped_matmul(rows, ew1, counts, mode=mode)
    gate = grouped_matmul(rows, ew3, counts, mode=mode)
    mid = (jax.nn.silu(up.astype(jnp.float32))
           * gate.astype(jnp.float32)).astype(x.dtype)
    y = grouped_matmul(mid, ew2, counts, mode=mode)
    back = jnp.zeros((a,), jnp.int32).at[order].set(
        jnp.arange(a, dtype=jnp.int32))
    y = jnp.where((flat < E)[:, None], y[back], 0).reshape(m, k, d)
    out = jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)
    return out.astype(x.dtype), counts


def expert_stats(counts):
    """int32 (assignments, distinct experts touched, the most any
    expert got) of the live rows the held experts got."""
    return jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                      jnp.max(counts)]).astype(jnp.int32)


__all__ = ["routed_experts", "expert_stats", "route_sigmoid",
           "route_softmax"]
