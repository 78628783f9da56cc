"""Grouped matrix product for dropless experts: rows sorted by group,
each group's rows times that group's matrix, in one pass that reads
only the matrices of groups that HAVE rows.

    out[r] = lhs[r] @ rhs[g]   for offsets[g] <= r < offsets[g + 1]

A decode step of a sparse-expert layer routes a few dozen assignments
over most of the experts (16 slots x 4 = 64 rows over ~41 of 64): the
step is the bytes of the matrices it touches. The kernel therefore
walks VISITS, not experts: a visit is one (group, row tile) pair that
shares rows, in row order; its index maps pick that group's matrix
block by scalar prefetch, so a group with no row is never read, and a
row tile met by several groups stays in VMEM while they take turns
writing their own rows of it (a masked store). The contraction is not
split (one block holds the whole ``k``), so there is no accumulator
and a visit is one MXU product. Same idea as megablox's ``gmm``
(jax.experimental.pallas.ops.tpu.megablox), cut to what serving needs:
forward only, a static grid (visits past the last are masked off and
re-use the last visit's blocks, so they move no bytes), rows past
``sum(group_sizes)`` belonging to no group (their output is left
unwritten: the caller masks it).

Dispatch mirrors ``ops/paged_attention_pallas.py``:
``pallas`` (a TPU), ``interpret`` (the same kernel through the Pallas
interpreter, for tests), ``xla`` (``jax.lax.ragged_dot``, the
reference the other two are tested against).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

#: the weight block a visit brings in is at most this many bytes (two
#: of them are in flight), so a step's time is the DMA's, not the
#: grid's ~0.35 us a step
_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT = 48 << 20


def row_tile(rows: int) -> int:
    """Rows a visit works on: 128 (the MXU's side), or all of them
    rounded up to bf16's sublane packing where there are fewer."""
    return min(128, -(-rows // 16) * 16)


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of one weight block: the widest multiple of 128 that
    divides ``n`` within ``_BLOCK_BYTES`` (all of ``n`` where 128 does
    not divide it: a small test size)."""
    if n % 128:
        return n
    best = 128
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= _BLOCK_BYTES:
            best = tn
    return best


def visits(group_sizes, rows: int, tm: int):
    """The walk as scalar-prefetch arrays: ``offsets [G + 1]``, and per
    visit the group and the row tile (``[tiles + G - 1]`` each, the
    most there can be; entries past ``count`` repeat the last visit),
    and ``count [1]``."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offsets[:-1] // tm
    last = jnp.maximum(ends - 1, 0) // tm
    per = jnp.where(sizes > 0, last - first + 1, 0)      # tiles a group meets
    upto = jnp.cumsum(per)
    count = upto[-1]
    v = jnp.minimum(jnp.arange(rows // tm + G - 1, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(upto, v, side="right"),
                      G - 1).astype(jnp.int32)
    tile = first[gid] + (v - (upto[gid] - per[gid]))
    tile = jnp.clip(tile, 0, rows // tm - 1).astype(jnp.int32)
    return offsets, gid, tile, count[None].astype(jnp.int32)


def _kernel(offsets_ref, gid_ref, tile_ref, count_ref, lhs_ref, rhs_ref,
            out_ref, *, tm):
    from jax.experimental import pallas as pl

    v = pl.program_id(1)

    @pl.when(v < count_ref[0])
    def _visit():
        g = gid_ref[v]
        # one MXU pass of the operands as held, float32 accumulation,
        # whatever default precision the caller's context sets
        acc = lax.dot_general(lhs_ref[...], rhs_ref[...],
                              (((1,), (0,)), ((), ())),
                              precision=lax.Precision.DEFAULT,
                              preferred_element_type=jnp.float32)
        row = tile_ref[v] * tm + lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])


def _pallas_grouped_matmul(lhs, rhs, group_sizes, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    G, _, n = rhs.shape
    tm = row_tile(m)
    if m % tm:
        raise ValueError(f"{m} rows are no multiple of the row tile {tm}")
    tn = _col_tile(k, n, jnp.dtype(rhs.dtype).itemsize)
    offsets, gid, tile, count = visits(group_sizes, m, tm)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, gid.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, o, g, t, c: (t[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, o, g, t, c: (g[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, o, g, t, c: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # what a device trace calls the kernel (PERF.md section 3)
        name="moe_experts",
    )(offsets, gid, tile, count, lhs, rhs)


def grouped_matmul(lhs, rhs, group_sizes, *, mode=None):
    """``lhs [m, k]`` (rows sorted by group) times ``rhs [G, k, n]`` by
    ``group_sizes [G]`` -> ``[m, n]`` in ``lhs.dtype``. Rows past
    ``sum(group_sizes)`` belong to no group: what comes back for them
    is unspecified (zeros in ``xla`` mode), never read them. ``m`` is a
    multiple of ``row_tile(m)``. ``mode``: ``pallas`` | ``interpret`` |
    ``xla``; None is ``pallas`` on a TPU and ``xla`` elsewhere."""
    mode = mode or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if mode == "xla":
        return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
    if mode not in ("pallas", "interpret"):
        raise ValueError(f"unknown grouped-matmul mode {mode!r} (expected "
                         "'pallas', 'interpret' or 'xla')")
    return _pallas_grouped_matmul(lhs, rhs, group_sizes,
                                  interpret=(mode == "interpret"))


__all__ = ["grouped_matmul", "row_tile", "visits"]
