"""The grouped product as Pallas TPU kernels: rows sorted by group times each
group's matrix, and its two gradients.

    grouped_dot(xs [rows, K], w [groups, K, N], sizes [groups] int32) -> [rows, N]

in xs' type with float32 sums: rows sizes[:g].sum() .. sizes[:g+1].sum() are
multiplied by w[g].  Rows past the last group are UNDEFINED (whatever the
output's buffer held, NaN included), as they are for `jax.lax.ragged_dot`,
and so is their row gradient: the caller cuts them out (`layers/moe.py`
`_experts`).  Three kernels, each over the row tiles that HOLD rows, so an
empty group costs nothing and a nearly empty call a handful of tile visits:

  * the product, grid (N / tn, visits): one [tm, K] tile of rows against the
    [K, tn] block of its group's matrix, one visit a (row tile, group) pair
    that shares rows, the rows of other groups in the tile masked out of the
    store.  The WHOLE K is one block: the matrix block stays in VMEM over the
    row tiles a group spans and is read once, where a cut K would read the
    [K, tn] column again every visit (scripts/grouped_product_sweep.py: the
    kernel then runs at what the product must read, 0.14 ms for 108 MB);
  * the row gradient: the same kernel on the matrices read transposed
    (g [rows, N] x w[g]^T), no transposed copy of them made;
  * the matrices' gradient, grid (N / tn, visits): xs^T g summed in a float32
    [K, tn] VMEM block over a group's row tiles, written when the group ends;
    an empty group gets one visit, which writes its zeros.

Which (row tile, group) pairs exist is worked out once from `sizes`
(`_visits`, traced once) and handed to the kernels as prefetched
scalars; the grid's length is the count of visits, a traced number.  It is the
layout of `jax.experimental.pallas.ops.tpu.megablox` (which jax ships and the
sweep timed: same speed at the same tiles), written here because that package
works its tile metadata out inside every one of its jitted kernels with
`jnp.repeat`, `histogram` and `roll`, whose tracing and lowering was most of
what its kernels cost a warm boot (PERF.md section 6, PR 39).

`layers/moe.py` `_grouped_dot` takes these kernels on the TPU backend where
`supported` says the shapes fit; XLA partitions no Mosaic kernel, so in a
program over several devices the layer keeps `ragged_dot`.  `interpret=True`
runs them on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What follows is traced and lowered at every boot of a model that holds
# experts, so it is written in `lax` operations: a `jnp` function is a jitted
# function of its own to trace, milliseconds each on the first call of a shape
# (the same code in `jnp` cost the cell's boot half as much again to trace).

ROW_TILE = 128  # the sweep: 128 beats 64, 256 and 512 in every routing
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_VMEM_LIMIT = 64 * 1024 * 1024
# the most bytes of one buffer of the [K, tn] matrix block (the product, the
# row gradient) and of the float32 sum of the matrices' gradient
_BLOCK_BYTES = 12 * 1024 * 1024


def _n_tile(k, n, itemsize):
    """The widest tile of N (a multiple of 128 lanes, or N whole) whose
    [k, tile] block stays under `_BLOCK_BYTES`; None where K alone is too long."""
    if k * n * itemsize <= _BLOCK_BYTES:
        return n
    return next((t for t in (2048, 1024, 512, 256, 128) if t < n and k * t * itemsize <= _BLOCK_BYTES), None)


def supported(rows, k, n, dtypes):
    """-> None where the kernels take [rows, k] x [groups, k, n] of `dtypes`
    (rows', matrices'), else why not."""
    if rows % ROW_TILE:
        return f"the kernels' row tile of {ROW_TILE} does not divide {rows} rows"
    if not all(d in (jnp.bfloat16, jnp.float32) for d in dtypes):
        return f"the kernels take bfloat16 and float32, not {' x '.join(str(d) for d in dtypes)}"
    if _n_tile(max(k, n), 128, 4) is None:
        return f"a [{max(k, n)}, 128] block of a matrix does not fit the kernels' VMEM"
    return None


@functools.partial(jax.jit, static_argnums=(1,))
def _visits(sizes, rows):
    """The (row tile, group) pairs that share rows, in order -> (edges
    [groups + 1], (group of each visit, its row tile, how many visits), the
    same three with one visit for an empty group too: of the tile its rows
    would start in, so that something writes its gradient's zeros).  Static
    lengths: rows / tile + groups - 1 visits at most (+ groups with the empty
    ones); those past the count name the last group and tile.

    One jitted function for both lists: what is not read is not computed."""
    groups = sizes.shape[0]
    tiles = rows // ROW_TILE
    like = lambda value, x: lax.full_like(x, value)
    ends = lax.cumsum(sizes, axis=0)
    first = lax.min(lax.div(lax.sub(ends, sizes), like(ROW_TILE, sizes)), like(tiles - 1, sizes))
    last = lax.div(lax.sub(ends, like(1, sizes)), like(ROW_TILE, sizes))
    spans = lax.select(lax.gt(sizes, like(0, sizes)),
                       lax.add(lax.sub(last, first), like(1, sizes)), like(0, sizes))

    def visits(span, longest):
        upto = lax.cumsum(span, axis=0)
        over = lambda x: lax.broadcast_in_dim(x, (longest, groups), (1,))
        visit = lax.broadcasted_iota(jnp.int32, (longest, groups), 0)
        # a visit's group: the one whose visits begin at or before it and end after it
        here = lax.convert_element_type(
            lax.bitwise_and(lax.ge(visit, over(lax.sub(upto, span))), lax.lt(visit, over(upto))), jnp.int32)
        pick = lambda x: lax.reduce(lax.mul(here, over(x)), np.int32(0), lax.add, (1,))
        count = lax.index_in_dim(upto, groups - 1, keepdims=False)
        past = lax.ge(lax.iota(jnp.int32, longest), lax.broadcast(count, (longest,)))
        group = lax.select(past, lax.full((longest,), groups - 1, jnp.int32),
                           pick(lax.iota(jnp.int32, groups)))
        # its tile: the group's first, and one on for each visit the group had before this one
        tile = lax.add(lax.iota(jnp.int32, longest), pick(lax.sub(first, lax.sub(upto, span))))
        tile = lax.select(past, lax.full((longest,), tiles - 1, jnp.int32), tile)
        return group, tile, count

    edges = lax.concatenate([lax.full((1,), 0, jnp.int32), ends], 0)
    return (edges, visits(spans, tiles + groups - 1),
            visits(lax.max(spans, like(1, spans)), tiles + 2 * groups - 1))


def _own_rows(edges, group, tile, v, width):
    """[ROW_TILE, width] bool: the rows of visit v's tile that are its group's."""
    g = group[v]
    row = lax.add(lax.broadcasted_iota(jnp.int32, (ROW_TILE, width), 0),
                  lax.broadcast(lax.mul(tile[v], np.int32(ROW_TILE)), (ROW_TILE, width)))
    return lax.bitwise_and(lax.ge(row, lax.broadcast(edges[g], (ROW_TILE, width))),
                    lax.lt(row, lax.broadcast(edges[lax.add(g, np.int32(1))], (ROW_TILE, width))))


def _product_kernel(edges, group, tile, xs_ref, w_ref, out_ref, *, transposed):
    v = pl.program_id(1)
    dims = _NT if transposed else (((1,), (0,)), ((), ()))
    acc = lax.dot_general(xs_ref[...], w_ref[...], dims, preferred_element_type=jnp.float32)
    # the tile's rows of other groups keep what an earlier visit stored
    mine = _own_rows(edges, group, tile, v, acc.shape[1])
    kept = lax.convert_element_type(out_ref[...], jnp.float32)
    out_ref[...] = lax.convert_element_type(lax.select(mine, acc, kept), out_ref.dtype)


def _params(interpret):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)}


# Each call is a jitted function of its own: the layers and passes of one
# shape share ONE traced and lowered kernel (XLA inlines it under each call
# site's scope).
@functools.partial(jax.jit, static_argnums=(6, 7))
def _product_call(xs, w, edges, group, tile, count, transposed, interpret):
    rows, k = xs.shape
    n = w.shape[1] if transposed else w.shape[2]
    dtype = jnp.promote_types(xs.dtype, w.dtype)
    tn = _n_tile(k, n, jnp.dtype(dtype).itemsize)
    if transposed:
        w_block = pl.BlockSpec((None, tn, k), lambda j, v, e, g, t: (g[v], j, 0))
    else:
        w_block = pl.BlockSpec((None, k, tn), lambda j, v, e, g, t: (g[v], 0, j))
    return pl.pallas_call(
        functools.partial(_product_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), count),
            in_specs=[pl.BlockSpec((ROW_TILE, k), lambda j, v, e, g, t: (t[v], 0)), w_block],
            out_specs=pl.BlockSpec((ROW_TILE, tn), lambda j, v, e, g, t: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), xs.dtype),
        interpret=interpret,
        **_params(interpret),
    )(edges, group, tile, xs.astype(dtype), w.astype(dtype))


def _matrices_kernel(edges, group, tile, xs_ref, g_ref, out_ref, acc_ref):
    v, last = pl.program_id(1), lax.sub(pl.num_programs(1), np.int32(1))
    here = group[v]
    one = np.int32(1)

    @pl.when(lax.bitwise_or(lax.eq(v, np.int32(0)), lax.ne(group[lax.max(lax.sub(v, one), np.int32(0))], here)))
    def _():
        acc_ref[...] = lax.full(acc_ref.shape, 0, acc_ref.dtype)

    # the rows of other groups (and the undefined ones) out of BOTH operands:
    # 0 x NaN is NaN
    xs, g = xs_ref[...], g_ref[...]
    xs = lax.select(_own_rows(edges, group, tile, v, xs.shape[1]), xs, lax.full_like(xs, 0))
    g = lax.select(_own_rows(edges, group, tile, v, g.shape[1]), g, lax.full_like(g, 0))
    acc_ref[...] = lax.add(acc_ref[...], lax.dot_general(xs, g, _TN, preferred_element_type=jnp.float32))

    @pl.when(lax.bitwise_or(lax.eq(v, last), lax.ne(group[lax.min(lax.add(v, one), last)], here)))
    def _():
        out_ref[...] = lax.convert_element_type(acc_ref[...], out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _matrices_call(xs, g, edges, group, tile, count, groups, out_dtype, interpret):
    rows, k = xs.shape
    n = g.shape[1]
    tn = _n_tile(k, n, 4)  # the float32 sum is the block that counts
    return pl.pallas_call(
        _matrices_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), count),
            in_specs=[pl.BlockSpec((ROW_TILE, k), lambda j, v, e, gr, t: (t[v], 0)),
                      pl.BlockSpec((ROW_TILE, tn), lambda j, v, e, gr, t: (t[v], j))],
            out_specs=pl.BlockSpec((None, k, tn), lambda j, v, e, gr, t: (gr[v], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        interpret=interpret,
        **_params(interpret),
    )(edges, group, tile, xs, g.astype(xs.dtype))


def _one_trace_both_ways():
    """jit keys a function's trace on the mesh context as well, which is None
    where a forward pass is traced and an empty mesh where jax traces a
    backward pass: under this the two are the same key, and the forward
    kernel that a backward pass computes again is the forward pass's, not a
    second one to trace and lower."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_dot(xs, w, sizes, interpret=False):
    with _one_trace_both_ways():
        edges, visits, _ = _visits(sizes, xs.shape[0])
        return _product_call(xs, w, edges, *visits, False, interpret)


def _grouped_dot_fwd(xs, w, sizes, interpret):
    return grouped_dot(xs, w, sizes, interpret), (xs, w, sizes)


def _grouped_dot_bwd(interpret, res, g):
    xs, w, sizes = res
    with _one_trace_both_ways():
        edges, visits, with_empty = _visits(sizes, xs.shape[0])
        g_xs = _product_call(g, w, edges, *visits, True, interpret)
        g_w = _matrices_call(xs, g, edges, *with_empty, w.shape[0], w.dtype, interpret)
    return g_xs.astype(xs.dtype), g_w, None


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)
