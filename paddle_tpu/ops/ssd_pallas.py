"""The chunked scan of `ops/ssd.py` as two Pallas TPU kernels: what the XLA
path writes to HBM between its products (every chunk's [Q, Q] decay and
quadratic form, float32, forward and again in the backward) stays in VMEM.

Both kernels read the layer's own arrays, flattened without a copy: x and y
as [B, T, H x P] (a block is hb heads side by side, lane-dense), b and c as
[B, T, G x N] (a block is one group's [Q, N]).  The decay sums (a cumsum of
dt a within each chunk, float32, computed by XLA) and dt come as a chunk's
[Q, H] columns, the decay sums also as its [H, Q] rows, read once a chunk
by all its head blocks; a head's [Q, 1] column and [1, Q] row are picked by
a masked sum (a reduction's result is laid out along the other axis
already, so its broadcast over a [Q, Q] or [Q, 128] tile is free), and the
[Q, Q] decay is their difference.  The grid is (batch, chunk, head block),
the head blocks of a group consecutive:

  * forward: the state entering the chunk, [hb x P, N] float32 a block, is
    carried in VMEM scratch over the chunks; C B^T is computed at a group's
    first block and reused by its other blocks; for each head the masked
    decay exp(cum_q - cum_s), s <= q, is built from the decay sums, masked
    before the exponential; y = (C B^T * decay) (x dt) + (C s^T) exp(cum);
    the state leaving the chunk is exp(cum_Q) s + (b exp(cum_Q - cum))^T
    (x dt).  It writes y and the state ENTERING each chunk, [B, T/Q, H x P,
    N] float32: the custom VJP's residual, as on the XLA path.
  * backward: the same grid, chunks in reverse, the cotangent of the state
    carried in VMEM.  It works on the TRANSPOSED [s, q] matrices (B C^T, the
    decay, their product), so that each head's products take them as they
    are, recomputes them from the kept state, and writes dx, db and dc (a
    group's heads summed in float32 VMEM over its blocks), the cotangent of
    dt through x dt as [Q, H] columns, and that of the decay sums as columns
    and as rows (a head's row sums and column sums of the decay's cotangent,
    each a reduction along one axis; XLA adds the two).

A lane tile of 128 holds 128 / P heads (two at P = 64); a head's products
with the [Q, Q] matrices run over the whole tile with the other heads'
lanes zeroed in one operand, which costs the MXU what a product 64 wide
does.  No [Q, Q] array of any kind leaves VMEM.  `interpret=True` runs
both kernels on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.grouped_product import _one_trace_both_ways

_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_NN = (((1,), (0,)), ((), ()))
_VMEM_LIMIT = 64 * 1024 * 1024
# the widest block of heads, in lanes of x (scripts/ssd_sweep.py)
BLOCK_LANES = 1024


def _largest_divisor(n, fits):
    return max(d for d in range(1, n + 1) if n % d == 0 and (d == 1 or fits(d)))


def head_block(r, p):
    """Heads a grid step takes: the most of a group's r that divide it and
    span at most `BLOCK_LANES` lanes."""
    return _largest_divisor(r, lambda d: d * p <= BLOCK_LANES)


def _tile_heads(hb, p):
    """Heads a lane tile holds: the most of hb that divide it and fit 128."""
    return _largest_divisor(hb, lambda d: d * p <= _LANES)


def _vmem_bytes(chunk, hb, p, n, h, itemsize):
    """What a backward grid step keeps in VMEM (the larger kernel): blocks
    double-buffered, the carried state of every head, the [Q, Q] matrices a
    head works with."""
    q, w = chunk, hb * p
    blocks = 2 * (3 * q * w * itemsize + 4 * q * n * itemsize + w * n * 4 + 6 * q * max(h, _LANES) * 4)
    scratch = h * p * n * 4 + 2 * q * q * 4 + 2 * q * n * 4
    values = 8 * q * q * 4 + 6 * q * w * 4
    return blocks + scratch + values


def supported(chunk, h, p, g, n, dtype):
    """-> None where the kernels take a scan of h heads of width p in g
    groups, state n, chunks of `chunk`, x in `dtype`; else why not."""
    r = h // g
    hb = head_block(r, p)
    if chunk % _LANES:
        return f"a chunk of {chunk} is no multiple of the kernels' {_LANES} lanes"
    if (hb * p) % _LANES or (p < _LANES and _LANES % p):
        return f"heads of width {p} do not tile {_LANES} lanes"
    if n % _LANES and g > 1:
        return f"a state of {n} is no multiple of {_LANES} lanes"
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"the kernels take bfloat16 and float32, not {jnp.dtype(dtype)}"
    if _vmem_bytes(chunk, hb, p, n, h, jnp.dtype(dtype).itemsize) > _VMEM_LIMIT // 2:
        return f"a chunk of {chunk} with {hb} heads of {p} x {n} does not fit the kernels' VMEM"
    return None


class _Heads:
    """A grid step's view of its chunk's decay sums and dt, [Q, H] columns
    and [H, Q] rows, a head picked by a traced index; and the lanes of a
    tile of m heads."""

    def __init__(self, dt, cum, cum_rows, p, m):
        self.dt, self.cum, self.rows = dt, cum, cum_rows
        self.p, self.lt = p, m * p
        q = cum.shape[0]
        self.lane = lax.broadcasted_iota(jnp.int32, cum.shape, 1)
        self.sub = lax.broadcasted_iota(jnp.int32, cum_rows.shape, 0)
        self.last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
        self.tile_lane = lax.broadcasted_iota(jnp.int32, (q, self.lt), 1)
        self.tile_sub = lax.broadcasted_iota(jnp.int32, (self.lt, 1), 0)

    def column(self, tile, h):
        return jnp.sum(jnp.where(self.lane == h, tile, 0.0), axis=1, keepdims=True)

    def row(self, h):
        return jnp.sum(jnp.where(self.sub == h, self.rows, 0.0), axis=0, keepdims=True)

    def tile(self, first, m):
        """The decay sums, their last step and dt of the m heads from
        `first`: [Q, 1] columns, [1, 1] values, [1, Q] rows."""
        cums = [self.column(self.cum, first + j) for j in range(m)]
        lasts = [jnp.sum(jnp.where(self.last, col, 0.0), axis=0, keepdims=True) for col in cums]
        dts = [self.column(self.dt, first + j) for j in range(m)]
        rows = [self.row(first + j) for j in range(m)]
        return cums, lasts, dts, rows

    def mine(self, j):
        """[Q, lt] bool: the lanes of the tile's j-th head."""
        return (self.tile_lane >= j * self.p) & (self.tile_lane < (j + 1) * self.p)

    def spread(self, cols):
        """One [Q, 1] column a head of the tile -> [Q, lt], each over its
        head's lanes."""
        out = jnp.broadcast_to(cols[0], (cols[0].shape[0], self.lt))
        for j in range(1, len(cols)):
            out = jnp.where(self.tile_lane >= j * self.p, cols[j], out)
        return out

    def spread_rows(self, scalars):
        """One [1, 1] value a head of the tile -> [lt, 1], each over its
        head's rows of a [lt, N] state."""
        out = jnp.broadcast_to(scalars[0], (self.lt, 1))
        for j in range(1, len(scalars)):
            out = jnp.where(self.tile_sub >= j * self.p, scalars[j], out)
        return out

    def group_sum(self, v, j, m):
        """[Q, lt] -> [Q, 1]: the sum over the tile's j-th head's lanes."""
        return jnp.sum(v if m == 1 else jnp.where(self.mine(j), v, 0.0), axis=1, keepdims=True)


def _masked_exp(mask, diff):
    # masked BEFORE the exponential: outside the mask the difference is
    # positive and may overflow
    return jnp.exp(jnp.where(mask, diff, -jnp.inf))


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(x_ref, dt_ref, cum_ref, cumr_ref, b_ref, c_ref, y_ref, s_out_ref, s_ref, scores_ref,
                *, hb, m, p, per_group):
    c, i = pl.program_id(1), pl.program_id(2)
    q, lt, dtype = x_ref.shape[0], m * p, x_ref.dtype

    @pl.when(c == 0)
    def _():
        s_ref[i] = jnp.zeros(s_ref.shape[1:], jnp.float32)

    @pl.when(i % per_group == 0)
    def _():
        scores_ref[...] = _dot(c_ref[...], b_ref[...], _NT)  # C B^T, [q, s]

    s = s_ref[i]
    s_out_ref[...] = s
    heads = _Heads(dt_ref[...], cum_ref[...], cumr_ref[...], p, m)
    causal = lax.broadcasted_iota(jnp.int32, (q, q), 0) >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    scores = scores_ref[...]
    bm, cm = b_ref[...], c_ref[...]
    carried = _dot(cm, s.astype(dtype), _NT)  # C s^T, [Q, hb x P]
    for k in range(hb // m):
        lanes = slice(k * lt, (k + 1) * lt)
        cums, lasts, dts, rows = heads.tile(i * hb + k * m, m)
        xdt = (x_ref[:, lanes].astype(jnp.float32) * heads.spread(dts)).astype(dtype)
        y = carried[:, lanes] * jnp.exp(heads.spread(cums))
        for j in range(m):
            # the decay from step s to step q: exp(cum_q - cum_s), s <= q
            mj = (scores * _masked_exp(causal, cums[j] - rows[j])).astype(dtype)
            yj = _dot(mj, xdt)
            y = y + (yj if m == 1 else jnp.where(heads.mine(j), yj, 0.0))
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        to_end = jnp.exp(heads.spread([last - col for last, col in zip(lasts, cums)]))
        fed = (xdt.astype(jnp.float32) * to_end).astype(dtype)
        kept = jnp.exp(heads.spread_rows(lasts)) * s[lanes]
        s_ref[i, lanes, :] = kept + _dot(fed, bm, _TN)


def _bwd_kernel(x_ref, dt_ref, cum_ref, cumr_ref, b_ref, c_ref, s_in_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dcum_ref, dcumr_ref, ddt_ref,
                g_ref, scores_ref, dscores_ref, db_acc, dc_acc, *, hb, m, p, per_group):
    c, i = pl.program_id(1), pl.program_id(2)
    q, lt, dtype = x_ref.shape[0], m * p, x_ref.dtype

    @pl.when(c == 0)
    def _():
        g_ref[i] = jnp.zeros(g_ref.shape[1:], jnp.float32)

    @pl.when(i % per_group == 0)
    def _():
        scores_ref[...] = _dot(b_ref[...], c_ref[...], _NT)  # B C^T = (C B^T)^T, [s, q]
        dscores_ref[...] = jnp.zeros(dscores_ref.shape, jnp.float32)
        db_acc[...] = jnp.zeros(db_acc.shape, jnp.float32)
        dc_acc[...] = jnp.zeros(dc_acc.shape, jnp.float32)

    @pl.when(i == 0)
    def _():
        for ref in (dcum_ref, dcumr_ref, ddt_ref):
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    g = g_ref[i]  # the cotangent of the state the chunk leaves
    s = s_in_ref[...]  # the state entering it
    heads = _Heads(dt_ref[...], cum_ref[...], cumr_ref[...], p, m)
    # [s, q]: s <= q
    causal = lax.broadcasted_iota(jnp.int32, (q, q), 0) <= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    scores = scores_ref[...]
    bm, cm = b_ref[...], c_ref[...]
    carried = _dot(cm, s.astype(dtype), _NT)  # C s^T
    fed_back = _dot(bm, g.astype(dtype), _NT)  # b g^T: what the leaving state takes of x dt
    dcum, dcumr, ddt = dcum_ref[...], dcumr_ref[...], ddt_ref[...]
    dscores = jnp.zeros((q, q), jnp.float32)
    db = jnp.zeros(db_acc.shape, jnp.float32)
    dc = jnp.zeros(dc_acc.shape, jnp.float32)
    for k in range(hb // m):
        lanes = slice(k * lt, (k + 1) * lt)
        first = i * hb + k * m
        cums, lasts, dts, rows = heads.tile(first, m)
        x = x_ref[:, lanes].astype(jnp.float32)
        dy = dy_ref[:, lanes]
        dt_lanes = heads.spread(dts)
        e = jnp.exp(heads.spread(cums))
        to_end = jnp.exp(heads.spread([last - col for last, col in zip(lasts, cums)]))
        xdt = (x * dt_lanes).astype(dtype)
        dxdt = fed_back[:, lanes] * to_end
        dy32 = dy.astype(jnp.float32)
        # what the carried state's term and the leaving state's weights take
        # of the decay sums, a head's lanes summed
        d_carried = dy32 * carried[:, lanes] * e
        d_to_end = xdt.astype(jnp.float32) * fed_back[:, lanes] * to_end
        g_s = g[lanes] * s[lanes]
        for j in range(m):
            h = first + j
            decay = _masked_exp(causal, rows[j] - cums[j])  # [s, q]: exp(cum_q - cum_s)
            mj = (scores * decay).astype(dtype)
            dyj = dy if m == 1 else jnp.where(heads.mine(j), dy, jnp.zeros_like(dy))
            dxdt = dxdt + _dot(mj, dyj)
            dm = _dot(xdt, dyj, _NT) * decay  # the cotangent of B C^T, this head's
            dscores = dscores + dm
            dl = dm * scores  # of the decay, times the decay
            de, dw = heads.group_sum(d_carried, j, m), heads.group_sum(d_to_end, j, m)
            whole = jnp.sum(jnp.sum(g_s[j * p:(j + 1) * p], axis=1, keepdims=True), axis=0, keepdims=True)
            to_last = jnp.sum(dw, axis=0, keepdims=True) + whole * jnp.exp(lasts[j])
            col = de - dw - jnp.sum(dl, axis=1, keepdims=True) + jnp.where(heads.last, to_last, 0.0)
            dcum = jnp.where(heads.lane == h, col, dcum)
            dcumr = jnp.where(heads.sub == h, jnp.sum(dl, axis=0, keepdims=True), dcumr)
        dx_ref[:, lanes] = (dxdt * dt_lanes).astype(dx_ref.dtype)
        for j in range(m):
            ddt = jnp.where(heads.lane == first + j, heads.group_sum(dxdt * x, j, m), ddt)
        dye = (dy32 * e).astype(dtype)
        kept = jnp.exp(heads.spread_rows(lasts)) * g[lanes]
        g_ref[i, lanes, :] = kept + _dot(dye, cm, _TN)
        dc = dc + _dot(dye, s[lanes].astype(dtype))
        fed = (xdt.astype(jnp.float32) * to_end).astype(dtype)
        db = db + _dot(fed, g[lanes].astype(dtype))
    dcum_ref[...], dcumr_ref[...], ddt_ref[...] = dcum, dcumr, ddt
    dscores_ref[...] += dscores
    db_acc[...] += db
    dc_acc[...] += dc

    @pl.when(i % per_group == per_group - 1)
    def _():
        ds = dscores_ref[...].astype(dtype)  # [s, q]
        db_ref[...] = (db_acc[...] + _dot(ds, cm)).astype(db_ref.dtype)
        dc_ref[...] = (dc_acc[...] + _dot(ds, bm, _TN)).astype(dc_ref.dtype)


def _params(interpret):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)}


def _specs(chunk, nc, h, hb, p, n, per_group, reverse):
    """Block specs of (x-like, a chunk's columns, its rows, b-like, state)
    over the grid (batch, chunk, head block), the chunks in reverse where
    asked."""
    ch = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    return (pl.BlockSpec((None, chunk, hb * p), lambda b, c, i: (b, ch(c), i)),
            pl.BlockSpec((None, chunk, h), lambda b, c, i: (b, ch(c), 0)),
            pl.BlockSpec((None, h, chunk), lambda b, c, i: (b, 0, ch(c))),
            pl.BlockSpec((None, chunk, n), lambda b, c, i: (b, ch(c), i // per_group)),
            pl.BlockSpec((None, None, hb * p, n), lambda b, c, i: (b, ch(c), i, 0)))


# Each call is a jitted function of its own: the layers and passes of one
# shape share ONE traced and lowered kernel (XLA inlines it under each call
# site's scope).
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _fwd_call(x, dt, cum, cum_rows, b, c, r, p, chunk, interpret):
    bsz, tp, h = cum.shape
    n = b.shape[2] * r // h
    nc, hb = tp // chunk, head_block(r, p)
    m, per_group = _tile_heads(hb, p), r // hb
    xs, col, row, bs, st = _specs(chunk, nc, h, hb, p, n, per_group, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, m=m, p=p, per_group=per_group),
        grid=(bsz, nc, h // hb),
        in_specs=[xs, col, col, row, bs, bs],
        out_specs=[xs, st],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, nc, h * p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h // hb, hb * p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        interpret=interpret,
        **_params(interpret),
    )(x, dt, cum, cum_rows, b, c)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _bwd_call(x, dt, cum, cum_rows, b, c, states, dy, b_dtype, r, p, chunk, interpret):
    bsz, tp, h = cum.shape
    n = b.shape[2] * r // h
    nc, hb = tp // chunk, head_block(r, p)
    m, per_group = _tile_heads(hb, p), r // hb
    xs, col, row, bs, st = _specs(chunk, nc, h, hb, p, n, per_group, True)
    f32 = lambda like: jax.ShapeDtypeStruct(like.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, m=m, p=p, per_group=per_group),
        grid=(bsz, nc, h // hb),
        in_specs=[xs, col, col, row, bs, bs, st, xs],
        out_specs=[xs, bs, bs, col, row, col],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(b.shape, b_dtype),
                   jax.ShapeDtypeStruct(b.shape, b_dtype), f32(cum), f32(cum_rows), f32(cum)],
        scratch_shapes=[pltpu.VMEM((h // hb, hb * p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, n), jnp.float32),
                        pltpu.VMEM((chunk, n), jnp.float32)],
        interpret=interpret,
        **_params(interpret),
    )(x, dt, cum, cum_rows, b, c, states, dy)


def _flat(chunk, x, dt, a, b, c):
    """[B, T, G, R, ...] -> the kernels' arrays, T padded to whole chunks
    with zeros (a padded step has dt = 0: it neither decays nor feeds the
    state): x [B, T', H x P], dt and the decay sums within each chunk as
    [B, T', H] columns, the decay sums as [B, H, T'] rows, b and c [B, T',
    G x N]."""
    bsz, t, g, r, p = x.shape
    pad = -t % chunk
    flat = lambda v: jnp.pad(v.reshape(bsz, t, -1), ((0, 0), (0, pad), (0, 0)))
    dtf = flat(dt)
    logs = (dtf * a.reshape(1, 1, -1)).reshape(bsz, -1, chunk, g * r)
    cum = jnp.cumsum(logs, axis=2).reshape(dtf.shape)
    return flat(x), dtf, cum, jnp.swapaxes(cum, 1, 2), flat(b).astype(x.dtype), flat(c).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(x, dt, a, b, c, chunk, interpret):
    bsz, t, g, r, p = x.shape
    y, states = _fwd_call(*_flat(chunk, x, dt, a, b, c), r, p, chunk, interpret)
    return y[:, :t].reshape(x.shape), states.reshape(bsz, -1, g, r, p, b.shape[-1])


@functools.partial(jax.jit, static_argnums=(7, 8))
def _backward(x, dt, a, b, c, states, dy, chunk, interpret):
    bsz, t, g, r, p = x.shape
    xf, dtf, cum, cum_rows, bf, cf = _flat(chunk, x, dt, a, b, c)
    dyf = jnp.pad(dy.reshape(bsz, t, -1), ((0, 0), (0, xf.shape[1] - t), (0, 0)))
    dx, db, dc, dcum, dcum_rows, ddt = _bwd_call(
        xf, dtf, cum, cum_rows, bf, cf, states.reshape(bsz, states.shape[1], -1, b.shape[-1]), dyf,
        b.dtype, r, p, chunk, interpret)
    # the decay sums are a cumsum of dt a within each chunk: their cotangent
    # summed from each step to the chunk's end is that of dt a
    dcum = (dcum + jnp.swapaxes(dcum_rows, 1, 2)).reshape(bsz, -1, chunk, g * r)
    dlog = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), axis=2), 2).reshape(dtf.shape)
    ddt = ddt + dlog * a.reshape(1, 1, -1)
    da = jnp.sum(dlog * dtf, axis=(0, 1)).reshape(a.shape)

    def cut(v, like):
        return v[:, :t].reshape(like.shape).astype(like.dtype)

    return cut(dx, x), cut(ddt, dt), da, cut(db, b), cut(dc, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def ssd_kernels(chunk, interpret, x, dt, a, b, c):
    """`ops/ssd.ssd_scan`'s contract (dt and a float32) on the kernels."""
    return _ssd_kernels_fwd(chunk, interpret, x, dt, a, b, c)[0]


@jax.named_scope("ssd_scan")
def _ssd_kernels_fwd(chunk, interpret, x, dt, a, b, c):
    with _one_trace_both_ways():
        y, states = _forward(x, dt, a, b, c, chunk, interpret)
    return y, (x, dt, a, b, c, states)


@jax.named_scope("ssd_scan")
def _ssd_kernels_bwd(chunk, interpret, res, dy):
    with _one_trace_both_ways():
        return _backward(*res, dy, chunk, interpret)


ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)
