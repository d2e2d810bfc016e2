"""Blocked (flash) attention as Pallas TPU kernels: softmax(QK^T)V and its
gradient with the [T, T] scores never in HBM.

The dense path writes the float32 scores, the weights kept for the backward
and both their gradients through HBM (2.2-2.4 GB a layer at B = 8, H = 8,
T = 1,024) and is bound by exactly those bytes; here a block of scores lives
in VMEM between two MXU products and the only things stored are the output
and one float32 log-sum-exp a row.  Two kernels:

  * forward, grid (B, head groups, T / bq): one [bq, W] block of queries
    against the row's keys and values (whole in VMEM), key blocks of bk in a
    loop with the online softmax;
  * ONE backward, grid (B, head groups, T / bk): one [bk, W] block of keys
    and values against the row's queries in a loop, p = exp(s - lse)
    recomputed once, five products a block pair (s, dv, dp, dk, dq), dk and
    dv summed in registers, dq in a float32 [T, W] VMEM accumulator across
    the key blocks.

They read and write the layer's own [B, T, H x dh] array: a block is W = 128
lanes of it, two heads side by side at dh = 64, one at dh = 128, so nothing
is transposed around the kernels and every load and store is lane-dense.
Inside a block a head is picked by zeroing the OTHER heads' lanes of one
operand (a contraction over 128 lanes costs the MXU what one over 64 does).
The backward works on the TRANSPOSED scores [bk, bq]: a row's statistics
(lse, delta) then run along the lanes, [1, bq] broadcast over sublanes, and
are stored as [B, H, T] float32 and not as one-lane columns.  The key-length
and causal masks are computed only in blocks that straddle a row's length or
the diagonal; blocks wholly beyond either are skipped; 1/sqrt(dh) is folded
into q where it is a power of two (dh = 64: exact), else applied to the
float32 scores.  bfloat16 operands into the MXU, float32 sums, float32
softmax statistics, exact exp.

`multi_head_attention` takes these kernels on the TPU backend from
`layers/attention._FLASH_FROM_KEYS` keys on (PERF.md section 6, PR 35: from
there they beat the dense path in time as well as in memory), and below it
where `use_pallas_attention` asks.  XLA partitions no Mosaic kernel: in a
program over several devices they must sit inside a shard_map that holds every
mesh axis, which the layer sees to (`layers/attention._blocked_core`: over the
rows of the batch).  `interpret=True` runs them on the CPU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def flash_attention(
    q: jnp.ndarray,  # [B, T, H, dh]
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: Optional[jnp.ndarray] = None,  # [B] valid key counts
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """[B, T, H, dh] -> [B, T, H, dh]; exact softmax attention (the kernel of
    the differentiable path; the log-sum-exp is dropped here)."""
    return _flash_fwd(q, k, v, lengths, causal, block_q, block_k, interpret)[0]


def supported(t: int, dh: int) -> bool:
    """Shapes the kernels take: T a multiple of the 128-lane block, a head
    width that tiles."""
    return t % _LANES == 0 and dh % 8 == 0


def auto_blocks(t: int, causal: bool = False) -> tuple:
    """(block_q, block_k) for T keys on a v5e: 512 queries against 1,024
    keys, 512 under the causal mask (smaller blocks skip more of what lies
    above the diagonal).  From `scripts/attention_sweep.py` (PR 35; forward
    + backward of the core, ms, bq x bk):
      B=8 T=1024 H=8 dh=64:   128x128 2.50, 256x256 1.50, 256x512 1.18,
        512x256 1.26, 512x512 1.10, 512x1024 1.07, 1024x1024 1.08;
        causal 128x128 1.58, 256x256 1.09, 256x512 1.00, 512x512 0.95,
        512x1024 1.10, 1024x1024 1.06
      B=4 T=2048 H=8 dh=64:   512x512 1.83, 512x1024 1.76, 1024x1024 1.77,
        2048x2048 1.98; causal 256x512 1.41, 512x512 1.32, 1024x1024 1.50
      B=2 T=2048 H=32 dh=128, causal: 256x512 3.55, 512x512 3.16,
        1024x1024 3.52
    The key block matters most (each one costs a pass over the [bq, 128]
    running statistics); the query block hardly from 256 up."""
    bq = min(512, t)
    bk = min(512 if causal else 1024, t)
    while t % bq:
        bq //= 2
    while t % bk:
        bk //= 2
    return bq, bk


def _heads_per_block(h: int, dh: int) -> int:
    """Heads that share one lane block of the [B, T, H x dh] array: as many
    as fill 128 lanes, one where a head fills them alone, all of them where
    neither divides (the block is then the array's whole width)."""
    if dh % _LANES == 0:
        return 1
    if _LANES % dh == 0 and h % (_LANES // dh) == 0:
        return _LANES // dh
    return h


def _scale_folds(dh: int) -> bool:
    """1/sqrt(dh) is a power of two: q * scale is exact in bfloat16."""
    return math.log2(dh) % 2 == 0


def _head_lanes(w, dh, hb):
    """[1, W] lane masks, one a head of the block (None where it holds one)."""
    if hb == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    return [(lane >= i * dh) & (lane < (i + 1) * dh) for i in range(hb)]


def _pick(lanes, x):
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _over_lanes(stat, w):
    """A lane-replicated [rows, 128] statistic over W lanes."""
    if w == _LANES:
        return stat
    if w % _LANES == 0:
        return jnp.tile(stat, (1, w // _LANES))
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], w))


def _two_loops(lo, mid, hi, step, carry, masked_first):
    """Blocks [lo, mid) then [mid, hi), one range with the mask computed and
    one without."""
    first = functools.partial(step, masked=masked_first)
    second = functools.partial(step, masked=not masked_first)
    return jax.lax.fori_loop(mid, hi, second, jax.lax.fori_loop(lo, mid, first, carry))


def _fa_fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, bq, bk, t, dh, hb, causal):
    qi = pl.program_id(2)
    valid = len_ref[pl.program_id(0)]
    w = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    fold = _scale_folds(dh)
    q = q_ref[...]
    if fold:
        q = (q * scale).astype(q.dtype)
    lanes = _head_lanes(w, dh, hb)
    qs = [_pick(m, q) for m in lanes]
    reps = bk // _LANES

    def step(j, carry, masked):
        ms, ls, acc = carry
        start = pl.multiple_of(j * bk, bk)
        k = k_ref[pl.ds(start, bk), :]
        v = v_ref[pl.ds(start, bk), :]
        if masked:
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = k_pos < valid
            if causal:
                keep &= k_pos <= qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        new_ms, new_ls, new_acc = [], [], None
        for i in range(hb):
            s = jax.lax.dot_general(qs[i], k, _NT, preferred_element_type=jnp.float32)
            if not fold:
                s = s * scale
            if masked:
                s = jnp.where(keep, s, NEG_INF)
            # the running max and sum stay replicated over 128 lanes, as in
            # jax's own TPU kernels: no one-lane columns to broadcast
            m_new = jnp.maximum(ms[i], jnp.max(s, axis=-1, keepdims=True))  # [bq, 128]
            p = jnp.exp(s - jnp.tile(m_new, (1, reps)))
            if masked:
                p = jnp.where(keep, p, 0.0)
            alpha = jnp.exp(ms[i] - m_new)
            new_ms.append(m_new)
            new_ls.append(alpha * ls[i] + jnp.sum(p, axis=-1, keepdims=True))
            # [bq, W]: this head's lanes hold its p @ v, the others' are not read
            term = _over_lanes(alpha, w) * acc + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            new_acc = term if new_acc is None else jnp.where(lanes[i], term, new_acc)
        return tuple(new_ms), tuple(new_ls), new_acc

    nk = t // bk
    # key blocks wholly past the row's length or the diagonal are skipped;
    # the mask is computed in those that straddle either
    hi = jnp.minimum(pl.cdiv((qi + 1) * bq, bk) if causal else nk, pl.cdiv(valid, bk))
    full = jnp.minimum((qi * bq + 1) // bk if causal else nk, valid // bk)
    stat = lambda c: tuple(jnp.full((bq, _LANES), c, jnp.float32) for _ in range(hb))
    init = (stat(NEG_INF), stat(0.0), jnp.zeros((bq, w), jnp.float32))
    ms, ls, acc = _two_loops(0, full, hi, step, init, masked_first=False)
    l_w = None
    for i in range(hb):
        l_safe = jnp.maximum(ls[i], 1e-20)  # a row of no keys: zeros out, no NaN
        l_i = _over_lanes(l_safe, w)
        l_w = l_i if l_w is None else jnp.where(lanes[i], l_i, l_w)
        # the row statistic leaves as a ROW: [bq, 128] replicated -> [1, bq]
        lse_ref[i:i + 1, :] = jnp.transpose(ms[i] + jnp.log(l_safe))[:1, :]
    o_ref[...] = (acc / l_w).astype(o_ref.dtype)


def _fa_bwd_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_acc, *, bq, bk, t, dh, hb, causal):
    ki = pl.program_id(2)
    valid = len_ref[pl.program_id(0)]
    w = k_ref.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    fold = _scale_folds(dh)
    k = k_ref[...]
    v = v_ref[...]
    lanes = _head_lanes(w, dh, hb)
    ks = [_pick(m, k) for m in lanes]

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(j, carry, masked):
        dk, dv = carry
        start = pl.multiple_of(j * bq, bq)
        q = q_ref[pl.ds(start, bq), :]
        do = do_ref[pl.ds(start, bq), :]
        if fold:
            q = (q * scale).astype(q.dtype)
        if masked:
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            keep = k_pos < valid
            if causal:
                keep &= k_pos <= start + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        dq = None
        for i in range(hb):
            q_i = _pick(lanes[i], q)
            do_i = _pick(lanes[i], do)
            # the scores TRANSPOSED, [bk, bq]: the rows' lse and delta run
            # along the lanes, [1, bq], and broadcast over sublanes
            s = jax.lax.dot_general(k, q_i, _NT, preferred_element_type=jnp.float32)
            if not fold:
                s = s * scale
            p = jnp.exp(s - lse_ref[j, pl.ds(i, 1), :])
            if masked:
                p = jnp.where(keep, p, 0.0)
            dv = dv + jnp.dot(p.astype(do.dtype), do_i, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do_i, _NT, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[j, pl.ds(i, 1), :])).astype(q.dtype)
            dk = dk + jnp.dot(ds, q_i, preferred_element_type=jnp.float32)
            dq_i = jax.lax.dot_general(ds, ks[i], _TN, preferred_element_type=jnp.float32)
            dq = dq_i if dq is None else dq + dq_i
        dq_acc[pl.ds(start, bq), :] += dq
        return dk, dv

    nq = t // bq
    # query blocks wholly above the diagonal are skipped, as is everything
    # where this key block is wholly padding; the mask is computed for every
    # query block where it straddles the row's length, else only on the diagonal
    lo = (ki * bk) // bq if causal else 0
    full = pl.cdiv((ki + 1) * bk - 1, bq) if causal else 0
    lo = jnp.where(ki * bk >= valid, nq, lo)
    full = jnp.maximum(jnp.where((ki + 1) * bk > valid, nq, full), lo)
    zeros = jnp.zeros((bk, w), jnp.float32)
    dk, dv = _two_loops(lo, full, nq, step, (zeros, zeros), masked_first=True)
    dk_ref[...] = (dk if fold else dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_diff(q, k, v, lengths, causal, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, lengths, causal, block_q, block_k, interpret)[0]


def _blocks(t, block_q, block_k):
    bq, bk = min(block_q, t), min(block_k, t)
    if t % bq or t % bk or bq % _LANES or bk % _LANES:
        raise ValueError(
            f"T={t} must be divisible by block sizes ({bq}, {bk}), themselves "
            f"multiples of {_LANES} — rows beyond the last full block would be "
            "silently dropped"
        )
    return bq, bk


def _params(interpret):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)}


# The two calls are jitted functions of their own: a model's layers of one
# shape then share ONE traced and lowered kernel a pass (XLA inlines it under
# each layer's scope).  Traced and lowered a layer, the Transformer's 36
# kernels cost a warm boot 6 s of Python (PERF.md section 6, PR 35).
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _fwd_call(q, k, v, lengths, causal, block_q, block_k, interpret):
    b, t, h, dh = q.shape
    bq, bk = _blocks(t, block_q, block_k)
    hb = _heads_per_block(h, dh)
    w = hb * dh
    flat = lambda x: x.reshape(b, t, h * dh)
    kernel = functools.partial(_fa_fwd_kernel, bq=bq, bk=bk, t=t, dh=dh, hb=hb, causal=causal)
    row = pl.BlockSpec((None, t, w), lambda bi, g, qi, _: (bi, 0, g))
    blk = pl.BlockSpec((None, bq, w), lambda bi, g, qi, _: (bi, qi, g))
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb, t // bq),
            in_specs=[blk, row, row],
            out_specs=[blk, pl.BlockSpec((None, None, hb, bq), lambda bi, g, qi, _: (bi, g, 0, qi))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, t, h * dh), q.dtype),
            jax.ShapeDtypeStruct((b, h // hb, hb, t), jnp.float32),
        ],
        interpret=interpret,
        **_params(interpret),
    )(lengths, flat(q), flat(k), flat(v))
    return out.reshape(b, t, h, dh), lse


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _bwd_call(q, k, v, lengths, out, lse, g, causal, block_q, block_k, interpret):
    b, t, h, dh = q.shape
    bq, bk = _blocks(t, block_q, block_k)
    hb = _heads_per_block(h, dh)
    w = hb * dh
    nq = t // bq
    # rows' statistics a query block: [B, groups, T / bq, hb, bq]
    by_block = lambda x: x.reshape(b, h // hb, hb, nq, bq).transpose(0, 1, 3, 2, 4)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B, T, H]
    delta = by_block(delta.transpose(0, 2, 1))
    flat = lambda x: x.reshape(b, t, h * dh)
    kernel = functools.partial(_fa_bwd_kernel, bq=bq, bk=bk, t=t, dh=dh, hb=hb, causal=causal)
    row = pl.BlockSpec((None, t, w), lambda bi, g_, ki, _: (bi, 0, g_))
    blk = pl.BlockSpec((None, bk, w), lambda bi, g_, ki, _: (bi, ki, g_))
    stat = pl.BlockSpec((None, None, nq, hb, bq), lambda bi, g_, ki, _: (bi, g_, 0, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb, t // bk),
            in_specs=[row, blk, blk, row, stat, stat],
            out_specs=[row, blk, blk],
            scratch_shapes=[pltpu.VMEM((t, w), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dh), x.dtype) for x in (q, k, v)],
        interpret=interpret,
        **_params(interpret),
    )(lengths, flat(q), flat(k), flat(v), flat(g), by_block(lse), delta)
    return tuple(x.reshape(b, t, h, dh) for x in (dq, dk, dv))


def _flash_fwd(q, k, v, lengths, causal, block_q, block_k, interpret):
    if lengths is None:
        lengths = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    out, lse = _fwd_call(q, k, v, lengths, causal, block_q, block_k, interpret)
    return out, (q, k, v, lengths, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    return (*_bwd_call(*res, g, causal, block_q, block_k, interpret), None)


flash_attention_diff.defvjp(_flash_fwd, _flash_bwd)
