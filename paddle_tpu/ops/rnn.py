"""Recurrent cells as lax.scan loops — the TPU-native replacement for the
reference's fused CUDA LSTM/GRU kernels (reference: paddle/cuda/src/
hl_cuda_lstm.cu, hl_gpu_gru.cuh, consumed by paddle/gserver/layers/
{LstmLayer,GatedRecurrentLayer}.cpp via SequenceToBatch reordering).

Instead of reordering variable-length sequences into shrinking per-timestep
batches (SequenceToBatch.h), we keep a fixed [B, T, ...] padded layout and
scan over T with a carry-through mask: padded steps propagate the previous
state unchanged.  XLA unrolls the per-step gate math into fused HLO while the
big input projections (x @ W) stay *outside* the scan as one [B*T] matmul on
the MXU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.ops import acc_einsum, acc_matmul
from paddle_tpu.ops.activations import get_activation

# Step-body unroll factor.  All three cells use custom-VJP cores (chain
# GEMMs only inside the scans, weight grads deferred to post-scan einsums),
# whose light bodies are latency-bound on the chained [B,H]x[H,*] matmul:
# unroll=1 measures fastest on v5e (LSTM text-cls B=128/T=100/H=512
# fwd+bwd: unroll 1 -> 5.9 ms, 4 -> 6.9 ms; a bare 200-GEMM chain
# microbench shows the same 13.4 vs 25.5 us/link shape).
_UNROLL_FUSED = 1


def _time_major(x):
    """[B, T, D] -> [T, B, D] for scan."""
    return jnp.swapaxes(x, 0, 1)


def _mask_seq(lengths: Optional[jnp.ndarray], max_len: int, reverse: bool):
    """[T, B, 1] carry mask; for reverse scans the *flipped* positions are
    valid when t >= T - len."""
    if lengths is None:
        return None
    t = jnp.arange(max_len, dtype=jnp.int32)[:, None]
    if reverse:
        valid = t >= (max_len - lengths[None, :])
    else:
        valid = t < lengths[None, :]
    return valid[..., None]


def _lstm_elem(acts, a, c_p, h_p, m, w_ci, w_cf, w_co):
    """The per-step ELEMENTWISE LSTM cell math (everything except the
    recurrent GEMM): a = x_t + h₋W (+bias) already combined.  Shared by the
    forward scan and the backward pass (which re-derives its local VJP from
    this closure, so peepholes/masking/activation choices stay exact)."""
    f_gate = get_activation(acts[0])
    f_act = get_activation(acts[1])
    f_state = get_activation(acts[2])
    a_i, a_f, a_g, a_o = jnp.split(a, 4, axis=-1)
    a_i = a_i + w_ci * c_p
    a_f = a_f + w_cf * c_p
    i_t = f_gate(a_i)
    f_t = f_gate(a_f)
    c_t = f_t * c_p + i_t * f_act(a_g)
    o_t = f_gate(a_o + w_co * c_t)
    h_t = o_t * f_state(c_t)
    h_t = jnp.where(m, h_t, h_p)
    c_t = jnp.where(m, c_t, c_p)
    return h_t, c_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lstm_core(acts, xs, w_h, w_ci, w_cf, w_co, h0, c0, mask):
    """Time-major LSTM recurrence with a hand-written VJP.

    Autodiff of the naive scan accumulates dW_h with an extra [H,4H]
    carry + a second [H,B]x[B,4H] GEMM in EVERY backward step — for
    B=128/T=100/H=512 that is ~100 extra chained GEMMs and ~800 MB of f32
    accumulator traffic.  Here the backward scan computes only the gate
    cotangents (one [B,4H]x[4H,H] GEMM per step) and the weight gradient
    is ONE batched einsum over the saved sequences afterwards — the same
    restructuring the reference's fused CUDA kernels do by hand
    (hl_cuda_lstm.cu backwardOneSequence vs its weight-grad GEMM pass).

    xs: [T,B,4H] input projections (+bias), mask: [T,B,1] bool.
    Returns (hs [T,B,H], h_last, c_last)."""
    hs, _as, _cs, h_last, c_last = _lstm_fwd_scan(
        acts, xs, w_h, w_ci, w_cf, w_co, h0, c0, mask
    )
    return hs, h_last, c_last


def _lstm_fwd_scan(acts, xs, w_h, w_ci, w_cf, w_co, h0, c0, mask):
    def step(carry, inp):
        h_p, c_p = carry
        x_t, m = inp
        a = x_t + acc_matmul(h_p, w_h)
        h_t, c_t = _lstm_elem(acts, a, c_p, h_p, m, w_ci, w_cf, w_co)
        return (h_t, c_t), (h_t, a, c_t)

    (h_last, c_last), (hs, a_seq, c_seq) = lax.scan(
        step, (h0, c0), (xs, mask), unroll=_UNROLL_FUSED
    )
    return hs, a_seq, c_seq, h_last, c_last


def _lstm_core_fwd(acts, xs, w_h, w_ci, w_cf, w_co, h0, c0, mask):
    hs, a_seq, c_seq, h_last, c_last = _lstm_fwd_scan(
        acts, xs, w_h, w_ci, w_cf, w_co, h0, c0, mask
    )
    res = (a_seq, c_seq, hs, w_h, w_ci, w_cf, w_co, h0, c0, mask)
    return (hs, h_last, c_last), res


def _lstm_core_bwd(acts, res, cts):
    a_seq, c_seq, hs, w_h, w_ci, w_cf, w_co, h0, c0, mask = res
    dhs, dh_last, dc_last = cts
    # previous-step state sequences aligned with step t
    h_prev_seq = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    c_prev_seq = jnp.concatenate([c0[None], c_seq[:-1]], axis=0)
    w_h_t = w_h.T
    # peephole-grad carries accumulate across all T steps: keep them at
    # >= f32 like the deferred weight einsums (bf16 += bf16 over 100 steps
    # loses low bits)
    acc_w = jnp.promote_types(w_ci.dtype, jnp.float32)
    zeros_w = (
        jnp.zeros(w_ci.shape, acc_w),
        jnp.zeros(w_cf.shape, acc_w),
        jnp.zeros(w_co.shape, acc_w),
    )

    def step(carry, inp):
        dh, dc, dwci, dwcf, dwco = carry
        a_t, c_p, h_p, m, dh_out = inp
        dh = dh + dh_out
        _, vjp_fn = jax.vjp(
            lambda a, cp, hp, wci, wcf, wco: _lstm_elem(
                acts, a, cp, hp, m, wci, wcf, wco
            ),
            a_t, c_p, h_p, w_ci, w_cf, w_co,
        )
        da, dc_p, dh_p_elem, dwci_t, dwcf_t, dwco_t = vjp_fn((dh, dc))
        dh_p = acc_matmul(da, w_h_t) + dh_p_elem  # the ONE backward-chain GEMM
        return (
            (
                dh_p,
                dc_p,
                dwci + dwci_t.astype(dwci.dtype),
                dwcf + dwcf_t.astype(dwcf.dtype),
                dwco + dwco_t.astype(dwco.dtype),
            ),
            da,
        )

    (dh0, dc0, dwci, dwcf, dwco), da_seq = lax.scan(
        step,
        (dh_last, dc_last, *zeros_w),
        (a_seq, c_prev_seq, h_prev_seq, mask, dhs),
        reverse=True,
        unroll=_UNROLL_FUSED,
    )
    # weight grad as ONE big GEMM over the whole sequence, accumulated at
    # >= f32 (bf16 inputs accumulate f32; f64 tests stay f64)
    acc = jnp.promote_types(w_h.dtype, jnp.float32)
    dw_h = jnp.einsum(
        "tbh,tbg->hg", h_prev_seq, da_seq,
        preferred_element_type=acc,
    ).astype(w_h.dtype)
    d_mask = np.zeros(mask.shape, dtype=jax.dtypes.float0)
    return (
        da_seq,
        dw_h,
        dwci.astype(w_ci.dtype),
        dwcf.astype(w_cf.dtype),
        dwco.astype(w_co.dtype),
        dh0,
        dc0,
        d_mask,
    )


_lstm_core.defvjp(_lstm_core_fwd, _lstm_core_bwd)


def lstm_scan(
    gates: jnp.ndarray,  # [B, T, 4H] pre-computed input projections (i,f,g,o)
    w_h: jnp.ndarray,  # [H, 4H] recurrent weight
    bias: Optional[jnp.ndarray],  # [4H]
    w_ci: Optional[jnp.ndarray],  # [H] peephole input-gate
    w_cf: Optional[jnp.ndarray],  # [H] peephole forget-gate
    w_co: Optional[jnp.ndarray],  # [H] peephole output-gate
    lengths: Optional[jnp.ndarray] = None,
    *,
    gate_act: str = "sigmoid",
    act: str = "tanh",
    state_act: str = "tanh",
    reverse: bool = False,
    h0: Optional[jnp.ndarray] = None,
    c0: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Paddle-v1 LSTM with peepholes (LstmLayer.cpp forwardSequence):
        i = σ(a_i + w_ci∘c₋)   f = σ(a_f + w_cf∘c₋)
        c = f∘c₋ + i∘act(a_g)  o = σ(a_o + w_co∘c)   h = o∘state_act(c)
    Returns ([B, T, H] hidden sequence, (h_last, c_last))."""
    b, t, g4 = gates.shape
    h = g4 // 4

    xs = _time_major(gates)
    if bias is not None:
        xs = xs + bias  # num: allow[N401] LSTM gate-bias grad reduce rides the compute dtype (folds into the projection GEMM's epilogue); weight grads accumulate f32 post-scan
    if reverse:
        xs = jnp.flip(xs, axis=0)
    mask = _mask_seq(lengths, t, reverse)
    if mask is None:
        mask = jnp.ones((t, b, 1), bool)

    h_prev = h0 if h0 is not None else jnp.zeros((b, h), gates.dtype)
    c_prev = c0 if c0 is not None else jnp.zeros((b, h), gates.dtype)
    zeros_h = jnp.zeros((h,), gates.dtype)
    hs, h_last, c_last = _lstm_core(
        (gate_act, act, state_act),
        xs,
        w_h,
        w_ci if w_ci is not None else zeros_h,
        w_cf if w_cf is not None else zeros_h,
        w_co if w_co is not None else zeros_h,
        h_prev,
        c_prev,
        mask,
    )
    if reverse:
        hs = jnp.flip(hs, axis=0)
    return jnp.swapaxes(hs, 0, 1), (h_last, c_last)


def gru_scan(
    gates: jnp.ndarray,  # [B, T, 3H] input projections (u, r, c)
    w_h: jnp.ndarray,  # [H, 2H] recurrent weight for update+reset
    w_c: jnp.ndarray,  # [H, H] recurrent weight for candidate
    bias: Optional[jnp.ndarray],  # [3H]
    lengths: Optional[jnp.ndarray] = None,
    *,
    gate_act: str = "sigmoid",
    act: str = "tanh",
    reverse: bool = False,
    h0: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paddle-v1 GRU (GatedRecurrentLayer.cpp / hl_cpu_gru.cuh:238-253,
    hl_gru_ops.cuh gru_resetOutput/gru_finalOutput):
        u = σ(x_u + U_u h₋)   r = σ(x_r + U_r h₋)
        c = act(x_c + (r∘h₋) U_c)        # resetOutput = prevOut*r, THEN gemm
        h = (1-u)∘h₋ + u∘c               # prevOut - u*prevOut + u*frameState
    Returns ([B, T, H], h_last)."""
    b, t, g3 = gates.shape
    h = g3 // 3

    xs = _time_major(gates)
    if bias is not None:
        xs = xs + bias  # num: allow[N401] GRU gate-bias grad reduce rides the compute dtype; weight grads accumulate f32 post-scan
    if reverse:
        xs = jnp.flip(xs, axis=0)
    mask = _mask_seq(lengths, t, reverse)
    if mask is None:
        mask = jnp.ones((t, b, 1), bool)

    h_prev = h0 if h0 is not None else jnp.zeros((b, h), gates.dtype)
    hs, h_last = _gru_core((gate_act, act), xs, w_h, w_c, h_prev, mask)
    if reverse:
        hs = jnp.flip(hs, axis=0)
    return jnp.swapaxes(hs, 0, 1), h_last


def _gru_reset(acts, p_r, h_p):
    """rh = σ(p_r) ∘ h₋ — the reference's gru_resetOutput (hl_gru_ops.cuh),
    separated out because the candidate GEMM consumes its result."""
    return get_activation(acts[0])(p_r) * h_p


def _gru_final(acts, p_u, p_c, h_p, m):
    """h = (1-u)∘h₋ + u∘c with carry-through masking (gru_finalOutput)."""
    u = get_activation(acts[0])(p_u)
    c = get_activation(acts[1])(p_c)
    h_t = (1.0 - u) * h_p + u * c
    return jnp.where(m, h_t, h_p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gru_core(acts, xs, w_h, w_c, h0, mask):
    """Time-major GRU recurrence with a hand-written VJP (same deferment
    as _lstm_core: the backward scan runs only the two transposed chain
    GEMMs per step; dW_h / dW_c become two post-scan einsums over the
    saved sequences instead of per-step accumulator carries).

    xs: [T,B,3H] input projections (+bias) in (u, r, c) slot order.
    Returns (hs [T,B,H], h_last)."""
    hs, _p, _q, h_last = _gru_fwd_scan(acts, xs, w_h, w_c, h0, mask)
    return hs, h_last


def _gru_fwd_scan(acts, xs, w_h, w_c, h0, mask):
    h = h0.shape[-1]

    def step(h_p, inp):
        x_t, m = inp
        ur = acc_matmul(h_p, w_h)
        p_ur = x_t[:, : 2 * h] + ur
        rh = _gru_reset(acts, p_ur[:, h:], h_p)
        p_c = x_t[:, 2 * h :] + acc_matmul(rh, w_c)
        h_t = _gru_final(acts, p_ur[:, :h], p_c, h_p, m)
        return h_t, (h_t, p_ur, p_c)

    h_last, (hs, p_ur_seq, p_c_seq) = lax.scan(
        step, h0, (xs, mask), unroll=_UNROLL_FUSED
    )
    return hs, p_ur_seq, p_c_seq, h_last


def _gru_core_fwd(acts, xs, w_h, w_c, h0, mask):
    hs, p_ur_seq, p_c_seq, h_last = _gru_fwd_scan(acts, xs, w_h, w_c, h0, mask)
    return (hs, h_last), (p_ur_seq, p_c_seq, hs, w_h, w_c, h0, mask)


def _gru_core_bwd(acts, res, cts):
    p_ur_seq, p_c_seq, hs, w_h, w_c, h0, mask = res
    dhs, dh_last = cts
    h = h0.shape[-1]
    h_prev_seq = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    w_h_t = w_h.T
    w_c_t = w_c.T

    def step(dh, inp):
        p_ur, p_c, h_p, m, dh_out = inp
        dh = dh + dh_out
        _, vjp_final = jax.vjp(
            lambda pu, pc, hp: _gru_final(acts, pu, pc, hp, m),
            p_ur[:, :h], p_c, h_p,
        )
        dp_u, dp_c, dh_p = vjp_final(dh)
        drh = acc_matmul(dp_c, w_c_t)
        rh, vjp_reset = jax.vjp(
            lambda pr, hp: _gru_reset(acts, pr, hp), p_ur[:, h:], h_p
        )
        dp_r, dh_p_r = vjp_reset(drh)
        dp_ur = jnp.concatenate([dp_u, dp_r], axis=-1)
        dh_p = dh_p + dh_p_r + acc_matmul(dp_ur, w_h_t)
        return dh_p, (dp_ur, dp_c, rh)

    dh0, (dp_ur_seq, dp_c_seq, rh_seq) = lax.scan(
        step,
        dh_last,
        (p_ur_seq, p_c_seq, h_prev_seq, mask, dhs),
        reverse=True,
        unroll=_UNROLL_FUSED,
    )
    dxs = jnp.concatenate([dp_ur_seq, dp_c_seq], axis=-1)
    acc = jnp.promote_types(w_h.dtype, jnp.float32)
    dw_h = jnp.einsum(
        "tbh,tbg->hg", h_prev_seq, dp_ur_seq,
        preferred_element_type=acc,
    ).astype(w_h.dtype)
    dw_c = jnp.einsum(
        "tbh,tbg->hg", rh_seq, dp_c_seq,
        preferred_element_type=acc,
    ).astype(w_c.dtype)
    d_mask = np.zeros(mask.shape, dtype=jax.dtypes.float0)
    return (dxs, dw_h, dw_c, dh0, d_mask)


_gru_core.defvjp(_gru_core_fwd, _gru_core_bwd)


# ---------------------------------------------------------------------------
# Fused attention-GRU decoder step — the NMT decoder recurrence
# ---------------------------------------------------------------------------
#
# The v1 attention decoder (networks.py simple_attention + gru_step inside a
# recurrent_group) lowers, layer by layer, to a per-step chain of SIX
# dependent GEMMs — expand+fc state projection (computed on [B*S] rows, S×
# redundant), score fc, context reduce, input fc, GRU gate GEMM, GRU
# candidate GEMM — which is exactly the per-timestep launch/latency overhead
# the reference's fused decoder kernels exist to kill (reference:
# paddle/cuda/src/hl_cuda_lstm.cu, 872 LoC of hand-fused per-step math).
#
# The fused core below collapses the step to the MINIMAL dependent chain:
#
#   a1    = h₋ @ [W_sp | U_ur]            one [B,H]x[H,P+2H] GEMM (state
#                                         projection + GRU update/reset
#                                         gates share the h₋ operand)
#   α     = softmax_S(act(ep + sp) · v)   score matvec (+ static enc mask)
#   ctx   = α · enc                       context reduce
#   p     = xg_t + ctx @ W_ctx            one [B,E]x[E,3H] GEMM (the
#                                         target-embedding half of the v1
#                                         "input fc" is precomputed for the
#                                         WHOLE sequence outside the scan)
#   c̃    = act(p_c + (r∘h₋) @ W_c)       the one unavoidable second link
#   h     = (1-u)∘h₋ + u∘c̃
#
# i.e. 2 chained [B,H]-class GEMMs + the attention matvec/reduce per step,
# with the same custom-VJP discipline as the cells above: the backward scan
# runs only transposed chain GEMMs; every weight gradient (dW1, dW_ctx,
# dW_c, dv) and the static-input gradients (d_enc, d_ep) are post-scan
# einsums over the saved sequences.


def _att_scores(att_act: str, ep, sp, v):
    """[B, S] unnormalized attention scores: act(ep + sp[:,None,:]) · v."""
    return jnp.einsum(
        "bsp,p->bs", get_activation(att_act)(ep + sp[:, None, :]), v
    )


def _att_softmax(score, emask):
    """Masked softmax over S, replicating the sequence_softmax activation
    (ops/activations.py): -1e9 fill, softmax, then zero the padding."""
    if emask is not None:
        score = jnp.where(emask, score, -1e9)
    alpha = jax.nn.softmax(score, axis=-1)
    if emask is not None:
        alpha = alpha * emask.astype(alpha.dtype)
    return alpha


def _attgru_step(acts, xg_t, h_p, enc, ep, emask, w1, v, w_ctx, w_c, m):
    """One fused decoder step.  Returns (h_t, saved) where saved carries the
    residuals the hand-written backward needs."""
    p_dim = ep.shape[-1]
    h = h_p.shape[-1]
    a1 = acc_matmul(h_p, w1)  # [B, P+2H]: state projection + GRU u/r gates fused
    sp, ur = a1[:, :p_dim], a1[:, p_dim:]
    alpha = _att_softmax(_att_scores(acts[2], ep, sp, v), emask)
    ctxv = acc_einsum("bs,bse->be", alpha, enc)
    p = xg_t + acc_matmul(ctxv, w_ctx)  # [B, 3H] in (u, r, c) slot order
    pu = p[:, :h] + ur[:, :h]
    pr = p[:, h : 2 * h] + ur[:, h:]
    rh = _gru_reset(acts, pr, h_p)
    cpre = p[:, 2 * h :] + acc_matmul(rh, w_c)
    h_t = _gru_final(acts, pu, cpre, h_p, m)
    return h_t, (sp, alpha, ctxv, pu, pr, cpre)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attgru_core(opts, xg, enc, ep, emask, w1, v, w_ctx, w_c, h0, mask):
    """Time-major fused attention-GRU recurrence with a hand-written VJP.

    opts: (gate_act, act, att_act, early_exit).
    xg: [T,B,3H] precomputed target-side gate projections (+ biases);
    enc: [B,S,E] context values; ep: [B,S,P] score keys (+ biases);
    emask: [B,S] bool encoder validity or None; w1: [H,P+2H] fused
    state weight [W_state_proj | U_ur]; v: [P] score vector; w_ctx:
    [E,3H]; w_c: [H,H]; mask: [T,B,1] bool decoder-step validity.
    Returns (hs [T,B,H], h_last)."""
    hs, *_rest, h_last = _attgru_fwd_scan(
        opts, xg, enc, ep, emask, w1, v, w_ctx, w_c, h0, mask
    )
    return hs, h_last


def _cond_step(active, live_fn, carry, ys_struct):
    """Shared early-exit step wrapper for the fused scans: run the live
    body when any batch row is live at this step, else pass the carry
    through emitting zeros in the live branch's exact output structure."""

    def dead(c):
        return c, jax.tree_util.tree_map(
            lambda st: jnp.zeros(st.shape, st.dtype), ys_struct
        )

    return lax.cond(active, live_fn, dead, carry)


# "attgru_core" (forward here, backward below) sets the recurrence itself
# apart, in a device trace, from the projections the decoder's
# recurrent_group scope also holds.  No colon: a "type:name" scope would
# become the operations' innermost LAYER scope and take them out of the
# layer's own reading.
@jax.named_scope("attgru_core")
def _attgru_fwd_scan(opts, xg, enc, ep, emask, w1, v, w_ctx, w_c, h0, mask):
    acts, early = opts[:3], opts[3]

    def live(h_p, x_t, m):
        h_t, saved = _attgru_step(
            acts, x_t, h_p, enc, ep, emask, w1, v, w_ctx, w_c, m
        )
        return h_t, (h_t,) + saved

    if early:
        # bucketed feeds pad T up to a ladder rung: steps past every row's
        # true length are dead for the WHOLE batch — skip their FLOPs, keep
        # the compiled shape (same contract as the generic group scan)
        active_seq = jnp.any(mask[:, :, 0], axis=1)  # [T]
        ys_struct = jax.eval_shape(
            lambda h, x, m: live(h, x, m)[1],
            h0, jax.tree_util.tree_map(lambda u: u[0], xg), mask[0],
        )

        def step(h_p, inp):
            x_t, m, a = inp
            h_t, ys = _cond_step(
                a, lambda h: live(h, x_t, m), h_p, ys_struct
            )
            # dead steps must still emit the CARRY as the step output so
            # hs stays the masked carry-through sequence
            ys = (jnp.where(a, ys[0], h_p),) + ys[1:]
            return h_t, ys

        h_last, seqs = lax.scan(
            step, h0, (xg, mask, active_seq), unroll=_UNROLL_FUSED
        )
    else:
        h_last, seqs = lax.scan(
            lambda h_p, inp: live(h_p, *inp), h0, (xg, mask),
            unroll=_UNROLL_FUSED,
        )
    hs, sp_seq, alpha_seq, ctx_seq, pu_seq, pr_seq, cpre_seq = seqs
    return hs, sp_seq, alpha_seq, ctx_seq, pu_seq, pr_seq, cpre_seq, h_last


def _attgru_core_fwd(opts, xg, enc, ep, emask, w1, v, w_ctx, w_c, h0, mask):
    hs, sp_seq, alpha_seq, ctx_seq, pu_seq, pr_seq, cpre_seq, h_last = (
        _attgru_fwd_scan(opts, xg, enc, ep, emask, w1, v, w_ctx, w_c, h0, mask)
    )
    res = (
        sp_seq, alpha_seq, ctx_seq, pu_seq, pr_seq, cpre_seq, hs,
        enc, ep, emask, w1, v, w_ctx, w_c, h0, mask,
    )
    return (hs, h_last), res


@jax.named_scope("attgru_core")
def _attgru_core_bwd(opts, res, cts):
    acts, early = opts[:3], opts[3]
    (sp_seq, alpha_seq, ctx_seq, pu_seq, pr_seq, cpre_seq, hs,
     enc, ep, emask, w1, v, w_ctx, w_c, h0, mask) = res
    dhs, dh_last = cts
    h = h0.shape[-1]
    h_prev_seq = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    w1_t, w_ctx_t, w_c_t = w1.T, w_ctx.T, w_c.T
    f_att = get_activation(acts[2])

    def live(dh, sp, alpha, pu, pr, cpre, h_p, m):
        # GRU tail (same structure as _gru_core_bwd, via the elementwise
        # closures so activation choices stay exact)
        _, vjp_final = jax.vjp(
            lambda a, c, hp: _gru_final(acts, a, c, hp, m), pu, cpre, h_p
        )
        dpu, dcpre, dh_p = vjp_final(dh)
        drh = acc_matmul(dcpre, w_c_t)  # chain GEMM 1
        rh, vjp_reset = jax.vjp(
            lambda p_r, hp: _gru_reset(acts, p_r, hp), pr, h_p
        )
        dpr, dh_p_r = vjp_reset(drh)
        dxg = jnp.concatenate([dpu, dpr, dcpre], axis=-1)  # == dp
        dctx = acc_matmul(dxg, w_ctx_t)  # chain GEMM 2
        dalpha = acc_einsum("be,bse->bs", dctx, enc)
        # masked-softmax VJP: padding has alpha == 0, so it drops out
        dpre = alpha * (
            dalpha - jnp.sum(alpha * dalpha, axis=-1, keepdims=True)
        )
        # score backward: dsp[b,p] = v[p] * Σ_s dpre·act'(ep+sp); act' via
        # jvp so any registered activation works (elementwise, fuses)
        x_s = ep + sp[:, None, :]
        _, fp = jax.jvp(f_att, (x_s,), (jnp.ones_like(x_s),))
        dsp = acc_einsum("bs,bsp->bp", dpre, fp) * v
        da1 = jnp.concatenate([dsp, dpu, dpr], axis=-1)
        dh_p = dh_p + dh_p_r + acc_matmul(da1, w1_t)  # chain GEMM 3 (the h₋ link)
        return dh_p, (da1, dxg, dctx, dpre, rh)

    if early:
        active_seq = jnp.any(mask[:, :, 0], axis=1)
        ys_struct = jax.eval_shape(
            lambda *a: live(*a)[1],
            dhs[0], sp_seq[0], alpha_seq[0], pu_seq[0], pr_seq[0],
            cpre_seq[0], h_prev_seq[0], mask[0],
        )

        def step(dh, inp):
            sp, alpha, pu, pr, cpre, h_p, m, dh_out, a = inp
            dh = dh + dh_out
            return _cond_step(
                a, lambda d: live(d, sp, alpha, pu, pr, cpre, h_p, m),
                dh, ys_struct,
            )

        xs_bwd = (
            sp_seq, alpha_seq, pu_seq, pr_seq, cpre_seq, h_prev_seq, mask,
            dhs, active_seq,
        )
    else:
        def step(dh, inp):
            sp, alpha, pu, pr, cpre, h_p, m, dh_out = inp
            return live(dh + dh_out, sp, alpha, pu, pr, cpre, h_p, m)

        xs_bwd = (
            sp_seq, alpha_seq, pu_seq, pr_seq, cpre_seq, h_prev_seq, mask,
            dhs,
        )

    dh0, (da1_seq, dxg_seq, dctx_seq, dpre_seq, rh_seq) = lax.scan(
        step, dh_last, xs_bwd, reverse=True, unroll=_UNROLL_FUSED
    )

    # every weight gradient is ONE post-scan einsum at >= f32 accumulation
    acc = jnp.promote_types(w1.dtype, jnp.float32)
    dw1 = jnp.einsum(
        "tbh,tbg->hg", h_prev_seq, da1_seq, preferred_element_type=acc
    ).astype(w1.dtype)
    dw_ctx = jnp.einsum(
        "tbe,tbg->eg", ctx_seq, dxg_seq, preferred_element_type=acc
    ).astype(w_ctx.dtype)
    dw_c = jnp.einsum(
        "tbh,tbg->hg", rh_seq, dxg_seq[..., 2 * h :],
        preferred_element_type=acc,
    ).astype(w_c.dtype)
    d_enc = jnp.einsum(
        "tbs,tbe->bse", alpha_seq, dctx_seq, preferred_element_type=acc
    ).astype(enc.dtype)
    # static score-key gradients: the [T,B,S,P] act/act' tensors are traced
    # broadcasts that XLA fuses straight into the t-reduction
    x_big = ep[None] + sp_seq[:, :, None, :]
    th_big = f_att(x_big)
    _, fp_big = jax.jvp(f_att, (x_big,), (jnp.ones_like(x_big),))
    dv = jnp.einsum(
        "tbs,tbsp->p", dpre_seq, th_big, preferred_element_type=acc
    ).astype(v.dtype)
    d_ep = (
        jnp.einsum(
            "tbs,tbsp->bsp", dpre_seq, fp_big, preferred_element_type=acc
        )
        * v.astype(acc)
    ).astype(ep.dtype)
    d_emask = (
        None if emask is None else np.zeros(emask.shape, jax.dtypes.float0)
    )
    d_mask = np.zeros(mask.shape, jax.dtypes.float0)
    return (
        dxg_seq, d_enc, d_ep, d_emask, dw1, dv, dw_ctx, dw_c, dh0, d_mask
    )


_attgru_core.defvjp(_attgru_core_fwd, _attgru_core_bwd)


def attention_gru_step(
    xg_t, h_p, enc, enc_proj, enc_mask, w1, v, w_ctx, w_c,
    *, gate_act: str = "sigmoid", act: str = "tanh", att_act: str = "tanh",
):
    """One fused decoder step for GENERATION (beam/greedy stepping): same
    math as the scan core's step, no mask (every generated step is live).
    xg_t: [B, 3H] this step's target-side gate projections (+ biases)."""
    m = jnp.ones((h_p.shape[0], 1), bool)
    h_t, _ = _attgru_step(
        (gate_act, act, att_act), xg_t, h_p, enc, enc_proj, enc_mask,
        w1, v, w_ctx, w_c, m,
    )
    return h_t


def attention_gru_scan(
    gates: jnp.ndarray,  # [B, T, 3H] target-side input projections (+bias)
    enc: jnp.ndarray,  # [B, S, E] encoded sequence (context values)
    enc_proj: jnp.ndarray,  # [B, S, P] projected keys (+ any biases folded)
    w1: jnp.ndarray,  # [H, P+2H] fused [W_state_proj | U_ur]
    v: jnp.ndarray,  # [P] attention score vector
    w_ctx: jnp.ndarray,  # [E, 3H] context -> gates projection
    w_c: jnp.ndarray,  # [H, H] GRU candidate recurrent weight
    enc_lengths: Optional[jnp.ndarray] = None,
    lengths: Optional[jnp.ndarray] = None,
    *,
    gate_act: str = "sigmoid",
    act: str = "tanh",
    att_act: str = "tanh",
    reverse: bool = False,
    h0: Optional[jnp.ndarray] = None,
    early_exit: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused Bahdanau-attention GRU decoder over a padded batch.

    Semantically identical to the unfused v1 lowering (simple_attention +
    gru_step in a recurrent_group) — pinned by tests/test_attention_gru_fused
    against naive autodiff in f64.  Returns ([B, T, H], h_last)."""
    b, t, _g3 = gates.shape
    h = w_c.shape[0]
    xs = _time_major(gates)
    if reverse:
        xs = jnp.flip(xs, axis=0)
    mask = _mask_seq(lengths, t, reverse)
    if mask is None:
        mask = jnp.ones((t, b, 1), bool)
    emask = None
    if enc_lengths is not None:
        s = enc.shape[1]
        emask = jnp.arange(s, dtype=jnp.int32)[None, :] < enc_lengths[:, None]
    h_prev = h0 if h0 is not None else jnp.zeros((b, h), gates.dtype)
    hs, h_last = _attgru_core(
        (gate_act, act, att_act, bool(early_exit)),
        xs, enc, enc_proj, emask, w1, v, w_ctx, w_c, h_prev, mask,
    )
    if reverse:
        hs = jnp.flip(hs, axis=0)
    return jnp.swapaxes(hs, 0, 1), h_last


def simple_rnn_scan(
    x: jnp.ndarray,  # [B, T, H] input projections
    w_h: jnp.ndarray,  # [H, H]
    bias: Optional[jnp.ndarray],
    lengths: Optional[jnp.ndarray] = None,
    *,
    act: str = "tanh",
    reverse: bool = False,
    h0: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Plain recurrence h_t = act(x_t + h₋ W) (RecurrentLayer.cpp)."""
    b, t, h = x.shape
    xs = _time_major(x)
    if bias is not None:
        xs = xs + bias
    if reverse:
        xs = jnp.flip(xs, axis=0)
    mask = _mask_seq(lengths, t, reverse)
    if mask is None:
        mask = jnp.ones((t, b, 1), bool)
    h_prev = h0 if h0 is not None else jnp.zeros((b, h), x.dtype)
    hs, h_last = _rnn_core((act,), xs, w_h, h_prev, mask)
    if reverse:
        hs = jnp.flip(hs, axis=0)
    return jnp.swapaxes(hs, 0, 1), h_last


def _rnn_act(acts, a, h_p, m):
    return jnp.where(m, get_activation(acts[0])(a), h_p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rnn_core(acts, xs, w_h, h0, mask):
    """Plain recurrence with the same deferred-weight-grad VJP as
    _lstm_core / _gru_core."""
    hs, _a, h_last = _rnn_fwd_scan(acts, xs, w_h, h0, mask)
    return hs, h_last


def _rnn_fwd_scan(acts, xs, w_h, h0, mask):
    def step(h_p, inp):
        x_t, m = inp
        a = x_t + acc_matmul(h_p, w_h)
        h_t = _rnn_act(acts, a, h_p, m)
        return h_t, (h_t, a)

    h_last, (hs, a_seq) = lax.scan(step, h0, (xs, mask), unroll=_UNROLL_FUSED)
    return hs, a_seq, h_last


def _rnn_core_fwd(acts, xs, w_h, h0, mask):
    hs, a_seq, h_last = _rnn_fwd_scan(acts, xs, w_h, h0, mask)
    return (hs, h_last), (a_seq, hs, w_h, h0, mask)


def _rnn_core_bwd(acts, res, cts):
    a_seq, hs, w_h, h0, mask = res
    dhs, dh_last = cts
    h_prev_seq = jnp.concatenate([h0[None], hs[:-1]], axis=0)
    w_h_t = w_h.T

    def step(dh, inp):
        a_t, h_p, m, dh_out = inp
        dh = dh + dh_out
        _, vjp_fn = jax.vjp(lambda a, hp: _rnn_act(acts, a, hp, m), a_t, h_p)
        da, dh_p_elem = vjp_fn(dh)
        return acc_matmul(da, w_h_t) + dh_p_elem, da

    dh0, da_seq = lax.scan(
        step,
        dh_last,
        (a_seq, h_prev_seq, mask, dhs),
        reverse=True,
        unroll=_UNROLL_FUSED,
    )
    dw_h = jnp.einsum(
        "tbh,tbg->hg", h_prev_seq, da_seq,
        preferred_element_type=jnp.promote_types(w_h.dtype, jnp.float32),
    ).astype(w_h.dtype)
    d_mask = np.zeros(mask.shape, dtype=jax.dtypes.float0)
    return (da_seq, dw_h, dh0, d_mask)


_rnn_core.defvjp(_rnn_core_fwd, _rnn_core_bwd)
