"""Chunked selective scan of a Mamba-2 layer (the state-space duality form of
Dao and Gu 2024, arXiv:2405.21060, section 6): the recurrence

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t b_t^T        h: [P, N] a head
    y_t = h_t c_t

computed a chunk of `chunk` tokens at a time.  Inside a chunk the outputs are
one masked quadratic form (c_q . b_s weighted by the decay from s to q), which
is MXU work; between chunks only the [P, N] state of each head is carried, a
scan of T/chunk steps instead of T.  A scan over 2,048 dependent steps is what
this chip does badly (ops/rnn.py's cores measure it).

Heads come in groups that share b and c (`x` [B, T, G, R, P]: G groups of R
heads).  Decay sums and the state are float32; the products run in x's dtype
with float32 accumulation.

The backward pass is hand-structured (custom VJP): it keeps the inputs and the
states entering each chunk ([B, T/chunk, G, R, P, N] float32), not every step's
state (P x N float32 a head and token otherwise), recomputes a chunk's
quadratic form, and carries the state's cotangent back over the chunks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.ops import acc_einsum


def _chunked(t, chunk, *arrays):
    """[B, T, ...] -> [B, T/chunk, chunk, ...], T padded with zeros: a padded
    step has dt = 0, so it neither decays nor feeds the state."""
    pad = -t % chunk
    out = []
    for a in arrays:
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        out.append(a.reshape(a.shape[0], (t + pad) // chunk, chunk, *a.shape[2:]))
    return out


def _cum_decay(dt, a):
    """Inclusive sum over a chunk's steps of the log-decay dt_t a (<= 0),
    float32 [B, C, Q, G, R]."""
    return jnp.cumsum(dt * a, axis=2)


def _local_states(xdt, b, cum):
    """What each chunk alone adds to the state by its end, and the decay of
    an entering state over the whole chunk."""
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, C, Q, G, R]
    fed = xdt * to_end[..., None].astype(xdt.dtype)
    s_local = jnp.einsum("bcqgn,bcqgrp->bcgrpn", b, fed,
                         preferred_element_type=jnp.float32)
    return s_local, jnp.exp(cum[:, :, -1])  # [B,C,G,R,P,N], [B,C,G,R]


def _outputs(xdt, b, c, cum, s_prev):
    """y of every chunk given the state entering it."""
    q = cum.shape[2]
    scores = acc_einsum("bcqgn,bcsgn->bcgqs", c, b)  # [B, C, G, Q, S]
    # decay from step s to step q, a head: exp(cum_q - cum_s) where s <= q.
    # masked BEFORE the exponential: above the diagonal the difference is
    # positive and may overflow
    ch = jnp.moveaxis(cum, 2, -1)  # [B, C, G, R, Q]
    diff = ch[..., :, None] - ch[..., None, :]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))  # [B, C, G, R, Q, S]
    m = (scores[:, :, :, None].astype(jnp.float32) * decay).astype(xdt.dtype)
    y = acc_einsum("bcgrqs,bcsgrp->bcqgrp", m, xdt)
    # what the entering state still contributes at step q
    carried = acc_einsum("bcqgn,bcgrpn->bcqgrp", c, s_prev.astype(xdt.dtype))
    return y + carried * jnp.exp(cum)[..., None].astype(xdt.dtype)


def _carry_states(s_local, chunk_decay):
    """The state entering each chunk: s_prev[0] = 0, s_prev[c+1] =
    chunk_decay[c] s_prev[c] + s_local[c]; a scan over the chunks."""

    def step(s, inp):
        loc, dec = inp
        return dec[..., None, None] * s + loc, s

    _, s_prev = jax.lax.scan(
        step, jnp.zeros_like(s_local[:, 0]),
        (jnp.moveaxis(s_local, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    return jnp.moveaxis(s_prev, 0, 1)


def _chunks(x, dt, a, b, c, s_prev):
    """Every chunk as a function of its inputs and the state entering it ->
    (y, the state it leaves): what the backward pass differentiates."""
    cum = _cum_decay(dt, a)
    xdt = x * dt[..., None].astype(x.dtype)
    s_local, chunk_decay = _local_states(xdt, b, cum)
    y = _outputs(xdt, b, c, cum, s_prev)
    return y, chunk_decay[..., None, None] * s_prev + s_local


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ssd_core(chunk, x, dt, a, b, c):
    return _ssd_core_fwd(chunk, x, dt, a, b, c)[0]


@jax.named_scope("ssd_scan")
def _ssd_core_fwd(chunk, x, dt, a, b, c):
    t = x.shape[1]
    xc, dtc, bc, cc = _chunked(t, chunk, x, dt, b, c)
    cum = _cum_decay(dtc, a)
    xdt = xc * dtc[..., None].astype(xc.dtype)
    s_prev = _carry_states(*_local_states(xdt, bc, cum))
    y = _outputs(xdt, bc, cc, cum, s_prev)
    y = y.reshape(y.shape[0], -1, *y.shape[3:])[:, :t]
    return y, (x, dt, a, b, c, s_prev)


@jax.named_scope("ssd_scan")
def _ssd_core_bwd(chunk, res, dy):
    x, dt, a, b, c, s_prev = res
    t = x.shape[1]
    xc, dtc, bc, cc, dyc = _chunked(t, chunk, x, dt, b, c, dy)
    # the cotangent of the state entering chunk c: what y of chunk c takes of
    # it directly, plus what passes through to the chunks after it
    cum = _cum_decay(dtc, a)
    direct = jnp.einsum(
        "bcqgn,bcqgrp->bcgrpn", cc, dyc * jnp.exp(cum)[..., None].astype(dyc.dtype),
        preferred_element_type=jnp.float32)
    chunk_decay = jnp.exp(cum[:, :, -1])

    def step(g_next, inp):
        d, dec = inp
        return d + dec[..., None, None] * g_next, g_next

    _, g_next = jax.lax.scan(
        step, jnp.zeros_like(direct[:, 0]),
        (jnp.moveaxis(direct, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)), reverse=True)
    g_next = jnp.moveaxis(g_next, 0, 1)  # cotangent of the state chunk c leaves
    _, vjp = jax.vjp(lambda *args: _chunks(*args, s_prev), xc, dtc, a, bc, cc)
    dx, ddt, da, db, dc = vjp((dyc, g_next))

    def flat(g):
        return g.reshape(g.shape[0], -1, *g.shape[3:])[:, :t]

    return flat(dx), flat(ddt), da, flat(db), flat(dc)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd_scan(x, dt, a, b, c, chunk=128):
    """x [B, T, G, R, P] (G groups of R heads of width P); dt [B, T, G, R]
    float32, the step sizes (>= 0); a [G, R] float32, the decay rates (< 0);
    b, c [B, T, G, N].  -> y [B, T, G, R, P] in x's dtype."""
    return _ssd_core(int(chunk), x, dt.astype(jnp.float32), a.astype(jnp.float32), b, c)
