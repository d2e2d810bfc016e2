"""Ring attention — exact sequence/context parallelism for long sequences.

The reference scales long sequences by CPU-side sequence re-batching inside
RecurrentGradientMachine; it has no attention-era context parallelism.  The
TPU-native design shards the SEQUENCE axis across a mesh axis and computes
exact attention by rotating key/value blocks around the ring with
``jax.lax.ppermute`` while accumulating an online (streaming) softmax —
attention memory per chip drops from O(T²) to O(T·T/n) and activations to
O(T/n), with the k/v transfer overlapping compute on ICI
(Liu et al., Ring Attention; the public long-context recipe).

``ring_attention`` is the shard_map-level primitive (q/k/v already sharded
[B, T/n, H, dh] per device); ``sequence_parallel_attention`` wraps it in
shard_map over a mesh for global [B, T, H, dh] arrays.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

def ring_attention(
    q: jnp.ndarray,  # [B, Tq_loc, H, dh]  (this device's query block)
    k: jnp.ndarray,  # [B, Tk_loc, H, dh]  (this device's key block)
    v: jnp.ndarray,  # [B, Tk_loc, H, dh]
    axis_name: str,
    lengths: Optional[jnp.ndarray] = None,  # [B] GLOBAL valid key count
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Exact attention over the ring; call inside shard_map with the
    sequence axis sharded over `axis_name`.  Returns [B, Tq_loc, H, dh].

    Each of the n ring steps computes this device's queries against ONE
    rotated k/v block and folds it into a streaming softmax (running max m,
    normalizer l, accumulator o) — numerically identical to softmax over
    the full row, never materializing the [T, T] matrix."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, t_loc, h, dh = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    q_pos = my * t_loc + jnp.arange(t_loc)  # global positions of my queries

    o = jnp.zeros((b, h, t_loc, dh), jnp.float32)
    m = jnp.full((b, h, t_loc), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t_loc), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    for step in range(n):  # n is static under shard_map tracing
        src = (my - step) % n  # whose block we hold this step
        k_pos = src * k.shape[1] + jnp.arange(k.shape[1])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if lengths is not None:
            s = jnp.where(
                (k_pos[None, :] < lengths[:, None])[:, None, None, :], s, -jnp.inf
            )
        if causal:
            s = jnp.where(
                (k_pos[None, :] <= q_pos[:, None])[None, None], s, -jnp.inf
            )
        blk_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # fully-masked rows keep m=-inf; shift by 0 there to avoid nan
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - shift[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)  # masked keys contribute 0
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - shift), 0.0)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v.astype(jnp.float32)
        )
        l = l * corr + jnp.sum(p, axis=-1)
        m = m_new
        if step != n - 1:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)

    out = o / jnp.maximum(l, 1e-20)[..., None]  # [B, H, Tq, dh]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # [B, Tq, H, dh]


def sequence_parallel_attention(
    q: jnp.ndarray,  # [B, T, H, dh] global
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str,
    lengths: Optional[jnp.ndarray] = None,
    causal: bool = False,
) -> jnp.ndarray:
    """shard_map wrapper: shards T over `axis_name`, runs the ring, returns
    the global [B, T, H, dh] result (sharded the same way under jit)."""
    t = q.shape[1]
    n = mesh.shape[axis_name]
    assert t % n == 0, f"sequence length {t} not divisible by ring size {n}"
    spec = P(None, axis_name, None, None)
    in_specs = (spec, spec, spec) + ((P(None),) if lengths is not None else ())
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal)

    if lengths is not None:
        def mapped(q_, k_, v_, len_):
            return fn(q_, k_, v_, lengths=len_)
    else:
        def mapped(q_, k_, v_):
            return fn(q_, k_, v_)


    shmapped = jax.shard_map(mapped, mesh=mesh, in_specs=in_specs, out_specs=spec)
    args = (q, k, v) + ((lengths,) if lengths is not None else ())
    return shmapped(*args)
