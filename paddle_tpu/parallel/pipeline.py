"""Pipeline parallelism over a mesh axis — GPipe-style microbatch
pipelining expressed as a ``shard_map`` collective-permute loop.

The 2017 reference's closest notion is ParallelNeuralNetwork's per-layer
`device` placement (reference: paddle/gserver/gradientmachines/
ParallelNeuralNetwork.h:34) — whole layers pinned to devices with
activations copied between them.  The TPU-native form: S equal-shape
stages live one per device slice along a mesh axis; M microbatches stream
through; each tick every stage computes its current microbatch and
``ppermute``s the activation to the next stage over ICI.  The classic
GPipe bubble is (S-1)/(M+S-1); everything is static-shape and jittable,
and ``jax.grad`` differentiates straight through the permutes (the
backward pipeline falls out of the transpose of ppermute).

Stages must be shape-preserving ([mb, D] -> [mb, D]) — the equal-width
transformer-block regime pipelining exists for.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.parallel.mesh import MODEL_AXIS


def stack_stage_params(per_stage_params) -> Any:
    """[S] list of identically-shaped stage param pytrees -> one pytree with
    a leading S axis (what pipeline_apply shards over the pipe axis)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params
    )


def split_microbatches(x: jnp.ndarray, num_microbatches: int) -> jnp.ndarray:
    """[B, ...] -> [M, B/M, ...]."""
    b = x.shape[0]
    assert b % num_microbatches == 0, (
        f"batch {b} not divisible by {num_microbatches} microbatches"
    )
    return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    microbatches: jnp.ndarray,
    mesh: Mesh,
    axis: str = MODEL_AXIS,
) -> jnp.ndarray:
    """Run every microbatch through all S pipeline stages.

    stage_params: pytree whose leaves have leading axis S == mesh.shape[axis]
    (see stack_stage_params); microbatches: [M, mb, D] (split_microbatches).
    Returns [M, mb, D] outputs, replicated across the pipe axis.
    """
    s_total = mesh.shape[axis]
    m_total = microbatches.shape[0]
    perm = [(i, (i + 1) % s_total) for i in range(s_total)]

    def spmd(params_slice, mbs):
        my_params = jax.tree_util.tree_map(lambda v: v[0], params_slice)
        s = jax.lax.axis_index(axis)
        mb_shape = mbs.shape[1:]
        x_cur = jnp.zeros(mb_shape, mbs.dtype)
        outputs = jnp.zeros_like(mbs)

        def tick(t, carry):
            x_cur, outputs = carry
            first_in = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, m_total - 1), axis=0, keepdims=False
            )
            xin = jnp.where(s == 0, first_in, x_cur)
            y = stage_fn(my_params, xin)
            out_idx = jnp.clip(t - (s_total - 1), 0, m_total - 1)
            write = jnp.logical_and(s == s_total - 1, t >= s_total - 1)
            outputs = jnp.where(
                write,
                jax.lax.dynamic_update_index_in_dim(outputs, y, out_idx, 0),
                outputs,
            )
            x_next = jax.lax.ppermute(y, axis, perm)
            return x_next, outputs

        _, outputs = jax.lax.fori_loop(
            0, m_total + s_total - 1, tick, (x_cur, outputs)
        )
        # only the last stage holds real outputs: zero the rest and psum to
        # replicate the result across the pipe axis
        outputs = jnp.where(s == s_total - 1, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        P(),
    )

    fn = jax.shard_map(
        spmd, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )
    return fn(stage_params, microbatches)
