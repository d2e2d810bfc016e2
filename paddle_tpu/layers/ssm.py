"""State-space layers: the Mamba-2 mixer (Dao and Gu 2024, arXiv:2405.21060)
as the `nemotron_h` family configures it.

    [z | xBC | dt] = u W_in                     widths inner, inner + 2 G N, H
    xBC = silu(causal depthwise conv(xBC) + bias)
    x [H, P], B [G, N], C [G, N] = split(xBC)   a group of B, C serves H/G heads
    dt = softplus(dt + dt_bias);  A = -exp(A_log)        a scalar a head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
    y = group_rms_norm(y * silu(z)) * gain      groups of inner / G channels
    out = y W_out

The recurrence is `ops/ssd.ssd_scan`, chunked, under the scope `ssd_scan`:
the Pallas kernels of `ops/ssd_pallas.py` where `_scan_kernels` finds them
usable, else XLA's operations; everything around it is projections and
elementwise work under the layer's own scope.  `inner` = H x P is an
attribute of its own, not a multiple of the input width.  No clamp on dt.  Decay sums and the state are float32; the
products run in the compute type.  Causal: a padded tail never reaches a
true position, so sequence lengths pass through untouched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core import initializers as init
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.layers.attention import rms_normalize
from paddle_tpu.layers.base import register_layer
from paddle_tpu.ops import acc_matmul
from paddle_tpu.ops.ssd import ssd_scan


def _dims(conf):
    h, p = conf.attrs["n_heads"], conf.attrs["head_dim"]
    g, n = conf.attrs["n_groups"], conf.attrs["state_size"]
    assert h % g == 0, f"{conf.name}: {h} heads do not divide into {g} groups"
    return h, p, g, n


def mamba2_init(conf, in_confs, rng):
    d = in_confs[0].size
    h, p, g, n = _dims(conf)
    inner, conv = h * p, h * p + 2 * g * n
    k = conf.attrs["conv_kernel"]
    r = jax.random.split(rng, 3)
    return {
        "w_in": init.normal(r[0], (d, 2 * inner + 2 * g * n + h), init.default_std(d)),
        "conv_w": init.normal(r[1], (k, conv), init.default_std(k)),
        "conv_b": init.zeros((conv,)),
        # a half-life of tens of tokens: softplus(-4) = 0.018 a step at A = -1
        "dt_bias": jnp.full((h,), -4.0, jnp.float32),
        "a_log": init.zeros((h,)),
        "d": init.ones((h,)),
        "norm": init.ones((inner,)),
        "w_out": init.normal(r[2], (inner, conf.size), init.default_std(inner)),
    }


def _scan_kernels(chunk, h, p, g, n, dtype, mesh):
    """-> (kernels, why): whether the layer's scan takes the Pallas kernels,
    and where not, why.  They run where the backend is the TPU, their tiles
    take the shapes and the program is one device's: XLA partitions no
    Mosaic kernel.  Counted: `ssd_kernel_layers` / `ssd_xla_layers`, one a
    layer traced."""
    from paddle_tpu.utils.timers import global_stats

    if jax.default_backend() != "tpu":
        why = f"the backend is {jax.default_backend()!r}, not 'tpu'"
    elif mesh is not None and mesh.size > 1:
        why = f"the mesh {dict(mesh.shape)} holds {mesh.size} devices"
    else:
        from paddle_tpu.ops import ssd_pallas  # Pallas is imported where it is used

        why = ssd_pallas.supported(chunk, h, p, g, n, dtype)
    global_stats.incr("ssd_xla_layers" if why else "ssd_kernel_layers")
    return why is None, why


def _causal_conv(x, w, bias):
    """Depthwise over time: y_t = sum_k w[k] x_{t-(K-1)+k} + bias, zeros
    before the row's start.  x [B, T, C], w [K, C]."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, i:i + t] * w[i] for i in range(k))
    return y + bias


@register_layer("mamba2", init=mamba2_init, auto_activation=False)
def mamba2_apply(conf, params, inputs, ctx):
    u = inputs[0]
    assert u.is_seq and not u.is_nested, f"{conf.name}: input must be a plain sequence"
    h, p, g, n = _dims(conf)
    inner = h * p
    eps = conf.attr("epsilon", 1e-5)
    bsz, t = u.data.shape[:2]

    zxbcdt = acc_matmul(u.data, params["w_in"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, params["conv_w"], params["conv_b"]))
    rows, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = rows.reshape(bsz, t, g, h // g, p)
    b = b.reshape(bsz, t, g, n)
    c = c.reshape(bsz, t, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    chunk = conf.attr("chunk_size", 128)
    kernels, _ = _scan_kernels(chunk, h, p, g, n, x.dtype, ctx.mesh)
    y = ssd_scan(x, dt.reshape(bsz, t, g, h // g), a.reshape(g, h // g), b, c,
                 chunk=chunk, kernels=kernels)
    # the skip term on the [B, T, H x P] rows: on [B, T, G, R, P] arrays, P
    # = 64 minor, XLA:TPU lays x and y out with T minor and copies each into
    # and out of that layout around the scan's kernels
    y = y.reshape(bsz, t, inner) + rows * jnp.repeat(params["d"], p).astype(x.dtype)

    # gate, then RMS norm over each group's channels, float32 statistics
    y = (y * jax.nn.silu(z)).astype(jnp.float32)
    yg = rms_normalize(y.reshape(bsz, t, g, inner // g), eps)
    y = yg.reshape(bsz, t, inner) * params["norm"].astype(jnp.float32)
    out = acc_matmul(y.astype(u.data.dtype), params["w_out"])
    return SeqTensor(out, u.lengths, u.sub_lengths)
