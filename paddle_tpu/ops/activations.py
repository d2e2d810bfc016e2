"""Activation registry — full parity with the reference activation set
(reference: paddle/gserver/activations/ActivationFunction.cpp:69-443).

Each activation is a pure elementwise jnp function; XLA fuses it into the
producing matmul so there is no separate kernel launch (unlike the
reference's separate forward/backward activation kernels).  ``softmax`` and
``sequence_softmax`` are the two non-elementwise members, handled with
explicit axis/mask semantics.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

Activation = Callable[..., jnp.ndarray]

_ACTIVATIONS: Dict[str, Activation] = {}


def register_activation(*names: str):
    def deco(fn: Activation) -> Activation:
        for n in names:
            _ACTIVATIONS[n] = fn
        return fn

    return deco


def get_activation(name: str) -> Activation:
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise KeyError(
            f"unknown activation {name!r}; known: {sorted(_ACTIVATIONS)}"
        ) from None


def registered_activations():
    """Sorted registered activation names (the graph linter's G013 domain)."""
    return sorted(_ACTIVATIONS)


def apply_activation(name: str, x: jnp.ndarray, mask: Optional[jnp.ndarray] = None):
    if name in ("sequence_softmax",):
        return _ACTIVATIONS[name](x, mask)
    return _ACTIVATIONS[name](x)


@register_activation("identity", "linear", "")
def _identity(x):
    return x


@register_activation("sigmoid")
def _sigmoid(x):
    return jax.nn.sigmoid(x)


@register_activation("softmax")
def _softmax(x):
    return jax.nn.softmax(x, axis=-1)  # num: allow[N401] softmax fwd sums in f32 inside jax.nn; the bwd [S]-sum rides the compute dtype (S bounded by the shape ladder)


@register_activation("sequence_softmax")
def _sequence_softmax(x, mask=None):
    """Softmax over the time axis of a [B, T, 1] / [B, T] sequence score,
    masking padding (reference ActivationFunction.cpp SequenceSoftmax)."""
    squeeze = x.ndim == 3 and x.shape[-1] == 1
    logits = x[..., 0] if squeeze else x
    if mask is not None:
        logits = jnp.where(mask > 0, logits, -1e9)
    out = jax.nn.softmax(logits, axis=-1)
    if mask is not None:
        out = out * mask
    return out[..., None] if squeeze else out


@register_activation("relu")
def _relu(x):
    return jax.nn.relu(x)


@register_activation("relu2")
def _relu2(x):
    # squared ReLU (So et al. 2021, "Primer"): the gateless MLPs of the
    # nemotron_h family
    return jnp.square(jax.nn.relu(x))


@register_activation("silu")
def _silu(x):
    # x * sigmoid(x): the gate of the gated MLPs (gate fc, up fc, dotmul, down fc)
    return jax.nn.silu(x)


@register_activation("brelu")
def _brelu(x):
    # Reference clips to [0, 24] (BReluActivation, ActivationFunction.cpp).
    return jnp.clip(x, 0.0, 24.0)


@register_activation("tanh")
def _tanh(x):
    return jnp.tanh(x)


@register_activation("stanh")
def _stanh(x):
    # Scaled tanh: 1.7159 * tanh(2/3 x) (STanhActivation).
    return 1.7159 * jnp.tanh((2.0 / 3.0) * x)


@register_activation("softrelu")
def _softrelu(x):
    # log(1 + exp(x)), input clipped to [-40, 40] like the reference.
    return jax.nn.softplus(jnp.clip(x, -40.0, 40.0))


@register_activation("abs")
def _abs(x):
    return jnp.abs(x)


@register_activation("square")
def _square(x):
    return jnp.square(x)


@register_activation("exponential", "exp")
def _exp(x):
    return jnp.exp(x)


@register_activation("reciprocal")
def _reciprocal(x):
    return 1.0 / x


@register_activation("sqrt")
def _sqrt(x):
    return jnp.sqrt(x)


@register_activation("log")
def _log(x):
    return jnp.log(x)
