"""Activation objects — the ``paddle.v2.activation`` surface (reference:
python/paddle/trainer_config_helpers/activations.py).  Layer functions accept
either these objects or plain strings."""

from __future__ import annotations


class BaseActivation:
    name = "identity"

    def __repr__(self) -> str:  # pragma: no cover
        return f"Activation({self.name})"


def _make(name_: str):
    cls = type(name_.title().replace("_", ""), (BaseActivation,), {"name": name_})
    return cls


Identity = _make("identity")
Linear = Identity
Sigmoid = _make("sigmoid")
Softmax = _make("softmax")
SequenceSoftmax = _make("sequence_softmax")
Relu = _make("relu")
Relu2 = _make("relu2")
Silu = _make("silu")
BRelu = _make("brelu")
Tanh = _make("tanh")
STanh = _make("stanh")
SoftRelu = _make("softrelu")
Abs = _make("abs")
Square = _make("square")
Exp = _make("exponential")
Reciprocal = _make("reciprocal")
Sqrt = _make("sqrt")
Log = _make("log")


def act_name(act) -> str:
    """Normalize an activation argument (object, string, or None) and
    validate it against the registry so typos fail at model-build time."""
    from paddle_tpu.ops.activations import get_activation

    if act is None:
        return "identity"
    if isinstance(act, str):
        name = act
    elif isinstance(act, BaseActivation) or hasattr(act, "name"):
        name = act.name
    elif isinstance(act, type) and issubclass(act, BaseActivation):
        name = act.name
    else:
        raise TypeError(f"bad activation: {act!r}")
    get_activation(name)  # raises KeyError with the known-names list
    return name
