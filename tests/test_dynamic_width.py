"""Dynamic-width consumers (fc / matrix projections over a whole-minibatch
trans) — reference TransLayer.cpp + FullyConnectedLayer.cpp.

The reference keeps the STATIC declared size for the fc weight (protostr
test_fc dims 100x100) and can therefore only run the graph when batch ==
that size.  Here the trainer resolves the true width from its first batch
(CompiledNetwork.resolve_dynamic_widths), so the reference's own test_fc
config builds warning-free AND trains at any batch size.
"""

import warnings

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.core.topology import Topology, reset_auto_names
from paddle_tpu.v1_compat import parse_config

L = paddle.layer
A = paddle.activation

# the shape of the reference's trainer_config_helpers/tests/configs/test_fc.py:
# a fully connected layer over the transpose of the whole minibatch
TRANS_FC = """\
from paddle.trainer_config_helpers import *
settings(batch_size=1000, learning_rate=1e-5)
din = data_layer(name='data', size=100)
hidden = fc_layer(input=trans_layer(input=din), size=100, bias_attr=False)
outputs(hidden)
"""


@pytest.fixture(autouse=True)
def _reset_names():
    reset_auto_names()
    yield


def test_reference_test_fc_builds_warning_free(tmp_path):
    """The r4 VERDICT regression: parsing + compiling the reference's
    test_fc.py (trans -> fc) must not emit the dynamic-width warning."""
    conf = tmp_path / "test_fc.py"
    conf.write_text(TRANS_FC)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        p = parse_config(str(conf))
        CompiledNetwork(p.topology)
    fc_conf = next(
        c for c in p.topology.layers.values() if c.type == "fc"
    )
    assert fc_conf.attr("dynamic_width_in") == (0,)


@pytest.mark.parametrize("batch", [7, 100, 160])
def test_trans_fc_trains_at_any_batch(batch):
    """trans -> fc -> sum cost trains at batch sizes below, equal to, and
    above the static width: the first batch resolves the fc weight to
    [batch, size] and cost decreases."""
    x = L.data("x", paddle.data_type.dense_vector(12))
    h = L.fc(L.trans(x), size=4, act=A.Tanh(), name="dynfc")
    cost = L.sum_cost(h)
    params = paddle.parameters.create(cost)
    # init builds the static shape (the reference's parameter dims)
    assert params.params["dynfc"]["w0"].shape == (12, 4)

    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(learning_rate=0.05),
    )
    rng = np.random.RandomState(0)
    rows = [(rng.randn(12).astype(np.float32),) for _ in range(batch * 4)]
    costs = []
    trainer.train(
        reader=paddle.batch(lambda: iter(rows), batch, drop_last=True),
        num_passes=3,
        event_handler=lambda e: costs.append(e.cost)
        if isinstance(e, paddle.event.EndIteration) else None,
        async_load_data=False,
    )
    # the weight was re-shaped to the runtime width...
    assert trainer.parameters.params["dynfc"]["w0"].shape == (batch, 4)
    # ...and gradients flow through it (sum cost is driven down)
    assert all(np.isfinite(costs))
    assert costs[-1] < costs[0] - 0.1, costs


def test_matrix_projection_resolves_too():
    """The mixed/full_matrix_projection analogue of trans -> fc."""
    x = L.data("x", paddle.data_type.dense_vector(10))
    m = L.mixed(
        size=3, input=L.full_matrix_projection(L.trans(x)), name="dynmix"
    )
    topo = Topology([m])
    net = CompiledNetwork(topo)
    assert net.has_dynamic_widths
    params, state = net.init(jax.random.PRNGKey(0))
    assert params["dynmix"]["p0_w"].shape == (10, 3)
    from paddle_tpu.core.batch import SeqTensor

    b = 6
    batch = {"x": SeqTensor(np.random.randn(b, 10).astype(np.float32))}
    params, changed = net.resolve_dynamic_widths(params, batch)
    assert changed
    assert params["dynmix"]["p0_w"].shape == (b, 3)
    outs, _ = net.apply(params, batch, state=state, train=False)
    assert outs["dynmix"].data.shape == (10, 3)  # [D rows, size]


def test_static_batch_still_uses_init_weights():
    """batch == static size: nothing to resolve, weights untouched."""
    x = L.data("x", paddle.data_type.dense_vector(8))
    h = L.fc(L.trans(x), size=2, act=A.Identity(), name="f")
    net = CompiledNetwork(Topology([h]))
    params, _ = net.init(jax.random.PRNGKey(0))
    from paddle_tpu.core.batch import SeqTensor

    batch = {"x": SeqTensor(np.zeros((8, 8), np.float32))}
    p2, changed = net.resolve_dynamic_widths(params, batch)
    assert not changed
    assert p2["f"]["w0"] is params["f"]["w0"]


def test_restored_other_batch_weights_raise_not_redraw():
    """Weights trained/restored at a different batch size must raise, not
    be silently replaced with fresh random values (r5 review finding)."""
    x = L.data("x", paddle.data_type.dense_vector(8))
    h = L.fc(L.trans(x), size=2, act=A.Identity(), name="f")
    net = CompiledNetwork(Topology([h]))
    params, _ = net.init(jax.random.PRNGKey(0))
    # simulate a checkpoint trained at batch 20 (static size is 8)
    params["f"]["w0"] = np.zeros((20, 2), np.float32)
    from paddle_tpu.core.batch import SeqTensor

    batch = {"x": SeqTensor(np.zeros((6, 8), np.float32))}
    with pytest.raises(ValueError, match="different batch size"):
        net.resolve_dynamic_widths(params, batch)
