"""Transformer-base MT (BASELINE.json configs #5) — attention building
blocks + end-to-end training."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import layers
from paddle_tpu.core.topology import reset_auto_names
from paddle_tpu.models.transformer import transformer_cost

from tests.layer_grad_util import check_layer_grad


def test_layer_norm_grad_and_stats():
    reset_auto_names()
    x = layers.data("x", paddle.data_type.dense_vector_sequence(6))
    out = layers.layer_norm(x)
    check_layer_grad(out)


def test_layer_norm_normalizes():
    import jax
    from paddle_tpu.core.batch import seq
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology

    reset_auto_names()
    x = layers.data("x", paddle.data_type.dense_vector_sequence(8))
    out = layers.layer_norm(x)
    net = CompiledNetwork(Topology([out]))
    params, state = net.init(jax.random.PRNGKey(0))
    data = np.random.RandomState(0).randn(2, 3, 8).astype(np.float32) * 5 + 3
    outs, _ = net.apply(params, {"x": seq(data, [3, 2])}, state=state)
    o = np.asarray(outs[out.name].data)
    np.testing.assert_allclose(o.mean(-1), 0.0, atol=1e-4)
    np.testing.assert_allclose(o.std(-1), 1.0, atol=1e-2)


def test_mha_self_attention_grad():
    reset_auto_names()
    x = layers.data("x", paddle.data_type.dense_vector_sequence(8))
    out = layers.multi_head_attention(x, n_heads=2)
    check_layer_grad(out, atol=8e-2, rtol=8e-2)


def test_mha_respects_key_padding():
    """Attention weights over padded keys must be ~0: growing the key
    padding must not change the output."""
    import jax
    from paddle_tpu.core.batch import seq
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology

    reset_auto_names()
    q = layers.data("q", paddle.data_type.dense_vector_sequence(8))
    kv = layers.data("kv", paddle.data_type.dense_vector_sequence(8))
    out = layers.multi_head_attention(q, key_value=kv, n_heads=2)
    net = CompiledNetwork(Topology([out]))
    params, state = net.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    qd = rng.randn(1, 3, 8).astype(np.float32)
    kd = rng.randn(1, 4, 8).astype(np.float32)
    kd_padded = np.concatenate([kd, rng.randn(1, 3, 8).astype(np.float32)], 1)
    o1, _ = net.apply(params, {"q": seq(qd, [3]), "kv": seq(kd, [2])}, state=state)
    o2, _ = net.apply(
        params, {"q": seq(qd, [3]), "kv": seq(kd_padded, [2])}, state=state
    )
    np.testing.assert_allclose(
        np.asarray(o1[out.name].data), np.asarray(o2[out.name].data),
        rtol=1e-4, atol=1e-5,
    )


def test_mha_causal_masks_future():
    """With causal=True, output at position t must not depend on inputs
    after t."""
    import jax
    from paddle_tpu.core.batch import seq
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology

    reset_auto_names()
    x = layers.data("x", paddle.data_type.dense_vector_sequence(8))
    out = layers.multi_head_attention(x, n_heads=2, causal=True)
    net = CompiledNetwork(Topology([out]))
    params, state = net.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    d1 = rng.randn(1, 4, 8).astype(np.float32)
    d2 = d1.copy()
    d2[0, 3] += 10.0  # perturb the LAST position only
    o1, _ = net.apply(params, {"x": seq(d1, [4])}, state=state)
    o2, _ = net.apply(params, {"x": seq(d2, [4])}, state=state)
    a, b = np.asarray(o1[out.name].data), np.asarray(o2[out.name].data)
    np.testing.assert_allclose(a[0, :3], b[0, :3], rtol=1e-4, atol=1e-5)
    assert np.abs(a[0, 3] - b[0, 3]).max() > 1e-3  # last position did change


# Readers yield (src, trg, trg_next); DFS feeding order visits the decoder
# subtree (trg_word) first — map explicitly (reference v2 feeding= contract).
_FEEDING = {"src_word": 0, "trg_word": 1, "trg_next": 2}


def test_transformer_trains_on_copy_task():
    reset_auto_names()
    V, BOS, EOS = 14, 0, 1
    cost, logits = transformer_cost(
        V, V, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Adam(learning_rate=3e-3),
    )
    rng = np.random.RandomState(0)

    def reader():
        for _ in range(160):
            s = list(rng.randint(2, V, size=rng.randint(2, 6)))
            yield s, [BOS] + s, s + [EOS]

    costs = []
    trainer.train(
        reader=paddle.batch(reader, 16),
        num_passes=10,
        event_handler=lambda e: costs.append(e.cost)
        if isinstance(e, paddle.event.EndIteration) else None,
        feeding=_FEEDING,
    )
    assert np.mean(costs[-5:]) < 0.6 * np.mean(costs[:5]), (
        costs[:5], costs[-5:],
    )


def test_transformer_infer():
    """Forward through paddle.infer: per-timestep distributions, unpadded."""
    reset_auto_names()
    V = 10
    cost, logits = transformer_cost(V, V, d_model=16, n_heads=2, n_layers=1, d_ff=32)
    params = paddle.parameters.create(cost)
    samples = [([2, 3, 4], [0, 2, 3, 4], [2, 3, 4, 1]), ([5, 6], [0, 5, 6], [5, 6, 1])]
    probs = paddle.infer(
        output_layer=logits, parameters=params, input=samples, feeding=_FEEDING
    )
    assert probs.shape == (7, V)  # 4 + 3 decoder timesteps
    np.testing.assert_allclose(probs.sum(1), 1.0, rtol=1e-3)


@pytest.mark.parametrize("keys,blocked_dense", [(1024, (18, 0)), (128, (0, 18))])
def test_the_18_attention_layers_count_their_path_where_they_choose_it(monkeypatch, keys, blocked_dense):
    """`transformer-train-1k` and `-128` as the chip would trace them (the
    backend's name is the one thing faked; nothing is compiled or run): six
    encoder layers, six decoder self- and six cross-attention layers, every
    one with as many queries as keys, take the blocked kernels at 1,024 keys
    and the dense path at 128, and say so in `attention_blocked_layers` /
    `attention_dense_layers`, one count a layer traced."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.utils.timers import global_stats

    reset_auto_names()
    cost, _ = transformer_cost(50, 50, d_model=16, n_heads=2, n_layers=6, d_ff=32)
    net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
    params, state = net.init(jax.random.PRNGKey(0))
    ids = SeqTensor(jnp.ones((2, keys), jnp.int32), jnp.full((2,), keys, jnp.int32))
    batch = {name: ids for name in ("src_word", "trg_word", "trg_next")}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = [global_stats.count(f"attention_{path}_layers") for path in ("blocked", "dense")]
    jax.eval_shape(lambda p: net.apply(p, batch, state=state, train=True)[0][cost.name].data, params)
    after = [global_stats.count(f"attention_{path}_layers") for path in ("blocked", "dense")]
    assert (after[0] - before[0], after[1] - before[1]) == blocked_dense


@pytest.mark.parametrize("rows,blocked_dense", [(4, (3, 0)), (6, (0, 3))])
def test_on_a_mesh_the_blocked_kernels_go_under_a_shard_map_over_the_rows(monkeypatch, rows, blocked_dense):
    """XLA partitions no Mosaic kernel, and the data-parallel step is one
    program over the mesh: where the layer sees a mesh of several devices
    (ctx.mesh) its kernels run under a shard_map over the rows, and rows that
    do not split over the data axis keep the layer dense, which it says.  The
    backend's name is faked and the step only traced; that it compiles for
    four chips is `tests/test_tpu_compile.py`'s to show."""
    import warnings

    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.utils.timers import global_stats

    reset_auto_names()
    cost, _ = transformer_cost(50, 50, d_model=16, n_heads=2, n_layers=1, d_ff=32)
    net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
    net.mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    params, state = net.init(jax.random.PRNGKey(0))
    ids = SeqTensor(jnp.ones((rows, 1024), jnp.int32), jnp.full((rows,), 1024, jnp.int32))
    batch = {name: ids for name in ("src_word", "trg_word", "trg_next")}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    count = lambda: [global_stats.count(f"attention_{path}_layers") for path in ("blocked", "dense")]
    before = count()
    with warnings.catch_warnings(record=True) as said:
        warnings.simplefilter("always")
        program = str(jax.make_jaxpr(
            lambda p: net.apply(p, batch, state=state, train=True)[0][cost.name].data)(params))
    assert tuple(a - b for a, b in zip(count(), before)) == blocked_dense
    assert program.count("shard_map") == blocked_dense[0]
    refusals = [str(w.message) for w in said if "do not split over its 'data' axis" in str(w.message)]
    assert len(refusals) == blocked_dense[1]


@pytest.mark.parametrize("held,taken", [(("data", "model"), True), (("data",), False)])
def test_inside_a_shard_map_the_kernels_are_called_bare_only_where_it_holds_every_axis(held, taken):
    """The quantized-allreduce step traces the layers inside a shard_map over
    the whole mesh: the program is a device's own there and the kernels need
    no wrapping.  Inside one that leaves an axis to XLA jax refuses them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.layers.attention import _blocked_core
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    seen = []

    def body(x):
        wrap, why = _blocked_core(mesh, x.shape[0])
        seen.append((wrap is not None, why))
        return x if wrap is None else wrap(lambda y: y)(x)

    jax.eval_shape(jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                                 axis_names=set(held), check_vma=False), jnp.ones((4, 8)))
    (got, why), = seen
    assert got == taken and (why is None) == taken


def test_the_transformers_cost_layer_reads_batch_major_logits_and_folds_no_label():
    """The Transformer's output layer is a plain `fc` softmax: no group
    exposes rows, so softmax-CE and the evaluator take `@logits` as they
    always did, say so (`ce_batch_major_layers` 1, `ce_hoisted_rows_layers`
    0, one count a cost layer traced), and the lowered training step moves
    no [B, T] integer array into another order (the fold of the label ids
    that the rows path of layers/cost.py makes)."""
    import re

    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.trainer.evaluators import default_metrics_fn
    from paddle_tpu.trainer.step import make_train_step
    from paddle_tpu.utils.timers import global_stats

    reset_auto_names()
    b, t = 3, 5
    cost, _ = transformer_cost(50, 50, d_model=16, n_heads=2, n_layers=1, d_ff=32)
    net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
    opt = paddle.optimizer.Adam(learning_rate=1e-3)
    params, state = net.init(jax.random.PRNGKey(0))
    ids = SeqTensor(jnp.ones((b, t), jnp.int32), jnp.full((b,), t, jnp.int32))
    batch = {name: ids for name in ("src_word", "trg_word", "trg_next")}
    count = lambda: [global_stats.count(f"ce_{path}_layers") for path in ("hoisted_rows", "batch_major")]
    before = count()
    step = make_train_step(net, opt, extra_metrics=default_metrics_fn(net.topology))
    text = step.lower(params, state, opt.init(params), batch, jax.random.PRNGKey(0)).as_text()
    assert [a - b_ for a, b_ in zip(count(), before)] == [0, 1]
    outs = jax.eval_shape(lambda p: net.apply(p, batch, state=state, train=True)[0], params)
    assert not [name for name in outs if name.endswith("@logits_rows")]
    moved = [
        line.strip()[:140] for line in text.splitlines()
        if re.search(rf"stablehlo\.transpose.*\(tensor<{b}x{t}xi32>\)", line)
        or re.search(rf"\(tensor<{b}x{t}xi32>\) -> tensor<{b * t}xi32>", line)
    ]
    assert moved == []
