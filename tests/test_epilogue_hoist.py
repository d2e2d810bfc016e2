"""recurrent_group epilogue hoisting (layers/recurrent_group.py
_split_epilogue): the rowwise suffix of a step graph runs once on the
stacked sequence instead of per scan step.  These tests pin (a) the
partition itself, (b) exact numerics vs the unhoisted path, and (c) the
group-level @logits exposure that lets cross_entropy fuse."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.core.topology import Topology, reset_auto_names
import importlib

rg = importlib.import_module("paddle_tpu.layers.recurrent_group")

L = paddle.layer
A = paddle.activation


def _group_cost(vocab=37):
    """A decoder-shaped group: GRU-ish recurrence + per-step vocab fc."""
    paddle.init(seed=5)
    x = L.data("x", paddle.data_type.integer_value_sequence(vocab))
    emb = L.embedding(x, size=12)

    def step(e_t):
        state = L.memory("st", 10)
        h = L.fc([e_t, state], size=10, act=A.Tanh(), name="st")
        return L.fc(h, size=vocab, act=A.Softmax(), name="head")

    dec = L.recurrent_group(step, input=[emb], name="dec_group")
    lab = L.data("y", paddle.data_type.integer_value_sequence(vocab))
    return L.classification_cost(input=dec, label=lab)


def _batch(vocab=37, b=3, t=6):
    rng = np.random.RandomState(0)
    lens = jnp.asarray([6, 4, 2], jnp.int32)
    return {
        "x": SeqTensor(
            jnp.asarray(rng.randint(0, vocab, size=(b, t)), jnp.int32), lens
        ),
        "y": SeqTensor(
            jnp.asarray(rng.randint(0, vocab, size=(b, t)), jnp.int32), lens
        ),
    }


def test_partition_hoists_head_only():
    reset_auto_names()
    cost = _group_cost()
    topo = Topology([cost])
    gconf = next(
        c for c in topo.layers.values() if c.type == "recurrent_group"
    )
    sub = gconf.attrs["_sub_topology"]
    epi, frontier = rg._split_epilogue(
        sub, gconf.attrs["_memories"], gconf.attrs["_output"], set()
    )
    assert epi == {"head"}
    # the head reads exactly the recurrent state from the loop
    assert frontier == ("st",)


def test_hoisted_numerics_match_unhoisted(monkeypatch):
    reset_auto_names()
    cost = _group_cost()
    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    batch = _batch()

    def cost_and_grads():
        def loss(p):
            # net.cost returns (cost, aux); take the scalar
            return net.cost(p, batch, state=state, rng=None, train=True)[0]

        return jax.value_and_grad(loss)(params)

    v_hoisted, g_hoisted = cost_and_grads()
    monkeypatch.setattr(
        rg, "_split_epilogue", lambda *a, **k: (None, (a[2],))
    )
    v_plain, g_plain = cost_and_grads()
    np.testing.assert_allclose(v_hoisted, v_plain, rtol=1e-5)
    flat_h = jax.tree_util.tree_leaves(g_hoisted)
    flat_p = jax.tree_util.tree_leaves(g_plain)
    for a, b in zip(flat_h, flat_p):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_group_exposes_fused_ce_logits():
    reset_auto_names()
    cost = _group_cost()
    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    outs, _ = net.apply(params, _batch(), state=state, train=True)
    lg = outs.get("dec_group@logits")
    assert lg is not None, "hoisted softmax must expose group-level logits"
    assert lg.data.shape == outs["dec_group"].data.shape
    # logits really are the pre-softmax values of the group output
    np.testing.assert_allclose(
        np.asarray(jax.nn.softmax(lg.data[..., :], axis=-1))[0, 0],
        np.asarray(outs["dec_group"].data)[0, 0],
        atol=1e-5,
    )


def test_memory_dependent_head_stays_in_loop():
    """A suffix that feeds a memory cannot hoist."""
    reset_auto_names()
    paddle.init(seed=6)
    x = L.data("x", paddle.data_type.integer_value_sequence(11))
    emb = L.embedding(x, size=8)

    def step(e_t):
        state = L.memory("looped", 11)
        h = L.fc([e_t, state], size=8, act=A.Tanh(), name="h")
        out = L.fc(h, size=11, act=A.Softmax(), name="looped")
        return out

    dec = L.recurrent_group(step, input=[emb], name="g2")
    topo = Topology([dec])
    gconf = next(
        c for c in topo.layers.values() if c.type == "recurrent_group"
    )
    epi, frontier = rg._split_epilogue(
        gconf.attrs["_sub_topology"], gconf.attrs["_memories"],
        gconf.attrs["_output"], set(),
    )
    assert epi is None and frontier == (gconf.attrs["_output"],)


def test_diamond_with_loop_resident_consumer():
    """p feeds both a hoistable suffix AND a loop-resident (dropout)
    layer: p must stay in the loop — a hoisted p would leave the loop
    consumer reading a never-computed output."""
    reset_auto_names()
    paddle.init(seed=7)
    x = L.data("x", paddle.data_type.integer_value_sequence(13))
    emb = L.embedding(x, size=8)

    def step(e_t):
        state = L.memory("s", 6)
        h = L.fc([e_t, state], size=6, act=A.Tanh(), name="s")
        p = L.fc(h, size=6, act=A.Tanh(), name="p")
        q = L.fc(p, size=6, act=A.Tanh(), name="q",
                 layer_attr=paddle.attr.ExtraAttr(drop_rate=0.5))
        return L.addto([p, q], act=A.Identity(), name="out",
                       bias_attr=False)

    dec = L.recurrent_group(step, input=[emb], name="g3")
    net = CompiledNetwork(Topology([dec]))
    params, state = net.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {
        "x": SeqTensor(
            jnp.asarray(rng.randint(0, 13, size=(2, 4)), jnp.int32),
            jnp.asarray([4, 2], jnp.int32),
        )
    }
    outs, _ = net.apply(
        params, batch, state=state, train=True, rng=jax.random.PRNGKey(1)
    )
    assert outs["g3"].data.shape == (2, 4, 6)


def test_seq_valued_frontier_disables_hoisting():
    """A loop layer emitting a per-step SEQUENCE (expand over a static
    seq) cannot be time-flattened: the abstract probe must disable
    hoisting and the nested output must match the unhoisted semantics."""
    reset_auto_names()
    paddle.init(seed=8)
    x = L.data("x", paddle.data_type.integer_value_sequence(13))
    emb = L.embedding(x, size=8)
    static = L.fc(emb, size=5, act=A.Tanh(), name="stat")

    from paddle_tpu.layers.recurrent_group import StaticInput

    def step(e_t, stat_seq):
        state = L.memory("s2", 5)
        h = L.fc([e_t, state], size=5, act=A.Tanh(), name="s2")
        ex = L.expand(h, stat_seq, name="ex")
        return L.fc(ex, size=5, act=A.Tanh(), name="head2")

    dec = L.recurrent_group(
        step, input=[emb, StaticInput(static, is_seq=True)], name="g4"
    )
    net = CompiledNetwork(Topology([dec]))
    params, state = net.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {
        "x": SeqTensor(
            jnp.asarray(rng.randint(0, 13, size=(2, 4)), jnp.int32),
            jnp.asarray([4, 3], jnp.int32),
        )
    }
    outs, _ = net.apply(params, batch, state=state, train=True)
    # nested [B, S, T, D] output, exactly as without hoisting
    assert outs["g4"].data.ndim == 4


def test_prologue_hoists_input_projection():
    """An in-step projection fed only by the scanned input (the
    sequence_layer_group.conf pattern: Layer(fc) over the step input
    before the recurrence) must land in the prologue set; the recurrent
    fc must not."""
    reset_auto_names()
    paddle.init(seed=9)
    x = L.data("x", paddle.data_type.integer_value_sequence(17))
    emb = L.embedding(x, size=9)

    def step(e_t):
        proj = L.fc(e_t, size=6, act=A.Identity(), name="in_proj")
        state = L.memory("rec", 6)
        return L.fc([proj, state], size=6, act=A.Tanh(), name="rec")

    g = L.recurrent_group(step, input=[emb], name="gg")
    topo = Topology([g])
    gconf = next(
        c for c in topo.layers.values() if c.type == "recurrent_group"
    )
    sub = gconf.attrs["_sub_topology"]
    pro = rg._split_prologue(
        sub, gconf.attrs["_scan_placeholders"],
        gconf.attrs["_static_placeholders"], set(),
    )
    assert any(sub.layers[n].name == "in_proj" for n in pro), pro
    assert all(sub.layers[n].name != "rec" for n in pro), pro


def test_prologue_numerics_match_unhoisted(monkeypatch):
    reset_auto_names()
    paddle.init(seed=10)
    x = L.data("x", paddle.data_type.integer_value_sequence(17))
    emb = L.embedding(x, size=12)
    g = paddle.networks.gru_group(emb, size=4, name="gg2")
    pool = L.last_seq(input=g)
    out = L.fc(pool, size=3, act=A.Softmax())
    lab = L.data("y", paddle.data_type.integer_value(3))
    cost = L.classification_cost(input=out, label=lab)
    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {
        "x": SeqTensor(
            jnp.asarray(rng.randint(0, 17, size=(3, 5)), jnp.int32),
            jnp.asarray([5, 3, 1], jnp.int32),
        ),
        "y": SeqTensor(jnp.asarray(rng.randint(0, 3, size=3), jnp.int32)),
    }

    def cg():
        def loss(p):
            return net.cost(p, batch, state=state, rng=None, train=True)[0]

        return jax.value_and_grad(loss)(params)

    v_h, g_h = cg()
    monkeypatch.setattr(rg, "_split_prologue", lambda *a, **k: set())
    v_p, g_p = cg()
    np.testing.assert_allclose(v_h, v_p, rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_h), jax.tree_util.tree_leaves(g_p)
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_epilogue_reads_step_input_directly():
    """A readout consuming the scanned input alongside the recurrent
    state: the placeholder is preset from the already-flattened xs (never
    re-stacked by the scan) and numerics hold."""
    reset_auto_names()
    paddle.init(seed=13)
    x = L.data("x", paddle.data_type.integer_value_sequence(19))
    emb = L.embedding(x, size=7)

    def step(e_t):
        state = L.memory("r5", 7)
        h = L.fc([e_t, state], size=7, act=A.Tanh(), name="r5")
        return L.fc([h, e_t], size=5, act=A.Softmax(), name="head5")

    g = L.recurrent_group(step, input=[emb], name="g5")
    net = CompiledNetwork(Topology([g]))
    params, state = net.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {
        "x": SeqTensor(
            jnp.asarray(rng.randint(0, 19, size=(2, 4)), jnp.int32),
            jnp.asarray([4, 2], jnp.int32),
        )
    }
    outs, _ = net.apply(params, batch, state=state, train=True)
    assert outs["g5"].data.shape == (2, 4, 5)
    # hoisting actually engaged (head5 in the epilogue)
    gconf = net.topology.layers["g5"]
    epi, frontier = rg._split_epilogue(
        gconf.attrs["_sub_topology"], gconf.attrs["_memories"],
        gconf.attrs["_output"], set(),
    )
    assert epi == {"head5"}
    assert "g5@in0" in frontier


# ---------------------------------------------------------------------------
# Row order under a data mesh (rg._HoistRows): hoisted rows are shard-major
# where the mesh's `data` axis divides B, so the values must not depend on
# the mesh at all.
# ---------------------------------------------------------------------------

MESHES = [None, 2, 4]


def _mesh(n):
    from paddle_tpu.parallel.mesh import make_mesh

    return None if n is None else make_mesh(data=n, devices=jax.devices()[:n])


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("reverse", [False, True])
def test_hoist_rows_orders_agree(n, reverse):
    """fold / tile / unfold / unfold_batch_major describe ONE row order:
    whatever it is, a value folded and unfolded comes back where it was."""
    t, b = 5, 8
    rows = rg._HoistRows(t, b, _mesh(n))
    assert rows.n == n
    x = jnp.arange(t * b * 3, dtype=jnp.float32).reshape(t, b, 3)
    r = rows.fold(x)
    assert r.shape == (t * b, 3)
    if n == 1:  # time-major, as without a mesh
        np.testing.assert_array_equal(r, x.reshape(t * b, 3))
    else:  # a shard's rows are one contiguous block
        np.testing.assert_array_equal(
            r[: t * b // n], x[:, : b // n].reshape(t * b // n, 3)
        )
    np.testing.assert_array_equal(rows.unfold(r), x)
    want = jnp.swapaxes(jnp.flip(x, axis=0) if reverse else x, 0, 1)
    np.testing.assert_array_equal(rows.unfold_batch_major(r, reverse), want)
    s = jnp.arange(b * 2, dtype=jnp.float32).reshape(b, 2)
    np.testing.assert_array_equal(
        rows.unfold(rows.tile(s)), jnp.broadcast_to(s[None], (t, b, 2))
    )


def _mesh_group(reverse=False, static=False, vocab=29):
    """Decoder-shaped group with BOTH hoists: an input projection (prologue),
    a recurrence, a vocab head (epilogue) that can also read a non-sequence
    static through the rows' tile()."""
    reset_auto_names()
    paddle.init(seed=21)
    x = L.data("x", paddle.data_type.integer_value_sequence(vocab))
    emb = L.embedding(x, size=12)
    ins = [emb]
    if static:
        summary = L.pooling(input=emb, pooling_type=paddle.pooling.Avg())
        ins.append(rg.StaticInput(summary))

    def step(e_t, *stat):
        proj = L.fc(e_t, size=10, act=A.Identity(), name="mg_proj")
        state = L.memory("mg_st", 10)
        h = L.fc([proj, state], size=10, act=A.Tanh(), name="mg_st")
        return L.fc([h, *stat], size=vocab, act=A.Softmax(), name="mg_head")

    dec = L.recurrent_group(step, input=ins, reverse=reverse, name="mg")
    lab = L.data("y", paddle.data_type.integer_value_sequence(vocab))
    return L.classification_cost(input=dec, label=lab)


def _mesh_batch(b=8, t=6, vocab=29):
    rng = np.random.RandomState(3)
    lens = jnp.asarray(rng.randint(1, t + 1, b), jnp.int32).at[0].set(t)
    ids = lambda: jnp.asarray(rng.randint(0, vocab, (b, t)), jnp.int32)  # noqa: E731
    return {"x": SeqTensor(ids(), lens), "y": SeqTensor(ids(), lens)}


def _value_grads_outs(cost, batch, n):
    """Cost, gradients and layer outputs of `cost` with the network on an
    n-way data mesh (None: no mesh), the batch sharded as the trainer's."""
    from paddle_tpu.parallel.mesh import shard_batch

    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    net.mesh = _mesh(n)
    if batch["x"].batch_size % (n or 1) == 0:
        batch = shard_batch(batch, net.mesh)

    def loss(p):
        return net.cost(p, batch, state=state, rng=None, train=True)[0]

    v, g = jax.jit(jax.value_and_grad(loss))(params)
    outs, _ = jax.jit(
        lambda p: net.apply(p, batch, state=state, train=True)
    )(params)
    return v, g, outs


def _assert_same(got, want):
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("n", MESHES)
@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reverse"])
@pytest.mark.parametrize("static", [False, True], ids=["plain", "static"])
def test_meshed_hoists_match_unhoisted(monkeypatch, n, reverse, static):
    """Both hoists on an n-way data mesh against the per-step scan with no
    mesh: forward and reverse groups, and a static (non-sequence) frontier
    input tiled into the rows."""
    batch = _mesh_batch()
    got = _value_grads_outs(_mesh_group(reverse, static), batch, n)
    monkeypatch.setattr(rg, "_split_epilogue", lambda *a, **k: (None, (a[2],)))
    monkeypatch.setattr(rg, "_split_prologue", lambda *a, **k: set())
    want = _value_grads_outs(_mesh_group(reverse, static), batch, None)
    _assert_same(got, want)
    np.testing.assert_allclose(
        got[2]["mg"].data, want[2]["mg"].data, rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("n", MESHES)
def test_meshed_group_exposes_batch_major_logits(n):
    batch = _mesh_batch()
    _, _, outs = _value_grads_outs(_mesh_group(), batch, n)
    lg = outs["mg@logits"]
    assert lg.data.shape == outs["mg"].data.shape == (8, 6, 29)
    np.testing.assert_array_equal(lg.lengths, batch["x"].lengths)
    valid = np.asarray(batch["x"].mask(bool))
    np.testing.assert_allclose(
        np.asarray(jax.nn.softmax(lg.data, axis=-1))[valid],
        np.asarray(outs["mg"].data)[valid],
        atol=1e-5,
    )
    _, _, plain = _value_grads_outs(_mesh_group(), batch, None)
    np.testing.assert_allclose(
        lg.data, plain["mg@logits"].data, rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("n", [2, 4])
def test_indivisible_batch_falls_back_to_time_major(n):
    """B=6 over 4 shards (or 3 over 2) cannot be split: the rows stay
    time-major (gathered under a real sharding, and correct)."""
    b = 6 if n == 4 else 3
    assert rg._HoistRows(6, b, _mesh(n)).n == 1
    batch = _mesh_batch(b=b)
    got = _value_grads_outs(_mesh_group(), batch, n)
    want = _value_grads_outs(_mesh_group(), batch, None)
    _assert_same(got, want)


# ---------------------------------------------------------------------------
# The rows path (rg.HoistedRows): a consumer that reduces the hoisted output
# over the vocabulary reads `<group>@logits_rows`, the [T*B, V] rows in the
# order they were computed in, folds its per-token input into that order and
# unfolds its per-row result; `<group>@logits` stays for everyone else.
# Withholding the rows puts cost layer and evaluator back on `@logits`.
# ---------------------------------------------------------------------------


def _withhold_rows(monkeypatch):
    monkeypatch.setattr(rg, "HoistedRows", lambda *a: None)


def _ce_counts():
    from paddle_tpu.utils.timers import global_stats

    return tuple(
        global_stats.count(f"ce_{path}_layers")
        for path in ("hoisted_rows", "batch_major")
    )


@pytest.mark.parametrize("n", MESHES)
@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reverse"])
@pytest.mark.parametrize("static", [False, True], ids=["plain", "static"])
def test_rows_path_matches_logits_path(monkeypatch, n, reverse, static):
    """Cost and every leaf's gradient through the rows against the same
    group with the rows withheld (the cost layer then reads `@logits`)."""
    batch = _mesh_batch()
    got = _value_grads_outs(_mesh_group(reverse, static), batch, n)
    hoisted = got[2]["mg@logits_rows"]
    assert isinstance(hoisted, rg.HoistedRows)
    assert hoisted.rows.shape == (6 * 8, 29)
    _withhold_rows(monkeypatch)
    want = _value_grads_outs(_mesh_group(reverse, static), batch, n)
    assert want[2]["mg@logits_rows"] is None
    _assert_same(got, want)
    # the rows ARE the logits, in another order
    np.testing.assert_allclose(
        hoisted.unfold(hoisted.rows), want[2]["mg@logits"].data,
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.parametrize("n", MESHES)
@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reverse"])
def test_default_evaluator_reads_the_rows(monkeypatch, n, reverse):
    """classification_error from the argmax over the rows, its ids unfolded,
    equals the one from the argmax over `@logits`, padding masked alike."""
    from paddle_tpu.trainer.evaluators import default_metrics_fn

    batch = _mesh_batch()

    def error():
        cost = _mesh_group(reverse)
        outs = _value_grads_outs(cost, batch, n)[2]
        return outs, default_metrics_fn(Topology([cost]))(outs)

    outs, got = error()
    assert isinstance(outs["mg@logits_rows"], rg.HoistedRows)
    _withhold_rows(monkeypatch)
    outs, want = error()
    assert outs["mg@logits_rows"] is None
    assert set(got) == {"classification_error"}
    # by hand from the batch-major logits: the value both must give
    valid = np.asarray(batch["y"].mask(bool))
    wrong = np.asarray(jnp.argmax(outs["mg@logits"].data, -1) != batch["y"].data)
    np.testing.assert_allclose(want["classification_error"], wrong[valid].mean(), rtol=1e-6)
    np.testing.assert_allclose(
        got["classification_error"], want["classification_error"], rtol=1e-6
    )


def _plain_softmax_cost(vocab=29):
    reset_auto_names()
    paddle.init(seed=21)
    x = L.data("x", paddle.data_type.integer_value_sequence(vocab))
    out = L.fc(L.embedding(x, size=12), size=vocab, act=A.Softmax())
    lab = L.data("y", paddle.data_type.integer_value_sequence(vocab))
    return L.classification_cost(input=out, label=lab)


@pytest.mark.parametrize(
    "build,withheld,rows_batch_major",
    [
        (_mesh_group, False, (1, 0)),
        (_mesh_group, True, (0, 1)),
        (_plain_softmax_cost, False, (0, 1)),
    ],
    ids=["hoisted_group", "hoisted_group_rows_withheld", "plain_fc_softmax"],
)
def test_cost_layer_counts_the_path_it_took(monkeypatch, build, withheld, rows_batch_major):
    """One count a cost layer traced: ce_hoisted_rows_layers where the
    producer exposed its rows, ce_batch_major_layers where it did not."""
    if withheld:
        _withhold_rows(monkeypatch)
    cost = build()
    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    before = _ce_counts()
    jax.eval_shape(
        lambda p: net.cost(p, _mesh_batch(), state=state, rng=None, train=True)[0],
        params,
    )
    after = _ce_counts()
    assert tuple(a - b for a, b in zip(after, before)) == rows_batch_major


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reverse"])
def test_hoisted_rows_cross_jit_as_a_pytree(n, reverse):
    """The array is the one leaf; the order (T, B, n, reverse) is static and
    comes back, so fold / unfold work on the far side of a jit and are each
    other's inverse there."""
    t, b = 5, 8
    order = rg._HoistRows(t, b, _mesh(n))
    x = jnp.arange(t * b * 3, dtype=jnp.float32).reshape(t * b, 3)
    hoisted = rg.HoistedRows(x, order, reverse)
    leaves, treedef = jax.tree_util.tree_flatten(hoisted)
    assert len(leaves) == 1 and leaves[0] is x
    # two traces of one shape give equal static parts: one jit cache entry
    assert treedef == jax.tree_util.tree_structure(
        rg.HoistedRows(x, rg._HoistRows(t, b, _mesh(n)), reverse)
    )
    assert treedef != jax.tree_util.tree_structure(
        rg.HoistedRows(x, order, not reverse)
    )
    back = jax.jit(lambda h: jax.tree_util.tree_map(lambda a: a * 2, h))(hoisted)
    assert isinstance(back, rg.HoistedRows)
    assert (back.order, back.reverse) == (order, reverse)
    np.testing.assert_array_equal(back.rows, x * 2)
    per_token = jnp.arange(b * t, dtype=jnp.int32).reshape(b, t)
    folded = jax.jit(lambda h, d: h.fold(d))(back, per_token)
    assert folded.shape == (t * b,)
    np.testing.assert_array_equal(back.unfold(folded), per_token)
    np.testing.assert_array_equal(
        back.unfold(back.rows), order.unfold_batch_major(x * 2, reverse)
    )


def test_inference_returns_batch_major_values_only():
    """`paddle.infer` keeps a selected layer's "@" side outputs, sliced back
    to the rows that were fed; the group's rows are its logits in an order of
    its own and are not among them."""
    from paddle_tpu.inference import Inference

    cost = _mesh_group()
    group = next(
        lo for lo in cost.parents if lo.conf.type == "recurrent_group"
    )
    inferer = Inference(
        output_layer=group, parameters=paddle.parameters.create(cost)
    )
    rng = np.random.RandomState(1)
    samples = [(list(rng.randint(0, 29, n)),) for n in (5, 2, 4)]
    (outs,) = list(inferer.iter_infer(input=samples))
    assert set(outs) == {"mg", "mg@logits"}
    assert outs["mg"].data.shape[0] == outs["mg@logits"].data.shape[0] == 3
    # ... while the network itself exposes them, in test mode too
    net_outs, _ = inferer.network.apply(
        inferer._params, _mesh_batch(), state=inferer._state, train=False
    )
    assert isinstance(net_outs["mg@logits_rows"], rg.HoistedRows)


# ---------------------------------------------------------------------------
# The rows that hold a token (rg._HoistRows.kept / take / put): where the feed
# bounds a slot's true tokens (core.batch.RowBound), a group that scans that
# slot's lengths runs its hoisted epilogue on R rows a shard, not T*B/n.  The
# cost, every gradient and classification_error are those of all the rows.
# ---------------------------------------------------------------------------


def _bounded(batch, slot, n):
    """The batch with the feeder's bound on `slot` over n shards."""
    from paddle_tpu.core.batch import row_bound

    t = batch[slot]
    bound = row_bound(np.asarray(t.lengths), t.data.shape[1], n or 1)
    assert bound is not None and bound.shards == (n or 1)
    out = dict(batch)
    out[slot] = SeqTensor(t.data, t.lengths, row_bound=bound)
    return out


def _short_batch(slots, b=64, t=32, vocab=29):
    """Lengths mostly at most t/2 (one at t): under half the rows hold a
    token, so the bound leaves rows out over 1, 2 and 4 shards."""
    rng = np.random.RandomState(7)
    lens = jnp.asarray(rng.randint(1, t // 2 + 1, b), jnp.int32).at[0].set(t)
    out = {}
    for name in slots:
        ids = jnp.asarray(rng.randint(2, vocab, (b, t)), jnp.int32)
        own = lens if name != "src_word" else jnp.asarray(
            rng.randint(2, t + 1, b), jnp.int32
        )
        out[name] = SeqTensor(ids, own)
    return out


def _seq2seq_cost(vocab=29):
    from paddle_tpu.models.seq2seq import seq2seq_cost

    reset_auto_names()
    paddle.init(seed=21)
    return seq2seq_cost(vocab, vocab, word_dim=6, hidden_dim=5)[0]


def _hoist_counts():
    from paddle_tpu.utils.timers import global_stats

    return tuple(
        global_stats.count(f"hoisted_rows_{path}_layers")
        for path in ("compacted", "full")
    )


def _step(cost, batch, n):
    """(cost, gradients, classification_error) of one jitted step on an
    n-way data mesh (None: no mesh), the batch an argument sharded as the
    trainer shards it; and the compiled step's text."""
    from paddle_tpu.parallel.mesh import shard_batch
    from paddle_tpu.trainer.evaluators import default_metrics_fn

    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    net.mesh = _mesh(n)
    batch = shard_batch(batch, net.mesh)
    metrics = default_metrics_fn(net.topology)

    def step(p, fed):
        def loss(q):
            return net.cost(q, fed, state=state, rng=None, train=True)

        (v, (outs, _)), g = jax.value_and_grad(loss, has_aux=True)(p)
        return v, g, metrics(outs)["classification_error"]

    jitted = jax.jit(step)
    text = jitted.lower(params, batch).compile().as_text()
    return jitted(params, batch), text


CASES = {
    "attention_gru_fused": (_seq2seq_cost, ("src_word", "trg_word", "trg_next"),
                            "trg_word", True),
    "attention_gru_generic": (_seq2seq_cost, ("src_word", "trg_word", "trg_next"),
                              "trg_word", False),
    "generic_forward": (lambda: _mesh_group(False, True), ("x", "y"), "x", True),
    "generic_reverse": (lambda: _mesh_group(True, True), ("x", "y"), "x", True),
}


@pytest.mark.parametrize("n", MESHES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kept_rows_match_all_rows(case, n):
    """Cost, every gradient leaf and classification_error with the epilogue
    on the kept rows against the same step on all rows (the batch without
    its bound), float32; under a data mesh the step gathers nothing."""
    from paddle_tpu.utils.flags import get_flag, set_flag

    build, slots, slot, fused = CASES[case]
    old = get_flag("fused_attention_gru")
    set_flag("fused_attention_gru", fused)
    try:
        plain = _short_batch(slots)
        before = _hoist_counts()
        (got, got_text) = _step(build(), _bounded(plain, slot, n), n)
        mid = _hoist_counts()
        (want, _) = _step(build(), plain, n)
        after = _hoist_counts()
    finally:
        set_flag("fused_attention_gru", old)
    # one group traced on each path (two traces each: lower, then call)
    assert (mid[0] - before[0], mid[1] - before[1]) == (1, 0)
    assert (after[0] - mid[0], after[1] - mid[1]) == (0, 1)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    g_got, g_want = (jax.tree_util.tree_leaves(x[1]) for x in (got, want))
    assert len(g_got) == len(g_want)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got[2], want[2], rtol=1e-6)
    assert 0.0 < float(got[2]) <= 1.0
    if n:
        assert " all-reduce" in got_text  # a partitioned step
        assert "all-gather" not in got_text


def test_kept_share_is_observed_at_trace():
    from paddle_tpu.utils.timers import global_stats

    plain = _short_batch(("x", "y"))
    fed = _bounded(plain, "x", None)
    cost = _mesh_group()
    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    before = global_stats.summary().get("hoisted_rows_kept_share", {}).get("count", 0)
    outs, _ = net.apply(params, fed, state=state, train=True)
    share = global_stats.summary()["hoisted_rows_kept_share"]
    assert share["count"] == before + 1
    bound = fed["x"].row_bound.rows
    assert outs["mg@logits_rows"].rows.shape == (bound, 29)
    # the last observation is this trace's: R / (T*B), in percent
    assert share["max"] >= 100.0 * bound / (32 * 64)
    t = global_stats.summary()["hoisted_rows_kept_share"]["total"]
    net.apply(params, fed, state=state, train=True)
    assert global_stats.summary()["hoisted_rows_kept_share"]["total"] - t == (
        pytest.approx(100.0 * bound / (32 * 64))
    )


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("reverse", [False, True], ids=["forward", "reverse"])
def test_kept_rows_take_and_put(n, reverse):
    """take gathers exactly the valid rows, shard by shard, in the rows'
    order; put is its inverse there and zero elsewhere; HoistedRows with
    positions crosses jit and folds / unfolds through them."""
    t, b = 5, 8
    order = rg._HoistRows(t, b, _mesh(n))
    lens = jnp.asarray([5, 1, 2, 3, 0, 4, 2, 1], jnp.int32)
    tpos = jnp.arange(t)[:, None]
    valid = tpos >= (t - lens[None]) if reverse else tpos < lens[None]
    # one fill row past the fullest shard's tokens
    bound = int(np.asarray(lens).reshape(n, -1).sum(1).max()) + 1
    pos = order.kept(valid, bound)
    assert pos.shape == (n, bound)
    x = jnp.arange(t * b * 2, dtype=jnp.float32).reshape(t, b, 2) + 1.0
    rows = order.fold(x)
    kept = order.take(rows, pos)
    m = np.asarray(order.fold(valid)).reshape(n, -1)
    for s in range(n):
        want = np.asarray(rows).reshape(n, -1, 2)[s][m[s]]
        got = np.asarray(kept).reshape(n, -1, 2)[s]
        np.testing.assert_array_equal(got[: len(want)], want)
    back = order.put(kept, pos)
    np.testing.assert_array_equal(
        back, np.where(np.asarray(order.fold(valid))[:, None], rows, 0.0)
    )
    hoisted = rg.HoistedRows(kept, order, reverse, pos)
    assert len(jax.tree_util.tree_leaves(hoisted)) == 2
    out = jax.jit(lambda h: h)(hoisted)
    per_token = jnp.arange(1, b * t + 1, dtype=jnp.int32).reshape(b, t)
    unfolded = np.asarray(out.unfold(out.fold(per_token)))
    mask_bt = np.asarray(jnp.swapaxes(jnp.flip(valid, 0) if reverse else valid, 0, 1))
    np.testing.assert_array_equal(unfolded, np.where(mask_bt, per_token, 0))


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_feeder_bound_covers_every_shard(shards):
    """The bound covers the largest shard's tokens, is a rung (a multiple of
    ceil(T*B/n/16), rounded up to 128) and leaves rows out; one (T, B)
    gives at most 16 bounds."""
    from paddle_tpu.core.batch import row_bound

    rng = np.random.RandomState(shards)
    t, b = 64, 256
    block = t * b // shards
    unit = -(-block // 16)
    seen = set()
    for _ in range(200):
        lens = rng.randint(0, t + 1, b)
        lens[rng.rand(b) < rng.rand()] = rng.randint(0, t + 1)
        bound = row_bound(lens, t, shards)
        counts = np.minimum(lens, t).reshape(shards, -1).sum(1)
        if bound is None:
            rung = -(-max(-(-counts.max() // unit), 1) * unit // 128) * 128
            assert rung >= block
            continue
        assert (bound.batch, bound.shards) == (b, shards)
        assert counts.max() <= bound.rows < block
        assert bound.rows % 128 == 0
        assert bound.rows - 128 < -(-counts.max() // unit) * unit or bound.rows == 128
        seen.add(bound.rows)
    assert seen and len(seen) <= 16


def test_feeder_bound_of_the_nmt_mix():
    """512 targets of 8-50 tokens spread evenly and padded to 56: 14.8k
    tokens in 28,672 rows, bound 16,128 (56.25%); four shards of such
    batches keep the same bound, and a batch size no shard count divides
    falls back to one shard."""
    from paddle_tpu.core.batch import row_bound

    lens = 8 + (np.arange(512) * 43) // 512
    assert row_bound(lens, 56).rows == 16128
    rng = np.random.RandomState(0)
    four = np.concatenate([rng.permutation(lens) for _ in range(4)])
    assert row_bound(four, 56, 4) == (2048, 4, 16128)
    assert row_bound(lens[:510], 56, 4).shards == 1


def test_trainer_feeds_the_bound_only_where_a_group_reads_it():
    """The trainer's feeder bounds the slot a hoisted epilogue scans; a
    network without one gets batches with no bound and the same shape key
    as before."""
    from paddle_tpu.core.batch import batch_shape_key

    def feeder(cost, feeding):
        trainer = paddle.trainer.SGD(
            cost=cost, parameters=paddle.parameters.create(cost, seed=0),
            update_equation=paddle.optimizer.Momentum(learning_rate=0.1),
        )
        return trainer.network.row_bound_slots(), trainer._make_feeder(feeding)

    rng = np.random.RandomState(2)
    pairs = [
        (list(rng.randint(2, 29, 9)), list(rng.randint(2, 29, k)),
         list(rng.randint(2, 29, k)))
        for k in [40] + [3] * 63
    ]
    slots, feed = feeder(_seq2seq_cost(), {"src_word": 0, "trg_word": 1, "trg_next": 2})
    assert slots == ("trg_word",)
    fed = feed(pairs)
    assert fed["trg_word"].row_bound == (64, 1, 384)
    assert fed["src_word"].row_bound is None and fed["trg_next"].row_bound is None
    assert dict((k[0], k) for k in batch_shape_key(fed))["trg_word"][3] == (64, 1, 384)

    reset_auto_names()
    paddle.init(seed=3)
    x = L.data("x", paddle.data_type.integer_value_sequence(29))
    g = paddle.networks.gru_group(L.embedding(x, size=12), size=4, name="cls_gru")
    out = L.fc(L.last_seq(input=g), size=3, act=A.Softmax())
    lab = L.data("y", paddle.data_type.integer_value(3))
    slots, feed = feeder(L.classification_cost(input=out, label=lab), {"x": 0, "y": 1})
    assert slots == ()
    fed = feed([(p[1], 1) for p in pairs])
    assert all(v.row_bound is None for v in fed.values())
    assert all(len(entry) == 3 for entry in batch_shape_key(fed))


def test_inference_takes_all_rows():
    """Inference feeds no bound: the group runs its epilogue on every row."""
    from paddle_tpu.inference import Inference

    cost = _mesh_group()
    group = next(lo for lo in cost.parents if lo.conf.type == "recurrent_group")
    inferer = Inference(output_layer=group, parameters=paddle.parameters.create(cost))
    rng = np.random.RandomState(1)
    before = _hoist_counts()
    (outs,) = list(inferer.iter_infer(input=[(list(rng.randint(0, 29, k)),) for k in (5, 2)]))
    after = _hoist_counts()
    assert after[0] == before[0] and after[1] > before[1]
    assert outs["mg"].data.shape[0] == 2


@pytest.mark.parametrize("shards", [1, 4])
def test_pass_cache_row_shuffle_keeps_only_a_one_shard_bound(shards):
    """Rows that change places change shards: a replayed, row-shuffled batch
    keeps a bound over one shard (the total is the same) and drops one over
    several."""
    from paddle_tpu.core.batch import row_bound
    from paddle_tpu.reader.pass_cache import _permute_rows

    lens = np.asarray([3, 1, 2, 4, 1, 1, 2, 1] * 8, np.int32)
    bound = row_bound(lens, 64, shards)
    assert bound is not None
    batch = {"x": SeqTensor(jnp.zeros((64, 64), jnp.int32), jnp.asarray(lens), row_bound=bound)}
    out = _permute_rows(batch, jnp.arange(64)[::-1])
    assert out["x"].row_bound == (bound if shards == 1 else None)
    np.testing.assert_array_equal(out["x"].lengths, lens[::-1])
