"""recurrent_group's hoists under data parallelism (layers/recurrent_group.py
_HoistRows): the rows the hoisted prologue/epilogue run on never merge T
across the sharded batch axis, so XLA's partitioner has nothing to gather.

The counter of the mechanism is read from the compiled train step: all-gathers
whose result carries the whole batch (the parent gathered the scan's [T, B, H]
states, the masks and the labels onto every device).  Without a mesh the
lowered step must be, to the letter, the one the plain time-major reshapes
give."""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.topology import reset_auto_names
from paddle_tpu.parallel.mesh import make_mesh, shard_batch
from paddle_tpu.utils.flags import get_flag, set_flag

rg = importlib.import_module("paddle_tpu.layers.recurrent_group")

L = paddle.layer
A = paddle.activation

VOCAB = 23
B, T = 40, 6  # B is no other axis' size: a gathered batch shows in a shape


def _seq2seq():
    from paddle_tpu.models.seq2seq import seq2seq_cost

    return seq2seq_cost(VOCAB, VOCAB, word_dim=5, hidden_dim=4)[0]


def _gru_group_tagger():
    """gru_unit's 3H input projection hoists as a prologue; the per-step
    softmax head hoists as an epilogue."""
    x = L.data("src_word", paddle.data_type.integer_value_sequence(VOCAB))
    emb = L.embedding(x, size=12)

    def step(e_t):
        proj = L.fc(e_t, size=9, act=A.Identity(), bias_attr=False, name="gt_proj")
        h = paddle.networks.gru_unit(input=proj, size=3, name="gt_unit")
        return L.fc(h, size=VOCAB, act=A.Softmax(), name="gt_head")

    tags = L.recurrent_group(step, input=[emb], name="gt")
    lab = L.data("trg_next", paddle.data_type.integer_value_sequence(VOCAB))
    return L.classification_cost(input=tags, label=lab)


def _lstm_group_classifier():
    """An lstmemory_unit step under a pooled classifier: a prologue (the 4H
    input projection) and no epilogue; reverse, so the rows are folded from
    flipped inputs."""
    x = L.data("src_word", paddle.data_type.integer_value_sequence(VOCAB))
    emb = L.embedding(x, size=7)

    def step(e_t):
        proj = L.fc(e_t, size=12, act=A.Identity(), bias_attr=False, name="lg_proj")
        return paddle.networks.lstmemory_unit(input=proj, size=3, name="lg_unit")

    g = L.recurrent_group(step, input=[emb], reverse=True, name="lg")
    out = L.fc(L.first_seq(input=g), size=VOCAB, act=A.Softmax())
    lab = L.data("label", paddle.data_type.integer_value(VOCAB))
    return L.classification_cost(input=out, label=lab)


# builder, the fused attention-GRU flag, (prologue, epilogue) hoisted or not
MODELS = {
    "seq2seq_fused": (_seq2seq, True, (False, True)),
    "seq2seq_generic": (_seq2seq, False, (False, True)),
    "gru_group_tagger": (_gru_group_tagger, True, (True, True)),
    "lstm_group_classifier": (_lstm_group_classifier, True, (True, False)),
}


@pytest.fixture(params=sorted(MODELS))
def model(request):
    build, fused, _ = MODELS[request.param]
    old = get_flag("fused_attention_gru")
    set_flag("fused_attention_gru", fused)
    reset_auto_names()
    paddle.init(seed=11)
    yield build()
    set_flag("fused_attention_gru", old)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_models_hoist_what_they_claim(name):
    """The cases below mean something only where the hoists engage."""
    from paddle_tpu.core.topology import Topology

    reset_auto_names()
    build, _, want = MODELS[name]
    (g,) = [
        c for c in Topology([build()]).layers.values()
        if c.type == "recurrent_group"
    ]
    a = g.attrs
    static = a["_static_placeholders"]
    epi, _ = rg._split_epilogue(
        a["_sub_topology"], a["_memories"], a["_output"],
        {p for p, is_seq in static if is_seq},
    )
    pro = rg._split_prologue(
        a["_sub_topology"], a["_scan_placeholders"], static, epi or set()
    )
    assert (bool(pro), bool(epi)) == want


def _batch(b=B, t=T):
    rng = np.random.RandomState(4)
    ids = lambda: jnp.asarray(rng.randint(2, VOCAB, (b, t)), jnp.int32)  # noqa: E731
    lens = lambda: jnp.asarray(rng.randint(2, t + 1, b), jnp.int32).at[0].set(t)  # noqa: E731
    trg_lens = lens()
    return {
        "src_word": SeqTensor(ids(), lens()),
        "trg_word": SeqTensor(ids(), trg_lens),
        "trg_next": SeqTensor(ids(), trg_lens),
        "label": SeqTensor(jnp.asarray(rng.randint(0, VOCAB, b), jnp.int32)),
    }


def _trainer_and_args(cost, mesh, batch=None, lr=1e-3):
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=paddle.parameters.create(cost, seed=0),
        update_equation=paddle.optimizer.Momentum(learning_rate=lr, momentum=0.0),
        mesh=mesh,
    )
    slots = set(trainer.network.topology.data_layers())
    fed = {k: v for k, v in (batch or _batch()).items() if k in slots}
    return trainer, (
        trainer.parameters.params, trainer.parameters.state,
        trainer._opt_state, shard_batch(fed, mesh), jax.random.PRNGKey(0),
    )


def _mesh(n):
    return make_mesh(data=n, devices=jax.devices()[:n])


def _gathered_shapes(compiled_text):
    """Shapes that all-gathers of a compiled module produce, as dim tuples."""
    out = []
    for line in compiled_text.splitlines():
        m = re.search(r"= (.*?) all-gather(?:-start)?\(", line)
        if m:
            out += [
                tuple(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"[a-z]+[0-9]*\[([0-9,]*)\]", m.group(1))
            ]
    return out


@pytest.mark.parametrize("n", [2, 4])
def test_compiled_step_gathers_no_batch(model, n):
    """The mechanism's counter: activations gathered to the whole batch in
    the compiled data-parallel step (seq2seq on the parent: the [T, B, H]
    states, two [T, B] masks and the [T, B] labels; now none)."""
    trainer, args = _trainer_and_args(model, _mesh(n))
    text = trainer._train_step.lower(*args).compile().as_text()
    assert " all-reduce" in text  # it IS a partitioned step
    whole_batch = [s for s in _gathered_shapes(text) if B in s]
    assert not whole_batch, whole_batch


class _TimeMajorRows:
    """The hoists' row handling as it was before _HoistRows: plain
    time-major reshapes, no notion of a mesh."""

    def __init__(self, t, b, mesh):
        self.t, self.b = t, b

    def fold(self, d):
        return d.reshape((self.t * self.b,) + d.shape[2:])

    def tile(self, d):
        return jnp.broadcast_to(d[None], (self.t,) + d.shape).reshape(
            (self.t * d.shape[0],) + d.shape[1:]
        )

    def unfold(self, r):
        return r.reshape((self.t, self.b) + r.shape[1:])

    def unfold_batch_major(self, r, reverse):
        r = self.unfold(r)
        if reverse:
            r = jnp.flip(r, axis=0)
        return jnp.swapaxes(r, 0, 1)

    def fold_batch_major(self, d, reverse):
        d = jnp.swapaxes(d, 0, 1)
        if reverse:
            d = jnp.flip(d, axis=0)
        return self.fold(d)


@pytest.mark.parametrize("mesh_n", [None, 1], ids=["no_mesh", "data1"])
def test_one_shard_lowers_to_the_time_major_program(model, mesh_n, monkeypatch):
    """No mesh (or a data axis of one): the lowered step is letter for
    letter the program of the plain time-major reshapes."""
    mesh = None if mesh_n is None else _mesh(mesh_n)
    trainer, args = _trainer_and_args(model, mesh)
    ours = trainer._train_step.lower(*args).as_text()
    monkeypatch.setattr(rg, "_HoistRows", _TimeMajorRows)
    trainer, args = _trainer_and_args(model, mesh)
    assert trainer._train_step.lower(*args).as_text() == ours


@pytest.mark.parametrize("n", [2, 4])
def test_meshed_step_matches_single_device_step(model, n):
    """Cost and first gradients of the data-parallel step against the
    single-device step, float32 at precision=highest on both sides (plain
    SGD: the parameters' change after one step IS the gradient)."""
    lr = 0.5
    got = {}
    with jax.default_matmul_precision("highest"):
        for key, mesh in (("one", None), ("dp", _mesh(n))):
            trainer, args = _trainer_and_args(model, mesh, lr=lr)
            before = jax.tree_util.tree_map(np.asarray, args[0])
            params, _, _, metrics = trainer._train_step(*args)
            grads = jax.tree_util.tree_map(
                lambda a, b_: (a - np.asarray(b_)) / lr, before, params
            )
            got[key] = (float(metrics["cost"]), grads)
    np.testing.assert_allclose(got["dp"][0], got["one"][0], rtol=1e-5)
    one, dp = (jax.tree_util.tree_leaves(got[k][1]) for k in ("one", "dp"))
    assert any(np.abs(g).max() > 1e-4 for g in one)
    for a, b_ in zip(dp, one):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# The hoisted output layer's logits as rows (rg.HoistedRows): softmax-CE and
# the evaluator's argmax read the [T*B, V] rows where they lie, so the
# training step never views them as [B, T, V].
# ---------------------------------------------------------------------------

_RANK3_VOCAB = re.compile(
    rf"stablehlo\.(?:transpose|reshape)\b.*-> tensor<\d+x\d+x{VOCAB}x[a-z]"
)


def _rank3_vocab_views(lowered_text):
    """transposes and reshapes of a lowered step whose result has the
    vocabulary as the last of three axes: the [B, T, V] (or [T, B, V]) view
    of the hoisted rows and its cotangent's way back.  jax lowers what the
    step's outputs need and nothing else, so a view nobody reads is not in
    the text."""
    return [
        line.strip()[:160] for line in lowered_text.splitlines()
        if _RANK3_VOCAB.search(line)
    ]


@pytest.mark.parametrize("mesh_n", [None, 4], ids=["no_mesh", "data4"])
@pytest.mark.parametrize("name", ["seq2seq_fused", "gru_group_tagger"])
def test_lowered_step_never_views_the_rows_batch_major(name, mesh_n, monkeypatch):
    """A count that carries over to the chip: with cost layer and evaluator on
    the rows, the lowered training step holds no rank-3 view of the logits
    (on the chip each is a copy of the whole array, PERF.md PR 37); with the
    rows withheld it holds them, so the count reads what it says."""
    build, fused, _ = MODELS[name]
    mesh = None if mesh_n is None else _mesh(mesh_n)
    old = get_flag("fused_attention_gru")
    set_flag("fused_attention_gru", fused)
    try:
        texts = []
        for withheld in (False, True):
            if withheld:
                monkeypatch.setattr(rg, "HoistedRows", lambda *a: None)
            reset_auto_names()
            paddle.init(seed=11)
            trainer, args = _trainer_and_args(build(), mesh)
            texts.append(trainer._train_step.lower(*args).as_text())
    finally:
        set_flag("fused_attention_gru", old)
    assert _rank3_vocab_views(texts[0]) == []
    assert _rank3_vocab_views(texts[1])


@pytest.mark.parametrize("n", [2, 4])
def test_quantized_allreduce_step_keeps_the_rows_inside_its_shards(n):
    """The quantized-allreduce step runs the network inside a shard_map and
    joins the shards' outputs along the batch; the rows' order is not the
    batch's, so they stay inside and the evaluator outside reads `@logits`:
    cost and classification_error are the plain data-parallel step's."""
    got = {}
    for quantized in (False, True):
        old = get_flag("quantized_allreduce")
        set_flag("quantized_allreduce", quantized)
        try:
            reset_auto_names()
            paddle.init(seed=11)
            trainer, args = _trainer_and_args(_seq2seq(), _mesh(n))
            metrics = trainer._train_step(*args)[3]
        finally:
            set_flag("quantized_allreduce", old)
        got[quantized] = {k: float(metrics[k]) for k in ("cost", "classification_error")}
    assert 0.0 < got[True]["classification_error"] <= 1.0
    np.testing.assert_allclose(got[True]["cost"], got[False]["cost"], rtol=1e-5)
    np.testing.assert_allclose(
        got[True]["classification_error"], got[False]["classification_error"], rtol=1e-6
    )
