"""The looped decoder (models/looped_lm.py) and what it is built from, CPU,
float32, seeded weights, toy widths: `layer_loop` against hand-unrolled
applications of its sub-network, the rotary position code, the exit
distribution and the expected loss over the passes, `Silu`, and the whole
model through `trainer.SGD` against the benchmark's plain reference."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import layers as L
from paddle_tpu.core.batch import SeqTensor, seq as mkseq
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.core.topology import Topology, reset_auto_names
from paddle_tpu.layers.attention import rotary
from paddle_tpu.layers.cost import exit_distribution
from paddle_tpu.models.looped_lm import looped_lm_cost
from paddle_tpu.utils.timers import global_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = paddle.activation
VOCAB, HIDDEN, LAYERS, PASSES, HEADS, HEAD_DIM, MLP = 50, 16, 2, 3, 2, 8, 24


@pytest.fixture(autouse=True)
def _fresh_names():
    reset_auto_names()


def _model(passes=PASSES, beta=0.05, dtype=jnp.float32):
    reset_auto_names()
    cost, logits = looped_lm_cost(VOCAB, HIDDEN, LAYERS, passes, HEADS, HEAD_DIM, MLP, exit_beta=beta)
    net = CompiledNetwork(Topology([cost]), compute_dtype=dtype)
    params, state = net.init(jax.random.PRNGKey(3))
    return net, params, state, cost


def _rows(lens=(7, 4), seed=0):
    rng = np.random.default_rng(seed)
    t = max(lens)
    ids = rng.integers(0, VOCAB, (len(lens), t + 1)).astype(np.int32)
    n = np.asarray(lens, np.int32)
    return {"word": SeqTensor(jnp.asarray(ids[:, :-1]), jnp.asarray(n)),
            "next_word": SeqTensor(jnp.asarray(ids[:, 1:]), jnp.asarray(n))}


# -- the loop ----------------------------------------------------------------

def _loop_net(n_steps):
    reset_auto_names()
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(HIDDEN))

    def step(x):
        h = L.rms_norm(x, name="norm")
        h = L.multi_head_attention(h, n_heads=HEADS, head_dim=HEAD_DIM, causal=True, bias_attr=False,
                                   rope_theta=1e4, name="attn")
        h = L.fc(h, size=HIDDEN, act=A.Silu(), bias_attr=False, name="mix")
        return L.addto([x, h], act=A.Identity(), bias_attr=False, name="res")

    loop = L.layer_loop(step, x_in, n_steps, name="ut")
    return CompiledNetwork(Topology([loop])), loop


@pytest.mark.parametrize("n_steps", [1, 2, 4])
def test_the_loop_is_its_sub_network_applied_n_times_with_the_same_weights(n_steps):
    """Forward, every pass's output and every gradient against a hand-unrolled
    build with no scan and no recomputation: the scan's transpose sums the
    weights' gradients over the passes, and running a pass's forward again on
    the way back changes no number beyond float32 rounding."""
    net, loop = _loop_net(n_steps)
    params, state = net.init(jax.random.PRNGKey(1))
    assert sorted(params["ut"]) == ["attn", "mix", "norm"]  # nested under the group's name
    x = mkseq(jax.random.normal(jax.random.PRNGKey(2), (2, 6, HIDDEN)), np.asarray([6, 4], np.int32))
    w = jax.random.normal(jax.random.PRNGKey(4), (n_steps, 2, 6, HIDDEN))
    sub = CompiledNetwork(loop.conf.attrs["_sub_topology"])

    def looped(p, data):
        outs, _ = net.apply(p, {"x": x.with_data(data)}, state=state, train=True)
        passes = outs["ut@passes"].data
        return jnp.sum(passes * w), (outs["ut"].data, passes)

    def unrolled(p, data):
        passes = []
        for _ in range(n_steps):
            data = sub.apply(p["ut"], {"ut@in": x.with_data(data)}, train=True)[0]["res"].data
            passes.append(data)
        return jnp.sum(jnp.stack(passes) * w), (data, jnp.stack(passes))

    (got, (last, passes)), grads = jax.value_and_grad(looped, argnums=(0, 1), has_aux=True)(params, x.data)
    (want, (last_u, passes_u)), grads_u = jax.value_and_grad(unrolled, argnums=(0, 1), has_aux=True)(params, x.data)
    assert passes.shape == (n_steps, 2, 6, HIDDEN)
    np.testing.assert_allclose(last, passes[-1])
    np.testing.assert_allclose(passes, passes_u, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_u)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(b))))


def test_the_loop_is_one_scan_over_one_traced_pass_and_counts_itself():
    net, _ = _loop_net(3)
    params, state = net.init(jax.random.PRNGKey(1))
    x = mkseq(jnp.ones((1, 4, HIDDEN)), np.asarray([4], np.int32))
    count = lambda: [global_stats.count(k) for k in ("loop_passes", "loop_recomputed_units")]
    before = count()
    jaxpr = jax.make_jaxpr(lambda p: net.apply(p, {"x": x}, state=state, train=True)[0]["ut"].data)(params)
    assert [a - b for a, b in zip(count(), before)] == [3, 3]
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 3
    # the scan's body is the recomputed unit, and the pass was traced once:
    # one attention layer's two rotations (q and k), not three passes' six
    assert [e.primitive.name for e in scans[0].params["jaxpr"].jaxpr.eqns] == ["remat2"]
    assert len(re.findall(r"\bcos\b", str(jaxpr))) == 2
    before = count()
    jax.eval_shape(lambda p: net.apply(p, {"x": x}, state=state, train=False)[0]["ut"].data, params)
    assert [a - b for a, b in zip(count(), before)] == [3, 0]  # nothing is recomputed where nothing is differentiated


@pytest.mark.parametrize("what,match", [
    ("width", "width"), ("data", "data layers"), ("memory", "memory"), ("steps", "n_steps"),
])
def test_a_step_that_cannot_be_looped_is_refused_in_words(what, match):
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(HIDDEN))
    other = paddle.layer.data("y", paddle.data_type.dense_vector_sequence(HIDDEN))

    def memory_step(x):
        return L.addto([x, L.memory(name="m", size=HIDDEN)], name="m")

    step = {"width": lambda x: L.fc(x, size=HIDDEN + 1),
            "data": lambda x: L.addto([x, other]),
            "memory": memory_step,
            "steps": lambda x: L.fc(x, size=HIDDEN)}[what]
    with pytest.raises(ValueError, match=match):
        L.layer_loop(step, x_in, 0 if what == "steps" else 2)


# -- the rotary position code --------------------------------------------------

def test_rotary_scores_depend_on_the_distance_alone():
    """The same q at every query position and the same k at every key
    position: q_i . k_j is then a function of i - j (a Toeplitz matrix), and
    position 0 is left as it was."""
    q = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 8)), (1, 9, 2, 8))
    k = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(1), (1, 1, 2, 8)), (1, 9, 2, 8))
    rq, rk = rotary(q, 100.0), rotary(k, 100.0)
    np.testing.assert_allclose(rq[:, 0], q[:, 0], rtol=1e-6)
    s = np.asarray(jnp.einsum("bqhd,bkhd->bhqk", rq, rk))
    np.testing.assert_allclose(s[..., 1:, 1:], s[..., :-1, :-1], rtol=1e-4, atol=1e-5)
    assert np.abs(s[..., 0, 1] - s[..., 0, 2]).max() > 1e-3  # and it does depend on it
    # a turn keeps a pair's norm
    np.testing.assert_allclose(jnp.linalg.norm(rq, axis=-1), jnp.linalg.norm(q, axis=-1), rtol=1e-5)


def _attention_net(rope_theta):
    reset_auto_names()
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(HIDDEN))
    m = L.multi_head_attention(x_in, n_heads=HEADS, head_dim=HEAD_DIM, causal=True, bias_attr=False,
                               rope_theta=rope_theta, name="att")
    return CompiledNetwork(Topology([m])), m


def test_no_rope_theta_lowers_to_the_operations_the_layer_had_before():
    """`rope_theta=None` is the attribute left out, as every layer built
    before it had it: the lowered text is the same and holds no sine."""
    net, m = _attention_net(None)
    params, state = net.init(jax.random.PRNGKey(0))
    x = mkseq(jnp.ones((2, 8, HIDDEN)), np.asarray([8, 5], np.int32))
    lower = lambda n: jax.jit(lambda p: n.apply(p, {"x": x}, state=state, train=True)[0]["att"].data).lower(params).as_text()
    before = dataclasses.replace(m.conf, attrs={k: v for k, v in m.conf.attrs.items() if k != "rope_theta"})
    old = CompiledNetwork(Topology([paddle.core.topology.LayerOutput(before, m.parents)]))
    assert lower(net) == lower(old) and "sine" not in lower(net)
    assert "sine" in lower(_attention_net(1e4)[0])


def test_the_dense_core_and_the_blocked_kernels_agree_with_rope_on(monkeypatch):
    """Rotary turns q and k before the core, so both cores take it unchanged:
    the blocked kernels (interpret mode) against the dense path, forward and
    gradients, 128 keys."""
    from paddle_tpu.ops import pallas_attention as fa

    net, _ = _attention_net(1e4)
    params, state = net.init(jax.random.PRNGKey(0))
    x = mkseq(jax.random.normal(jax.random.PRNGKey(1), (2, 128, HIDDEN)), np.asarray([128, 100], np.int32))

    def run(p):
        out = net.apply(p, {"x": x}, state=state, train=True)[0]["att"]
        return jnp.sum(jnp.square(out.data * out.mask(jnp.float32)[..., None]))

    dense, g_dense = jax.value_and_grad(run)(params)
    real = fa.flash_attention_diff
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(paddle.utils.flags, "get_flag",
                        lambda name, real=paddle.utils.flags.get_flag: True if name == "use_pallas_attention" else real(name))
    monkeypatch.setattr(fa, "flash_attention_diff", lambda *a: real(*a[:-1], True))
    before = global_stats.count("attention_blocked_layers")
    blocked, g_blocked = jax.value_and_grad(run)(params)
    assert global_stats.count("attention_blocked_layers") == before + 1
    np.testing.assert_allclose(blocked, dense, rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g_blocked), jax.tree_util.tree_leaves(g_dense)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4 * float(jnp.max(jnp.abs(b))))


def test_silu_is_an_activation_of_the_dsl():
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector(4))
    net = CompiledNetwork(Topology([L.fc(x_in, size=3, act=A.Silu(), bias_attr=False, name="f")]))
    params, state = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 4))
    y = x @ params["f"]["w0"]
    got = net.apply(params, {"x": SeqTensor(x)}, state=state, train=False)[0]["f"].data
    np.testing.assert_allclose(got, y * jax.nn.sigmoid(y), rtol=1e-6)


# -- the exit distribution and the expected loss --------------------------------

@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest(passes):
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(passes), (passes, 2, 5))
    p = np.asarray(jnp.exp(exit_distribution(z)))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    g = np.asarray(jax.nn.sigmoid(z))
    stay = np.cumprod(1.0 - g, axis=0)
    if passes > 1:
        np.testing.assert_allclose(p[0], g[0], rtol=1e-5)
        np.testing.assert_allclose(p[-1], stay[-2], rtol=1e-5)  # whatever the gate says of the last pass
    for t in range(1, passes - 1):
        np.testing.assert_allclose(p[t], g[t] * stay[t - 1], rtol=1e-5)
    # a gate that is shut hard leaves no NaN behind: log(1 - g) is log_sigmoid(-z)
    far = exit_distribution(jnp.full((passes, 1), 200.0))
    assert np.all(np.isfinite(np.asarray(jnp.exp(far)))) and not np.any(np.isnan(np.asarray(far)))


def _plain_ce(params, passes_out, batch, t):
    """Row sums of -log softmax(x^t W_out)[next] over the row's true tokens."""
    logp = jax.nn.log_softmax(passes_out[t] @ params["lm_out"]["w0"], axis=-1)
    nll = -jnp.take_along_axis(logp, batch["next_word"].data[..., None], axis=-1)[..., 0]
    return nll * batch["word"].mask(jnp.float32)


@pytest.mark.parametrize("bias,taken", [(-30.0, PASSES - 1), (30.0, 0)])
def test_a_shut_gate_gives_the_last_passs_cross_entropy_and_an_open_one_the_firsts(bias, taken):
    net, params, state, cost = _model(beta=0.0)
    params = dict(params, exit_gate=dict(params["exit_gate"], b=jnp.full((1,), bias)))
    batch = _rows()
    outs, _ = net.apply(params, batch, state=state, train=True)
    want = jnp.sum(_plain_ce(params, outs["ut@passes"].data, batch, taken), axis=1)
    np.testing.assert_allclose(outs[cost.name].data[:, 0], want, rtol=1e-5)
    p = np.asarray(outs["lm_cost@exit_p"].data)
    assert p.shape == (2, PASSES) and np.allclose(p[:, taken], 1.0, atol=1e-6)


def test_the_aux_outputs_are_the_rows_means_and_padding_costs_nothing():
    net, params, state, cost = _model(beta=0.05)
    batch = _rows(lens=(7, 4))
    outs, _ = net.apply(params, batch, state=state, train=True)
    pass_ce, exit_p = np.asarray(outs["lm_cost@pass_ce"].data), np.asarray(outs["lm_cost@exit_p"].data)
    assert pass_ce.shape == exit_p.shape == (2, PASSES)
    np.testing.assert_allclose(exit_p.sum(axis=1), 1.0, rtol=1e-5)
    lens = np.asarray([7.0, 4.0])
    for t in range(PASSES):
        want = np.asarray(jnp.sum(_plain_ce(params, outs["ut@passes"].data, batch, t), axis=1)) / lens
        np.testing.assert_allclose(pass_ce[:, t], want, rtol=1e-5)
    # the whole cost by hand, from the gate's logits
    x = outs["ut@passes"].data
    z = (x @ params["exit_gate"]["w0"])[..., 0] + params["exit_gate"]["b"][0]
    log_p = exit_distribution(z)
    ce = jnp.stack([_plain_ce(params, x, batch, t) for t in range(PASSES)])
    token = jnp.sum(jnp.exp(log_p) * (ce + 0.05 * log_p), axis=0) * batch["word"].mask(jnp.float32)
    np.testing.assert_allclose(outs[cost.name].data[:, 0], jnp.sum(token, axis=1), rtol=1e-5)
    # other ids in the second row's padding: not one number of its cost moves
    def padded(b, fill):
        word = b["word"].data.at[1, 4:].set(fill)
        return {k: SeqTensor(word if k == "word" else v.data.at[1, 4:].set(fill), v.lengths) for k, v in b.items()}
    a = net.apply(params, padded(batch, 3), state=state, train=True)[0][cost.name].data
    b = net.apply(params, padded(batch, 11), state=state, train=True)[0][cost.name].data
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


@pytest.mark.parametrize("what", ["unnamed_head", "biased_head", "wide_gate", "not_a_loop"])
def test_the_cost_refuses_a_head_or_a_gate_it_cannot_share_weights_with(what):
    named = paddle.attr.ParamAttr
    word = paddle.layer.data("word", paddle.data_type.integer_value_sequence(VOCAB))
    nxt = paddle.layer.data("next_word", paddle.data_type.integer_value_sequence(VOCAB))
    emb = L.embedding(word, size=HIDDEN)
    x = L.layer_loop(lambda h: L.fc(h, size=HIDDEN, bias_attr=False), emb, 2)
    head = L.fc(x, size=VOCAB, act=A.Softmax(), bias_attr=(what == "biased_head"),
                param_attr=None if what == "unnamed_head" else named(name="h.w"))
    gate = L.fc(x, size=2 if what == "wide_gate" else 1, act=A.Sigmoid(), param_attr=named(name="g.w"),
                bias_attr=named(name="g.b"))
    with pytest.raises(ValueError, match="looped_exit_cost"):
        L.looped_exit_cost(emb if what == "not_a_loop" else x, head=head, gate=gate, label=nxt)


def test_the_head_and_the_gate_own_the_weights_the_cost_reads():
    """No second copy: the cost layer holds no parameter of its own, and a
    gradient reaches `lm_out/w0` and `exit_gate/*` through it."""
    net, params, state, cost = _model()
    assert "lm_cost" not in params and sorted(params) == ["embed", "exit_gate", "lm_out", "ut"]
    g = jax.grad(lambda p: net.cost(p, _rows(), state=state)[0])(params)
    for leaf in (g["lm_out"]["w0"], g["exit_gate"]["w0"], g["exit_gate"]["b"], g["ut"]["l0_gate"]["w0"]):
        assert float(jnp.max(jnp.abs(leaf))) > 0.0


# -- the whole model through trainer.SGD against the benchmark's reference ----

@pytest.fixture
def harness():
    sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
    import refsteps
    import run

    yield run, refsteps
    del sys.path[:2]


@pytest.mark.parametrize("seed", [5, 2147483659])
def test_looped_lm_trains_as_the_plain_reference_does(harness, seed):
    """2 layers, 3 passes at toy widths, float32 on both sides, two steps of
    `trainer.SGD.train` on ragged rows: each step's loss, the first gradient
    of every leaf (to 1e-5 of its norm) and every leaf's change are the
    reference's, which unrolls the layers in Python, keeps explicit cos/sin
    tables and dense scores, and recomputes layer by layer."""
    run, refsteps = harness
    _, cell, cfg, mix, _ = run.load_cell("ouro-train-2k", rehearsal=True)
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"]) == (2, 3)
    cfg, mix = dict(cfg, compute_dtype="float32"), dict(mix, checked_steps=2)
    got = run.program_readings(cell, cfg, mix, seed=seed)
    ref = run.reference_readings(cell, cfg, mix, seed=seed)
    assert len(got["losses"]) == 2
    numbers, _ = refsteps.compare(got, ref)
    assert numbers["loss_gap"] < 2e-6
    assert numbers["grad_norm_gap"] < 1e-5
    assert numbers["change_norm_gap"] < 2e-5
    assert numbers["grad_diff"] < 1e-5


@pytest.mark.parametrize("fault", ["three_passes", "no_entropy_term"])
def test_the_references_planted_faults_change_its_readings(harness, fault):
    run, refsteps = harness
    _, cell, cfg, mix, _ = run.load_cell("ouro-train-2k", rehearsal=True)
    mix = dict(mix, checked_steps=1)
    ref = run.reference_readings(cell, cfg, mix, seed=3)
    bad = run.reference_readings(cell, dict(cfg, reference_fault=fault), mix, seed=3)
    numbers, _ = refsteps.compare(bad, ref)
    assert numbers["loss_gap"] > 1e-3 and numbers["grad_diff"] > 1e-2
