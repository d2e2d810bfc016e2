"""What granite-4.0-h-micro brought to the hybrid decoder (models/hybrid_lm.py),
CPU, seeded weights, tiny widths: the whole model through `trainer.SGD`
against the benchmark's reference; the tied head's one parameter and its
summed gradient; recomputation of distinct layers (`layers.recompute`) on and
off; the dense gated MLP letter; the attention scale on the dense path; and,
with every new argument at its default, Nemotron's step as the parent lowered
it."""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import layers as L
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.core.topology import Topology, reset_auto_names
from paddle_tpu.models.hybrid_lm import hybrid_lm_cost
from paddle_tpu.trainer.step import make_train_step
from paddle_tpu.utils.timers import global_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = paddle.activation
V, D = 40, 16


@pytest.fixture(autouse=True)
def _fresh_names():
    reset_auto_names()


@pytest.fixture
def harness():
    sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
    import refsteps
    import run

    yield run, refsteps
    del sys.path[:2]


def _rows(lens=(7, 4), seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (len(lens), max(lens) + 1)).astype(np.int32)
    n = jnp.asarray(lens, jnp.int32)
    return {"word": SeqTensor(jnp.asarray(ids[:, :-1]), n), "next_word": SeqTensor(jnp.asarray(ids[:, 1:]), n)}


def _granite(pattern="MG*G", recompute=False, **kw):
    reset_auto_names()
    kw = dict(dict(embedding_multiplier=12.0, residual_multiplier=0.22, attention_multiplier=1 / 64,
                   logits_scaling=8.0, tie_embeddings=True), **kw)
    cost, _ = hybrid_lm_cost(
        pattern, V, D, mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=4, chunk_size=4,
        attn_heads=4, attn_kv_heads=2, attn_head_dim=4, mlp_hidden=24, recompute=recompute, **kw)
    return CompiledNetwork(Topology([cost]))


def _loss(net, batch, train=True):
    return lambda p: jnp.sum(net.apply(p, batch, train=train)[0]["lm_cost"].data)


# -- the whole model against the benchmark's reference --------------------------

@pytest.mark.parametrize("seed", [5, 2147483659])
def test_granite_trains_as_the_plain_reference_does(harness, seed):
    """Layers M and A each followed by the gated MLP, every multiplier as
    published, tied head, every layer recomputed; float32 on both sides, two
    steps of `trainer.SGD.train` on ragged rows: each step's loss, the first
    gradient of every leaf and every leaf's change are the reference's (which
    computes the scan in its quadratic form one head at a time, repeats no
    head and recomputes nothing of the program's)."""
    run, refsteps = harness
    _, cell, cfg, mix, _ = run.load_cell("granite-micro-train-2k", rehearsal=True)
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == ["mamba", "attention", "mamba"]
    cfg, mix = dict(cfg, compute_dtype="float32"), dict(mix, checked_steps=2)
    got = run.program_readings(cell, cfg, mix, seed=seed)
    ref = run.reference_readings(cell, cfg, mix, seed=seed)
    assert len(got["losses"]) == 2 and "embed" in got["grad"] and "out.w" not in got["grad"]
    numbers, _ = refsteps.compare(got, ref)
    assert numbers["loss_gap"] < 2e-6
    assert numbers["grad_norm_gap"] < 2e-5
    assert numbers["change_norm_gap"] < 5e-5
    assert numbers["grad_diff"] < 5e-5


# -- the tied head ---------------------------------------------------------------

def _head_net(tied):
    reset_auto_names()
    word = L.data("word", paddle.data_type.integer_value_sequence(V))
    nxt = L.data("next_word", paddle.data_type.integer_value_sequence(V))
    emb = L.embedding(word, size=D, param_attr=paddle.attr.ParamAttr(name="embed") if tied else None,
                      name="embed")
    h = L.fc(emb, size=D, act=A.Tanh(), bias_attr=False, name="mix")
    if tied:
        logits = L.tied_output(h, emb, act=A.Softmax(), name="head")
    else:
        logits = L.fc(h, size=V, act=A.Softmax(), bias_attr=False, name="head")
    return CompiledNetwork(Topology([L.classification_cost(input=logits, label=nxt, name="lm_cost")]))


def test_the_tied_heads_gradient_is_the_sum_of_the_embeddings_and_the_heads():
    """ONE [V, D] leaf, stored at the embedding: the loss is the untied
    model's with the head's matrix set to the table transposed, and its
    gradient is the embedding's gradient plus the head's, transposed."""
    tied, untied = _head_net(True), _head_net(False)
    p_tied = tied.init_params(jax.random.PRNGKey(0))
    assert sorted(p_tied) == ["embed", "mix"] and p_tied["embed"]["w"].shape == (V, D)
    assert tied.named_parameters() == {"embed": "embed"}
    table = p_tied["embed"]["w"]
    p_untied = {"embed": {"w": table}, "mix": p_tied["mix"], "head": {"w0": table.T}}
    batch = _rows()
    loss_t, g_t = jax.value_and_grad(_loss(tied, batch))(p_tied)
    loss_u, g_u = jax.value_and_grad(_loss(untied, batch))(p_untied)
    np.testing.assert_allclose(loss_t, loss_u, rtol=1e-6)
    both = g_u["embed"]["w"] + g_u["head"]["w0"].T
    assert float(jnp.linalg.norm(g_u["head"]["w0"])) > 0.1 * float(jnp.linalg.norm(both))
    np.testing.assert_allclose(g_t["embed"]["w"], both, rtol=1e-5, atol=1e-7)


def test_a_tied_output_wants_a_named_embedding():
    word = L.data("word", paddle.data_type.integer_value_sequence(V))
    emb = L.embedding(word, size=D, name="embed")
    with pytest.raises(ValueError, match="named parameter"):
        L.tied_output(emb, emb)


# -- recomputation of distinct layers ------------------------------------------

def _flatten_units(params):
    """`l<i>/<inner>/<key>` -> `<inner>/<key>`: the recompute units' trees as
    the plain build holds them."""
    flat = {}
    for name, sub in params.items():
        if re.fullmatch(r"l\d+", name):
            flat.update(sub)
        else:
            flat[name] = sub
    return flat


def test_recomputation_on_and_off_gives_the_same_loss_and_gradients():
    """Each published layer a `recompute` unit (its parameters nested under
    `l<i>/`), against the same layers built plainly with the same weights."""
    plain, units = _granite(recompute=False), _granite(recompute=True)
    p_units = units.init_params(jax.random.PRNGKey(1))
    assert sorted(p_units) == ["embed", "final_norm", "l0", "l1"]
    assert sorted(p_units["l1"]) == ["l1_attn", "l1_mlp", "l1_mlp_norm", "l1_norm"]
    p_plain = _flatten_units(p_units)
    batch = _rows()
    loss_u, g_u = jax.value_and_grad(_loss(units, batch))(p_units)
    loss_p, g_p = jax.value_and_grad(_loss(plain, batch))(p_plain)
    np.testing.assert_allclose(loss_u, loss_p, rtol=1e-6)
    g_u = _flatten_units(g_u)
    for path, got in jax.tree_util.tree_leaves_with_path(g_u):
        want = g_p
        for k in path:
            want = want[k.key]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6 * float(jnp.max(jnp.abs(want))),
                                   err_msg=jax.tree_util.keystr(path))


def test_a_training_trace_recomputes_each_layer_and_names_the_recomputed_forward():
    """`recomputed_layers` counts a unit a training trace (none where nothing
    is differentiated), and the forward run again on the way back carries
    jax's `rematted_computation` inside the unit's `recompute:l<i>` scope,
    the MLP letter its own `gated_mlp:l<i>_mlp`: what `recompute_share` and
    `gated_mlp_share` read from the device trace."""
    net = _granite(recompute=True)
    params = net.init_params(jax.random.PRNGKey(1))
    batch = _rows()
    before = global_stats.count("recomputed_layers")
    jax.eval_shape(_loss(net, batch, train=False), params)
    assert global_stats.count("recomputed_layers") == before
    text = jax.jit(jax.grad(_loss(net, batch))).lower(params).as_text(debug_info=True)
    assert global_stats.count("recomputed_layers") == before + 2
    names = set(re.findall(r'loc\("(jit\([^"]*)"', text))
    rematted = {n for n in names if "rematted_computation" in n}
    assert any("recompute:l0" in n and "mamba2:l0_mamba" in n for n in rematted)
    assert any("recompute:l1" in n and "gated_mlp:l1_mlp" in n for n in rematted)
    assert all("recompute:" in n for n in rematted)
    assert any("gated_mlp:l0_mlp" in n and "rematted_computation" not in n for n in names)


def test_a_step_that_cannot_be_recomputed_is_refused_in_words():
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(D))
    with pytest.raises(ValueError, match="recompute .*width"):
        L.recompute(lambda x: L.fc(x, size=D + 1), x_in)


# -- the gated MLP letter, the pattern ------------------------------------------

def test_the_gated_mlp_is_silu_of_the_first_half_times_the_second():
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(D))
    net = CompiledNetwork(Topology([L.gated_mlp(x_in, hidden=24, name="mlp")]))
    p = net.init_params(jax.random.PRNGKey(2))["mlp"]
    assert p["w_in"].shape == (D, 48) and p["w_out"].shape == (24, D)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, D))
    got = net.apply({"mlp": p}, {"x": SeqTensor(x, jnp.asarray([5, 3]))}, train=False)[0]["mlp"].data
    a, b = x @ p["w_in"][:, :24], x @ p["w_in"][:, 24:]
    np.testing.assert_allclose(got, (jax.nn.silu(a) * b) @ p["w_out"], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("pattern,layers", [("MG*G", ["MG", "*G"]), ("MEM*E", ["M", "E", "M", "*", "E"]),
                                            ("GMG", ["G", "MG"])])
def test_a_mixer_and_the_mlp_after_it_are_one_layer(pattern, layers):
    reset_auto_names()
    cost, _ = hybrid_lm_cost(
        pattern, V, D, mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=4, chunk_size=4,
        attn_heads=4, attn_kv_heads=2, attn_head_dim=4, num_experts=4, experts_per_token=2,
        expert_hidden=8, shared_hidden=8, mlp_hidden=24, recompute=True)
    units = [c for c in Topology([cost]).layers.values() if c.type == "recompute"]
    assert [c.name for c in units] == [f"l{i}" for i in range(len(layers))]
    letters = {"mamba2": "M", "multi_head_attention": "*", "moe_topk": "E", "gated_mlp": "G"}
    got = ["".join(letters[s.type] for s in c.attrs["_sub_topology"].layers.values() if s.type in letters)
           for c in units]
    assert got == layers


def test_a_g_needs_its_width():
    with pytest.raises(ValueError, match="mlp_hidden"):
        hybrid_lm_cost("MG", V, D, mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=4,
                       attn_heads=4, attn_kv_heads=2, attn_head_dim=4)


# -- the attention scale --------------------------------------------------------

@pytest.mark.parametrize("scale", [None, 1 / 64, 0.3])
def test_the_attention_scale_multiplies_the_scores_on_the_dense_path(scale):
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(D))
    att = L.multi_head_attention(x_in, n_heads=4, n_kv_heads=2, head_dim=4, causal=True, bias_attr=False,
                                 scale=scale, name="att")
    net = CompiledNetwork(Topology([att]))
    p = net.init_params(jax.random.PRNGKey(4))["att"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 6, D))
    got = net.apply({"att": p}, {"x": SeqTensor(x, jnp.asarray([6]))}, train=False)[0]["att"].data
    q = (x @ p["wq"]).reshape(1, 6, 2, 2, 4)
    k, v = ((x @ p[w]).reshape(1, 6, 2, 4) for w in ("wk", "wv"))
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * (0.5 if scale is None else scale)
    s = jnp.where(jnp.tril(jnp.ones((6, 6), bool)), s, -jnp.inf)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v).reshape(1, 6, 16)
    np.testing.assert_allclose(got, o @ p["wo"], rtol=1e-5, atol=1e-6)


def test_the_ring_refuses_a_scale_it_does_not_apply():
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(D))
    att = L.multi_head_attention(x_in, n_heads=4, seq_parallel_axis="seq", scale=0.1, name="att")
    net = CompiledNetwork(Topology([att]))
    p = net.init_params(jax.random.PRNGKey(4))
    with pytest.raises(ValueError, match="ring"):
        net.apply(p, {"x": SeqTensor(jnp.ones((1, 4, D)), jnp.asarray([4]))}, train=False)


# -- the defaults leave the accepted model as it was -----------------------------

# nemotron-train-2k's rehearsal step at the parent commit (PR 39's tree), read by
# this test's own fingerprint: the parameter tree, the topology's text, the
# name stacks of the lowered step (its `type:name` scopes) and the StableHLO
# text without locations.  The text is the one lowered since the Mamba-2
# layer adds its skip term D x on the flat [B, T, H x P] rows (the same
# values; on the chip it keeps x and y out of a transposed layout around the
# scan's kernels); the other three are as they were
_NEMOTRON_AT_THE_PARENT = {
    "params": "219f45103aca5adfddfc9418d39f7668d844978cca00869a8619a2ef157f0ade",
    "topology": "aa6d89366d3273c0673c3015f6a4ee0251a96af60647901f655f4e1cbab847bc",
    "scopes": "50c025658230f6143def8e5a93cf319341fadd7fa6354e2ac9a9a15e8faa0eae",
    "lowered": "3c077216e408a009d2b903b67d7237d6e1400f99327a1cbe0355d9cb8ab53445",
}


def test_the_defaults_leave_nemotrons_step_as_the_parent_lowered_it(harness):
    run, refsteps = harness
    _, _, cfg, _, _ = run.load_cell("nemotron-train-2k", rehearsal=True)
    cost, _ = refsteps.load_by_name("models", cfg["model"]).build(cfg)
    topo = Topology([cost])
    net = CompiledNetwork(topo, compute_dtype=jnp.bfloat16)
    opt = paddle.optimizer.Adam(learning_rate=1e-4)
    params, state = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    ids = SeqTensor(jax.ShapeDtypeStruct((2, 16), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.int32))
    args = (params, state, jax.eval_shape(opt.init, params), {"word": ids, "next_word": ids},
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    low = make_train_step(net, opt).trace(*args).lower()
    tree = sorted((jax.tree_util.keystr(k), v.shape) for k, v in jax.tree_util.tree_leaves_with_path(params))
    names = sorted(set(re.findall(r'loc\("(jit\([^"]*)"', low.as_text(debug_info=True))))
    digest = lambda s: hashlib.sha256(s.encode()).hexdigest()
    assert {"params": digest(repr(tree)), "topology": digest(topo.serialize()),
            "scopes": digest("\n".join(names)), "lowered": digest(low.as_text())} == _NEMOTRON_AT_THE_PARENT
