"""Network-compare tests (reference test strategy: gserver/tests/
test_NetworkCompare.cpp + test_RecurrentLayer.cpp — two equivalent
configurations must produce identical outputs).  Here: the recurrent_group
compositions (gru_group / lstmemory_group) vs the fused single-scan layers
(grumemory / lstmemory) with tied parameters, on variable-length batches."""

import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import activation as A
from paddle_tpu.core.batch import SeqTensor, seq as mkseq
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.core.topology import Topology, reset_auto_names
from paddle_tpu.layers import networks
import paddle_tpu.layers as L

H = 6
B, T = 3, 5


LENS = np.asarray([T, 3, 1], np.int32)


def _var_len_batch(dim, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(B, T, dim).astype(np.float32)
    for i, n in enumerate(LENS):
        x[i, n:] = 0.0
    return mkseq(x, LENS)


def _assert_valid_close(a, b):
    """Compare only the VALID timesteps — the two forms differ in what they
    leave in padding (zeros vs carried state), which no downstream masked
    layer ever reads."""
    mask = (np.arange(T)[None, :] < LENS[:, None])[..., None]
    np.testing.assert_allclose(
        np.asarray(a) * mask, np.asarray(b) * mask, rtol=1e-5, atol=1e-6
    )


def _single_subparam(params, group_name):
    """The one param-bearing inner layer of a group's sub-topology."""
    sub = params[group_name]
    assert len(sub) == 1, f"expected one inner param layer, got {list(sub)}"
    return next(iter(sub.values()))


@pytest.mark.parametrize("reverse", [False, True])
def test_gru_group_matches_fused_grumemory(reverse):
    reset_auto_names()
    din = L.data("x", paddle.data_type.dense_vector_sequence(3 * H))
    fused = L.grumemory(din, size=H, reverse=reverse, name="fused")
    group = networks.gru_group(din, size=H, reverse=reverse, name="group")
    net = CompiledNetwork(Topology([fused, group]))
    params, state = net.init(jax.random.PRNGKey(0))

    # tie the group's step params (w_h [H,2H], w_c [H,H], b [3H]) to the
    # fused layer's — identical layout by design
    inner = _single_subparam(params, "group")
    for k in ("w_h", "w_c", "b"):
        inner[k] = params["fused"][k]

    batch = {"x": _var_len_batch(3 * H)}
    outs, _ = net.apply(params, batch, state=state, train=False)
    _assert_valid_close(outs["group"].data, outs["fused"].data)


@pytest.mark.parametrize("reverse", [False, True])
def test_lstm_group_matches_fused_lstmemory_without_peepholes(reverse):
    """lstmemory_group (mixed recurrence + weightless lstm_step) equals the
    fused lstmemory when the fused peepholes are zeroed (the reference
    lstm_step form has no peepholes — lstm_step_layer docs)."""
    reset_auto_names()
    din = L.data("x", paddle.data_type.dense_vector_sequence(4 * H))
    fused = L.lstmemory(din, size=H, reverse=reverse, name="fused")
    group = networks.lstmemory_group(din, size=H, reverse=reverse, name="group")
    net = CompiledNetwork(Topology([fused, group]))
    params, state = net.init(jax.random.PRNGKey(0))

    for k in ("w_ci", "w_cf", "w_co"):
        params["fused"][k] = np.zeros_like(params["fused"][k])
    # group inner layers: the mixed input_recurrent (p1_w = W_h) and the
    # lstm_step (b)
    sub = params["group"]
    mixed_name = [n for n in sub if "input_recurrent" in n][0]
    step_name = [n for n in sub if n != mixed_name][0]
    sub[mixed_name]["p1_w"] = params["fused"]["w_h"]
    sub[step_name]["b"] = params["fused"]["b"]

    batch = {"x": _var_len_batch(4 * H, seed=1)}
    outs, _ = net.apply(params, batch, state=state, train=False)
    _assert_valid_close(outs["group"].data, outs["fused"].data)


def test_simple_gru_matches_simple_gru2():
    """simple_gru (recurrent_group form) and simple_gru2 (fused form) are
    the same function of the same parameters (reference networks.py doc:
    'gru_memory ... does same calculation with gru_group')."""
    reset_auto_names()
    din = L.data("x", paddle.data_type.dense_vector_sequence(4))
    g1 = networks.simple_gru(din, size=H, name="a")
    g2 = networks.simple_gru2(din, size=H, name="b")
    net = CompiledNetwork(Topology([g1, g2]))
    params, state = net.init(jax.random.PRNGKey(0))

    params["b_transform"]["w0"] = params["a_transform"]["w0"]
    inner = _single_subparam(params, "a")
    for k in ("w_h", "w_c", "b"):
        params["b"][k] = inner[k]

    batch = {"x": _var_len_batch(4, seed=2)}
    outs, _ = net.apply(params, batch, state=state, train=False)
    _assert_valid_close(outs["a"].data, outs["b"].data)


def test_mixed_sharing_registries_cannot_cross():
    """A parameter name used both whole-layer (embedding) and per-key
    (fc/projection) must fail loudly at build, not silently diverge."""
    reset_auto_names()
    from paddle_tpu.attr import ParamAttr

    shared = ParamAttr(name="tied")
    ids = L.data("ids", paddle.data_type.integer_value_sequence(7))
    emb = L.embedding(ids, size=4, param_attr=shared)
    vec = L.data("v", paddle.data_type.dense_vector(7))
    fcw = L.fc(vec, size=4, param_attr=shared, bias_attr=False)
    with pytest.raises(ValueError, match="whole-layer"):
        CompiledNetwork(Topology([emb, fcw]))


# ---------------------------------------------------------------------------
# The reference's OWN NetworkCompare fixtures (gserver/tests/*.conf pairs,
# driver: test_NetworkCompare.cpp) — two config files that must compute the
# same function.  We parse both unmodified, tie parameters by signature,
# and require numerically equal outputs.
# ---------------------------------------------------------------------------

GSERVER = "/root/reference/paddle/gserver/tests"


def _param_dicts(tree):
    """Innermost param dicts (those holding arrays) in deterministic
    traversal order."""
    out = []

    def walk(d):
        if not isinstance(d, dict):
            return
        if any(not isinstance(v, dict) for v in d.values()):
            out.append(d)
        for v in d.values():
            walk(v)

    walk(tree)
    return out


def _tie_by_signature(src_tree, dst_tree):
    """Copy src param values into dst, pairing innermost param dicts by
    their shape multiset in traversal order (key NAMES differ across
    equivalent forms: fc 'w0' vs mixed 'p0_w')."""
    src = _param_dicts(src_tree)
    dst = _param_dicts(dst_tree)

    def sig(d):
        return tuple(sorted(np.shape(v) for v in d.values()))

    def ordered_keys(d):
        return [k for _, k in sorted((np.shape(d[k]), k) for k in d)]

    unused = list(src)
    for d in dst:
        i = next(j for j, s in enumerate(unused) if sig(s) == sig(d))
        s = unused.pop(i)
        for dk, sk in zip(ordered_keys(d), ordered_keys(s)):
            d[dk] = s[sk]


def _build(conf_path, config_args=""):
    from paddle_tpu.v1_compat import parse_config

    old = os.getcwd()
    os.chdir("/root/reference/paddle")  # configs open data files relatively
    try:
        p = parse_config(conf_path, config_args)
    finally:
        os.chdir(old)
    net = CompiledNetwork(p.topology)
    params, state = net.init(jax.random.PRNGKey(0))
    return p, net, params, state


@pytest.mark.skipif(
    not os.path.isdir(GSERVER),
    reason=f"needs the reference's own .conf pairs under {GSERVER}",
)
@pytest.mark.parametrize(
    "pair",
    ["concat_dotmul", "concat_fullmatrix", "concat_slice", "concat_table",
     "img_pool"],
)
def test_reference_network_compare_pairs(pair):
    reset_auto_names()
    pa, neta, params_a, state_a = _build(f"{GSERVER}/{pair}_a.conf")
    reset_auto_names()
    pb, netb, params_b, state_b = _build(f"{GSERVER}/{pair}_b.conf")
    _tie_by_signature(params_a, params_b)

    rng = np.random.RandomState(0)
    size = next(iter(pa.topology.data_layers().values())).size
    name = next(iter(pa.topology.data_layers()))
    if pair == "concat_table":
        x = rng.randint(0, size, size=(4, 1)).astype(np.int32)
    else:
        x = rng.randn(4, size).astype(np.float32)
    batch = {name: SeqTensor(x)}
    outs_a, _ = neta.apply(params_a, batch, state=state_a, train=False)
    outs_b, _ = netb.apply(params_b, batch, state=state_b, train=False)
    for oa, ob in zip(pa.output_layers, pb.output_layers):
        np.testing.assert_allclose(
            np.asarray(outs_a[oa].data),
            np.asarray(outs_b[ob].data),
            rtol=1e-5,
            atol=1e-6,
        )


def test_reference_nested_rnn_equals_flat_rnn():
    """sequence_nest_rnn.conf vs sequence_rnn.conf (reference
    test_RecurrentGradientMachine): the hierarchical RNN whose inner memory
    boots from the previous subsequence's last state computes exactly the
    flat RNN over the concatenated tokens."""
    from paddle_tpu.reader.feeder import DataFeeder

    reset_auto_names()
    pn, netn, params_n, state_n = _build(f"{GSERVER}/sequence_nest_rnn.conf")
    reset_auto_names()
    pf, netf, params_f, state_f = _build(f"{GSERVER}/sequence_rnn.conf")
    _tie_by_signature(params_f, params_n)

    nested_rows = [
        ([[1, 3, 2], [4, 5, 2]], 0),
        ([[0, 2], [2, 5], [0, 1, 2]], 1),
    ]
    flat_rows = [
        ([t for sub in row for t in sub], lab) for row, lab in nested_rows
    ]
    fn = DataFeeder(pn.topology.data_types())
    ff = DataFeeder(pf.topology.data_types())
    outs_n, _ = netn.apply(params_n, fn(nested_rows), state=state_n, train=False)
    outs_f, _ = netf.apply(params_f, ff(flat_rows), state=state_f, train=False)
    cost_n = np.asarray(outs_n[pn.output_layers[0]].data)
    cost_f = np.asarray(outs_f[pf.output_layers[0]].data)
    np.testing.assert_allclose(cost_n, cost_f, rtol=1e-5, atol=1e-6)


def test_gru_group_partial_sharing_named_weight_unnamed_bias():
    """ADVICE r2 (medium): a named recurrent param + unnamed default bias
    must share the WEIGHTS across groups (per-key, like the reference's
    global parameter table) while each group keeps its own bias."""
    reset_auto_names()
    pa = paddle.attr.ParamAttr(name="shared_gru_w")
    din = L.data("x", paddle.data_type.dense_vector_sequence(3 * H))
    g1 = networks.gru_group(din, size=H, name="g1", gru_param_attr=pa)
    g2 = networks.gru_group(din, size=H, name="g2", gru_param_attr=pa)
    net = CompiledNetwork(Topology([g1, g2]))
    params, state = net.init(jax.random.PRNGKey(0))

    # g1 owns the named weights; g2's subtree keeps ONLY its own bias
    p1 = params["g1"]["g1_unit"]
    p2 = params["g2"]["g2_unit"]
    assert "w_h" in p1 and "w_c" in p1 and "b" in p1
    assert "w_h" not in p2 and "w_c" not in p2 and "b" in p2

    # with equal biases the two groups compute identically (same weights)
    params["g2"]["g2_unit"]["b"] = params["g1"]["g1_unit"]["b"]
    batch = {"x": _var_len_batch(3 * H)}
    outs, _ = net.apply(params, batch, state=state, train=False)
    _assert_valid_close(outs["g1"].data, outs["g2"].data)

    # ...and with different biases they diverge (biases are NOT shared)
    params["g2"]["g2_unit"]["b"] = params["g1"]["g1_unit"]["b"] + 1.0
    outs2, _ = net.apply(params, batch, state=state, train=False)
    a = np.asarray(outs2["g1"].data)
    b = np.asarray(outs2["g2"].data)
    assert not np.allclose(a[:, :1], b[:, :1], rtol=1e-5, atol=1e-6)


def test_inner_group_param_shares_with_outer_layer():
    """Per-key sharing crosses the group boundary in both directions: an fc
    OUTSIDE a group and the gru_step INSIDE one can't collide, but a named
    bias ties an outer fc bias to the in-group step bias (global table)."""
    reset_auto_names()
    bname = paddle.attr.ParamAttr(name="tied_bias")
    din = L.data("x", paddle.data_type.dense_vector_sequence(3 * H))
    outer = L.fc(
        L.first_seq(din), size=3 * H, bias_attr=bname, act=A.Identity(),
        name="outer_fc",
    )
    g = networks.gru_group(din, size=H, name="g", gru_bias_attr=bname)
    net = CompiledNetwork(Topology([outer, g]))
    params, _ = net.init(jax.random.PRNGKey(0))
    # owner: outer_fc (earlier in order); the group's step bias is grafted
    assert "b" in params["outer_fc"]
    assert "b" not in params.get("g", {}).get("g_unit", {})


def test_gru_fused_and_naive_share_reference_recurrence():
    """GruStepLayer.cpp and gru_step_naive_layer lower to the SAME GruCompute
    recurrence in the reference (hl_gru_ops.cuh gru_resetOutput/
    gru_finalOutput, hl_cpu_gru.cuh:238-253): c = act(x_c + (r⊙h₋)·W_c),
    h = (1-u)⊙h₋ + u⊙c.  With identical params both paths must produce
    identical outputs, and both must match a numpy transcription of the
    reference formula."""
    reset_auto_names()
    din = L.data("x", paddle.data_type.dense_vector_sequence(3 * H))
    fused = networks.gru_group(din, size=H, name="fused")
    naive = networks.gru_group(din, size=H, name="naive", naive=True)
    net = CompiledNetwork(Topology([fused, naive]))
    params, state = net.init(jax.random.PRNGKey(2))
    params["naive"]["naive_unit"] = jax.tree_util.tree_map(
        lambda x: x, params["fused"]["fused_unit"]
    )
    batch = {"x": _var_len_batch(3 * H, seed=3)}
    outs, _ = net.apply(params, batch, state=state, train=False)

    # same params, SAME math (reference checkpoints produce identical
    # outputs whichever layer type a config uses)
    _assert_valid_close(outs["fused"].data, np.asarray(outs["naive"].data))

    # numpy transcription of the reference GruCompute formula
    p = jax.tree_util.tree_map(np.asarray, params["naive"]["naive_unit"])
    x = np.asarray(batch["x"].data)
    h_prev = np.zeros((B, H), np.float32)
    want = np.zeros((B, T, H), np.float32)
    for t in range(T):
        xt = x[:, t] + p["b"]
        x_u, x_r, x_c = np.split(xt, 3, axis=-1)
        ur = h_prev @ p["w_h"]
        u = 1.0 / (1.0 + np.exp(-(x_u + ur[:, :H])))
        r = 1.0 / (1.0 + np.exp(-(x_r + ur[:, H:])))
        c = np.tanh(x_c + (r * h_prev) @ p["w_c"])
        h_t = (1.0 - u) * h_prev + u * c
        alive = (t < LENS)[:, None]
        h_prev = np.where(alive, h_t, h_prev)
        want[:, t] = h_prev
    _assert_valid_close(outs["naive"].data, want)
    _assert_valid_close(outs["fused"].data, want)


def test_gru_naive_named_param_ties_three_blocks():
    """Reference gru_step_naive_layer with a NAMED param_attr hands the same
    name to all three full_matrix_projections — one shared H×H recurrent
    matrix.  naive=True + ParamAttr(name=...) must build a single tied `w`
    and match the formula with U_u = U_r = W_c = w."""
    from paddle_tpu.layers.recurrent_group import memory, recurrent_group

    reset_auto_names()
    din = L.data("x", paddle.data_type.dense_vector_sequence(3 * H))

    def step(ipt):
        mem = memory(name="tied_out", size=H)
        return L.gru_step(
            input=ipt,
            output_mem=mem,
            size=H,
            naive=True,
            param_attr=paddle.attr.ParamAttr(name="shared_w"),
            name="tied_out",
        )

    out = recurrent_group(step=step, input=din, name="tied_grp")
    net = CompiledNetwork(Topology([out]))
    params, state = net.init(jax.random.PRNGKey(4))
    leaves, _ = jax.tree_util.tree_flatten(params)
    # one H×H recurrent weight + one 3H bias — no w_h/w_c pair
    shapes = sorted(tuple(l.shape) for l in leaves)
    assert (H, H) in shapes and (H, 2 * H) not in shapes, shapes

    batch = {"x": _var_len_batch(3 * H, seed=5)}
    outs, _ = net.apply(params, batch, state=state, train=False)
    flat = {
        "/".join(map(str, path)): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            params, is_leaf=lambda x: hasattr(x, "shape")
        )[0]
    }
    w = next(v for v in flat.values() if v.shape == (H, H))
    b = next((v for v in flat.values() if v.shape == (3 * H,)), None)
    x = np.asarray(batch["x"].data)
    h_prev = np.zeros((B, H), np.float32)
    want = np.zeros((B, T, H), np.float32)
    for t in range(T):
        xt = x[:, t] + (b if b is not None else 0.0)
        x_u, x_r, x_c = np.split(xt, 3, axis=-1)
        hw = h_prev @ w
        u = 1.0 / (1.0 + np.exp(-(x_u + hw)))
        r = 1.0 / (1.0 + np.exp(-(x_r + hw)))
        c = np.tanh(x_c + (r * h_prev) @ w)
        h_t = (1.0 - u) * h_prev + u * c
        alive = (t < LENS)[:, None]
        h_prev = np.where(alive, h_t, h_prev)
        want[:, t] = h_prev
    _assert_valid_close(outs["tied_grp"].data, want)


def test_two_inner_declarers_chain_to_outer_owner():
    """Two in-group layers declaring the SAME global name while the owner is
    an outer layer: the group's sub-network chains the second to the first,
    the first grafts from the outer owner — no KeyError, one storage."""
    from paddle_tpu.layers.recurrent_group import memory, recurrent_group

    reset_auto_names()
    bname = paddle.attr.ParamAttr(name="tri_bias")
    din = L.data("x", paddle.data_type.dense_vector_sequence(3 * H))
    outer = L.fc(
        L.first_seq(din), size=3 * H, bias_attr=bname, act=A.Identity(),
        name="owner_fc",
    )

    def step(x):
        m1 = memory(name="s1", size=H)
        m2 = memory(name="s2", size=H)
        s1 = L.gru_step(x, output_mem=m1, size=H, bias_attr=bname, name="s1")
        s2 = L.gru_step(x, output_mem=m2, size=H, bias_attr=bname, name="s2")
        return L.addto([s1, s2], act=A.Identity(), name="both")

    g = recurrent_group(step=step, input=din, name="g")
    net = CompiledNetwork(Topology([outer, g]))
    params, state = net.init(jax.random.PRNGKey(0))
    assert "b" in params["owner_fc"]
    assert "b" not in params.get("g", {}).get("s1", {})
    assert "b" not in params.get("g", {}).get("s2", {})
    outs, _ = net.apply(
        params, {"x": _var_len_batch(3 * H)}, state=state, train=False
    )
    assert np.isfinite(np.asarray(outs["g"].data)).all()
