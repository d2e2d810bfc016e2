"""The layers of the hybrid decoder (models/hybrid_lm.py) against plain forms
of the same equations, CPU, seeded weights, tiny widths: the chunked
selective scan against a scan over tokens, top-k experts over a held range
against a masked sum, the shares of the experts against the uncut layer,
grouped key/value heads against repeated heads, the token-row reader, and
the whole model through `trainer.SGD` against the benchmark's reference."""

import os
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
from paddle_tpu.ops.ssd import ssd_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_names():
    reset_auto_names()


# -- the chunked scan ---------------------------------------------------------

def ssd_scan_reference(x, dt, a, b, c):
    """The same recurrence as a scan over the tokens, float32: what the
    chunked form is tested against."""
    x, dt, a, b, c = (v.astype(jnp.float32) for v in (x, dt, a, b, c))

    def step(h, inp):
        xt, dtt, bt, ct = inp  # [B,G,R,P], [B,G,R], [B,G,N], [B,G,N]
        h = (jnp.exp(dtt * a)[..., None, None] * h
             + jnp.einsum("bgrp,bgn->bgrpn", xt * dtt[..., None], bt))
        return h, jnp.einsum("bgrpn,bgn->bgrp", h, ct)

    h0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("t,chunk", [(16, 4), (13, 4), (3, 8), (64, 16), (37, 16)])
def test_chunked_scan_matches_a_scan_over_tokens(t, chunk):
    """Forward and every gradient; lengths that are and are not multiples of
    the chunk, and one shorter than a chunk."""
    k = jax.random.split(jax.random.PRNGKey(t), 6)
    b, g, r, p, n = 2, 2, 3, 4, 5
    x = jax.random.normal(k[0], (b, t, g, r, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, g, r)) - 1.0)
    a = -jnp.exp(0.3 * jax.random.normal(k[2], (g, r)))
    bb = jax.random.normal(k[3], (b, t, g, n))
    cc = jax.random.normal(k[4], (b, t, g, n))
    w = jax.random.normal(k[5], (b, t, g, r, p))

    def both(fn):
        return jax.value_and_grad(lambda *args: jnp.sum(fn(*args) * w), argnums=(0, 1, 2, 3, 4))(
            x, dt, a, bb, cc)

    y, grads = both(lambda *args: ssd_scan(*args, chunk=chunk))
    y_ref, grads_ref = both(ssd_scan_reference)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5)
    for got, want in zip(grads, grads_ref):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(want))))


def test_chunked_scan_keeps_the_states_between_chunks_not_every_steps():
    """What the backward pass keeps: the inputs and one [P, N] state a head
    and CHUNK, not one a token."""
    b, t, g, r, p, n, chunk = 1, 32, 1, 2, 4, 8, 8
    args = (jnp.ones((b, t, g, r, p)), jnp.ones((b, t, g, r)), -jnp.ones((g, r)),
            jnp.ones((b, t, g, n)), jnp.ones((b, t, g, n)))
    _, res = jax.vjp(lambda *a: ssd_scan(*a, chunk=chunk), *args)
    sizes = sorted(x.size for x in jax.tree_util.tree_leaves(res))
    assert sizes[-1] == b * (t // chunk) * g * r * p * n  # the states entering each chunk
    assert sum(sizes) < b * t * g * r * p * n  # less than one state a token


# -- experts chosen top-k over a held range -----------------------------------

D, E, HID, SHARED, K = 6, 8, 5, 7, 3


def _moe_net(held, shared=SHARED, num_experts=E):
    reset_auto_names()
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector(D))
    m = L.moe_topk(x_in, expert_hidden=HID, num_experts=num_experts, top_k=K, experts_held=held,
                   shared_hidden=shared, scaling=2.5, name="moe")
    return CompiledNetwork(Topology([m]))


def _plain_moe(x, p, lo, hi):
    """shared(x) + the masked sum over the held experts, nothing sorted."""
    def ffn(h, up, down):
        return jnp.square(jax.nn.relu(h @ up)) @ down

    s = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(s + p["router_bias"], K)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * 2.5
    out = ffn(x, p["shared_w1"], p["shared_w2"]) if "shared_w1" in p else 0.0
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)
        out = out + w_e * ffn(x, p["w1"][e - lo], p["w2"][e - lo])
    return out, chosen


def _routing_bias(kind, lo):
    """uniform: the router's own choice.  one_expert: every token chooses
    the first held expert.  all_held: every token's K choices are held (the
    worst case the static shapes are sized for)."""
    bias = np.zeros(E, np.float32)
    if kind == "one_expert":
        bias[lo] = 10.0
    elif kind == "all_held":
        bias[lo:lo + K] = 10.0
    return jnp.asarray(bias)


def _against_the_plain_sum(net, p, x, lo, hi):
    """Value and the gradients of every leaf and of x against `_plain_moe`;
    -> the layer's counters and the held rows counted from the plain choice."""
    tilt = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    state = net.init(jax.random.PRNGKey(0))[1]

    def layer(p, x):
        outs, _ = net.apply({"moe": p}, {"x": SeqTensor(x)}, state=state, train=True)
        return jnp.sum(outs["moe"].data * tilt), outs

    def plain(p, x):
        return jnp.sum(_plain_moe(x, p, lo, hi)[0] * tilt)

    (got, outs), grads = jax.value_and_grad(layer, argnums=(0, 1), has_aux=True)(p, x)
    want, grads_ref = jax.value_and_grad(plain, argnums=(0, 1))(p, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    _, chosen = _plain_moe(x, p, lo, hi)
    counters = {k: int(outs[f"moe@{k}"].data[0, 0])
                for k in ("rows_held", "rows_over_bound", "rows_dropped")}
    return counters, int(jnp.sum((chosen >= lo) & (chosen < hi)))


@pytest.mark.parametrize("routing", ["uniform", "one_expert", "all_held"])
def test_held_experts_match_a_plain_masked_sum(routing):
    lo, hi, n = 2, 6, 24
    net = _moe_net((lo, hi))
    params, _ = net.init(jax.random.PRNGKey(0))
    params["moe"]["router_bias"] = _routing_bias(routing, lo)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, D))
    # the counters: every (token, choice) that fell on a held expert was
    # computed, whatever the skew, and none dropped; half the experts held
    # make the bound all the pairs there are, so one pass takes them
    counters, held_rows = _against_the_plain_sum(net, params["moe"], x, lo, hi)
    assert counters == {"rows_held": held_rows, "rows_over_bound": 0, "rows_dropped": 0}
    assert {"uniform": 0 < held_rows < n * K, "one_expert": held_rows >= n,
            "all_held": held_rows == n * K}[routing]


# the held experts' block in passes of `held_rows_bound` rows (layers/moe.py):
# a quarter or less of the experts held, so the bound is under the N x K pairs

@pytest.mark.parametrize("routing,num_experts,held,rows,passes", [
    ("uniform", 8, (2, 4), None, 1),            # the router's own choice: under the bound of 40
    ("none_held", 8, (2, 4), 0, 1),             # no token chooses a held expert
    ("exactly_the_bound", 8, (2, 4), 40, 1),    # the last row of the one pass is a held pair
    ("all_held", 8, (2, 4), 48, 2),             # every token chooses both: a second pass of 8 rows
    ("all_held", 16, (2, 5), 72, 3),            # bound 32 of 72 pairs: two full passes and 8 rows
])
def test_held_experts_in_passes_match_a_plain_masked_sum(routing, num_experts, held, rows, passes):
    from paddle_tpu.layers.moe import held_rows_bound

    (lo, hi), n = held, 24
    bound = held_rows_bound(n, K, hi - lo, num_experts)
    assert bound < n * K and bound == {8: 40, 16: 32}[num_experts]
    net = _moe_net(held, num_experts=num_experts)
    p = net.init(jax.random.PRNGKey(0))[0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (n, D))
    bias = np.zeros(num_experts, np.float32)
    if routing != "uniform":
        bias[lo:hi] = -10.0 if routing == "none_held" else 10.0
    if routing == "exactly_the_bound":
        # the first held expert by its bias, the second by the sign of x[:, 0]: 24 + 16 rows
        bias[lo + 1] = 0.0
        p["router"] = p["router"].at[:, lo + 1].set(0.0).at[0, lo + 1].set(10.0)
        x = x.at[:, 0].set(jnp.where(jnp.arange(n) < 16, 3.0, -3.0))
    p["router_bias"] = jnp.asarray(bias)
    counters, held_rows = _against_the_plain_sum(net, p, x, lo, hi)
    assert counters == {"rows_held": held_rows, "rows_over_bound": passes - 1, "rows_dropped": 0}
    assert held_rows == rows if rows is not None else 0 < held_rows <= bound


def test_the_row_bound_follows_the_shapes():
    from paddle_tpu.layers.moe import held_rows_bound

    assert held_rows_bound(4096, 6, 8, 128) == 3072  # the benchmark's cell: twice the 1,536 expected
    assert held_rows_bound(4096, 6, 128, 128) == 4096 * 6  # every expert held: all the pairs
    assert held_rows_bound(24, 3, 4, 8) == 72 and held_rows_bound(24, 3, 2, 8) == 40
    assert held_rows_bound(4100, 6, 8, 128) == 3584  # whole tiles of 512 rows from one tile on
    for n, k, held, e in [(24, 3, 2, 8), (500, 2, 3, 64), (4096, 6, 8, 128), (8192, 8, 16, 256)]:
        r = held_rows_bound(n, k, held, e)
        assert 2 * n * k * held / e <= r or r == n * k
        assert r % (512 if r >= 512 else 8) == 0 or r == n * k
        assert held_rows_bound(n + 1, k, held, e) >= r and held_rows_bound(n, k + 1, held, e) >= r
        assert held_rows_bound(n, k, held + 1, e) >= r >= held_rows_bound(n, k, held, 2 * e)


def _primitives_and_row_counts(jaxpr, widths, into):
    """Every primitive of a jaxpr and of the jaxprs inside its equations,
    and the rows (elements over the last axis) of every result whose last
    axis is one of `widths`."""
    for eqn in jaxpr.eqns:
        into[0].add(eqn.primitive.name)
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if shape and shape[-1] in widths:
                into[1].append(int(np.prod(shape[:-1])))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives_and_row_counts(sub, widths, into)
    return into


@pytest.mark.parametrize("held,looped", [((0, 8), False), ((2, 6), False), ((2, 4), True)])
def test_no_array_of_the_held_experts_outgrows_the_row_bound(held, looped):
    """The mechanism, read from the program (counts carry over from the CPU):
    forward and backward, no result D or H wide has more rows than the bound
    of a pass (96 here, over the 64 tokens): N x K rows appear only where the
    bound is N x K.  With every pair inside the bound there is no loop and
    no branch at all: one pass, by shape."""
    from paddle_tpu.layers.moe import held_rows_bound

    n, d, hid = 64, 16, 12
    reset_auto_names()
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector(d))
    m = L.moe_topk(x_in, expert_hidden=hid, num_experts=8, top_k=K, experts_held=held, name="moe")
    net = CompiledNetwork(Topology([m]))
    params, state = net.init(jax.random.PRNGKey(0))
    bound = held_rows_bound(n, K, held[1] - held[0], 8)
    assert bound == (96 if looped else n * K)

    def loss(p, x):
        return jnp.sum(net.apply(p, {"x": SeqTensor(x)}, state=state, train=True)[0]["moe"].data)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, jnp.ones((n, d)))
    primitives, rows = _primitives_and_row_counts(jaxpr.jaxpr, (d, hid), (set(), []))
    assert "ragged_dot_general" in primitives or "ragged_dot" in primitives
    assert ("while" in primitives) == looped and "cond" not in primitives
    assert max(rows) == bound and rows.count(bound) >= 6  # a pass's rows, both ways


def test_padded_positions_ask_nothing_of_the_experts():
    reset_auto_names()
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(D))
    m = L.moe_topk(x_in, expert_hidden=HID, num_experts=E, top_k=K, shared_hidden=SHARED, name="moe")
    net = CompiledNetwork(Topology([m]))
    params, state = net.init(jax.random.PRNGKey(0))
    x = np.random.RandomState(0).randn(2, 4, D).astype(np.float32)
    outs, _ = net.apply(params, {"x": mkseq(x, np.asarray([4, 1], np.int32))}, state=state, train=False)
    np.testing.assert_array_equal(np.asarray(outs["moe"].data)[1, 1:], 0.0)
    assert int(outs["moe@rows_held"].data[0, 0]) == 5 * K  # five true tokens, all experts held


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_routed_parts_and_the_shared_expert_once_give_the_uncut_layer(shares):
    """The cut of the benchmark's configuration, tied to the model: each of
    `shares` chips holds E / shares experts and returns shared(x) + its own
    experts' part; the parts of all of them, with the shared expert counted
    once, add up to the layer that holds every expert."""
    whole = _moe_net((0, E))
    params, state = whole.init(jax.random.PRNGKey(3))
    p = params["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (20, D))
    uncut = whole.apply(params, {"x": SeqTensor(x)}, state=state, train=False)[0]["moe"].data
    shared = jnp.square(jax.nn.relu(x @ p["shared_w1"])) @ p["shared_w2"]
    per = E // shares
    total, rows = shared, 0
    for i in range(shares):
        lo, hi = i * per, (i + 1) * per
        net = _moe_net((lo, hi))
        mine = dict(p, w1=p["w1"][lo:hi], w2=p["w2"][lo:hi])
        outs, _ = net.apply({"moe": mine}, {"x": SeqTensor(x)}, state=state, train=False)
        total = total + (outs["moe"].data - shared)  # this share's routed part
        rows += int(outs["moe@rows_held"].data[0, 0])
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-6)
    assert rows == 20 * K  # every (token, choice) was some share's


# -- grouped key/value heads --------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_grouped_heads_match_repeated_heads(causal):
    d_in, heads, kv_heads, dh = 10, 4, 2, 3

    def net_of(kv):
        reset_auto_names()
        x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(d_in))
        m = L.multi_head_attention(x_in, n_heads=heads, n_kv_heads=kv, head_dim=dh, causal=causal,
                                   bias_attr=False, name="att")
        return CompiledNetwork(Topology([m]))

    grouped, full = net_of(kv_heads), net_of(None)
    params, state = grouped.init(jax.random.PRNGKey(5))
    p = params["att"]
    assert p["wq"].shape == (d_in, heads * dh) and p["wk"].shape == (d_in, kv_heads * dh)
    assert p["wo"].shape == (heads * dh, d_in)

    def repeat(w):  # a key/value head for every query head of its group
        return jnp.repeat(w.reshape(d_in, kv_heads, dh), heads // kv_heads, axis=1).reshape(d_in, heads * dh)

    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, d_in))
    batch = {"x": mkseq(x, np.asarray([5, 3], np.int32))}

    def out(net, p):
        return net.apply({"att": p}, batch, state=state, train=False)[0]["att"].data

    want = out(full, dict(p, wk=repeat(p["wk"]), wv=repeat(p["wv"])))
    np.testing.assert_allclose(out(grouped, p), want, rtol=1e-5, atol=1e-6)
    # and the gradient reaches the shared heads as the sum over their group
    g = jax.grad(lambda p: jnp.sum(jnp.square(out(grouped, p))))(p)
    g_full = jax.grad(lambda p: jnp.sum(jnp.square(out(full, p))))(
        dict(p, wk=repeat(p["wk"]), wv=repeat(p["wv"])))
    summed = g_full["wk"].reshape(d_in, kv_heads, heads // kv_heads, dh).sum(axis=2).reshape(d_in, -1)
    np.testing.assert_allclose(g["wk"], summed, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("keys,blocked", [(1024, True), (512, False)])
def test_the_blocked_kernel_is_taken_from_1024_keys_on(monkeypatch, keys, blocked):
    """On the TPU self-attention takes `ops/pallas_attention` unasked from
    1,024 keys on (`transformer-train-1k`, `nemotron-train-2k`) and stays dense
    below (`transformer-train-128`), hands the kernel a key/value head for
    every query head, and counts the choice where it makes it, at trace time.
    The kernel is a stand-in here: the choice and the wiring are what is read."""
    from paddle_tpu.layers import attention
    from paddle_tpu.ops import pallas_attention as fa
    from paddle_tpu.utils.timers import global_stats

    assert attention._FLASH_FROM_KEYS == 1024
    d_in, heads, kv_heads, dh = 6, 2, 1, 8
    reset_auto_names()
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector_sequence(d_in))
    m = L.multi_head_attention(x_in, n_heads=heads, n_kv_heads=kv_heads, head_dim=dh, causal=True,
                               bias_attr=False, name="att")
    net = CompiledNetwork(Topology([m]))
    params, state = net.init(jax.random.PRNGKey(7))
    batch = {"x": mkseq(jax.random.normal(jax.random.PRNGKey(8), (1, keys, d_in)),
                        np.asarray([keys], np.int32))}

    def counted():
        return (global_stats.count("attention_blocked_layers"),
                global_stats.count("attention_dense_layers"))

    before = counted()
    dense = net.apply(params, batch, state=state, train=False)[0]["att"].data
    assert counted() == (before[0], before[1] + 1)  # the CPU backend: dense whatever the keys
    seen = []

    def stand_in(q, k, v, lengths, causal, bq, bk, interpret):
        seen.append((q.shape, k.shape, v.shape, causal))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
        s = jnp.where(jnp.tril(jnp.ones((keys, keys), bool)), s, -1e9)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "flash_attention_diff", stand_in)
    before = counted()
    got = net.apply(params, batch, state=state, train=False)[0]["att"].data
    assert seen == ([((1, keys, heads, dh),) * 3 + (True,)] if blocked else [])
    assert counted() == (before[0] + blocked, before[1] + (not blocked))
    np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-5)


def test_the_hybrid_decoders_attention_layer_counts_as_blocked_at_2048_keys(monkeypatch):
    """`nemotron-train-2k`'s pattern as the chip would trace it (only the
    backend's name is faked; nothing is compiled or run): its one attention
    layer, 2,048 keys, grouped heads of 128, takes the blocked kernels: 1 / 0."""
    from paddle_tpu.models.hybrid_lm import hybrid_lm_cost
    from paddle_tpu.utils.timers import global_stats

    cost, _ = hybrid_lm_cost(
        "MEMEMEM*E", 64, 32, mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=8,
        attn_heads=4, attn_kv_heads=2, attn_head_dim=128, num_experts=4, experts_per_token=2,
        expert_hidden=16, shared_hidden=16, experts_held=(0, 2))
    net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
    params, state = net.init(jax.random.PRNGKey(0))
    ids = SeqTensor(jnp.ones((2, 2048), jnp.int32), jnp.full((2,), 2048, jnp.int32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    count = lambda: [global_stats.count(f"attention_{path}_layers") for path in ("blocked", "dense")]
    before = count()
    jax.eval_shape(lambda p: net.apply(p, {"word": ids, "next_word": ids}, state=state, train=True)[0][cost.name].data,
                   params)
    assert [a - b for a, b in zip(count(), before)] == [1, 0]


# -- the token-row reader ------------------------------------------------------

@pytest.mark.parametrize("stream", ["ids", "documents"])
def test_next_token_rows_cuts_a_stream_into_full_rows(stream):
    ids = list(range(100, 111))  # 11 ids: two rows of 4 + 1 ids share a boundary, the tail is left out
    source = (lambda: iter(ids)) if stream == "ids" else (lambda: iter([ids[:3], ids[3:4], ids[4:]]))
    rows = list(paddle.reader.next_token_rows(source, row_len=4)())
    assert rows == [([100, 101, 102, 103], [101, 102, 103, 104]),
                    ([104, 105, 106, 107], [105, 106, 107, 108])]
    with pytest.raises(ValueError):
        paddle.reader.next_token_rows(source, row_len=0)


# -- the whole model through trainer.SGD against the benchmark's reference ----

@pytest.fixture
def harness():
    sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
    import refsteps
    import run

    yield run, refsteps
    del sys.path[:2]


@pytest.mark.parametrize("seed", [5, 2147483659])
def test_hybrid_lm_trains_as_the_plain_reference_does(harness, seed):
    """Pattern MEM*E at toy widths, float32 on both sides, two steps of
    `trainer.SGD.train` on ragged rows: each step's loss, the first gradient
    of every leaf and every leaf's change are the reference's, up to the
    order of the sums (the reference scans nothing in chunks, sorts no rows
    and repeats no head)."""
    run, refsteps = harness
    _, cell, cfg, mix, _ = run.load_cell("nemotron-train-2k", rehearsal=True)
    assert cfg["hybrid_override_pattern"] == "MEM*E"
    cfg, mix = dict(cfg, compute_dtype="float32"), dict(mix, checked_steps=2)
    got = run.program_readings(cell, cfg, mix, seed=seed)
    ref = run.reference_readings(cell, cfg, mix, seed=seed)
    assert len(got["losses"]) == 2
    numbers, _ = refsteps.compare(got, ref)
    assert numbers["loss_gap"] < 2e-6
    assert numbers["grad_norm_gap"] < 2e-5
    assert numbers["change_norm_gap"] < 2e-5
    assert numbers["grad_diff"] < 5e-5


def test_hybrid_lm_refuses_a_pattern_it_does_not_know():
    from paddle_tpu.models.hybrid_lm import hybrid_lm_cost

    with pytest.raises(ValueError, match="pattern"):
        hybrid_lm_cost("MXE", 10, 8, mamba_heads=2, mamba_head_dim=4, mamba_groups=1, state_size=4,
                       attn_heads=2, attn_kv_heads=1, attn_head_dim=4, num_experts=4,
                       experts_per_token=2, expert_hidden=4, shared_hidden=4)
