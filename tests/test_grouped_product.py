"""The held experts' grouped products on their two kernels (layers/moe.py):
the Pallas grouped product, run by the interpreter on the CPU, against
`jax.lax.ragged_dot` through `_held_experts` whole (its output and all five
gradients), over the routings that stress the kernel's tiles; which of the two
a layer takes and with which tiles, as a function of the shapes, the platform
and the mesh; the two counters that say so."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import layers as L
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.core.topology import Topology, reset_auto_names
from paddle_tpu.utils.timers import global_stats

from paddle_tpu.ops import grouped_product as gp

moe = importlib.import_module("paddle_tpu.layers.moe")  # `layers.moe` is the layer of that name

N, K, HELD, D, H = 96, 4, 4, 32, 48  # tokens, choices a token, experts held, widths
BOUND = 256  # rows of a pass: two row tiles of the kernels


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _routing(sizes):
    """chosen [N, K] (HELD = an expert held elsewhere) with `sizes[e]` pairs
    on held expert e, dealt over the tokens in a seeded order."""
    flat = np.full(N * K, HELD, np.int32)
    flat[:sum(sizes)] = np.repeat(np.arange(HELD), sizes)
    return jnp.asarray(np.random.RandomState(0).permutation(flat).reshape(N, K))


def _operands(sizes):
    """What `moe_topk_apply` hands `_held_experts` for this routing."""
    chosen = _routing(sizes)
    key = chosen.reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    group_sizes = jnp.bincount(key, length=HELD + 1)[:HELD].astype(jnp.int32)
    assert [int(n) for n in group_sizes] == list(sizes)
    r = jax.random.split(jax.random.PRNGKey(1), 5)
    tokens = jax.random.normal(r[0], (N, D))
    w1 = jax.random.normal(r[1], (HELD, D, H)) / np.sqrt(D)
    w2 = jax.random.normal(r[2], (HELD, H, D)) / np.sqrt(H)
    weights = jnp.where(chosen < HELD, jax.random.uniform(r[3], (N, K)), 0.0)
    tilt = jax.random.normal(r[4], (N, D))
    return (tokens, w1, w2, weights), (order, group_sizes), tilt


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _nan_past_the_last_group(dot, xs, w, sizes):
    """`dot` with what its contract leaves undefined made as bad as it may
    be: NaN in every row past the last group, forward and in the row gradient."""
    return jnp.where((jnp.arange(xs.shape[0]) < jnp.sum(sizes))[:, None], dot(xs, w, sizes), jnp.nan)


def _nan_fwd(dot, xs, w, sizes):
    return _nan_past_the_last_group(dot, xs, w, sizes), (xs, w, sizes)


def _nan_bwd(dot, res, g):
    xs, w, sizes = res
    g_xs, g_w = jax.vjp(lambda xs, w: dot(xs, w, sizes), xs, w)[1](g)
    return jnp.where((jnp.arange(xs.shape[0]) < jnp.sum(sizes))[:, None], g_xs, jnp.nan), g_w, None


_nan_past_the_last_group.defvjp(_nan_fwd, _nan_bwd)

_interpreted = functools.partial(gp.grouped_dot, interpret=True)

ROUTINGS = {
    "even": (48, 48, 48, 48),                 # 192 of the 256 rows of the one pass
    "one_empty_expert": (100, 0, 90, 40),     # a group the grid never visits
    "straddles_a_row_tile": (100, 60, 30, 2), # the second group lies on both tiles, the last holds two rows
    "fewer_rows_than_a_tile": (3, 0, 0, 5),   # the second row tile holds no row at all
    "a_full_pass": (64, 64, 64, 64),          # the last row of the pass is a held pair
    "no_rows": (0, 0, 0, 0),                  # nothing routed here: the products' grids are empty
    "two_passes": (120, 100, 90, 74),         # 384 rows: a second pass whose first group began in the first
}


@pytest.mark.parametrize("poisoned", [False, True], ids=["as_computed", "undefined_rows_nan"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_kernel_path_gives_what_the_ragged_dot_path_gives(routing, poisoned):
    """`_held_experts` on the kernels' products, forward and every gradient
    (tokens, w1, w2, weights; the two integer arguments have none), against
    the same on `jax.lax.ragged_dot`.  With `poisoned` the rows past the last
    group of a pass, which neither kernel defines, come back NaN from both
    products and from their row gradients: `_experts`' masks keep them out of
    the output and of every gradient."""
    args, (order, group_sizes), tilt = _operands(ROUTINGS[routing])
    assert (sum(ROUTINGS[routing]) > BOUND) == (routing == "two_passes")

    def value_and_grads(dot):
        if poisoned:
            dot = functools.partial(_nan_past_the_last_group, dot)

        def loss(tokens, w1, w2, weights):
            out = moe._held_experts(_relu2, K, BOUND, dot, tokens, w1, w2, weights, order, group_sizes)
            return jnp.sum(out * tilt), out

        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(*args)
        return (out, *grads)

    got, want = value_and_grads(_interpreted), value_and_grads(moe._xla_dot)
    for name, a, b in zip(("out", "tokens", "w1", "w2", "weights"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    assert (float(jnp.abs(want[0]).max()) > 0.1) == (routing != "no_rows")  # rows were sent: not zeros compared


def test_the_kernels_take_bfloat16_rows_and_matrices_and_sum_in_float32():
    """The cell's types: bfloat16 in and out of both products and of all
    three kernels, with float32 sums inside; against ragged_dot on the same
    bfloat16 operands, to bfloat16's rounding of the results."""
    (tokens, w1, w2, weights), (order, group_sizes), tilt = _operands(ROUTINGS["straddles_a_row_tile"])
    args = (tokens.astype(jnp.bfloat16), w1.astype(jnp.bfloat16), w2.astype(jnp.bfloat16), weights)

    def grads(dot):
        def loss(*a):
            return jnp.sum(moe._held_experts(_relu2, K, BOUND, dot, *a, order, group_sizes).astype(jnp.float32) * tilt)
        return jax.grad(loss, argnums=(0, 1, 2, 3))(*args)

    for a, b in zip(grads(_interpreted), grads(moe._xla_dot)):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0.02, atol=0.02 * np.abs(b).max())


# -- which kernel, which tiles -------------------------------------------------

def test_the_tiles_follow_the_shapes_as_the_sweep_says():
    """Rows of 128, the whole K in one block, N as wide as the block's bytes
    allow (scripts/grouped_product_sweep.py; the table in layers/moe.py)."""
    assert gp.ROW_TILE == 128
    # the cell's two products in bfloat16: a group's whole matrix is one block, 9.98 MB
    assert gp._n_tile(2688, 1856, 2) == 1856 and gp._n_tile(1856, 2688, 2) == 2688
    # the float32 sum of their gradient is cut along N to 11 MB and 7.6 MB
    assert gp._n_tile(2688, 1856, 4) == 1024 and gp._n_tile(1856, 2688, 4) == 1024
    # narrow matrices are one block; a long K narrows the block; too long a K leaves none
    assert gp._n_tile(32, 48, 2) == 48 and gp._n_tile(16384, 4096, 2) == 256
    assert gp._n_tile(65536, 4096, 2) is None
    for k, n, itemsize in [(2688, 1856, 2), (7168, 2048, 2), (64, 64, 4), (16384, 4096, 4)]:
        tn = gp._n_tile(k, n, itemsize)
        assert (tn == n or tn % 128 == 0) and k * tn * itemsize <= gp._BLOCK_BYTES
    assert gp.supported(3072, 2688, 1856, (jnp.bfloat16, jnp.bfloat16)) is None
    assert gp.supported(3072, 2688, 1856, (jnp.bfloat16, jnp.float32)) is None
    assert "row tile" in gp.supported(40, 32, 48, (jnp.float32,) * 2)
    assert "row tile" in gp.supported(3072 + 64, 32, 48, (jnp.float32,) * 2)
    assert "float16" in gp.supported(3072, 32, 48, (jnp.float16,) * 2)
    assert "VMEM" in gp.supported(3072, 65536, 4096, (jnp.bfloat16,) * 2)


@pytest.mark.parametrize("sizes,empty_groups,groups_of_visits,tiles_of_visits", [
    ((48, 48, 48, 48), False, [0, 1, 2, 2, 3], [0, 0, 0, 1, 1]),           # group 2 straddles the tiles
    ((100, 0, 90, 40), False, [0, 2, 2, 3], [0, 0, 1, 1]),                 # the empty group has no visit
    ((100, 0, 90, 40), True, [0, 1, 2, 2, 3], [0, 0, 0, 1, 1]),            # ... but one where its zeros are due
    ((3, 0, 0, 5), False, [0, 3], [0, 0]),
    ((0, 0, 0, 0), False, [], []),                                         # no row: the grid is empty
    ((0, 0, 0, 0), True, [0, 1, 2, 3], [0, 0, 0, 0]),
    ((64, 64, 64, 64), False, [0, 1, 2, 3], [0, 0, 1, 1]),                 # groups that end on the tiles' edges
    ((0, 0, 0, 256), True, [0, 1, 2, 3, 3], [0, 0, 0, 0, 1]),
])
def test_the_visits_are_the_row_tile_and_group_pairs_that_share_rows(sizes, empty_groups, groups_of_visits, tiles_of_visits):
    edges, visits, with_empty = gp._visits(jnp.asarray(sizes, jnp.int32), BOUND)
    group, tile, count = with_empty if empty_groups else visits
    assert [int(e) for e in edges] == [0] + list(np.cumsum(sizes))
    assert group.shape == tile.shape == (BOUND // 128 + HELD - 1 + (HELD if empty_groups else 0),)
    assert int(count) == len(groups_of_visits)
    assert [int(g) for g in group[:int(count)]] == groups_of_visits
    assert [int(t) for t in tile[:int(count)]] == tiles_of_visits
    assert 0 <= int(group.min()) and int(group.max()) < HELD and 0 <= int(tile.min()) and int(tile.max()) < BOUND // 128


@pytest.mark.parametrize("backend,rows,dtypes,devices,why_not", [
    ("tpu", 3072, ("bfloat16", "bfloat16"), 1, None),    # the cell
    ("tpu", 3072, ("bfloat16", "float32"), 1, None),
    ("tpu", 3072, ("bfloat16", "bfloat16"), None, None), # no mesh at all
    ("cpu", 3072, ("bfloat16", "bfloat16"), 1, "backend"),
    ("tpu", 40, ("bfloat16", "bfloat16"), 1, "row tile"),     # the few rows of a small layer
    ("tpu", 3072 + 8, ("bfloat16", "bfloat16"), 1, "row tile"),
    ("tpu", 3072, ("float16", "float16"), 1, "float16"),
    ("tpu", 3072, ("bfloat16", "bfloat16"), 4, "4 devices"),  # XLA partitions no Mosaic kernel
])
def test_the_path_follows_the_platform_the_rows_the_types_and_the_mesh(monkeypatch, backend, rows, dtypes, devices, why_not):
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = None if devices is None else make_mesh(data=devices, devices=jax.devices()[:devices])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    count = lambda: [global_stats.count(f"moe_grouped_{path}_layers") for path in ("kernel", "xla")]
    before = count()
    dot, why = moe._grouped_dot(rows, 2688, 1856, tuple(jnp.dtype(d) for d in dtypes), mesh)
    if why_not is None:
        assert dot is gp.grouped_dot and why is None
    else:
        assert dot is moe._xla_dot and why_not in why
    assert [a - b for a, b in zip(count(), before)] == [int(why_not is None), int(why_not is not None)]


@pytest.mark.parametrize("tokens,kernel", [(512, True), (24, False)])
def test_a_traced_layer_counts_the_path_its_grouped_products_took(monkeypatch, tokens, kernel):
    """`moe_topk` as the chip would trace it (only the backend's name is
    faked; nothing is lowered or run): with 2 of 8 experts held, 512 tokens
    make passes of 512 rows, which take the kernels (a `pallas_call` for each
    product and gradient, no `ragged_dot` left); 24 tokens make passes of 24
    rows and keep XLA's.  On the CPU backend both count as XLA's."""
    from paddle_tpu.layers.moe import held_rows_bound

    d, hid = 16, 12
    reset_auto_names()
    x_in = paddle.layer.data("x", paddle.data_type.dense_vector(d))
    m = L.moe_topk(x_in, expert_hidden=hid, num_experts=8, top_k=2, experts_held=(2, 4), name="moe")
    net = CompiledNetwork(Topology([m]))
    params, state = net.init(jax.random.PRNGKey(0))
    assert held_rows_bound(tokens, 2, 2, 8) == (512 if kernel else 24)

    def loss(p, x):
        return jnp.sum(net.apply(p, {"x": SeqTensor(x)}, state=state, train=True)[0]["moe"].data)

    def traced():
        before = [global_stats.count(f"moe_grouped_{path}_layers") for path in ("kernel", "xla")]
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, jnp.ones((tokens, d))))
        after = [global_stats.count(f"moe_grouped_{path}_layers") for path in ("kernel", "xla")]
        return [a - b for a, b in zip(after, before)], text

    counted, text = traced()
    assert counted == [0, 1] and "ragged_dot" in text and "pallas_call" not in text
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    counted, text = traced()
    assert counted == ([1, 0] if kernel else [0, 1])
    assert ("pallas_call" in text, "ragged_dot" in text) == (kernel, not kernel)
    if kernel:
        # six kernels (the product, its row gradient, its matrices' gradient, of w1 and of w2), each
        # traced ONCE for the first pass, the loop's body, the forward and the forward computed again
        # (jax prints a jaxpr that several call sites share once)
        assert text.count("pallas_call") == 6
