"""The blocked attention kernels (`ops/pallas_attention.py`): exactness of
the forward and of the one fused backward against dense attention, in
interpret mode on the CPU, at head widths 64 (two heads a 128-lane block) and
128 (one).  The lowering for the chip is exercised by `chip_smoke.py` and the
benchmark's `transformer-train-1k`."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_attention import (
    flash_attention,
    flash_attention_diff,
    supported,
)


def _dense(q, k, v, lengths=None, causal=False):
    b, t, h, dh = q.shape
    P = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=P) / math.sqrt(dh)
    if lengths is not None:
        s = jnp.where(
            (jnp.arange(t)[None, :] < lengths[:, None])[:, None, None, :],
            s, -jnp.inf,
        )
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=P
    )


def _qkv(t=256, b=2, h=2, dh=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, dh), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense_interpret(causal):
    q, k, v = _qkv()
    lens = jnp.asarray([256, 173], jnp.int32)
    got = flash_attention(q, k, v, lengths=lens, causal=causal, interpret=True)
    want = _dense(q, k, v, lengths=lens, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense_interpret(causal):
    q, k, v = _qkv(t=128)
    lens = jnp.asarray([128, 90], jnp.int32)

    def loss_flash(q_, k_, v_):
        o = flash_attention_diff(q_, k_, v_, lens, causal, 128, 128, True)
        return jnp.sum(o**2)

    def loss_dense(q_, k_, v_):
        return jnp.sum(_dense(q_, k_, v_, lens, causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_flash_padding_invariance_interpret():
    q, k, v = _qkv(t=128)
    lens = jnp.asarray([70, 128], jnp.int32)
    base = flash_attention(q, k, v, lengths=lens, interpret=True)
    k2 = k.at[0, 70:].set(50.0)
    v2 = v.at[0, 70:].set(-50.0)
    pert = flash_attention(q, k2, v2, lengths=lens, interpret=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(pert), atol=5e-5)


# (dh, causal, (bq, bk), lengths of the two rows of T = 256)
_CASES = {
    # a length that ends inside a block: the mask is computed there only
    "dh64-ragged": (64, False, (128, 128), [256, 173]),
    "dh64-ragged-causal": (64, True, (128, 128), [256, 173]),
    "dh128-ragged": (128, False, (128, 128), [256, 173]),
    "dh128-ragged-causal": (128, True, (128, 128), [256, 173]),
    # causal, blocks of unequal sides: the diagonal crosses them off-centre
    "dh64-causal-256x128": (64, True, (256, 128), [256, 200]),
    "dh64-causal-128x256": (64, True, (128, 256), [256, 200]),
    "dh128-causal-256x128": (128, True, (256, 128), [250, 256]),
    "dh128-causal-128x256": (128, True, (128, 256), [250, 256]),
    # rows full to T: no block computes a length mask
    "dh64-full-rows": (64, False, (128, 128), [256, 256]),
    "dh128-full-rows-causal": (128, True, (128, 128), [256, 256]),
    # a whole key block is padding: it is skipped, forward and backward
    "dh64-padding-block": (64, False, (128, 128), [256, 100]),
    "dh128-padding-block-causal": (128, True, (128, 128), [128, 256]),
    # a head width that fills no lane block: every head in one block
    "dh32-three-heads": (32, True, (128, 128), [256, 131]),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_blocked_forward_and_backward_match_dense(case):
    dh, causal, (bq, bk), lens = _CASES[case]
    h = 3 if dh == 32 else 2
    q, k, v = _qkv(t=256, h=h, dh=dh, seed=len(case))
    g = _qkv(t=256, h=h, dh=dh, seed=1)[0]
    lens = jnp.asarray(lens, jnp.int32)
    got, vjp = jax.vjp(lambda *a: flash_attention_diff(*a, lens, causal, bq, bk, True), q, k, v)
    want, vjp_dense = jax.vjp(lambda *a: _dense(*a, lens, causal), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
    for name, a, b in zip("qkv", vjp(g), vjp_dense(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, err_msg=f"d{name}")


def test_a_row_of_no_keys_gives_zeros_and_no_nan():
    q, k, v = _qkv(t=128)
    lens = jnp.asarray([0, 128], jnp.int32)
    out, vjp = jax.vjp(lambda *a: flash_attention_diff(*a, lens, False, 128, 128, True), q, k, v)
    grads = vjp(jnp.ones_like(out))
    assert not np.asarray(out[0]).any()
    for x in (out, *grads):
        assert np.isfinite(np.asarray(x)).all()
    assert not any(np.asarray(x[0]).any() for x in grads)


@pytest.mark.parametrize("dh", [64, 128])
def test_padding_invariance_of_the_gradients(dh):
    """What lies beyond a row's length moves neither the output nor any
    gradient, and takes none."""
    q, k, v = _qkv(t=256, dh=dh)
    lens = jnp.asarray([70, 256], jnp.int32)

    def grads(k_, v_):
        out, vjp = jax.vjp(lambda *a: flash_attention_diff(*a, lens, True, 128, 128, True), q, k_, v_)
        return (out, *vjp(jnp.ones_like(out)))

    base = grads(k, v)
    pert = grads(k.at[0, 70:].set(50.0), v.at[0, 70:].set(-50.0))
    for a, b in zip(base, pert):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)
    assert not np.asarray(base[2][0, 70:]).any() and not np.asarray(base[3][0, 70:]).any()


def test_under_the_layers_shard_map_the_kernels_give_what_they_give_bare():
    """On a mesh of several devices `multi_head_attention` runs the kernels
    under a shard_map over the rows (`layers/attention._blocked_core`): the
    output and the three gradients are the bare call's, row for row."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.layers.attention import _blocked_core
    from paddle_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    wrap, why = _blocked_core(mesh, 4)
    assert why is None
    q, k, v = _qkv(t=256, b=4)
    lens = jnp.asarray([256, 200, 130, 17], jnp.int32)
    core = lambda q, k, v, n: flash_attention_diff(q, k, v, n, True, 128, 128, True)

    def out_and_grads(f, **jit):
        def both(q, k, v):
            out, vjp = jax.vjp(lambda *a: f(*a, lens), q, k, v)
            return (out, *vjp(jnp.ones_like(out)))
        return jax.jit(both, **jit)(q, k, v)

    rows = NamedSharding(mesh, P("data"))
    bare = out_and_grads(core)
    mapped = out_and_grads(wrap(core), in_shardings=(rows,) * 3)
    for a, b in zip(bare, mapped):
        assert b.sharding.is_equivalent_to(rows, b.ndim)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_blocks_that_do_not_tile_are_refused():
    q, k, v = _qkv(t=256)
    with pytest.raises(ValueError, match="divisible by block sizes"):
        flash_attention(q, k, v, block_q=192, block_k=128, interpret=True)


def test_supported_shapes():
    assert supported(256, 64)
    assert supported(128, 8)
    assert not supported(100, 64)  # T not a block multiple
    assert not supported(64, 64)  # too short to pay off
    assert not supported(256, 7)  # lane-hostile head dim
