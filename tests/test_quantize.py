"""Block-scaled quantization plane (ops/quantize.py) unit drills.

Covers all three surfaces of the format: the numpy wire half (elastic
contributions ride master_wire as int8 blocks + f32 scales, with the
compact ``q``/``Q`` array tags and the wire-byte counters), the in-graph
jax half (quantized_psum's psum-of-amax shared scale is overflow-free by
construction and its error stays within the block-scale bound), and the
serving weight bundle (weight-only int8, ~4x resident-byte reduction,
drift bounded).
"""

import multiprocessing.connection as mpc

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import master_wire as wire
from paddle_tpu.ops import quantize as bsq


# ---------------------------------------------------------------------------
# numpy wire half
# ---------------------------------------------------------------------------


def test_quantize_array_roundtrip_within_block_scale_bound():
    rng = np.random.RandomState(0)
    a = (rng.randn(40, 25) * rng.uniform(0.1, 10)).astype(np.float32)
    d = bsq.quantize_array(a, block=64)
    assert bsq.is_quantized_array(d)
    assert d["q"].dtype == np.int8 and d["s"].dtype == np.float32
    back = bsq.dequantize_array(d)
    assert back.shape == a.shape and back.dtype == a.dtype
    # round-half-even against scale amax/127: error <= scale/2 per block
    bound = np.repeat(d["s"], 64)[: a.size].reshape(a.shape) / 2 + 1e-7
    assert np.all(np.abs(back - a) <= bound)


def test_quantize_array_zero_block_and_scalar_edge():
    d = bsq.quantize_array(np.zeros((130,), np.float32), block=64)
    assert np.all(d["s"] == 0.0) and np.all(d["q"] == 0)
    assert np.all(bsq.dequantize_array(d) == 0.0)
    one = bsq.quantize_array(np.asarray([3.5], np.float32))
    assert bsq.dequantize_array(one).shape == (1,)


def test_quantize_tree_mixed_and_wire_bytes():
    rng = np.random.RandomState(1)
    tree = {
        "layer": {"w": rng.randn(64, 32).astype(np.float32)},
        "rows": 17,  # non-array leaf passes through
        "ids": np.arange(5, dtype=np.int32),  # non-float array untouched
    }
    qt = bsq.quantize_tree(tree, block=128)
    assert bsq.is_quantized_array(qt["layer"]["w"])
    assert qt["rows"] == 17 and qt["ids"].dtype == np.int32
    back = bsq.dequantize_tree(qt)
    assert back["layer"]["w"].shape == (64, 32)
    # mixed map (one producer quantized, one not) dequantizes only marked
    mixed = bsq.dequantize_tree({"a": qt["layer"]["w"], "b": tree["ids"]})
    assert mixed["a"].dtype == np.float32 and mixed["b"] is tree["ids"]
    # the >= 3x wire-byte reduction the elastic bench gates on
    f32_bytes = bsq.tree_wire_bytes({"w": tree["layer"]["w"]})
    q_bytes = bsq.tree_wire_bytes({"w": qt["layer"]["w"]})
    assert f32_bytes >= 3 * q_bytes, (f32_bytes, q_bytes)


def test_wire_codec_compact_int8_tags_and_counters():
    """int8/uint8 arrays ride the dedicated ``q``/``Q`` tags (no dtype
    string) and send/recv tally wire_bytes counters, per endpoint label."""
    a8 = np.arange(-5, 5, dtype=np.int8).reshape(2, 5)
    u8 = np.arange(10, dtype=np.uint8)
    payload = wire.encode_payload((a8, u8))
    back_a, back_u = wire.decode_payload(payload)
    assert np.array_equal(back_a, a8) and back_a.dtype == np.int8
    assert np.array_equal(back_u, u8) and back_u.dtype == np.uint8
    # compact framing: the generic 'a' tag spends 5 extra bytes on the
    # "|i1" dtype string; the compact tag must not
    generic = wire.encode_payload(a8.astype(np.int16))
    assert len(wire.encode_payload(a8)) < len(generic)

    wire.counters.reset()
    left, right = mpc.Pipe()
    try:
        wire.send_msg(left, {"g": a8}, label="test")
        got = wire.recv_msg(right, label="test")
        assert np.array_equal(got["g"], a8)
        snap = wire.counters.snapshot()
        assert snap["wire_bytes_sent"] == snap["wire_bytes_recv"] > 0
        assert snap["wire_bytes_sent[test]"] == snap["wire_bytes_sent"]
    finally:
        left.close()
        right.close()
        wire.counters.reset()


def test_reduce_results_dequantizes_then_reduces_deterministically():
    """A quantized contribution reduces to the SAME mean no matter which
    worker reduces it (everyone dequantizes the producer's bytes), and a
    mixed map (fleet mid-flag-flip) still reduces."""
    from paddle_tpu.trainer.elastic import reduce_results

    rng = np.random.RandomState(2)
    g0 = {"w": rng.randn(30, 10).astype(np.float32)}
    g1 = {"w": rng.randn(30, 10).astype(np.float32)}
    q1 = bsq.quantize_tree(g1)
    results = {
        0: {"grads": g0, "cost": 1.0, "rows": 10},
        1: {"grads": q1, "cost": 3.0, "rows": 30},
    }
    mean_a, cost_a, rows_a = reduce_results(results)
    mean_b, cost_b, rows_b = reduce_results(dict(reversed(results.items())))
    assert np.array_equal(mean_a["w"], mean_b["w"])  # sorted-order contract
    assert rows_a == rows_b == 40 and cost_a == cost_b == 0.1
    expect = (g0["w"] * 10 + bsq.dequantize_tree(q1)["w"] * 30) / 40
    assert np.allclose(mean_a["w"], expect)


# ---------------------------------------------------------------------------
# in-graph jax half
# ---------------------------------------------------------------------------


def _psum_ab(tree_parts, **kw):
    """Run quantized_psum over the devices axis via shard_map; returns the
    per-shard outputs (all identical) next to the exact f32 psum."""
    from jax.sharding import Mesh, PartitionSpec as P


    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def body(t):
        return bsq.quantized_psum(t, "dp", **kw)

    out = jax.shard_map(
        body, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
        check_vma=False,
    )(tree_parts)
    return out


def test_quantized_psum_matches_exact_sum_within_bound():
    n_dev = len(jax.devices())
    rng = np.random.RandomState(3)
    parts = rng.randn(n_dev, 500).astype(np.float32)
    out = np.asarray(_psum_ab(jnp.asarray(parts), block=128))
    exact = parts.sum(axis=0)
    # shared bound S = sum_i amax_i; per-element error <= S/127 per shard
    # rounding, n_dev shards -> loose bound n_dev * S / (2 * 127)
    s = np.abs(parts).max(axis=1).sum()
    bound = n_dev * s / (2 * 127) + 1e-5
    for d in range(n_dev):
        assert np.all(np.abs(out[d] - exact) <= bound)
    # every shard sees the SAME reduced value (it is an allreduce)
    for d in range(1, n_dev):
        assert np.array_equal(out[d], out[0])


def test_quantized_psum_bf16_payload_and_mean():
    n_dev = len(jax.devices())
    rng = np.random.RandomState(4)
    parts = rng.randn(n_dev, 300).astype(np.float32)
    out = np.asarray(_psum_ab(
        jnp.asarray(parts), payload_dtype=jnp.bfloat16, mean=True,
    ))
    exact = parts.mean(axis=0)
    assert np.max(np.abs(out[0] - exact)) < 0.05
    assert out.dtype == np.float32


def test_quantized_psum_stochastic_rounding_unbiased_runs():
    n_dev = len(jax.devices())
    rng = np.random.RandomState(5)
    parts = rng.randn(n_dev, 256).astype(np.float32)

    from jax.sharding import Mesh, PartitionSpec as P


    mesh = Mesh(np.array(jax.devices()), ("dp",))
    out = jax.shard_map(
        lambda t, k: bsq.quantized_psum(t, "dp", stochastic=True, rng=k),
        mesh=mesh, in_specs=(P("dp"), P()), out_specs=P("dp"),
        check_vma=False,
    )(jnp.asarray(parts), jax.random.PRNGKey(0))
    exact = parts.sum(axis=0)
    s = np.abs(parts).max(axis=1).sum()
    assert np.max(np.abs(np.asarray(out)[0] - exact)) <= n_dev * s / 127


def test_quantize_block_scaled_roundtrip_and_zero_guard():
    x = jnp.asarray(np.random.RandomState(6).randn(17, 13), jnp.float32)
    p, s = bsq.quantize_block_scaled(x, block=64)
    assert p.dtype == jnp.int8 and s.dtype == jnp.float32
    back = bsq.dequantize_block_scaled(p, s, x.shape, x.dtype)
    assert float(jnp.max(jnp.abs(back - x))) <= float(jnp.max(s)) / 2 + 1e-6
    # exact-zero input: guard pins scale path, output is exactly zero
    pz, sz = bsq.quantize_block_scaled(jnp.zeros((70,), jnp.float32))
    assert float(jnp.max(jnp.abs(pz))) == 0.0


# ---------------------------------------------------------------------------
# serving weight bundles
# ---------------------------------------------------------------------------


def test_weight_bundle_quantize_shrinks_and_bounds_drift():
    rng = np.random.RandomState(7)
    w = {
        "head_w": jnp.asarray(rng.randn(48, 40), jnp.float32),
        "w_ctx": jnp.asarray(rng.randn(96, 144), jnp.float32),
        "v": jnp.asarray(rng.randn(48), jnp.float32),  # 1-D: untouched
        "head_b": None,  # None leaves ride through
        "sp_b": jnp.asarray(rng.randn(48), jnp.float32),
    }
    wq, meta = bsq.quantize_weight_bundle(w, block=128)
    assert set(meta) == {"head_w", "w_ctx"}
    assert wq["v"] is w["v"] and wq["head_b"] is None
    f32_bytes = bsq.weight_bundle_bytes(w)
    q_bytes = bsq.weight_bundle_bytes(wq)
    assert q_bytes < f32_bytes / 2.5, (q_bytes, f32_bytes)
    deq = bsq.dequantize_weight_bundle(wq, meta)
    for k in meta:
        a = np.asarray(w[k])
        drift = np.max(np.abs(np.asarray(deq[k]) - a)) / np.max(np.abs(a))
        assert drift < 0.01, (k, drift)
    assert deq["v"] is wq["v"]
