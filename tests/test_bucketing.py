"""Length-bucketed batching + token-budget packing + shape-ladder contract.

Covers the feed→compile→scan chain of the bucketing subsystem:
reader.bucketing (bucket assignment, token-budget invariants, epoch
coverage), core.batch ladder rounding / canonicalization, jit-cache
boundedness over a length-skewed epoch (CompileShapeCache), and the pinned
numerics A/B — the same batch padded to two different ladder rungs trains
identically (masked positions contribute zero grad), with the
recurrent_group scan early-exit on and off.
"""

import numpy as np
import pytest

from paddle_tpu.core.batch import (
    DEFAULT_LADDER,
    batch_shape_key,
    canonicalize_batch,
    ladder_len,
    nested_seq,
    seq,
    shape_ladder,
)
from paddle_tpu.reader import bucketing


# ---------------------------------------------------------------------------
# ladder rounding
# ---------------------------------------------------------------------------


def test_ladder_rounding():
    assert shape_ladder(16, 4) == (16, 32, 64, 128)
    assert ladder_len(1) == 16
    assert ladder_len(16) == 16
    assert ladder_len(17) == 32
    assert ladder_len(50) == 64
    assert ladder_len(4096) == 4096
    # past the top rung: next multiple of it, never an error
    assert ladder_len(4097) == 8192
    assert ladder_len(9000, (16, 32)) == 9024


def test_sample_len_default_and_slots():
    s = ([1, 2, 3], [1] * 7, 0)
    assert bucketing.sample_len(s) == 7
    assert bucketing.sample_len(s, slots=(0,)) == 3
    assert bucketing.sample_len((np.zeros((5, 2)), 1)) == 5
    assert bucketing.sample_len(3) == 1


# ---------------------------------------------------------------------------
# token-budget batcher
# ---------------------------------------------------------------------------


def _corpus(n=600, lo=2, hi=120, seed=0):
    rng = np.random.RandomState(seed)
    return [
        ([1] * int(l), int(l) % 2) for l in rng.randint(lo, hi, size=n)
    ]


def test_bucket_assignment_and_budget_invariant():
    budget = 1024
    samples = _corpus()
    rd = bucketing.token_budget_batch(
        lambda: iter(samples), token_budget=budget
    )
    batches = list(rd())
    assert len(batches) > 4
    for b in batches:
        lens = [bucketing.sample_len(s) for s in b]
        rung = ladder_len(max(lens))
        # every sample sits in the bucket of its own rung: the batch's
        # padded extent IS the ladder rung of its longest member
        assert all(ladder_len(l) == rung for l in lens)
        # token budget: padded tokens per step never exceed the budget
        # (a batch of one oversized sample is the only allowed overflow)
        assert len(b) * rung <= budget or len(b) == 1
        cap = bucketing.bucket_batch_size(rung, budget)
        assert len(b) <= cap


def test_full_batches_keep_tokens_per_step_constant():
    budget = 2048
    samples = _corpus(n=2000)
    rd = bucketing.token_budget_batch(
        lambda: iter(samples), token_budget=budget, drop_last=True
    )
    for b in rd():
        rung = ladder_len(max(bucketing.sample_len(s) for s in b))
        # drop_last=True emits only canonical-size batches: padded tokens
        # per step fill at least half the budget at every rung
        assert len(b) == bucketing.bucket_batch_size(rung, budget)
        assert budget // 2 <= len(b) * rung <= budget


def test_epoch_coverage_and_drop_last():
    samples = _corpus(n=333, seed=3)
    key = lambda s: (tuple(s[0]), s[1])
    rd = bucketing.token_budget_batch(lambda: iter(samples), token_budget=512)
    got = sorted(key(s) for b in rd() for s in b)
    assert got == sorted(key(s) for s in samples)  # nothing lost or duplicated

    dropped = bucketing.token_budget_batch(
        lambda: iter(samples), token_budget=512, drop_last=True
    )
    n_dropped = sum(len(b) for b in dropped())
    assert n_dropped <= len(samples)
    for b in dropped():
        rung = ladder_len(max(bucketing.sample_len(s) for s in b))
        assert len(b) == bucketing.bucket_batch_size(rung, 512)


def test_budget_derived_from_batch_size():
    # budget=None derives batch_size x tallest first-window rung — the
    # padded token count the unbucketed feed would have spent per step
    samples = _corpus(n=400, lo=2, hi=100, seed=1)  # max rung = 128
    rd = bucketing.token_budget_batch(
        lambda: iter(samples), batch_size=4, window=400
    )
    batches = list(rd())
    budget = 4 * 128
    for b in batches:
        rung = ladder_len(max(bucketing.sample_len(s) for s in b))
        assert len(b) * rung <= budget or len(b) == 1


def test_derived_budget_pinned_across_passes():
    """The derived token budget is pinned on the first pass: a shuffled
    second pass whose first window happens to hold longer samples must NOT
    re-derive a bigger budget (that would change every rung's canonical
    batch size and recompile every bucket per pass)."""
    short = [([1] * 60, 0)] * 64   # rung 64 -> budget = 8 * 64 = 512
    longer = [([1] * 100, 0)] * 64  # rung 128
    calls = [0]

    def rd():
        calls[0] += 1
        return iter(short if calls[0] == 1 else longer)

    batched = bucketing.token_budget_batch(rd, batch_size=8, window=64)
    pass1 = list(batched())
    pass2 = list(batched())
    assert all(len(b) == 8 for b in pass1)  # 512 // 64
    # pass 2's rung-128 batches use the PINNED 512 budget: 512 // 128 = 4
    assert all(len(b) == 4 for b in pass2), [len(b) for b in pass2]


def test_feeder_ladders_nested_s_axis():
    """With a ladder, the nested-sequence S axis is a laddered compiled
    extent too (canonicalize_batch and the feeder must agree)."""
    from paddle_tpu.core.data_types import integer_value_sub_sequence
    from paddle_tpu.reader.feeder import DataFeeder

    f = DataFeeder(
        [("z", integer_value_sub_sequence(10))], ladder=DEFAULT_LADDER
    )
    out = f([([[1, 2], [3]] * 3,)])  # 6 subsequences, max sub len 2
    # S on the shallow sub-ladder (rung 8), T on the time ladder (rung 16)
    assert out["z"].data.shape == (1, 8, 16)
    assert out["z"].sub_lengths.shape == (1, 8)
    plain = DataFeeder([("z", integer_value_sub_sequence(10))])
    assert plain([([[1, 2], [3]] * 3,)])["z"].data.shape == (1, 8, 8)


def test_sort_within_window():
    samples = _corpus(n=64, seed=5)
    rd = bucketing.sort_within_window(lambda: iter(samples), window=32)
    out = list(rd())
    assert sorted(
        (tuple(s[0]), s[1]) for s in out
    ) == sorted((tuple(s[0]), s[1]) for s in samples)
    lens = [bucketing.sample_len(s) for s in out]
    assert lens[:32] == sorted(lens[:32])
    assert lens[32:] == sorted(lens[32:])


def test_batcher_requires_budget_or_batch_size():
    with pytest.raises(ValueError):
        bucketing.token_budget_batch(lambda: iter([]))


# ---------------------------------------------------------------------------
# canonicalization + shape keys
# ---------------------------------------------------------------------------


def test_canonicalize_batch_rounds_to_ladder():
    b = {
        "x": seq(np.zeros((4, 50, 3), np.float32), [3, 50, 20, 7]),
        "y": seq(np.zeros((4, 20), np.int32), [3, 20, 11, 7]),
        "z": nested_seq(
            np.zeros((4, 5, 9, 2), np.float32),
            [2, 5, 1, 3],
            np.ones((4, 5), np.int32),
        ),
    }
    c = canonicalize_batch(b)
    assert c["x"].data.shape == (4, 64, 3)
    assert c["y"].data.shape == (4, 32)
    # S rounds on the shallow 4-based sub-ladder, T on the time ladder
    assert c["z"].data.shape == (4, 8, 16, 2)
    # sub_lengths track the padded S axis so the nested SeqTensor stays
    # internally consistent — its joint mask must still evaluate
    assert c["z"].sub_lengths.shape == (4, 8)
    assert c["z"].sub_mask().shape == (4, 8, 16)
    np.testing.assert_array_equal(np.asarray(c["x"].lengths), b["x"].lengths)
    # already-canonical batches pass through shape-identical
    c2 = canonicalize_batch(c)
    assert batch_shape_key(c2) == batch_shape_key(c)


def test_batch_shape_key_ignores_values_tracks_shapes():
    a = {"x": seq(np.zeros((2, 16), np.int32), [3, 4])}
    b = {"x": seq(np.ones((2, 16), np.int32), [9, 1])}
    c = {"x": seq(np.zeros((2, 32), np.int32), [3, 4])}
    assert batch_shape_key(a) == batch_shape_key(b)
    assert batch_shape_key(a) != batch_shape_key(c)


def test_jit_cache_bounded_over_skewed_epoch():
    """A length-skewed epoch through bucketing + laddered feeder produces at
    most one distinct batch shape per ladder rung (the contract the compile
    counter enforces); full batches alone stay within the ladder size."""
    from paddle_tpu.core.compiler import CompileShapeCache
    from paddle_tpu.core.data_types import integer_value_sequence, integer_value
    from paddle_tpu.reader.feeder import DataFeeder
    from paddle_tpu.utils.timers import StatSet

    rng = np.random.RandomState(0)
    # heavily skewed: most samples short, a long tail (zipf-ish)
    lens = np.minimum(2 + (rng.zipf(1.5, size=1500) % 120), 120)
    samples = [([1] * int(l), int(l) % 2) for l in lens]
    budget = 512
    rd = bucketing.token_budget_batch(
        lambda: iter(samples), token_budget=budget, drop_last=True
    )
    feeder = DataFeeder(
        [("w", integer_value_sequence(10)), ("lbl", integer_value(2))],
        ladder=DEFAULT_LADDER,
    )
    stats = StatSet()
    cache = CompileShapeCache("test_step", stats=stats)
    n_batches = 0
    for raw in rd():
        cache.observe(feeder(raw))
        n_batches += 1
    assert n_batches > 10
    n_rungs = len([r for r in DEFAULT_LADDER if r <= 128])
    assert cache.misses <= n_rungs, cache.shapes
    assert cache.hits == n_batches - cache.misses
    assert stats.count("test_step/compile_miss") == cache.misses
    assert stats.count("test_step/compile_hit") == cache.hits


# ---------------------------------------------------------------------------
# numerics: pinned A/B across paddings + scan early-exit
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_seq2seq():
    import jax

    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology, reset_auto_names
    from paddle_tpu.models.seq2seq import seq2seq_cost

    reset_auto_names()
    cost, _ = seq2seq_cost(40, 40, word_dim=8, hidden_dim=8)
    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    return net, params, state


def _nmt_batch(T, lens=(3, 9, 5, 7)):
    import jax.numpy as jnp

    from paddle_tpu.core.batch import SeqTensor

    lens = np.asarray(lens, np.int32)
    out = {}
    for k, name in enumerate(("src_word", "trg_word", "trg_next")):
        r = np.random.RandomState(42 + k)
        arr = np.zeros((len(lens), T), np.int32)
        for i, l in enumerate(lens):
            arr[i, :l] = r.randint(1, 40, size=l)
        out[name] = SeqTensor(jnp.asarray(arr), jnp.asarray(lens))
    return out


def _train_once(net, params, state, batch, *, key=11):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.trainer.step import make_train_step

    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    step = make_train_step(net, opt, mesh=None)
    p = jax.tree_util.tree_map(jnp.array, params)  # copies: step donates
    s = jax.tree_util.tree_map(jnp.array, state)
    p2, _, _, m = step(p, s, opt.init(p), batch, jax.random.PRNGKey(key))
    return float(m["cost"]), p2


def test_numerics_pinned_ab_bucketed_vs_unbucketed(small_seq2seq):
    """The SAME batch padded to two different ladder rungs (the bucketed
    shape vs the global-max shape) yields the same cost and the same updated
    parameters: masked positions contribute zero grad, so bucketing changes
    shapes, never numbers."""
    import jax

    net, params, state = small_seq2seq
    c16, p16 = _train_once(net, params, state, _nmt_batch(16))
    c32, p32 = _train_once(net, params, state, _nmt_batch(32))
    assert np.isfinite(c16)
    assert abs(c16 - c32) < 1e-5
    for a, b in zip(
        jax.tree_util.tree_leaves(p16), jax.tree_util.tree_leaves(p32)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_scan_early_exit_matches_full_scan(small_seq2seq):
    """Dead trailing steps skipped by the lax.cond early-exit produce the
    same training step as the full masked scan (flag off)."""
    import jax

    from paddle_tpu.utils.flags import reset_flags, set_flag

    net, params, state = small_seq2seq
    try:
        set_flag("scan_early_exit", True)
        c_on, p_on = _train_once(net, params, state, _nmt_batch(32))
        set_flag("scan_early_exit", False)
        c_off, p_off = _train_once(net, params, state, _nmt_batch(32))
    finally:
        reset_flags()
    assert abs(c_on - c_off) < 1e-5
    for a, b in zip(
        jax.tree_util.tree_leaves(p_on), jax.tree_util.tree_leaves(p_off)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


# ---------------------------------------------------------------------------
# flag plumbing
# ---------------------------------------------------------------------------


def test_make_batched_reader_flag_routing(monkeypatch):
    """v1 configs opt into bucketing via the use_bucketing flag alone: the
    CLI's batch reader routes through token_budget_batch, budget from the
    bucketing_token_budget flag."""
    import paddle_tpu.v1_compat as v1
    from paddle_tpu.utils.flags import reset_flags, set_flag

    samples = _corpus(n=100, seed=7)
    monkeypatch.setattr(
        v1, "make_config_reader",
        lambda parsed, d, train=True: lambda: iter(samples),
    )
    try:
        plain = list(v1.make_batched_reader(None, ".", 4)())
        assert all(len(b) == 4 for b in plain[:-1])  # paddle.batch semantics

        set_flag("use_bucketing", True)
        set_flag("bucketing_token_budget", 256)
        bucketed = list(v1.make_batched_reader(None, ".", 4)())
        assert sum(len(b) for b in bucketed) == len(samples)
        for b in bucketed:
            rung = ladder_len(max(bucketing.sample_len(s) for s in b))
            assert all(
                ladder_len(bucketing.sample_len(s)) == rung for s in b
            )
            assert len(b) * rung <= 256 or len(b) == 1
    finally:
        reset_flags()


def test_use_bucketing_flag_ladders_the_sgd_feeder():
    from paddle_tpu.utils.flags import reset_flags, set_flag

    try:
        set_flag("use_bucketing", True)
        from paddle_tpu.core.data_types import integer_value_sequence
        from paddle_tpu.reader.feeder import DataFeeder

        # the SGD feeder path reads the flag; check the feeder-level effect
        f = DataFeeder(
            [("w", integer_value_sequence(10))], ladder=DEFAULT_LADDER
        )
        out = f([([1] * 50,)])
        assert out["w"].data.shape == (1, 64)
    finally:
        reset_flags()
