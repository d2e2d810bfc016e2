"""Whole-pass on-device epoch program (trainer/step.py make_epoch_program +
SGD's ``whole_pass_program`` feed switch): cached epochs >= 2 run as ONE
lax.scan dispatch over the stacked pass cache, bit-exact against the
stepwise path — params, costs, events, the RNG chain, and the divergence
sentinel's skip decisions (a NaN-injected step) all match — with O(1) host
dispatches per epoch counter-asserted, and every unsupported configuration
falling back to stepwise replay."""

import logging

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.topology import reset_auto_names
from paddle_tpu.utils.flags import reset_flags, set_flag
from paddle_tpu.utils.timers import global_stats


@pytest.fixture(autouse=True)
def _clean():
    global_stats.reset()
    yield
    reset_flags()
    global_stats.reset()


def _model():
    reset_auto_names()
    x = paddle.layer.data("x", paddle.data_type.dense_vector(6))
    h = paddle.layer.fc(x, size=8, act=paddle.activation.Relu())
    pred = paddle.layer.fc(h, size=3, act=paddle.activation.Softmax())
    y = paddle.layer.data("y", paddle.data_type.integer_value(3))
    return paddle.layer.classification_cost(input=pred, label=y)


def _samples(n=16, seed=0, nan_at=None):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        v = rng.randn(6).astype(np.float32)
        if nan_at is not None and i == nan_at:
            v[2] = np.nan
        out.append((v, int(rng.randint(3))))
    return out


def _train(whole_pass, num_passes=3, samples=None, collect=None,
           batch_size=4):
    set_flag("cache_pass_in_mem", True)
    if whole_pass:
        set_flag("whole_pass_program", True)
    cost = _model()
    params = paddle.parameters.create(cost, seed=0)
    tr = paddle.trainer.SGD(
        cost=cost, parameters=params, seed=0,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-2),
    )
    s = samples if samples is not None else _samples()

    def reader():
        yield from s

    tr.train(
        reader=paddle.batch(reader, batch_size), num_passes=num_passes,
        event_handler=collect or (lambda e: None), async_load_data=False,
    )
    return tr


def _params_equal(a, b):
    for name in a.parameters.params:
        for k, v in a.parameters.params[name].items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(b.parameters.params[name][k]),
                err_msg=f"{name}.{k} diverged",
            )


def _end_iterations(events):
    return [
        (e.pass_id, e.batch_id, e.cost)
        for e in events if isinstance(e, paddle.event.EndIteration)
    ]


# ---------------------------------------------------------------------------
# bit-exact parity vs the stepwise path
# ---------------------------------------------------------------------------


def test_whole_pass_bit_exact_params_and_events():
    ev_a, ev_b = [], []
    a = _train(False, collect=lambda e: ev_a.append(e))
    reset_flags()
    global_stats.reset()
    b = _train(True, collect=lambda e: ev_b.append(e))
    _params_equal(a, b)
    ia, ib = _end_iterations(ev_a), _end_iterations(ev_b)
    assert ia == ib and len(ia) == 12  # 4 batches x 3 passes
    assert global_stats.count("epoch_program/dispatches") == 2
    # the carried RNG chain matched the host-side split sequence
    np.testing.assert_array_equal(np.asarray(a._rng), np.asarray(b._rng))
    assert a._step_count == b._step_count == 12


def test_whole_pass_end_pass_metrics_match():
    evs = {}
    for whole in (False, True):
        ev = []
        _train(whole, collect=lambda e: ev.append(e))
        evs[whole] = [
            e.evaluator for e in ev if isinstance(e, paddle.event.EndPass)
        ]
        reset_flags()
        global_stats.reset()
    assert len(evs[False]) == 3
    for ma, mb in zip(evs[False], evs[True]):
        assert set(ma) == set(mb)
        for k in ma:
            assert float(ma[k]) == float(mb[k]), k


def test_sentinel_skipped_step_parity():
    """Acceptance: a NaN batch inside the cached pass is SKIPPED on device
    by both paths — identical params, identical skip decisions, and the
    unhealthy step's cost excluded from the pass report in both."""
    samples = _samples(nan_at=5)  # lands in batch 1 of the pass
    ev_a, ev_b = [], []
    a = _train(False, samples=samples, collect=lambda e: ev_a.append(e))
    reset_flags()
    global_stats.reset()
    b = _train(True, samples=samples, collect=lambda e: ev_b.append(e))
    _params_equal(a, b)
    ia, ib = _end_iterations(ev_a), _end_iterations(ev_b)
    assert len(ia) == len(ib) == 12
    for (pa, ba, ca), (pb, bb, cb) in zip(ia, ib):
        assert (pa, ba) == (pb, bb)
        assert (ca == cb) or (np.isnan(ca) and np.isnan(cb))
    # the poisoned batch replays every pass; every replay skips
    assert sum(np.isnan(c) for _, _, c in ib) == 3
    ep_a = [e for e in ev_a if isinstance(e, paddle.event.EndPass)]
    ep_b = [e for e in ev_b if isinstance(e, paddle.event.EndPass)]
    for ma, mb in zip(ep_a, ep_b):
        assert float(ma.evaluator["mean_cost"]) == float(
            mb.evaluator["mean_cost"]
        )
        assert np.isfinite(ma.evaluator["mean_cost"])


def test_whole_pass_composes_with_aot_cache(tmp_path):
    set_flag("aot_cache_dir", str(tmp_path))
    tr = _train(True)
    assert global_stats.count("epoch_program/dispatches") == 2
    kinds = {e["key"]["kind"] for e in tr._aot_cache.entries()}
    assert kinds == {"train_step", "epoch_program"}


# ---------------------------------------------------------------------------
# dispatch accounting + fallbacks
# ---------------------------------------------------------------------------


def test_o1_dispatches_per_cached_epoch():
    _train(True, num_passes=5)
    # pass 1 streams + captures; passes 2-5 are ONE dispatch each
    assert global_stats.count("epoch_program/dispatches") == 4
    assert global_stats.count("epoch_program/steps") == 16


def test_multi_bucket_pass_falls_back_stepwise(caplog):
    """Two batch shapes (ragged tail) can't stack — the stepwise cached
    replay runs instead, with a warning naming why."""
    with caplog.at_level("WARNING", logger="paddle_tpu.trainer"):
        a = _train(True, samples=_samples(18))  # 4+4+4+4+2 rows
    assert global_stats.count("epoch_program/dispatches") == 0
    assert any("shape buckets" in r.getMessage() for r in caplog.records)
    # and the run still trains correctly vs plain stepwise caching
    reset_flags()
    global_stats.reset()
    b = _train(False, samples=_samples(18))
    _params_equal(a, b)


def test_checkpoint_plane_falls_back_stepwise(tmp_path, caplog):
    set_flag("cache_pass_in_mem", True)
    set_flag("whole_pass_program", True)
    cost = _model()
    params = paddle.parameters.create(cost, seed=0)
    tr = paddle.trainer.SGD(
        cost=cost, parameters=params, seed=0,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-2),
    )
    s = _samples()

    def reader():
        yield from s

    with caplog.at_level("WARNING", logger="paddle_tpu.trainer"):
        tr.train(
            reader=paddle.batch(reader, 4), num_passes=3,
            async_load_data=False, checkpoint_dir=str(tmp_path),
        )
    assert global_stats.count("epoch_program/dispatches") == 0
    assert any(
        "checkpoint/rollback" in r.getMessage() for r in caplog.records
    )


def test_flag_off_never_uses_program():
    _train(False)
    assert global_stats.count("epoch_program/dispatches") == 0


def test_stacked_copy_over_budget_falls_back_stepwise(caplog):
    """The whole-pass program needs a SECOND copy of the pass in HBM; a
    pass captured just under pass_cache_hbm_budget_mb must replay stepwise
    (with the reason named) instead of silently doubling past the budget."""
    set_flag("cache_pass_in_mem", True)
    set_flag("whole_pass_program", True)
    cost = _model()
    params = paddle.parameters.create(cost, seed=0)
    tr = paddle.trainer.SGD(
        cost=cost, parameters=params, seed=0,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-2),
    )
    s = _samples()

    def reader():
        yield from s

    def shrink_budget(e):
        # after pass 1 sealed the capture, leave room for the pass once
        # but not for the stacked second copy
        if isinstance(e, paddle.event.EndPass) and e.pass_id == 0:
            cache = tr._pass_cache
            assert cache is not None and cache.ready
            cache.budget = cache.nbytes * 2 - 1
            assert not cache.fits_stacked()

    with caplog.at_level("WARNING", logger="paddle_tpu.trainer"):
        tr.train(reader=paddle.batch(reader, 4), num_passes=3,
                 event_handler=shrink_budget, async_load_data=False)
    assert global_stats.count("epoch_program/dispatches") == 0
    assert any(
        "stacked copy would exceed" in r.getMessage()
        for r in caplog.records
    )


def test_flag_without_pass_cache_warns(caplog):
    """whole_pass_program without cache_pass_in_mem can never engage — the
    run must say so instead of silently training stepwise forever."""
    set_flag("whole_pass_program", True)
    cost = _model()
    params = paddle.parameters.create(cost, seed=0)
    tr = paddle.trainer.SGD(
        cost=cost, parameters=params, seed=0,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-2),
    )
    s = _samples()

    def reader():
        yield from s

    with caplog.at_level("WARNING", logger="paddle_tpu.trainer"):
        tr.train(reader=paddle.batch(reader, 4), num_passes=2,
                 async_load_data=False)
    assert global_stats.count("epoch_program/dispatches") == 0
    assert any(
        "no device-resident pass cache" in r.getMessage()
        for r in caplog.records
    )


# ---------------------------------------------------------------------------
# make_epoch_program unit behavior (carry fold semantics)
# ---------------------------------------------------------------------------


def test_carry_accumulators_fold_health_and_cost():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology
    from paddle_tpu.trainer.step import (
        make_epoch_program,
        make_train_carry,
    )

    cost = _model()
    net = CompiledNetwork(Topology([cost]))
    opt = paddle.optimizer.Adam(learning_rate=1e-2)
    params, state = net.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    rng = np.random.RandomState(0)
    batches = []
    for i in range(4):
        xs = rng.randn(4, 6).astype(np.float32)
        if i == 2:
            xs[0, 0] = np.nan
        batches.append({
            "x": SeqTensor(jnp.asarray(xs)),
            "y": SeqTensor(jnp.asarray(
                rng.randint(0, 3, 4).astype(np.int32)
            )),
        })
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    prog = make_epoch_program(net, opt, mesh=None)
    carry = make_train_carry(params, state, opt_state, jax.random.PRNGKey(7))
    carry, ms = prog(carry, stacked, jnp.arange(4))
    assert float(carry["skipped"]) == 1.0
    assert float(carry["health_min"]) == 0.0
    assert float(carry["ok_steps"]) == 3.0
    healthy_costs = [
        float(c) for c, h in zip(np.asarray(ms["cost"]),
                                 np.asarray(ms["health"])) if h >= 0.5
    ]
    np.testing.assert_allclose(
        float(carry["cost_sum"]), sum(healthy_costs), rtol=1e-6
    )
    # the skipped step's params passed through inside the scan: replaying
    # with the NaN batch REMOVED from the healthy steps' view would differ,
    # but health semantics are already pinned by the parity tests above
    assert np.isnan(np.asarray(ms["cost"])[2])
