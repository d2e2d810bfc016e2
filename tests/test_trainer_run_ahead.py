"""The stepwise loop keeps one step in flight (ISSUE 32): ``trainer.SGD.train``
dispatches step N+1 before it waits for step N's cost, unless settling N
reads the parameters as N left them.  Order of events, the bits the steps
compute, what a batch-period save writes, and the two counters the mechanism
brings (``run_ahead_steps``, ``run_ahead_drains``).  CPU, counts and bits
only: no time is asserted."""

import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.topology import reset_auto_names
from paddle_tpu.utils import flags
from paddle_tpu.utils.timers import global_stats

_N = 7  # batches a pass
_ROWS = 4


@pytest.fixture(autouse=True)
def _clean():
    yield
    flags.reset_flags()


def _trainer():
    reset_auto_names()
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(4))
    y = paddle.layer.data(name="y", type=paddle.data_type.dense_vector(1))
    hidden = paddle.layer.fc(input=x, size=8, act=paddle.activation.Tanh())
    pred = paddle.layer.fc(input=hidden, size=1, act=paddle.activation.Linear())
    cost = paddle.layer.square_error_cost(input=pred, label=y)
    return paddle.trainer.SGD(
        cost=cost,
        parameters=paddle.parameters.create(cost, seed=0),
        update_equation=paddle.optimizer.Adam(learning_rate=0.05),
    )


def _reader(n=_N):
    def samples():
        rng = np.random.RandomState(0)
        for _ in range(n * _ROWS):
            xv = rng.randn(4).astype(np.float32)
            yield xv, np.array([xv.sum()], np.float32)

    return paddle.batch(samples, _ROWS)


def _counters():
    return (global_stats.count("run_ahead_steps"),
            global_stats.count("run_ahead_drains"))


def _counted(train):
    """-> (run_ahead_steps, run_ahead_drains) that `train()` added."""
    before = _counters()
    train()
    return tuple(b - a for a, b in zip(before, _counters()))


def _host(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _state_of(trainer):
    return _host((trainer.parameters.params, trainer.parameters.state,
                  trainer._opt_state))


def _assert_same_bits(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes()


def _train_logged(monkeypatch, **kw):
    """Trains with the point of dispatch and the handler both writing one
    log -> [(what, pass_id, batch_id or None), ...]."""
    log = []
    real = paddle.trainer.SGD._run_train_step
    at = {}

    def step(self, *args):
        log.append(("dispatch", at["pass"], at["batch"]))
        return real(self, *args)

    def handler(e):
        if isinstance(e, paddle.event.BeginIteration):
            at.update({"pass": e.pass_id, "batch": e.batch_id})
        log.append((type(e).__name__, e.pass_id, getattr(e, "batch_id", None)))

    monkeypatch.setattr(paddle.trainer.SGD, "_run_train_step", step)
    _trainer().train(_reader(), event_handler=handler, **kw)
    return log


@pytest.mark.parametrize("async_load_data", [True, False])
def test_the_next_step_is_dispatched_before_the_last_ones_cost_is_waited_for(
        monkeypatch, async_load_data):
    log = _train_logged(monkeypatch, num_passes=2, async_load_data=async_load_data)
    for p in range(2):
        for b in range(_N):
            begin = log.index(("BeginIteration", p, b))
            dispatch = log.index(("dispatch", p, b))
            end = log.index(("EndIteration", p, b))
            assert begin < dispatch < end  # the contract's first clause
            if b + 1 < _N:  # step b+1 is on the device before b is settled
                assert log.index(("dispatch", p, b + 1)) < end
        # every EndIteration of a pass, in order, exactly once, before its EndPass
        ends = [e for e in log if e[0] == "EndIteration" and e[1] == p]
        assert ends == [("EndIteration", p, b) for b in range(_N)]
        assert log.index(("EndIteration", p, _N - 1)) < log.index(("EndPass", p, None))
    # and nothing of the next pass starts before the last pass is closed
    assert log.index(("EndPass", 0, None)) < log.index(("BeginPass", 1, None))
    assert log.index(("BeginPass", 1, None)) < log.index(("dispatch", 1, 0))


@pytest.mark.parametrize("held_by", ["checkpoint_dir", "num_sanitizer"])
def test_at_depth_0_every_step_is_settled_before_the_next_is_dispatched(
        monkeypatch, tmp_path, held_by):
    kw = {}
    if held_by == "checkpoint_dir":
        kw["checkpoint_dir"] = str(tmp_path / "ck")
    else:
        flags.set_flag("num_sanitizer", True)
    before = _counters()
    log = _train_logged(monkeypatch, num_passes=2, **kw)
    assert _counters() == before  # nothing ran ahead, so nothing was drained
    steps = [e for e in log if e[0] in ("BeginIteration", "dispatch", "EndIteration")]
    assert steps == [(what, p, b) for p in range(2) for b in range(_N)
                     for what in ("BeginIteration", "dispatch", "EndIteration")]


@pytest.mark.parametrize("num_passes", [1, 2])
def test_a_plain_pass_of_n_steps_runs_ahead_n_minus_1_times(num_passes):
    trainer = _trainer()
    got = _counted(lambda: trainer.train(_reader(), num_passes=num_passes))
    assert got == (num_passes * (_N - 1), 0)


@pytest.mark.parametrize("k", [1, 2, 3, 7])
def test_the_drains_count_the_steps_at_which_a_save_fell_due(tmp_path, k):
    trainer = _trainer()
    got = _counted(lambda: trainer.train(
        _reader(), num_passes=1, save_dir=str(tmp_path), saving_period_by_batches=k))
    # a save falls due after batches k, 2k, ...; the one after the pass's
    # last batch is settled by the end of the pass, not ahead of a dispatch
    due = [b for b in range(1, _N + 1) if b % k == 0]
    drains = len([b for b in due if b < _N])
    assert got == (_N - 1 - drains, drains)
    assert sorted(os.listdir(tmp_path)) == sorted(
        [f"pass-00000-batch-{b}" for b in due] + ["pass-00000"])


def test_the_stats_period_drains_by_the_trainers_step_count(caplog):
    trainer = _trainer()
    with caplog.at_level("INFO", logger="paddle_tpu.trainer"):
        got = _counted(lambda: trainer.train(
            _reader(), num_passes=2, show_parameter_stats_period=5))
    # steps 5 and 10 of 14: the second falls in the second pass
    assert got == (2 * (_N - 1) - 2, 2)
    shown = [r.getMessage().splitlines()[0] for r in caplog.records
             if r.getMessage().startswith("parameter stats")]
    assert shown == ["parameter stats @ step 5:", "parameter stats @ step 10:"]


def _costs_state_and_counts(**kw):
    costs = []
    trainer = _trainer()
    counts = _counted(lambda: trainer.train(
        _reader(), num_passes=2,
        event_handler=lambda e: costs.append((e.pass_id, e.batch_id, e.cost))
        if isinstance(e, paddle.event.EndIteration) else None, **kw))
    return costs, _state_of(trainer), counts


@pytest.mark.parametrize("drained_by", ["show_parameter_stats_period", "checkpoint_dir"])
def test_running_ahead_computes_the_bits_of_a_run_that_drains_every_step(tmp_path, drained_by):
    kw = ({"show_parameter_stats_period": 1} if drained_by == "show_parameter_stats_period"
          else {"checkpoint_dir": str(tmp_path / "ck")})
    costs, state, counts = _costs_state_and_counts()
    drained_costs, drained_state, drained_counts = _costs_state_and_counts(**kw)
    assert counts == (2 * (_N - 1), 0) and drained_counts[0] == 0
    assert len(costs) == 2 * _N
    assert costs == drained_costs  # floats from the same bits compare equal
    _assert_same_bits(state, drained_state)


@pytest.mark.parametrize("k", [1, 3])
def test_a_batch_period_save_writes_the_parameters_after_k_steps(tmp_path, k):
    trainer = _trainer()
    trainer.train(_reader(), num_passes=1, save_dir=str(tmp_path),
                  saving_period_by_batches=k)
    stopped = _trainer()
    stopped.train(_reader(k), num_passes=1)
    saved = _trainer()
    with open(tmp_path / f"pass-00000-batch-{k}" / "params.tar", "rb") as f:
        saved.parameters.from_tar(f)
    for name in stopped.parameters.names():
        a, b = np.asarray(saved.parameters.get(name)), np.asarray(stopped.parameters.get(name))
        assert a.tobytes() == b.tobytes(), name
    # and the run went on from there: its own parameters are those after all
    # the steps, not the saved ones
    whole = _trainer()
    whole.train(_reader(), num_passes=1)
    _assert_same_bits(_state_of(trainer), _state_of(whole))


def test_the_log_line_names_the_step_it_logs(caplog):
    flags.set_flag("log_period", 3)
    costs = {}
    trainer = _trainer()
    with caplog.at_level("INFO", logger="paddle_tpu.trainer"):
        trainer.train(
            _reader(), num_passes=1,
            event_handler=lambda e: costs.update({e.batch_id: e.cost})
            if isinstance(e, paddle.event.EndIteration) else None)
    lines = [r.getMessage() for r in caplog.records if r.getMessage().startswith("pass 0 batch")]
    # steps 3 and 6 are batches 2 and 5, each with its own cost
    assert lines == [f"pass 0 batch {b} cost {costs[b]:.6f}" for b in (2, 5)]
