"""What decides `correct`, at a size a test run can hold (toy widths, ragged
batches, the CPU): the plain references against the program in float32; the
control (the reference in fp8 in the program's place) read as not correct; and
a whole run of the harness, its look for a chip skipped, with the timed path
broken underneath, once for each fault a training cell can have."""

import json
import os

import pytest

import refsteps
import run


def _configs_and_cells():
    """Every configuration of BENCHMARK.json with its first one-chip cell: a
    configuration added later stands under the same tests without an edit."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = [c["name"] for c in bench["configs"]]
    return configs, {c: next(w["name"] for w in bench["workloads"]
                             if w["config"] == c and w["chips"] == 1) for c in configs}


CONFIGS, CELL = _configs_and_cells()


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_agrees_with_the_program_in_float32_on_ragged_batches(config):
    _, cell, cfg, mix, _ = run.load_cell(CELL[config], rehearsal=True)
    cfg = dict(cfg, compute_dtype="float32")
    got = run.program_readings(cell, cfg, mix, seed=5)
    ref = run.reference_readings(cell, cfg, mix, seed=5)
    numbers, _ = refsteps.compare(got, ref)
    # float32 on both sides: what is left is the order of the sums
    assert numbers["loss_gap"] < 2e-6
    assert numbers["grad_norm_gap"] < 2e-5
    assert numbers["change_norm_gap"] < 2e-5


@pytest.mark.parametrize("config", CONFIGS)
def test_control_and_planted_faults_read_as_not_correct(config):
    _, cell, cfg, mix, limits = run.load_cell(CELL[config], rehearsal=True)
    ref = run.reference_readings(cell, cfg, mix, seed=4)
    sound, compared, _ = run.decide_correct(run.program_readings(cell, cfg, mix, seed=4), ref, limits)
    assert sound, compared
    control = run.reference_readings(cell, cfg, mix, seed=4, precision="fp8")
    ok, compared, _ = run.decide_correct(control, ref, limits)
    assert not ok, compared
    half = run.reference_readings(cell, cfg, mix, seed=4, fault="half_batch")
    ok, compared, _ = run.decide_correct(half, ref, limits)
    assert not ok, compared
    # a step that returns its state unchanged: every leaf's change reads 0
    # against the reference's, a gap of 1
    still = dict(ref, change_norms={k: 0.0 for k in ref["change_norms"]})
    ok, compared, _ = run.decide_correct(still, ref, limits)
    assert not ok and compared["change_norm_gap"][0] == pytest.approx(1.0)


def _run_broken(monkeypatch, capsys, workload, breaker):
    breaker(monkeypatch)
    rc = run.main(["--workload", workload, "--seed", "2147483659", "--seconds", "0.5",
                   "--trace", "0", "--rehearsal", "1"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _sound(monkeypatch):
    pass


def _state_unchanged(monkeypatch):
    import paddle_tpu as paddle

    monkeypatch.setattr(paddle.optimizer.Adam, "rule",
                        lambda self, g, p, lr, slots, step: (p, slots))


def _feed_altered(alter):
    def breaker(monkeypatch):
        import paddle_tpu as paddle

        real = paddle.trainer.SGD.train

        def train(self, reader, *a, **kw):
            return real(self, lambda: map(alter, reader()), *a, **kw)

        monkeypatch.setattr(paddle.trainer.SGD, "train", train)
    return breaker


# half of the batch left out, the mean taken over the rest
_half_left_out = _feed_altered(lambda batch: batch[: len(batch) // 2])
# every chip computes on the first chip's shard: what that chip's step gives
# where the exchange of gradients between the four is left out
_exchange_left_out = _feed_altered(lambda batch: batch[: len(batch) // 4] * 4)


@pytest.mark.parametrize("workload,breaker,want", [
    ("nmt-train", _sound, True),
    ("nmt-train", _state_unchanged, False),
    ("nmt-train", _half_left_out, False),
    ("transformer-train-1k", _state_unchanged, False),
    ("transformer-train-128", _half_left_out, False),
    ("nmt-train-dp4", _exchange_left_out, False),
    ("nmt-train-dp4", _sound, True),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(monkeypatch, capsys, workload, breaker, want):
    result = _run_broken(monkeypatch, capsys, workload, breaker)
    assert result["correct"] is want, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}


def test_exits_without_a_result_where_there_is_no_chip(capsys):
    rc = run.main(["--workload", "nmt-train", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc == run.EXIT_NO_DEVICE
    assert capsys.readouterr().out == ""
