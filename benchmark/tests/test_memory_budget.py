"""What the harness holds on the device, by phase (PERF.md section 4), read
from `jax.live_arrays()` at toy widths on the CPU: while the program steps in
set-up nothing of the benchmark's is there, and the reference never holds
more than three float32 copies of the parameter set beside a block's rows."""

import gc
import math

import jax
import numpy as np
import pytest

import refsteps
import run


def _buffers(arrays):
    """{address: bytes} of the device buffers under the arrays: a replicated
    leaf and the view of its first shard are one buffer, counted once."""
    out = {}
    for x in arrays:
        if not isinstance(x, jax.Array) or x.is_deleted():  # a batch still on the host
            continue
        if len(x.sharding.device_set) == 1:
            out[x.unsafe_buffer_pointer()] = x.nbytes
        else:  # (its shards are cached on the array, in a cycle: the collector frees it)
            for shard in x.addressable_shards:
                out[shard.data.unsafe_buffer_pointer()] = shard.data.nbytes
    return out


def _live_bytes():
    return sum(_buffers(jax.live_arrays()).values())


def _one_set_bytes(cfg):
    shapes = refsteps.load_by_name("reference", cfg["reference"]).param_shapes(cfg)
    return 4 * sum(math.prod(shape) for shape, _ in shapes.values())


@pytest.mark.parametrize("workload", ["nmt-train", "transformer-train-128", "nmt-train-dp4"])
def test_nothing_of_the_benchmarks_is_on_the_device_while_the_program_steps(monkeypatch, workload):
    import paddle_tpu as paddle

    _, cell, cfg, mix, _ = run.load_cell(workload, rehearsal=True)
    real = paddle.trainer.SGD._run_train_step
    not_the_programs = []

    def step(self, params, state, opt_state, batch, rng):
        theirs = _buffers(jax.tree_util.tree_leaves((params, state, opt_state, batch, rng)))
        # the feed's next batch (integers) and the costs handed to the handler
        # (scalars) are the program's too; the benchmark's own would be
        # float32 leaves
        floats = _buffers(x for x in jax.live_arrays()
                          if x.ndim and np.issubdtype(x.dtype, np.floating))
        not_the_programs.append(sum(n for at, n in floats.items() if at not in theirs))
        return real(self, params, state, opt_state, batch, rng)

    monkeypatch.setattr(paddle.trainer.SGD, "_run_train_step", step)
    got = run.program_readings(cell, cfg, mix, seed=5)
    assert len(not_the_programs) == mix["checked_steps"] == len(got["losses"])
    assert not_the_programs == [0] * mix["checked_steps"]
    assert all(isinstance(x, np.ndarray) for x in got["grad"].values())


@pytest.mark.parametrize("workload,precision,fault", [
    ("nmt-train", "float32", None),
    ("transformer-train-128", "float32", None),
    ("transformer-train-128", "fp8", None),
    ("nmt-train", "float32", "half_batch"),
    ("nmt-train-dp4", "float32", "no_exchange"),
])
def test_the_reference_holds_at_most_three_parameter_sets(workload, precision, fault):
    _, cell, cfg, mix, _ = run.load_cell(workload, rehearsal=True)
    gc.collect()
    before = _live_bytes()
    held = []
    ref = run.reference_readings(
        cell, cfg, mix, seed=5, precision=precision, fault=fault,
        watch=lambda: held.append(_live_bytes() - before))
    one_set = _one_set_bytes(cfg)
    # a block's rows as the reference takes them: five int32 arrays, the
    # widest [rows, longest]; and the block's cost
    rows = mix["reference_block_rows"]
    block = 4 * rows * (3 * mix["trg_len"][1] + 2) + 64
    assert max(held) <= 3 * one_set + block, (max(held) / one_set, block)
    # it is held at the fullest moment, two blocks' gradients and the weights,
    # unless the fault leaves one block of the batch
    blocks = mix["batch_size"] // (2 if fault == "half_batch" else cell["chips"] if fault else 1) // rows
    assert max(held) >= (3 if blocks > 1 else 2) * one_set
    # what comes back waits on the host
    assert all(isinstance(x, np.ndarray) for x in ref["grad"].values())
    assert _live_bytes() - before < 64
