"""The draw laws of `weights.py` and the rehearsal's choice of mix."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import refsteps
import run
import weights as W


def _parents_make_weights(shapes, seed):
    """`weights.make_weights` as commit 520c00d (PR 27) had it, word for word:
    what the four cells' weights were, and have to stay."""
    names = sorted(shapes)

    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, law = shapes[name]
            if law == "normal":
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                ) * (1.0 / math.sqrt(shape[0]))
            elif law == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return jax.jit(lambda s: draw(jax.random.PRNGKey(s)))(jnp.uint32(seed % (2 ** 32)))


def _configs():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["configs"]]


@pytest.mark.parametrize("seed", [7, 2147483659])
@pytest.mark.parametrize("config", _configs())
def test_the_cells_weights_are_the_parents_bit_for_bit(config, seed):
    with open(os.path.join(run.HERE, "rehearsal", config + ".json")) as f:
        cfg = json.load(f)
    shapes = refsteps.load_by_name("reference", cfg["reference"]).param_shapes(cfg)
    want = _parents_make_weights(shapes, seed)
    draw = W.drawer(shapes)
    for got in (draw(seed), draw(seed)):  # and the second draw is the first
        assert sorted(got) == sorted(want)
        for k in want:
            assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k


def test_a_leaf_may_state_its_fan_in_or_a_constant():
    shapes = {
        "experts.w1": ((8, 256, 64), ("normal", 256)),   # expert-major: fan-in is not shape[0]
        "ssm.dt_bias": ((4096,), ("constant", -4.6)),
        "ssm.a_log": ((16, 4), ["constant", 0.5]),        # as a JSON file would give it
        "fc.w": ((256, 64), "normal"),
        "ln.gamma": ((64,), "ones"),
        "fc.b": ((64,), "zeros"),
    }
    w = {k: np.asarray(x) for k, x in W.drawer(shapes)(11).items()}
    assert {k: x.shape for k, x in w.items()} == {k: s for k, (s, _) in shapes.items()}
    assert all(x.dtype == np.float32 for x in w.values())
    # 131,072 draws: the standard deviation stands to 1% (its own is 0.2%)
    assert np.std(w["experts.w1"]) == pytest.approx(1 / math.sqrt(256), rel=0.01)
    assert abs(np.mean(w["experts.w1"])) < 0.01 / math.sqrt(256)
    assert np.std(w["fc.w"]) == pytest.approx(1 / math.sqrt(256), rel=0.03)
    assert np.all(w["ssm.dt_bias"] == np.float32(-4.6)) and np.all(w["ssm.a_log"] == 0.5)
    assert np.all(w["ln.gamma"] == 1.0) and np.all(w["fc.b"] == 0.0)
    # every expert has values of its own
    assert not np.array_equal(w["experts.w1"][0], w["experts.w1"][1])


def test_a_law_that_does_not_exist_is_refused_in_words():
    with pytest.raises(ValueError, match="no draw law"):
        W.drawer({"w": ((4, 4), ("uniform", 1.0))})(1)


def test_a_rehearsal_gets_the_mix_it_names_or_the_ragged_one(monkeypatch, tmp_path):
    import traffic

    _, _, cfg, mix, _ = run.load_cell("nmt-train", rehearsal=True)
    assert "mix" not in cfg and mix == traffic.load_mix("rehearsal-ragged")

    # a second benchmark directory, with a rehearsal file that names its mix
    for folder in ("rehearsal", "traffic"):
        (tmp_path / folder).mkdir()
    (tmp_path / "rehearsal" / "nmt-attgru-512.json").write_text(json.dumps(dict(cfg, mix="toy-b2")))
    toy = dict(mix, batch_size=2, why="two rows a batch")
    (tmp_path / "traffic" / "toy-b2.json").write_text(json.dumps(toy))
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    _, cell, cfg2, mix2, limits = run.load_cell("nmt-train", rehearsal=True)
    assert cfg2["mix"] == "toy-b2" and mix2 == toy and limits == cfg["limits"]


def test_half_batch_refuses_a_batch_of_one_row_with_the_cells_name():
    _, cell, cfg, mix, _ = run.load_cell("nmt-train", rehearsal=True)
    one_row = dict(mix, batch_size=1, reference_block_rows=1)
    with pytest.raises(ValueError, match="nmt-train.*half_batch"):
        run.reference_readings(cell, cfg, one_row, seed=3, fault="half_batch")
    # the sound reference takes the same mix
    assert len(run.reference_readings(cell, cfg, one_row, seed=3)["losses"]) == mix["checked_steps"]
