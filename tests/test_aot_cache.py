"""Persistent AOT executable cache (core/aot_cache.py): roundtrip + warm
hit, stale/corrupt robustness (truncated blob, mismatched jax-version key,
foreign-topology key — each falls back to retrace, warns once, bumps its
counter, never crashes or loads wrong code), maintenance surface
(ls/prune/clear + the CLI), and the subprocess warm-boot e2e: a second
process boots from the first's cache with ZERO full retraces,
compile-counter-asserted."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import aot_cache as ac
from paddle_tpu.core.topology import reset_auto_names
from paddle_tpu.utils.flags import reset_flags, set_flag
from paddle_tpu.utils.timers import StatSet, global_stats


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    reset_flags()


def _jitted():
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda p, x: {k: v + x.mean() for k, v in p.items()},
        donate_argnums=(0,),
    ), ({"w": jnp.ones((16, 16)), "b": jnp.ones((16,))}, jnp.ones((4, 16)))


def _identity(n=None):
    return {"kind": "test_step", "n_steps": n, "topology": "t0",
            "batch": "b0", "mesh": "none", "donation": "(0,)"}


# ---------------------------------------------------------------------------
# store/load roundtrip + counters
# ---------------------------------------------------------------------------


def test_miss_then_hit_roundtrip(tmp_path):
    import jax.numpy as jnp

    stats = StatSet()
    cache = ac.AOTCache(str(tmp_path), stats=stats)
    fn, args = _jitted()
    exe = cache.get_or_compile(fn, args, _identity())
    assert cache.compiles == 1 and stats.count("aot_cache/miss") == 1
    out = exe(*_jitted()[1])
    np.testing.assert_allclose(np.asarray(out["b"]), 2.0)

    # a second cache object (fresh process stand-in) loads, no compile
    stats2 = StatSet()
    cache2 = ac.AOTCache(str(tmp_path), stats=stats2)
    exe2 = cache2.get_or_compile(fn, _jitted()[1], _identity())
    assert cache2.compiles == 0 and cache2.loads == 1
    assert stats2.count("aot_cache/hit") == 1
    out2 = exe2(*_jitted()[1])
    np.testing.assert_array_equal(np.asarray(out2["w"]), np.asarray(out["w"]))


def test_distinct_identities_are_distinct_entries(tmp_path):
    cache = ac.AOTCache(str(tmp_path), stats=StatSet())
    fn, args = _jitted()
    cache.get_or_compile(fn, args, _identity())
    cache.get_or_compile(fn, _jitted()[1], _identity(n=8))
    assert len(cache.entries()) == 2
    assert cache.compiles == 2


def test_serialization_writes_real_entries(tmp_path):
    cache = ac.AOTCache(str(tmp_path), stats=StatSet())
    fn, args = _jitted()
    cache.get_or_compile(fn, args, _identity())
    ents = cache.entries()
    assert len(ents) == 1 and ents[0]["bytes"] > 0
    assert ents[0]["key"]["kind"] == "test_step"
    assert ents[0]["key"]["jax"]  # env fields in the header provenance


# ---------------------------------------------------------------------------
# robustness: truncated / version-stale / foreign-topology entries
# ---------------------------------------------------------------------------


def _entry_paths(tmp_path):
    return [
        os.path.join(str(tmp_path), f)
        for f in sorted(os.listdir(str(tmp_path))) if f.endswith(".aotx")
    ]


def test_truncated_entry_falls_back_to_retrace(tmp_path, caplog):
    stats = StatSet()
    cache = ac.AOTCache(str(tmp_path), stats=stats)
    fn, args = _jitted()
    cache.get_or_compile(fn, args, _identity())
    (path,) = _entry_paths(tmp_path)
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: len(data) // 2])  # torn write / partial copy

    stats2 = StatSet()
    cache2 = ac.AOTCache(str(tmp_path), stats=stats2)
    with caplog.at_level("WARNING", logger="paddle_tpu.aot_cache"):
        exe = cache2.get_or_compile(fn, _jitted()[1], _identity())
        # warn once, not per load
        cache2.load(_identity())
    assert cache2.compiles == 1  # retraced, not crashed
    assert stats2.count("aot_cache/corrupt") >= 1
    assert sum("damaged" in r.getMessage() for r in caplog.records) == 1
    out = exe(*_jitted()[1])
    np.testing.assert_allclose(np.asarray(out["b"]), 2.0)


def test_header_level_truncation_falls_back_to_retrace(tmp_path):
    """Truncation INSIDE the fixed-size framing fields (magic + partial
    length u32, or cut before the CRC) must be a corrupt entry, not an
    unhandled struct.error — regression test for the length-checked
    header reads."""
    cache = ac.AOTCache(str(tmp_path), stats=StatSet())
    fn, args = _jitted()
    cache.get_or_compile(fn, args, _identity())
    (path,) = _entry_paths(tmp_path)
    data = open(path, "rb").read()
    for cut in (9, len(ac._MAGIC) + 2, len(ac._MAGIC) + 4 + 10):
        with open(path, "wb") as f:
            f.write(data[:cut])
        stats = StatSet()
        cache2 = ac.AOTCache(str(tmp_path), stats=stats)
        assert cache2.load(_identity()) is None  # never raises
        assert stats.count("aot_cache/corrupt") == 1
        ents = cache2.entries()  # ls lists it as corrupt, no crash
        assert len(ents) == 1 and "corrupt" in ents[0]
    exe = cache2.get_or_compile(fn, _jitted()[1], _identity())
    assert cache2.compiles == 1
    out = exe(*_jitted()[1])
    np.testing.assert_allclose(np.asarray(out["b"]), 2.0)


def test_mismatched_jax_version_key_is_stale(tmp_path, caplog, monkeypatch):
    """An entry written by a different jax (or backend) must be detected
    and retraced — simulated by rewriting the header's env fields, the
    exact bytes a version upgrade leaves behind."""
    stats = StatSet()
    cache = ac.AOTCache(str(tmp_path), stats=stats)
    fn, args = _jitted()
    cache.get_or_compile(fn, args, _identity())
    (path,) = _entry_paths(tmp_path)
    header, blob = ac._read_entry(path)
    header["key"]["jax"] = "0.0.1-foreign"
    ac._write_entry(path, header, blob)

    stats2 = StatSet()
    cache2 = ac.AOTCache(str(tmp_path), stats=stats2)
    with caplog.at_level("WARNING", logger="paddle_tpu.aot_cache"):
        exe = cache2.get_or_compile(fn, _jitted()[1], _identity())
    assert cache2.compiles == 1 and cache2.loads == 0
    assert stats2.count("aot_cache/stale") == 1
    assert any("jax" in r.getMessage() for r in caplog.records)
    # the retrace OVERWROTE the stale entry: next boot is warm again
    cache3 = ac.AOTCache(str(tmp_path), stats=StatSet())
    assert cache3.load(_identity()) is not None
    out = exe(*_jitted()[1])
    np.testing.assert_allclose(np.asarray(out["b"]), 2.0)


def test_foreign_topology_entry_never_loads(tmp_path):
    """A valid entry for a DIFFERENT program renamed into this identity's
    path (hash collision stand-in): the full-key comparison rejects it —
    wrong code can never load."""
    stats = StatSet()
    cache = ac.AOTCache(str(tmp_path), stats=stats)
    fn, args = _jitted()
    foreign = dict(_identity(), topology="OTHER-NET")
    cache.get_or_compile(fn, args, foreign)
    os.rename(cache.entry_path(foreign), cache.entry_path(_identity()))

    stats2 = StatSet()
    cache2 = ac.AOTCache(str(tmp_path), stats=stats2)
    assert cache2.load(_identity()) is None
    assert stats2.count("aot_cache/stale") == 1
    exe = cache2.get_or_compile(fn, _jitted()[1], _identity())
    assert cache2.compiles == 1
    out = exe(*_jitted()[1])
    np.testing.assert_allclose(np.asarray(out["b"]), 2.0)


def test_meta_mismatch_is_stale(tmp_path):
    """Same program identity, different hyperparameters (the optimizer
    fingerprint): the old executable bakes the old constants — stale."""
    cache = ac.AOTCache(str(tmp_path), stats=StatSet())
    fn, args = _jitted()
    cache.get_or_compile(fn, args, _identity(), {"optimizer": "lr=0.1"})
    stats2 = StatSet()
    cache2 = ac.AOTCache(str(tmp_path), stats=stats2)
    assert cache2.load(_identity(), {"optimizer": "lr=0.01"}) is None
    assert stats2.count("aot_cache/stale") == 1


def test_optimizer_fingerprint_distinguishes_hyperparams():
    a = ac.optimizer_fingerprint(paddle.optimizer.Adam(learning_rate=1e-2))
    b = ac.optimizer_fingerprint(paddle.optimizer.Adam(learning_rate=1e-3))
    c = ac.optimizer_fingerprint(
        paddle.optimizer.Momentum(momentum=0.9, learning_rate=1e-2)
    )
    d = ac.optimizer_fingerprint(
        paddle.optimizer.Adam(
            learning_rate=1e-2, learning_rate_schedule="exp",
            learning_rate_decay_a=0.5, learning_rate_decay_b=100.0,
        )
    )
    assert len({a, b, c, d}) == 4


# ---------------------------------------------------------------------------
# maintenance: ls / prune / clear
# ---------------------------------------------------------------------------


def test_prune_drops_oldest_until_fit(tmp_path):
    cache = ac.AOTCache(str(tmp_path), stats=StatSet())
    fn, args = _jitted()
    for i in range(3):
        cache.get_or_compile(fn, args, _identity(n=i))
        path = cache.entry_path(_identity(n=i))
        os.utime(path, (i + 1, i + 1))  # deterministic age order
    sizes = {e["file"]: e["bytes"] for e in cache.entries()}
    keep_newest = cache.entry_path(_identity(n=2))
    removed = cache.prune(max_bytes=sizes[os.path.basename(keep_newest)])
    assert len(removed) == 2
    assert os.path.exists(keep_newest)
    assert cache.load(_identity(n=2)) is not None


def test_prune_and_clear_sweep_orphaned_tmp_files(tmp_path):
    """A writer SIGKILLed mid-_write_entry leaves <hash>.aotx.tmp.<pid>;
    the maintenance commands must reclaim it even though it is not a
    listable entry."""
    cache = ac.AOTCache(str(tmp_path), stats=StatSet())
    fn, args = _jitted()
    cache.get_or_compile(fn, args, _identity())
    orphan = os.path.join(str(tmp_path), "deadbeef.aotx.tmp.12345")
    with open(orphan, "wb") as f:
        f.write(b"x" * 1024)
    assert all("tmp" not in e["file"] for e in cache.entries())
    removed = cache.prune(max_bytes=1 << 30)  # fits: only the tmp goes
    assert os.path.basename(orphan) in removed
    assert not os.path.exists(orphan)
    with open(orphan, "wb") as f:
        f.write(b"x")
    assert cache.clear() == 2  # the entry + the orphan
    assert os.listdir(str(tmp_path)) == []


def test_clear_empties_store(tmp_path):
    cache = ac.AOTCache(str(tmp_path), stats=StatSet())
    fn, args = _jitted()
    cache.get_or_compile(fn, args, _identity())
    assert len(cache.entries()) == 1
    assert cache.clear() == 1
    assert cache.entries() == []


# ---------------------------------------------------------------------------
# SGD integration: dispatch table + warm_compile
# ---------------------------------------------------------------------------


def _model():
    reset_auto_names()
    x = paddle.layer.data("x", paddle.data_type.dense_vector(6))
    h = paddle.layer.fc(x, size=8, act=paddle.activation.Relu())
    pred = paddle.layer.fc(h, size=3, act=paddle.activation.Softmax())
    y = paddle.layer.data("y", paddle.data_type.integer_value(3))
    return paddle.layer.classification_cost(input=pred, label=y)


def _samples(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return [
        (rng.randn(6).astype(np.float32), int(rng.randint(3)))
        for _ in range(n)
    ]


def _train(num_passes=2, seed=0):
    cost = _model()
    params = paddle.parameters.create(cost, seed=0)
    tr = paddle.trainer.SGD(
        cost=cost, parameters=params, seed=seed,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-2),
    )
    s = _samples()

    def reader():
        yield from s

    tr.train(reader=paddle.batch(reader, 4), num_passes=num_passes,
             async_load_data=False)
    return tr


def test_sgd_aot_dispatch_cold_then_warm_trainer(tmp_path):
    """Two trainers sharing one cache dir: the second resolves every shape
    by deserializing — zero compiles — and trains to bit-identical
    params."""
    set_flag("aot_cache_dir", str(tmp_path))
    t1 = _train()
    assert t1._aot_cache.compiles >= 1
    global_stats.reset()
    t2 = _train()
    assert t2._aot_cache.compiles == 0
    assert t2._aot_cache.loads >= 1
    for name in t1.parameters.params:
        for k, v in t1.parameters.params[name].items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(t2.parameters.params[name][k]),
                err_msg=f"{name}.{k} diverged cold vs warm",
            )


def test_sgd_without_flag_has_no_cache(tmp_path):
    t = _train(num_passes=1)
    assert t._aot_cache is None
    assert os.listdir(str(tmp_path)) == []


def test_warm_compile_populates_without_stepping(tmp_path):
    import jax

    set_flag("aot_cache_dir", str(tmp_path))
    cost = _model()
    params = paddle.parameters.create(cost, seed=0)
    tr = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-2),
    )
    before = jax.tree_util.tree_map(np.asarray, tr.parameters.params)
    from paddle_tpu.core.batch import SeqTensor

    batch = {
        "x": SeqTensor(np.zeros((4, 6), np.float32)),
        "y": SeqTensor(np.zeros((4,), np.int32)),
    }
    assert tr.warm_compile(batch) is True
    assert tr.warm_compile(batch) is False  # shape already resolved
    assert tr._aot_cache.compiles == 1
    after = jax.tree_util.tree_map(np.asarray, tr.parameters.params)
    for name in before:
        for k in before[name]:
            np.testing.assert_array_equal(before[name][k], after[name][k])


# ---------------------------------------------------------------------------
# subprocess e2e: a second PROCESS warm-boots from the first's cache
# ---------------------------------------------------------------------------

_CHILD = """
import json, sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.core.topology import reset_auto_names
from paddle_tpu.utils.flags import set_flag
from paddle_tpu.utils.timers import global_stats

set_flag("aot_cache_dir", sys.argv[1])
set_flag("cache_pass_in_mem", True)
set_flag("whole_pass_program", True)

def model():
    reset_auto_names()
    x = paddle.layer.data("x", paddle.data_type.dense_vector(6))
    h = paddle.layer.fc(x, size=8, act=paddle.activation.Relu())
    pred = paddle.layer.fc(h, size=3, act=paddle.activation.Softmax())
    y = paddle.layer.data("y", paddle.data_type.integer_value(3))
    return paddle.layer.classification_cost(input=pred, label=y)

def train(batch_size, passes):
    cost = model()
    params = paddle.parameters.create(cost, seed=0)
    tr = paddle.trainer.SGD(cost=cost, parameters=params, seed=0,
                            update_equation=paddle.optimizer.Adam(
                                learning_rate=1e-2))
    rng = np.random.RandomState(0)
    s = [(rng.randn(6).astype(np.float32), int(rng.randint(3)))
         for _ in range(16)]
    def reader():
        yield from s
    tr.train(reader=paddle.batch(reader, batch_size), num_passes=passes,
             async_load_data=False)
    return tr

# run A: two ladder rungs (full 6-row batches + the ragged 4-row tail),
# stepwise; run B: single rung, whole-pass epoch program for passes >= 2
t1 = train(6, 1)
t2 = train(4, 3)
leaf = np.asarray(
    next(iter(t2.parameters.params["__fc_layer_0__"].values()))
)
print(json.dumps({
    "compiles": t1._aot_cache.compiles + t2._aot_cache.compiles,
    "loads": t1._aot_cache.loads + t2._aot_cache.loads,
    "hit": global_stats.count("aot_cache/hit"),
    "miss": global_stats.count("aot_cache/miss"),
    "stale": global_stats.count("aot_cache/stale"),
    "corrupt": global_stats.count("aot_cache/corrupt"),
    "epoch_dispatches": global_stats.count("epoch_program/dispatches"),
    "fingerprint": float(np.abs(leaf).sum()),
}))
"""


def _boot(tmp_path, cache_dir):
    script = os.path.join(str(tmp_path), "child.py")
    with open(script, "w") as f:
        f.write(_CHILD)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, script, cache_dir],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_subprocess_warm_boot_zero_retraces(tmp_path):
    """Acceptance: a second process against a populated cache performs
    ZERO full retraces for the rungs (train-step shapes + the whole-pass
    epoch program) the first process compiled — compile-counter-asserted —
    and trains to the identical trajectory."""
    cache_dir = os.path.join(str(tmp_path), "cache")
    cold = _boot(tmp_path, cache_dir)
    # 2 train-step rungs (6-row + 4-row: run A's ragged tail IS run B's
    # full rung, so run B hits run A's entry in-process) + the whole-pass
    # epoch program
    assert cold["compiles"] == 3, cold
    assert cold["miss"] == cold["compiles"]
    assert cold["hit"] == 1  # the cross-run 4-row reuse above
    assert cold["epoch_dispatches"] == 2  # passes 2 and 3: one each

    warm = _boot(tmp_path, cache_dir)
    assert warm["compiles"] == 0, warm  # the headline: zero retraces
    # 4 deserializations: run A loads its 2 rungs, run B its rung (its own
    # trainer-local executable table) + the epoch program
    assert warm["loads"] == 4 and warm["hit"] == 4
    assert warm["miss"] == 0
    assert warm["stale"] == 0 and warm["corrupt"] == 0
    assert warm["fingerprint"] == cold["fingerprint"]


# ---------------------------------------------------------------------------
# CLI face
# ---------------------------------------------------------------------------


def _run_cli(args, cwd=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu", *args],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=600,
    )


def _write_v1_config(tmp_path):
    (tmp_path / "conf.py").write_text(
        "from paddle.trainer_config_helpers import *\n"
        "define_py_data_sources2(train_list='t', test_list=None,\n"
        "                        module='prov', obj='process')\n"
        "settings(batch_size=4, learning_rate=1e-3,\n"
        "         learning_method=AdamOptimizer())\n"
        "img = data_layer(name='pixel', size=12)\n"
        "lbl = data_layer(name='label', size=3)\n"
        "fc1 = fc_layer(input=img, size=3, act=SoftmaxActivation())\n"
        "outputs(classification_cost(input=fc1, label=lbl))\n"
    )
    (tmp_path / "prov.py").write_text(
        "from paddle.trainer.PyDataProvider2 import *\n"
        "@provider(input_types=[dense_vector(12), integer_value(3)],\n"
        "          should_shuffle=False)\n"
        "def process(settings, f):\n"
        "    for i in range(16):\n"
        "        yield [0.125 * (i % 8)] * 12, i % 3\n"
    )
    (tmp_path / "t").write_text("dummy\n")
    return str(tmp_path / "conf.py")


@pytest.mark.slow
def test_cache_cli_warm_ls_prune_clear(tmp_path):
    cfg = _write_v1_config(tmp_path)
    d = str(tmp_path / "cache")
    r = _run_cli(["cache", "warm", "--dir", d, "--config", cfg])
    assert r.returncode == 0, r.stderr[-2000:]
    cold = json.loads(r.stdout.strip().splitlines()[-1])
    assert cold["compiles"] >= 1 and cold["entries"] >= 1

    r = _run_cli(["cache", "warm", "--dir", d, "--config", cfg])
    warm = json.loads(r.stdout.strip().splitlines()[-1])
    assert warm["compiles"] == 0 and warm["loads"] == cold["compiles"]
    assert warm["warm_s"] < cold["warm_s"]

    r = _run_cli(["cache", "ls", "--dir", d])
    assert r.returncode == 0
    assert "kind=train_step" in r.stdout  # key provenance listed

    r = _run_cli(["cache", "prune", "--dir", d, "--max-mb", "0"])
    assert r.returncode == 0
    assert json.loads(r.stdout.strip().splitlines()[-1])["entries"] == 0

    _run_cli(["cache", "warm", "--dir", d, "--config", cfg])
    r = _run_cli(["cache", "clear", "--dir", d])
    assert json.loads(r.stdout.strip().splitlines()[-1])["entries"] == 0
