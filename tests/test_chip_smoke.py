"""chip_smoke.py's legs at toy sizes on the CPU — the same functions the
script runs at full size on the chip — plus its refusal to run without a
TPU, and the compile-cache placement helper."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from paddle_tpu.core.compiler import (
    get_default_compute_dtype,
    set_default_compute_dtype,
)
from paddle_tpu.utils import compile_cache
from paddle_tpu.utils.flags import reset_flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NMT_TOY = dict(vocab=200, word_dim=16, hidden_dim=16)


@pytest.fixture(scope="module")
def meter():
    # jax.monitoring listeners cannot be unregistered: one meter a process
    return chip_smoke.CompileMeter()


@pytest.fixture(autouse=True)
def _restore_globals():
    prev = get_default_compute_dtype()
    yield
    set_default_compute_dtype(prev)  # the legs call paddle.init(bfloat16)
    reset_flags()


def test_nmt_train_then_serve(meter):
    report, parameters = chip_smoke.nmt_train(
        meter, **NMT_TOY, batch_size=8, n_batches=3, passes=4,
        min_len=3, max_len=12,
    )
    assert report["steps"] == 12 and report["last_cost"] < report["first_cost"]
    assert report["batch_shapes"] >= 1 and report["step_ms"] > 0
    served = chip_smoke.nmt_serve(
        meter, parameters, **NMT_TOY, max_length=12, n_requests=4,
        min_len=3, max_len=12,
    )
    assert served["served"] == 4 and served["tokens"] >= 4
    assert served["trace_counts"]["decode"] == served["decode_shapes"]


def test_nmt_train_data_parallel_spreads_the_batch(meter):
    from paddle_tpu.parallel.mesh import make_mesh

    n = 4
    mesh = make_mesh(data=n, devices=jax.devices()[:n])
    report, _ = chip_smoke.nmt_train(
        meter, **NMT_TOY, batch_size=8, n_batches=2, passes=2,
        min_len=3, max_len=12, mesh=mesh,
    )
    assert report["mesh_devices"] == n
    assert len(report["bytes_in_use_per_device"]) == n


def test_flash_kernels_agree_with_dense_in_interpret_mode():
    report = chip_smoke.flash_kernels(2, 256, 2, 8, interpret=True)
    assert report["blocks"] == [256, 256]
    assert max(report["kernel_max_rel_err"].values()) <= chip_smoke.FLASH_GRAD_RTOL


def test_flash_train_fails_when_the_kernel_is_not_in_the_program():
    """On the CPU the layer computes dense and says so; the leg must not
    take that for the kernel."""
    with pytest.warns(UserWarning, match="use_pallas_attention is on but"):
        with pytest.raises(AssertionError, match="not in the program"):
            chip_smoke.flash_train(
                vocab=50, d_model=16, n_heads=2, n_layers=1, d_ff=32,
                shapes=((2, 128),), steps=1,
            )


def test_resnet_train(meter):
    report = chip_smoke.resnet50_train(
        meter, depth=18, class_num=10, img_size=32, batch_size=4, steps=2,
    )
    assert report["steps"] == 2 and np.isfinite(report["costs"]).all()


def test_main_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO,
    )
    assert r.returncode not in (0, None)
    assert r.stdout == ""  # no result line where there is no chip
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr


def test_run_leg_reports_a_failure_and_goes_on(meter, capsys):
    def boom():
        raise ValueError("nope")

    ok, value = chip_smoke.run_leg("boom", meter, boom)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert (ok, value) == (False, None)
    assert '"ok": false' in line and "ValueError: nope" in line
    for field in ("platform", "device_kind", "device_count", "jax"):
        assert f'"{field}"' in line


@pytest.mark.parametrize("failing", [None, "resnet50_train"])
def test_last_line_has_exactly_the_keys_the_driver_reads(
        meter, capsys, monkeypatch, failing):
    """``run_all`` with stub legs: the last line of standard output is the
    result object and nothing more, and one failed leg fails the run."""
    import json

    def stub(name, value):
        def leg(*args, **kwargs):
            assert name != failing, "planted"
            return value
        monkeypatch.setattr(chip_smoke, name, leg)

    stub("nmt_train", ({"first_cost": 2.0}, "parameters"))
    for name in ("nmt_serve", "resnet50_train", "flash_attention"):
        stub(name, {})
    code = chip_smoke.run_all(meter)
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1] == {
        "ok": failing is None,
        "device": {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": jax.device_count()},
    }
    assert code == (0 if failing is None else 1)
    # more than one device here, so the data-parallel leg ran too
    assert lines[-2]["leg"] == "summary" and len(lines[-2]["legs"]) == 5
    assert [n for n, ok in lines[-2]["legs"].items() if not ok] == (
        [failing] if failing else [])


def test_compile_cache_dir_is_left_alone_when_the_variable_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.configure_compile_cache() == before
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        assert compile_cache.configure_compile_cache() == compile_cache.DEFAULT_DIR
        assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    finally:  # tier-1 must not write its cache into the checkout
        jax.config.update("jax_compilation_cache_dir", before)
