"""The ``paddle train`` CLI face (paddle_tpu/cli.py) — subprocess tests.

Reference parity: paddle/trainer/TrainerMain.cpp:32-65 (the paddle_trainer
binary and its --job dispatch), paddle/scripts/submit_local.sh.in (the
``paddle`` wrapper's subcommands), TrainerBenchmark.cpp:71 (--job=time).
The fast tests drive the reference's own self-contained OnePass fixture
(sample_trainer_config_opt_a.conf + the checked-in mnist_bin_part); the
slow tests run the reference's real demo dirs (v1_api_demo/mnist,
quick_start) from a shell, unmodified, with synthesized data files.
"""

import json
import os
import shutil
import struct
import subprocess
import sys

import numpy as np
import pytest

REF = "/root/reference"
REF_TESTS = f"{REF}/paddle/trainer/tests"
OPT_A = f"{REF_TESTS}/sample_trainer_config_opt_a.conf"
# a config the repo holds, for the tests of this CLI's own faces
DEMO_MLP = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "configs", "demo_mnist_mlp.py"
)


def run_cli(args, cwd=None, timeout=900):
    """Run `python -m paddle_tpu <args>` like a user would from a shell."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # the package runs from the source tree in CI; a user would have it
    # pip-installed and need no PYTHONPATH
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu", *args],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=timeout,
    )


def test_help_lists_commands():
    r = run_cli(["--help"])
    assert r.returncode == 0
    for cmd in ("train", "version", "dump_config", "merge_model"):
        assert cmd in r.stdout


def test_unknown_command_fails():
    r = run_cli(["frobnicate"])
    assert r.returncode == 1
    assert "unknown command" in r.stderr


@pytest.mark.slow
def test_train_job_writes_pass_checkpoints(tmp_path):
    """`paddle-tpu train --config=... --save_dir=... --num_passes=...` on the
    reference's own OnePass config + binary data: two passes, pass-%05d dirs
    with params.tar + v1 per-parameter binaries (TrainerMain.cpp + the
    Trainer.cpp checkpoint cadence)."""
    save = tmp_path / "model"
    r = run_cli([
        "train", f"--config={OPT_A}", f"--save_dir={save}",
        "--num_passes=2", "--batch_size=200", "--dot_period=2",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Pass 0" in r.stdout and "Pass 1" in r.stdout
    for p in ("pass-00000", "pass-00001"):
        d = save / p
        assert (d / "params.tar").exists()
        assert (d / "__fc_layer_0__.w0").exists()  # v1 binary plane


@pytest.mark.slow
def test_test_job_evaluates_saved_model(tmp_path):
    """--job=test loads --init_model_path and reports cost + evaluator
    metrics (Tester.cpp)."""
    save = tmp_path / "model"
    r = run_cli([
        "train", f"--config={OPT_A}", f"--save_dir={save}",
        "--num_passes=1", "--batch_size=400",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    r = run_cli([
        "train", f"--config={OPT_A}", "--job=test",
        f"--init_model_path={save / 'pass-00000'}", "--batch_size=400",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Test cost" in r.stdout
    assert "classification_error" in r.stdout


@pytest.mark.slow
def test_time_job_prints_stat_table():
    """--job=time: burn-in + timed loop + the StatSet table
    (TrainerBenchmark.cpp:30-90)."""
    r = run_cli([
        "train", f"--config={OPT_A}", "--job=time",
        "--test_period=5", "--batch_size=100",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Burning time" in r.stdout
    assert "FwdBwd" in r.stdout
    assert "ms/batch" in r.stdout


@pytest.mark.slow
def test_checkgrad_job_passes():
    """--job=checkgrad: float64 directional finite differences vs the VJP
    (Trainer::checkGradient; fd accuracy from x64 like the reference's
    WITH_DOUBLE build)."""
    r = run_cli([
        "train", f"--config={OPT_A}", "--job=checkgrad", "--batch_size=8",
    ])
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "checkgrad PASSED" in r.stdout


def test_dump_config_prints_topology():
    r = run_cli(["dump_config", DEMO_MLP])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "fc __fc_layer_0__ size=128" in r.stdout and "pixel" in r.stdout


# ---------------------------------------------------------------------------
# the reference demo dirs, run from a shell the way their train.sh does
# ---------------------------------------------------------------------------

def _write_idx_mnist(prefix, n):
    """Raw MNIST idx files the demo's mnist_util.read_from_mnist expects:
    <prefix>-images-idx3-ubyte (16-byte header) and -labels-idx1-ubyte
    (8-byte header)."""
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 10, size=n).astype(np.uint8)
    # class-dependent pixels so one pass actually learns something
    images = (labels[:, None] * 20 + rng.randint(0, 40, size=(n, 784))).astype(
        np.uint8
    )
    with open(f"{prefix}-images-idx3-ubyte", "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(images.tobytes())
    with open(f"{prefix}-labels-idx1-ubyte", "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(labels.tobytes())


@pytest.mark.slow
def test_v1_api_demo_mnist_runs_from_shell(tmp_path):
    """The README path: copy the reference's v1_api_demo/mnist dir verbatim,
    synthesize the raw MNIST files its provider reads, and run
    `paddle-tpu train --config=light_mnist.py` from the demo dir exactly like
    its train.sh runs `paddle train` — checkpoints land in pass-%05d/.

    NB the test name must not contain 'train': pytest puts it in tmp_path,
    and the demo's mnist_util.read_from_mnist keys its sample count on
    `"train" in filename` (60000 vs 10000)."""
    demo = tmp_path / "mnist_demo"
    shutil.copytree(f"{REF}/v1_api_demo/mnist", demo)
    raw = demo / "data" / "raw_data"
    raw.mkdir(parents=True)
    _write_idx_mnist(str(raw / "t10k"), 10000)  # 't10k' => n=10000 branch
    (demo / "data" / "train.list").write_text("data/raw_data/t10k\n")
    (demo / "data" / "test.list").write_text("data/raw_data/t10k\n")
    save = demo / "mnist_model"
    r = run_cli(
        [
            "train", "--config=light_mnist.py", f"--save_dir={save}",
            "--num_passes=1", "--batch_size=1000", "--use_gpu=0",
            "--trainer_count=1", "--dot_period=10", "--log_period=100",
        ],
        cwd=str(demo),
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "Pass 0" in r.stdout
    assert (save / "pass-00000" / "params.tar").exists()


@pytest.mark.slow
def test_quick_start_trains_from_shell(tmp_path):
    """quick_start parity: the reference's trainer_config.lr.py + its own
    dataprovider_bow provider, run from the shell with synthesized
    '<label>\\t<text>' data (demo/quick_start/train.sh shape)."""
    demo = tmp_path / "qs_demo"
    shutil.copytree(f"{REF}/v1_api_demo/quick_start", demo, dirs_exist_ok=True)
    data = demo / "data"
    data.mkdir(exist_ok=True)
    rng = np.random.RandomState(0)
    words = [f"w{i}" for i in range(100)]
    (data / "dict.txt").write_text(
        "\n".join(f"{w}\t{i}" for i, w in enumerate(words))
    )
    lines = []
    for _ in range(400):
        label = rng.randint(2)
        base = 10 if label else 60
        toks = [words[base + rng.randint(20)] for _ in range(rng.randint(3, 8))]
        lines.append(f"{label}\t{' '.join(toks)}")
    (data / "train.txt").write_text("\n".join(lines))
    (data / "train.list").write_text("data/train.txt\n")
    (data / "test.list").write_text("data/train.txt\n")
    save = demo / "output"
    r = run_cli(
        [
            "train", "--config=trainer_config.lr.py",
            "--config_args=dict_file=data/dict.txt",
            f"--save_dir={save}", "--num_passes=1", "--batch_size=100",
        ],
        cwd=str(demo),
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert (save / "pass-00000" / "params.tar").exists()


@pytest.mark.slow
def test_merge_model_roundtrip(tmp_path):
    """merge_model bundles a pass dir + config into one file the inference
    face can load (submit_local.sh.in merge_model / paddle_merge_model)."""
    save = tmp_path / "model"
    r = run_cli([
        "train", f"--config={OPT_A}", f"--save_dir={save}",
        "--num_passes=1", "--batch_size=400",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    bundle = tmp_path / "merged.paddle"
    r = run_cli([
        "merge_model", f"--model_dir={save / 'pass-00000'}",
        f"--config_file={OPT_A}", f"--model_file={bundle}",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert bundle.exists() and bundle.stat().st_size > 1000
    # the bundle round-trips through --init_model_path (detected as a
    # merged bundle, not a bare params.tar)
    r = run_cli([
        "train", f"--config={OPT_A}", "--job=test",
        f"--init_model_path={bundle}", "--batch_size=400",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Test cost" in r.stdout


def _write_provider_config(d):
    """A config with a provider of its own beside it: what `train` needs to
    get as far as its reader."""
    (d / "conf.py").write_text(
        "from paddle.trainer_config_helpers import *\n"
        "define_py_data_sources2(train_list='train.list', test_list=None,\n"
        "                        module='prov', obj='process')\n"
        "settings(batch_size=4, learning_rate=1e-3,\n"
        "         learning_method=MomentumOptimizer())\n"
        "img = data_layer(name='pixel', size=12)\n"
        "lbl = data_layer(name='label', size=3)\n"
        "fc1 = fc_layer(input=img, size=3, act=SoftmaxActivation())\n"
        "outputs(classification_cost(input=fc1, label=lbl))\n"
    )
    (d / "prov.py").write_text(
        "from paddle.trainer.PyDataProvider2 import *\n"
        "@provider(input_types=[dense_vector(12), integer_value(3)])\n"
        "def process(settings, f):\n"
        "    for i in range(16):\n"
        "        yield [0.125 * (i % 8)] * 12, i % 3\n"
    )
    (d / "train.list").write_text("dummy\n")
    return d / "conf.py"


def test_reference_train_sh_flag_lines_accepted(tmp_path):
    """A reference train.sh command line (mnist/train.sh passes
    --test_all_data_in_one_period and friends) must run — unknown gflags
    are warned about, never fatal."""
    conf = _write_provider_config(tmp_path)
    r = run_cli([
        "train", f"--config={conf}", "--num_passes=0", "--batch_size=400",
        "--test_all_data_in_one_period=1", "--num_gradient_servers=1",
        "--nics=eth0", "--ports_num=1",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ignoring reference trainer flags" in r.stderr

    # typos of SUPPORTED flags and stray tokens stay fatal — a multi-hour
    # run must not silently drop --save_dir because of a typo
    r = run_cli([
        "train", f"--config={conf}", "--num_passes=0", "--save_dri=/tmp/x",
    ])
    assert r.returncode == 2
    assert "unrecognized arguments" in r.stderr
    r = run_cli(["train", f"--config={conf}", "num_passes=5"])
    assert r.returncode == 2

    # gflags separate-value and --no<flag> boolean-negation spellings of
    # ignored reference flags must also pass, including negative values
    r = run_cli([
        "train", f"--config={conf}", "--num_passes=0",
        "--nics", "eth0", "--gpu_id", "-1", "--nolocal", "--notest_wait",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ignoring reference trainer flags" in r.stderr

    # a stray key=value token after a BOOLEAN ignored flag is NOT its
    # value — it stays a hard error (would otherwise silently drop a
    # mistyped option)
    r = run_cli([
        "train", f"--config={conf}", "--nolocal", "batch_size=32",
    ])
    assert r.returncode == 2
    assert "unrecognized arguments" in r.stderr


@pytest.mark.slow
def test_start_pass_resumes_from_save_dir(tmp_path):
    """--start_pass=N without --init_model_path resumes from
    save_dir/pass-%05d (reference ParamUtil loadParametersWithPath)."""
    save = tmp_path / "model"
    r = run_cli([
        "train", f"--config={OPT_A}", f"--save_dir={save}",
        "--num_passes=1", "--batch_size=400",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    r = run_cli([
        "train", f"--config={OPT_A}", f"--save_dir={save}",
        "--num_passes=1", "--start_pass=1", "--batch_size=400",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Pass 1" in r.stdout
    assert (save / "pass-00001" / "params.tar").exists()


def test_make_diagram_writes_dot(tmp_path):
    """make_diagram renders a config to Graphviz dot
    (submit_local.sh.in make_diagram -> python -m paddle.utils.make_model_diagram)."""
    out = tmp_path / "net.dot"
    r = run_cli(["make_diagram", DEMO_MLP, str(out)])
    assert r.returncode == 0, r.stderr[-2000:]
    text = out.read_text()
    assert text.startswith("digraph")
    assert "__fc_layer_0__" in text
