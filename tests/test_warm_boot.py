"""The warm boot that `setup_s` is measured through, for every kind of program
the system dispatches: a second process on the same
``JAX_COMPILATION_CACHE_DIR`` loads each of them from jax's persistent cache,
compiles none, and computes what the first process computed to the last bit;
a program that differs (a layer's width, the compute dtype, the divergence
sentinel) is compiled anew and never served another program's executable.

One fixture boots a child script in fresh processes: all kinds against an
empty cache directory, then all kinds again against the directory the first
boot filled; and, for each change, the changed program once against that
filled directory and once against an empty one of its own.  The child reads
the counters of the program's own ``jax.monitoring`` listener
(``utils/compile_cache.py``: ``jit/compile``, ``jit/cache_hit``,
``jit/cache_miss`` in ``global_stats``; a backend-compile event fires for a
hit too, so what was really compiled is the difference), which makes every
case here a test of that listener: ``cache`` reads miss on the empty
directory and hit on the filled one.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KINDS = [
    "train:demo_mnist_mlp", "train:demo_text_lstm", "train:demo_seq2seq_attention",
    "train:transformer", "train:hybrid_lm", "train:looped_lm", "train:data_mesh4",
    "eval:demo_mnist_mlp", "eval:demo_text_lstm", "eval:demo_seq2seq_attention",
    "infer:forward", "generate:beam",
    "serving:prefill", "serving:decode", "serving:beam",
    "grad_step",
]
CHANGES = ["width", "bfloat16", "sentinel_off"]
CHANGED_KIND = "train:demo_mnist_mlp"

CHILD = r'''
"""One boot: builds every kind of program the system dispatches, at toy
widths, and prints one JSON object {kind: {"backend_compiles", "cache_hits",
"cache_misses", "value"}} as its last line.
argv: <repo> <tmp dir> [--only KIND] [--change C]"""
import json
import os
import sys

REPO, TMP = sys.argv[1], sys.argv[2]
ONLY = sys.argv[sys.argv.index("--only") + 1] if "--only" in sys.argv else None
CHANGE = sys.argv[sys.argv.index("--change") + 1] if "--change" in sys.argv else None
sys.path.insert(0, REPO)

import jax
import numpy as np

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import paddle_tpu as paddle
from paddle_tpu.core.topology import reset_auto_names
from paddle_tpu.utils import flags
from paddle_tpu.utils.timers import global_stats
from paddle_tpu.v1_compat import make_optimizer, parse_config

paddle.init(compute_dtype="bfloat16" if CHANGE == "bfloat16" else "float32", seed=0)
if CHANGE == "sentinel_off":
    flags.set_flag("divergence_sentinel", False)

T = paddle.data_type
NMT_FEEDING = {"src_word": 0, "trg_word": 1, "trg_next": 2}
RESULTS = {}


def exact(x):
    """A value that compares equal only where every bit does."""
    a = np.asarray(x)
    return [a.dtype.str, list(a.shape), a.tobytes().hex()]


def counts():
    """What the program's own listener (utils/compile_cache.py) has counted:
    a backend-compile event fires for a load from the cache too."""
    return {"backend_compiles": global_stats.count("jit/compile"),
            "cache_hits": global_stats.count("jit/cache_hit"),
            "cache_misses": global_stats.count("jit/cache_miss")}


def kind(name):
    def wrap(fn):
        if ONLY in (None, name):
            before = counts()
            value = fn()
            RESULTS[name] = {k: n - before[k] for k, n in counts().items()}
            RESULTS[name]["value"] = value
        return fn
    return wrap


def ids(rng, vocab, n):
    return rng.randint(2, vocab, size=n).tolist()


def pairs(rng, n, src_vocab, trg_vocab):
    out = []
    for _ in range(n):
        src = ids(rng, src_vocab, rng.randint(3, 8))
        trg = ids(rng, trg_vocab, rng.randint(3, 8))
        out.append((src, [0] + trg[:-1], trg))
    return out


def demo(name):
    """A topology of tests/configs/ with its slots typed as a provider's
    declaration types them, its samples and its feeding."""
    rng = np.random.RandomState(0)
    text = open(os.path.join(REPO, "tests", "configs", name + ".py")).read()
    if CHANGE == "width":
        assert "size=128" in text
        text = text.replace("size=128", "size=96")
    path = os.path.join(TMP, name + ".py")
    with open(path, "w") as f:
        f.write(text)
    reset_auto_names()
    parsed = parse_config(path, "")
    if name == "demo_mnist_mlp":
        types = {"pixel": T.dense_vector(784), "label": T.integer_value(10)}
        samples = [(rng.randn(784).astype(np.float32), int(rng.randint(10))) for _ in range(8)]
        feeding = {"pixel": 0, "label": 1}
    elif name == "demo_text_lstm":
        types = {"word": T.integer_value_sequence(100), "label": T.integer_value(2)}
        samples = [(ids(rng, 100, rng.randint(3, 8)), int(rng.randint(2))) for _ in range(8)]
        feeding = {"word": 0, "label": 1}
    else:
        types = {"src_word": T.integer_value_sequence(40), "trg_word": T.integer_value_sequence(45),
                 "trg_next": T.integer_value_sequence(45)}
        samples = pairs(rng, 8, 40, 45)
        feeding = NMT_FEEDING
    for conf in parsed.topology.data_layers().values():
        object.__setattr__(conf, "input_type", types[conf.name])
        conf.attrs.pop("_v1_size_only", None)
    return parsed, samples, feeding


def first_cost(trainer, samples, feeding):
    costs = []
    trainer.train(paddle.batch(lambda: iter(samples), len(samples)), num_passes=1,
                  event_handler=lambda e: costs.append(e.cost)
                  if isinstance(e, paddle.event.EndIteration) else None,
                  feeding=feeding)
    return exact(np.float32(costs[0]))


def demo_trainer(parsed, mesh=None):
    return paddle.trainer.SGD(
        cost=parsed.topology, parameters=paddle.parameters.create(parsed.topology, seed=0),
        update_equation=make_optimizer(parsed.settings), mesh=mesh)


TRAINERS = {}
for name in ("demo_mnist_mlp", "demo_text_lstm", "demo_seq2seq_attention"):
    @kind("train:" + name)
    def _(name=name):
        parsed, samples, feeding = demo(name)
        TRAINERS[name] = demo_trainer(parsed), samples, feeding
        return first_cost(*TRAINERS[name])


def adam():
    return paddle.optimizer.Adam(learning_rate=1e-3)


@kind("train:transformer")
def _():
    from paddle_tpu.models.transformer import transformer_cost

    reset_auto_names()
    cost, _ = transformer_cost(40, 45, d_model=16, n_heads=2, n_layers=1, d_ff=32)
    trainer = paddle.trainer.SGD(cost=cost, parameters=paddle.parameters.create(cost, seed=0),
                                 update_equation=adam())
    return first_cost(trainer, pairs(np.random.RandomState(1), 4, 40, 45), NMT_FEEDING)


@kind("train:hybrid_lm")
def _():
    from paddle_tpu.models.hybrid_lm import hybrid_lm_cost

    reset_auto_names()
    cost, _ = hybrid_lm_cost(
        "ME", 50, 16, mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=4, chunk_size=4,
        attn_heads=2, attn_kv_heads=1, attn_head_dim=8, num_experts=4, experts_per_token=2,
        expert_hidden=8, shared_hidden=16)
    trainer = paddle.trainer.SGD(cost=cost, parameters=paddle.parameters.create(cost, seed=0),
                                 update_equation=adam())
    rng = np.random.RandomState(2)
    rows = [ids(rng, 50, 9) for _ in range(2)]
    return first_cost(trainer, [(r[:-1], r[1:]) for r in rows], {"word": 0, "next_word": 1})


@kind("train:looped_lm")
def _():
    from paddle_tpu.models.looped_lm import looped_lm_cost

    reset_auto_names()
    cost, _ = looped_lm_cost(50, 16, n_layers=1, n_passes=2, n_heads=2, head_dim=8, intermediate=24,
                             exit_beta=0.05)
    trainer = paddle.trainer.SGD(cost=cost, parameters=paddle.parameters.create(cost, seed=0),
                                 update_equation=adam())
    rng = np.random.RandomState(3)
    rows = [ids(rng, 50, 9) for _ in range(2)]
    return first_cost(trainer, [(r[:-1], r[1:]) for r in rows], {"word": 0, "next_word": 1})


@kind("train:data_mesh4")
def _():
    from paddle_tpu.parallel.mesh import make_mesh

    parsed, samples, feeding = demo("demo_mnist_mlp")
    mesh = make_mesh(data=4, devices=jax.devices()[:4])
    return first_cost(demo_trainer(parsed, mesh), samples, feeding)


for name in ("demo_mnist_mlp", "demo_text_lstm", "demo_seq2seq_attention"):
    @kind("eval:" + name)
    def _(name=name):
        if name not in TRAINERS:
            parsed, samples, feeding = demo(name)
            TRAINERS[name] = demo_trainer(parsed), samples, feeding
        trainer, samples, feeding = TRAINERS[name]
        result = trainer.test(paddle.batch(lambda: iter(samples), len(samples)), feeding=feeding)
        return exact(np.float32(result.cost))


@kind("infer:forward")
def _():
    reset_auto_names()
    x = paddle.layer.data("x", T.dense_vector(12))
    h = paddle.layer.fc(x, size=8, act=paddle.activation.Tanh())
    out = paddle.layer.fc(h, size=3, act=paddle.activation.Softmax())
    params = paddle.parameters.create(out, seed=0)
    rng = np.random.RandomState(3)
    return exact(paddle.infer(out, params, [(rng.randn(12).astype(np.float32),) for _ in range(4)]))


GEN = {}


def generator():
    if not GEN:
        from paddle_tpu.models.seq2seq import Seq2SeqGenerator, seq2seq_cost

        reset_auto_names()
        cost, _ = seq2seq_cost(20, 20, word_dim=8, hidden_dim=12)
        GEN["gen"] = Seq2SeqGenerator(
            paddle.parameters.create(cost, seed=5), 20, 20, word_dim=8, hidden_dim=12,
            bos_id=0, eos_id=1, max_length=8)
    return GEN["gen"]


@kind("generate:beam")
def _():
    from paddle_tpu.reader.feeder import DataFeeder

    gen = generator()
    feeder = DataFeeder([("src_word", T.integer_value_sequence(20))], {"src_word": 0})
    seqs, scores = gen.generate(feeder([([3, 7, 4, 9, 2],)]), beam_size=3)
    return [exact(seqs), exact(scores)]


def engine():
    if "eng" not in GEN:
        from paddle_tpu.serving import ServingEngine
        from paddle_tpu.utils.timers import StatSet

        GEN["eng"] = ServingEngine(generator(), max_slots=4, hbm_budget_mb=2,
                                   max_new_tokens=8, stats=StatSet())
    return GEN["eng"]


def drain(eng):
    done = []
    for _ in range(100):
        done += eng.step()
        if not (eng.n_live or eng.n_prefilling):
            return done
    raise AssertionError("the engine did not drain")


@kind("serving:prefill")
def _():
    from paddle_tpu.serving import Request

    eng = engine()
    assert len(eng.admit([Request([3, 7, 4, 9, 2])])) == 1
    return exact(eng._h)


@kind("serving:decode")
def _():
    eng = engine()
    if not eng.n_live:
        from paddle_tpu.serving import Request

        eng.admit([Request([3, 7, 4, 9, 2])])
    (done,) = drain(eng)
    return exact(np.asarray(done.tokens, np.int32))


@kind("serving:beam")
def _():
    from paddle_tpu.serving import Request

    eng = engine()
    eng.admit([Request([3, 7, 4, 9, 2], beam_size=3)])
    (done,) = drain(eng)
    return [exact(np.asarray(done.tokens, np.int32)), exact(np.float32(done.beam_score))]


@kind("grad_step")
def _():
    from paddle_tpu.parallel.mesh import shard_batch
    from paddle_tpu.trainer.step import make_grad_step

    parsed, samples, feeding = demo("demo_mnist_mlp")
    trainer = demo_trainer(parsed)
    batch = shard_batch(trainer._make_feeder(feeding)(samples), trainer.mesh)
    grads, cost = make_grad_step(trainer.network, trainer.mesh)(
        trainer.parameters.params, trainer.parameters.state, batch, jax.random.PRNGKey(0))
    return [exact(cost)] + [exact(g) for g in jax.tree_util.tree_leaves(grads)]


print(json.dumps(RESULTS), flush=True)
'''


def _start(script, tmp, cache, *args):
    os.makedirs(tmp, exist_ok=True)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    return subprocess.Popen(
        [sys.executable, str(script), REPO, str(tmp), *args], env=env, cwd=str(tmp),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _result(proc):
    out, err = proc.communicate(timeout=900)
    assert proc.returncode == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def boots(tmp_path_factory):
    root = tmp_path_factory.mktemp("warm_boot")
    script = root / "child.py"
    script.write_text(CHILD)
    shared = root / "cache"

    def changed(c, where, cache):
        return _start(script, root / f"{c}_{where}", cache, "--only", CHANGED_KIND, "--change", c)

    # the first wave fills the caches, the second reads them
    cold = _start(script, root / "cold", shared)
    changed_cold = {c: changed(c, "cold", root / f"cache_{c}") for c in CHANGES}
    got = {"cold": _result(cold)}
    got.update({(c, "cold"): _result(p)[CHANGED_KIND] for c, p in changed_cold.items()})
    warm = _start(script, root / "warm", shared)
    changed_warm = {c: changed(c, "warm", shared) for c in CHANGES}
    got["warm"] = _result(warm)
    got.update({(c, "warm"): _result(p)[CHANGED_KIND] for c, p in changed_warm.items()})
    return got


def _compiled(counts):
    return counts["backend_compiles"] - counts["cache_hits"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_second_process_loads_the_program_and_compiles_nothing(boots, kind):
    cold, warm = boots["cold"][kind], boots["warm"][kind]
    assert _compiled(cold) >= 1, cold
    assert _compiled(warm) == 0 and warm["cache_hits"] >= 1, warm
    # every compile asked the cache, and was counted as the one or the other
    assert cold["cache_misses"] == _compiled(cold) and warm["cache_misses"] == 0
    assert warm["value"] == cold["value"]


@pytest.mark.parametrize("change", CHANGES)
def test_a_changed_program_is_compiled_anew(boots, change):
    on_the_filled_cache, on_an_empty_one = boots[change, "warm"], boots[change, "cold"]
    assert _compiled(on_the_filled_cache) >= 1, on_the_filled_cache
    assert on_the_filled_cache["value"] == on_an_empty_one["value"]
