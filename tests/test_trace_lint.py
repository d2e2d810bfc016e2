"""Trace-hygiene analyzer (analysis/trace_lint.py): jaxpr-level hazard rules
fire on deliberate mutations, stay silent on the real train/generation
steps, and the recompile audit enforces the shape-ladder contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import (
    format_diagnostics,
    lint_jaxpr,
    lint_step,
    recompile_audit,
    trace_step,
)
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.topology import Topology, reset_auto_names


def rules(diags):
    return [d.rule for d in diags]


# ---------------------------------------------------------------------------
# mutations: each hazard fires with its exact rule id
# ---------------------------------------------------------------------------


def test_t101_f64_leak_detected():
    with jax.enable_x64(True):
        def leaky(x):
            return x * np.float64(2.0)

        d = lint_step(leaky, jnp.ones((4,), jnp.float64))
    assert "T101" in rules(d)


def test_t101_silent_in_f32():
    def clean(x):
        return x * 2.0

    d = lint_step(clean, jnp.ones((4,), jnp.float32))
    assert "T101" not in rules(d)


def test_t102_closure_captured_weights():
    w = np.ones((256, 256), np.float32)  # 64k elements, over threshold

    def step(x):
        return x @ w

    d = lint_step(step, np.ones((4, 256), np.float32))
    assert "T102" in rules(d)
    # as an ARGUMENT the same array is fine
    d2 = lint_step(lambda wt, x: x @ wt, w, np.ones((4, 256), np.float32))
    assert "T102" not in rules(d2)


def test_t102_threshold_respected():
    small = np.ones((8, 8), np.float32)

    def step(x):
        return x @ small

    assert "T102" not in rules(lint_step(step, np.ones((4, 8), np.float32)))


def test_t103_debug_print_in_hot_path():
    def step(x):
        jax.debug.print("sum={s}", s=x.sum())
        return x * 2

    d = lint_step(step, np.ones((4,), np.float32))
    assert "T103" in rules(d)


def test_t103_detects_inside_scan_body():
    def step(x):
        def body(c, xt):
            jax.debug.print("c={c}", c=c)
            return c + xt, c

        out, _ = jax.lax.scan(body, x[0], x)
        return out

    d = lint_step(step, np.ones((4,), np.float32))
    assert "T103" in rules(d)


# ---------------------------------------------------------------------------
# recompile audit (T104/T105)
# ---------------------------------------------------------------------------


def test_t104_off_ladder_shapes():
    keys = [
        (("x", (32, 17, 8), "float32"),),   # 17 is no rung
        (("x", (32, 32, 8), "float32"),),   # 32 is
    ]
    d = recompile_audit(keys)
    assert rules(d) == ["T104"]
    assert "x axis 1: [17]" in d[0].message


def test_t104_silent_on_ladder():
    keys = [(("x", (32, r, 8), "float32"),) for r in (16, 32, 64, 128)]
    assert recompile_audit(keys) == []


def test_t105_shape_explosion():
    keys = [(("x", (b, 32, 8), "float32"),) for b in range(1, 40)]
    d = recompile_audit(keys, max_shapes=10)
    assert "T105" in rules(d)


def test_audit_accepts_compile_shape_cache():
    from paddle_tpu.core.compiler import CompileShapeCache
    from paddle_tpu.utils.timers import StatSet

    cache = CompileShapeCache("t", stats=StatSet())
    for t in (17, 33):  # unladdered VARYING lengths: one compile per batch
        cache.observe({"x": SeqTensor(np.zeros((4, t, 3), np.float32),
                                      np.full((4,), t, np.int32))})
    d = recompile_audit(cache)
    assert "T104" in rules(d)


def test_audit_accepts_feeder_batches():
    batches = [
        {"x": SeqTensor(np.zeros((4, 16, 3), np.float32),
                        np.full((4,), 9, np.int32))},
        {"x": SeqTensor(np.zeros((4, 64, 3), np.float32),
                        np.full((4,), 40, np.int32))},
    ]
    assert recompile_audit(batches) == []


# ---------------------------------------------------------------------------
# the real steps stay clean (and the satellite regression: params-as-arg)
# ---------------------------------------------------------------------------


def _lenet_step():
    import paddle_tpu.optimizer as O
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.models.lenet import lenet_cost
    from paddle_tpu.trainer.step import _train_step_body

    reset_auto_names()
    cost, _ = lenet_cost()
    net = CompiledNetwork(Topology([cost]))
    params, state = net.init(jax.random.PRNGKey(0))
    opt = O.Adam(learning_rate=1e-3)
    step = _train_step_body(net, opt)
    batch = {
        "pixel": SeqTensor(np.random.rand(8, 784).astype(np.float32)),
        "label": SeqTensor(np.random.randint(0, 10, (8,)).astype(np.int32)),
    }
    return step, (params, state, opt.init(params), batch, jax.random.PRNGKey(1))


def test_train_step_is_hazard_free():
    step, args = _lenet_step()
    d = lint_step(step, *args)
    assert d == [], format_diagnostics(d)


def test_train_step_with_debug_print_flagged():
    """Control for the clean-step test: the same step with a debug print
    spliced in is caught — the linter sees through value_and_grad/jit."""
    step, args = _lenet_step()

    def noisy(params, state, opt_state, batch, rng):
        jax.debug.print("step")
        return step(params, state, opt_state, batch, rng)

    assert "T103" in rules(lint_step(noisy, *args))


@pytest.mark.slow
def test_generator_params_as_argument_no_t102():
    """Satellite regression (bench_nmt_generate fix): jitting the generator
    with weights passed as an ARGUMENT keeps them out of the jaxpr consts;
    the old closure form bakes in every weight (T102)."""
    from paddle_tpu.models.seq2seq import Seq2SeqGenerator, seq2seq_cost

    reset_auto_names()
    cost, _ = seq2seq_cost(40, 45, word_dim=16, hidden_dim=16)
    params = paddle.parameters.create(cost, seed=0)
    gen = Seq2SeqGenerator(
        params, 40, 45, word_dim=16, hidden_dim=16, max_length=5, beam_size=2,
    )
    rng = np.random.RandomState(0)
    batch = {
        "src_word": SeqTensor(
            rng.randint(2, 40, size=(2, 6)).astype(np.int32),
            np.full((2,), 6, np.int32),
        )
    }
    # the fixed form: params ride as an argument
    good = lint_jaxpr(trace_step(
        lambda p, bt: gen.generate(bt, params=p), params.params, batch,
    ), const_elem_threshold=256)
    assert "T102" not in rules(good), format_diagnostics(good)
    # the old closure form is exactly what T102 exists to catch
    bad = lint_jaxpr(
        trace_step(lambda bt: gen.generate(bt), batch),
        const_elem_threshold=256,
    )
    assert "T102" in rules(bad)


# ---------------------------------------------------------------------------
# T106: buffer-donation audit
# ---------------------------------------------------------------------------


def _mlp_step_parts():
    import paddle_tpu.optimizer as O
    from paddle_tpu.core.compiler import CompiledNetwork

    reset_auto_names()
    x = paddle.layer.data("x", paddle.data_type.dense_vector(64))
    h = paddle.layer.fc(x, size=256, act=paddle.activation.Relu())
    pred = paddle.layer.fc(h, size=10, act=paddle.activation.Softmax())
    y = paddle.layer.data("y", paddle.data_type.integer_value(10))
    cost = paddle.layer.classification_cost(input=pred, label=y)
    net = CompiledNetwork(Topology([cost]))
    opt = O.Adam(learning_rate=1e-3)
    params, state = net.init(jax.random.PRNGKey(0))
    batch = {
        "x": SeqTensor(jnp.zeros((8, 64), jnp.float32)),
        "y": SeqTensor(jnp.zeros((8,), jnp.int32)),
    }
    return net, opt, (params, state, opt.init(params), batch,
                      jax.random.PRNGKey(1))


def test_t106_undonated_carry_fires():
    """A jitted train step WITHOUT donate_argnums double-buffers params and
    Adam slots — T106 names the copied argnums."""
    from paddle_tpu.analysis import donation_audit
    from paddle_tpu.trainer.step import _train_step_body

    net, opt, args = _mlp_step_parts()
    undonated = jax.jit(_train_step_body(net, opt))
    d = donation_audit(undonated, *args)
    assert "T106" in rules(d)
    # params (argnum 0) and opt slots (argnum 2) both carry large buffers
    assert any("argument 0" in x.message for x in d), format_diagnostics(d)
    assert any("argument 2" in x.message for x in d), format_diagnostics(d)


def test_t106_explicit_donate_argnums_on_plain_fn():
    """For an un-jitted fn the audit takes the donation the builder intends
    as an argument — same rule, no jit eqn to introspect."""
    from paddle_tpu.analysis import donation_audit
    from paddle_tpu.trainer.step import _train_step_body

    net, opt, args = _mlp_step_parts()
    body = _train_step_body(net, opt)
    assert "T106" in rules(donation_audit(body, *args))
    d = donation_audit(body, *args, donate_argnums=(0, 1, 2))
    assert d == [], format_diagnostics(d)


def test_t106_shipped_builders_are_clean():
    """The shipped step builders donate their carried state: make_train_step
    (params/state/opt-state) and make_multi_train_step both audit clean —
    the `make lint` --donation gate."""
    from paddle_tpu.analysis import donation_audit
    from paddle_tpu.trainer.step import make_multi_train_step, make_train_step

    net, opt, args = _mlp_step_parts()
    params, state, opt_state, batch, rng = args
    d = donation_audit(make_train_step(net, opt, mesh=None), *args)
    assert d == [], format_diagnostics(d)
    k = 4
    stacked = jax.tree_util.tree_map(lambda v: jnp.stack([v] * k), batch)
    d = donation_audit(
        make_multi_train_step(net, opt, k, mesh=None),
        params, state, opt_state, stacked, rng,
    )
    assert d == [], format_diagnostics(d)


def test_t106_read_only_inputs_never_flag():
    """A large input that is NOT returned (batch data) has no copy to save
    — the audit must not demand donating the feed."""
    from paddle_tpu.analysis import donation_audit

    def fn(w, big_batch):
        return w + big_batch.sum()

    d = donation_audit(
        fn, jnp.zeros((256, 256)), jnp.zeros((512, 512)), donate_argnums=()
    )
    # w IS returned updated (matching aval) -> flagged; batch is not
    assert all("argument 1" not in x.message for x in d)
