"""Precision-flow lint (analysis/numerics_lint.py): every N-rule fires on
a deliberate mutation and stays silent on the guarded idiom, pragmas
suppress with a justification, certify_precision_plan gates dtype plans
on the real train step, and the satellite guards (StatSet non-finite
bucket, bench non-finite regression) hold."""

import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.analysis.diagnostics import format_diagnostics
from paddle_tpu.analysis.numerics_lint import (
    certify_precision_plan,
    lint_numerics_config,
    lint_numerics_jaxpr,
    lint_numerics_step,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "tests", "configs")


def rules(diags):
    return [d.rule for d in diags]


def lint_fn(fn, *args, **kw):
    return lint_numerics_jaxpr(
        jax.make_jaxpr(fn)(*args), apply_pragmas=False, **kw
    )


# ---------------------------------------------------------------------------
# N401 low-precision accumulation
# ---------------------------------------------------------------------------


def test_n401_bf16_dot_without_f32_accumulator_fires():
    x = jnp.ones((4, 128), jnp.bfloat16)
    w = jnp.ones((128, 8), jnp.bfloat16)
    d = lint_fn(lambda a, b: a @ b, x, w)
    assert "N401" in rules(d), format_diagnostics(d)


def test_n401_silent_with_preferred_f32():
    x = jnp.ones((4, 128), jnp.bfloat16)
    w = jnp.ones((128, 8), jnp.bfloat16)
    d = lint_fn(
        lambda a, b: jnp.matmul(a, b, preferred_element_type=jnp.float32),
        x, w,
    )
    assert "N401" not in rules(d), format_diagnostics(d)
    # ...and at f32 the plain matmul is clean by construction
    d32 = lint_fn(lambda a, b: a @ b, x.astype(jnp.float32),
                  w.astype(jnp.float32))
    assert "N401" not in rules(d32)


def test_n401_long_bf16_reduce_fires_short_and_f32_do_not():
    # jnp.sum's default promotion accumulates bf16 sums in f32, so the
    # firing mutation is a LOW-dtype running reduction (cumsum keeps the
    # operand dtype — the pattern the softmax backward emits)
    big = jnp.ones((4, 256), jnp.bfloat16)
    d = lint_fn(lambda a: jnp.cumsum(a, axis=-1), big)
    assert "N401" in rules(d), format_diagnostics(d)
    small = jnp.ones((4, 8), jnp.bfloat16)
    assert "N401" not in rules(lint_fn(lambda a: jnp.cumsum(a, axis=-1),
                                       small))
    # the default (f32-accumulating) sum is the clean idiom
    assert "N401" not in rules(lint_fn(lambda a: a.sum(axis=-1), big))


def test_n401_scan_carry_accumulator_fires_state_carry_does_not():
    xs = jnp.ones((64, 8), jnp.bfloat16)

    def accumulating(xs):
        def body(c, x):
            return c + x, x  # running sum: quantizes every step

        return jax.lax.scan(body, jnp.zeros((8,), jnp.bfloat16), xs)

    d = lint_fn(accumulating, xs)
    assert "N401" in rules(d), format_diagnostics(d)
    assert any("carry" in x.message for x in d if x.rule == "N401")

    def overwriting(xs):
        def body(c, x):
            return jnp.tanh(x) * 0.5 + 0.5 * jnp.tanh(c), c

        return jax.lax.scan(body, jnp.zeros((8,), jnp.bfloat16), xs)

    d2 = lint_fn(overwriting, xs)
    assert not any("carry" in x.message for x in d2 if x.rule == "N401"), (
        format_diagnostics(d2)
    )


# ---------------------------------------------------------------------------
# N402 master-precision escape (via the step-level entry point)
# ---------------------------------------------------------------------------


def _fake_step(update_in_bf16):
    def step(params, state, opt_state, batch, rng):
        g = batch["x"].sum(axis=0) * 1e-3
        if update_in_bf16:
            p16 = params["w"].astype(jnp.bfloat16) - g.astype(jnp.bfloat16)
            new_w = p16.astype(jnp.float32)  # upcast AFTER the math
        else:
            new_w = params["w"] - g
        return ({"w": new_w}, state, opt_state, {"cost": g.sum()})

    params = {"w": jnp.zeros((8,), jnp.float32)}
    batch = {"x": jnp.ones((4, 8), jnp.float32)}
    return step, (params, {}, {}, batch, jax.random.PRNGKey(0))


def test_n402_update_math_below_master_precision_fires():
    step, args = _fake_step(update_in_bf16=True)
    d = lint_numerics_step(step, *args, master_argnums=(0,),
                           apply_pragmas=False)
    assert "N402" in rules(d), format_diagnostics(d)


def test_n402_silent_on_f32_update_math():
    step, args = _fake_step(update_in_bf16=False)
    d = lint_numerics_step(step, *args, master_argnums=(0,),
                           apply_pragmas=False)
    assert "N402" not in rules(d), format_diagnostics(d)


def test_n402_master_leaf_left_at_bf16_fires():
    def step(params, state, opt_state, batch, rng):
        return (
            {"w": params["w"] - batch["x"].sum(axis=0)},
            state, opt_state, {"cost": batch["x"].sum()},
        )

    params = {"w": jnp.zeros((8,), jnp.bfloat16)}
    batch = {"x": jnp.ones((4, 8), jnp.bfloat16)}
    d = lint_numerics_step(step, params, {}, {}, batch,
                           jax.random.PRNGKey(0), master_argnums=(0,),
                           apply_pragmas=False)
    assert "N402" in rules(d), format_diagnostics(d)


# ---------------------------------------------------------------------------
# N403 unguarded domain hazards
# ---------------------------------------------------------------------------


def test_n403_unguarded_exp_fires_max_subtracted_does_not():
    x = jnp.ones((4, 16), jnp.float32)
    d = lint_fn(lambda a: jnp.exp(a), x)
    assert "N403" in rules(d)

    def softmaxish(a):
        return jnp.exp(a - jax.lax.stop_gradient(a.max(-1, keepdims=True)))

    assert "N403" not in rules(lint_fn(softmaxish, x))


def test_n403_att_softmax_is_the_positive_pattern():
    """ops/rnn.py:_att_softmax — masked fill + softmax — must lint clean:
    the max-subtraction inside jax.nn.softmax guards the exp and the
    guaranteed exp(0)=1 term guards the normalizing division."""
    from paddle_tpu.ops.rnn import _att_softmax

    score = jnp.ones((4, 16), jnp.float32)
    emask = jnp.ones((4, 16), bool)
    d = lint_fn(_att_softmax, score, emask)
    assert "N403" not in rules(d), format_diagnostics(d)


def test_n403_unguarded_log_and_div_fire_epsilon_idiom_does_not():
    x = jnp.ones((4, 16), jnp.float32)
    assert "N403" in rules(lint_fn(lambda a: jnp.log(a), x))
    assert "N403" not in rules(lint_fn(lambda a: jnp.log(a + 1e-6), x))
    y = jnp.ones((4, 16), jnp.float32)
    assert "N403" in rules(lint_fn(lambda a, b: a / b, x, y))
    assert "N403" not in rules(
        lint_fn(lambda a, b: a / jnp.maximum(b, 1e-6), x, y)
    )
    assert "N403" in rules(lint_fn(lambda a: jax.lax.rsqrt(a), x))
    assert "N403" not in rules(lint_fn(lambda a: jax.lax.rsqrt(a + 1e-8), x))


# ---------------------------------------------------------------------------
# N404 sentinel literal overflow
# ---------------------------------------------------------------------------


def test_n404_1e9_mask_under_f16_fires():
    score = jnp.ones((4, 16), jnp.float16)
    mask = jnp.ones((4, 16), bool)
    d = lint_fn(lambda s, m: jnp.where(m, s, -1e9), score, mask)
    assert "N404" in rules(d), format_diagnostics(d)


def test_n404_silent_under_bf16_and_with_dtype_aware_fill():
    score16 = jnp.ones((4, 16), jnp.bfloat16)
    mask = jnp.ones((4, 16), bool)
    # bf16 has f32 range: -1e9 is representable
    d = lint_fn(lambda s, m: jnp.where(m, s, -1e9), score16, mask)
    assert "N404" not in rules(d)

    def dtype_aware(s, m):
        fill = jnp.asarray(jnp.finfo(s.dtype).min, s.dtype)
        return jnp.where(m, s, fill)

    d2 = lint_fn(dtype_aware, jnp.ones((4, 16), jnp.float16), mask)
    assert "N404" not in rules(d2), format_diagnostics(d2)


# ---------------------------------------------------------------------------
# N405 sub-f32 psum without block-scale structure
# ---------------------------------------------------------------------------


def _lint_psum(fn, *args):
    closed = jax.make_jaxpr(fn, axis_env=[("dp", 2)])(*args)
    return lint_numerics_jaxpr(closed, apply_pragmas=False)


def test_n405_lone_bf16_psum_fires():
    g = jnp.ones((8,), jnp.bfloat16)
    d = _lint_psum(lambda x: jax.lax.psum(x, "dp"), g)
    assert "N405" in rules(d), format_diagnostics(d)


def test_n405_block_scaled_psum_passes():
    g = jnp.ones((8,), jnp.bfloat16)
    s = jnp.ones((1,), jnp.float32)

    def block_scaled(x, scale):
        blocks = jax.lax.psum(x, "dp")
        scales = jax.lax.psum(scale, "dp")  # scales ride at f32
        return blocks.astype(jnp.float32) * scales

    assert "N405" not in rules(_lint_psum(block_scaled, g, s))
    # and a plain f32 psum never fires
    assert "N405" not in rules(
        _lint_psum(lambda x: jax.lax.psum(x, "dp"), g.astype(jnp.float32))
    )


def test_n405_quantized_psum_helper_lints_zero():
    """ACCEPT-path mutation check: the shipped ops.quantize.quantized_psum
    emits the payload psum + f32 scale psum pair, and the WHOLE jaxpr
    lints to zero diagnostics — not merely 'no N405' (a guard regression
    in the helper would surface as N403 here)."""
    from paddle_tpu.ops.quantize import quantized_psum

    g = {"w": jnp.ones((300,), jnp.float32), "b": jnp.ones((7,), jnp.float32)}
    for payload in (jnp.int8, jnp.bfloat16):
        d = _lint_psum(
            lambda t: quantized_psum(t, "dp", payload_dtype=payload), g
        )
        assert d == [], (str(payload), format_diagnostics(d))
    # stochastic rounding keeps the same psum structure
    d = _lint_psum(
        lambda t, k: quantized_psum(t, "dp", stochastic=True, rng=k),
        g, jax.random.PRNGKey(0),
    )
    assert d == [], format_diagnostics(d)


def test_n405_mutated_quantized_psum_fires_and_hint_names_helpers():
    """Strip the scale psum off the block-scaled pair (quantize against a
    purely LOCAL scale, psum only the int8 payload) — the exact mutation
    N405 exists to catch — and the fix hint must point at the ops
    quantize helpers."""

    def local_scale_only(x):
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax == 0.0, jnp.float32(1.0), amax / 127.0)
        q = jnp.round(x / scale).astype(jnp.int8)
        summed = jax.lax.psum(q, "dp")  # no f32 psum beside it
        return summed.astype(jnp.float32) * scale

    d = _lint_psum(local_scale_only, jnp.ones((64,), jnp.float32))
    n405 = [x for x in d if x.rule == "N405"]
    assert n405, format_diagnostics(d)
    assert "ops.quantize.quantized_psum" in (n405[0].hint or "")
    assert "quantize_block_scaled" in (n405[0].hint or "")


def test_n405_sees_through_shard_map():
    """The walker descends into shard_map bodies (where the quantized
    allreduce actually lives): a naked int8 psum inside one fires, the
    correctly paired one stays silent."""
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.ops.quantize import quantized_psum

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    n = len(jax.devices())

    def naked(g):
        def body(t):
            q = t.astype(jnp.int8)
            return jax.lax.psum(q, "dp").astype(jnp.float32)

        return jax.shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                         out_specs=P("dp"), check_vma=False)(g)

    closed = jax.make_jaxpr(naked)(jnp.zeros((n, 32), jnp.float32))
    assert "N405" in rules(lint_numerics_jaxpr(closed, apply_pragmas=False))

    def paired(g):
        return jax.shard_map(
            lambda t: quantized_psum(t, "dp", mean=True), mesh=mesh,
            in_specs=(P("dp"),), out_specs=P("dp"), check_vma=False,
        )(g)

    closed = jax.make_jaxpr(paired)(jnp.zeros((n, 300), jnp.float32))
    d = lint_numerics_jaxpr(closed, apply_pragmas=False)
    assert d == [], format_diagnostics(d)


# ---------------------------------------------------------------------------
# N406 dtype round-trip churn
# ---------------------------------------------------------------------------


def test_n406_f32_bf16_f32_roundtrip_fires():
    x = jnp.ones((4, 16), jnp.float32)
    d = lint_fn(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32) * 2.0, x
    )
    assert "N406" in rules(d), format_diagnostics(d)


def test_n406_one_way_casts_do_not_fire():
    x = jnp.ones((4, 16), jnp.float32)
    assert "N406" not in rules(
        lint_fn(lambda a: a.astype(jnp.bfloat16) * jnp.bfloat16(2), x)
    )
    # widening round trip (bf16 -> f32 -> bf16 loses nothing on the way up)
    y = jnp.ones((4, 16), jnp.bfloat16)
    assert "N406" not in rules(
        lint_fn(lambda a: a.astype(jnp.float32).astype(jnp.bfloat16), y)
    )


# ---------------------------------------------------------------------------
# pragma plane
# ---------------------------------------------------------------------------


def _write_module(tmp_path, name, body):
    p = tmp_path / f"{name}.py"
    p.write_text(textwrap.dedent(body))
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_num_pragma_suppresses_with_justification(tmp_path):
    mod = _write_module(tmp_path, "praggood", """
        import jax.numpy as jnp

        def f(x):
            return jnp.exp(x)  # num: allow[N403] scores are clipped by the caller
    """)
    x = jnp.ones((4, 16), jnp.float32)
    d = lint_numerics_jaxpr(jax.make_jaxpr(mod.f)(x))
    assert "N403" not in rules(d), format_diagnostics(d)
    # without pragma filtering the same jaxpr fires — the pragma did it
    d_raw = lint_numerics_jaxpr(jax.make_jaxpr(mod.f)(x),
                                apply_pragmas=False)
    assert "N403" in rules(d_raw)


def test_num_pragma_without_justification_is_rejected(tmp_path):
    mod = _write_module(tmp_path, "pragbad", """
        import jax.numpy as jnp

        def f(x):
            return jnp.exp(x)  # num: allow[N403]
    """)
    from paddle_tpu.analysis.numerics_lint import _PragmaFilter

    x = jnp.ones((4, 16), jnp.float32)
    f = _PragmaFilter()
    d = lint_numerics_jaxpr(jax.make_jaxpr(mod.f)(x), _filter=f)
    # the finding is NOT suppressed and the malformed pragma reports N400
    assert "N403" in rules(d)
    assert "N400" in rules(f.pragma_diags)


# ---------------------------------------------------------------------------
# certify_precision_plan — the ROADMAP item 2 gate
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_certify_rejects_bf16_master_accepts_bf16_compute_f32_master():
    """The documented gate: updating params IN bf16 is statically rejected
    (N402); the master-f32/compute-bf16 split passes on the LSTM
    flagship."""
    from paddle_tpu.v1_compat import parse_config

    topo = parse_config(
        os.path.join(CONFIGS, "demo_text_lstm.py"), ""
    ).topology

    good = certify_precision_plan(topo, {"compute_dtype": "bfloat16"})
    assert good.ok, good.format()
    assert good.master_dtype == "float32"
    # the certificate names the layers and shows f32 accumulators
    text = good.format()
    assert "ACCEPT" in text and "__lstmemory_0__" in text

    bad = certify_precision_plan(
        topo, {"compute_dtype": "bfloat16", "master_dtype": "bfloat16"}
    )
    assert not bad.ok, bad.format()
    assert "N402" in {d.rule for d in bad.diagnostics}
    assert "REJECT" in bad.format()


def test_certify_int8_weight_only_accepts_int8_master_rejects():
    """The quantization-plane split: declaring weight-ONLY int8 (the
    serving decode bundle) leaves the train plane untouched and ACCEPTs;
    asking for int8 master params or optimizer state REJECTs outright,
    without even tracing."""
    import paddle_tpu as paddle
    from paddle_tpu.core.topology import Topology, reset_auto_names

    reset_auto_names()
    x = paddle.layer.data("x", paddle.data_type.dense_vector(8))
    h = paddle.layer.fc(x, size=16, act=paddle.activation.Relu())
    pred = paddle.layer.fc(h, size=4, act=paddle.activation.Softmax())
    y = paddle.layer.data("y", paddle.data_type.integer_value(4))
    topo = Topology([paddle.layer.classification_cost(input=pred, label=y)])

    ok = certify_precision_plan(
        topo, {"compute_dtype": "bfloat16", "quantized_weights": True}
    )
    assert ok.ok, ok.format()

    for plan in (
        {"master_dtype": "int8"},
        {"compute_dtype": "int8"},
        {"compute_dtype": "bfloat16", "master_dtype": "int8",
         "quantized_weights": True},
    ):
        bad = certify_precision_plan(topo, plan)
        assert not bad.ok, (plan, bad.format())
        assert "N402" in {d.rule for d in bad.diagnostics}
        assert "weight-only" in bad.diagnostics[0].message
        assert "quantized_weights" in (bad.diagnostics[0].hint or "")


# ---------------------------------------------------------------------------
# the shipped corpus + package stay zero-diagnostic (make lint's contract)
# ---------------------------------------------------------------------------


def test_mnist_demo_config_zero_diagnostic_at_f32_and_bf16():
    cfg = os.path.join(CONFIGS, "demo_mnist_mlp.py")
    assert lint_numerics_config(cfg) == []
    d = lint_numerics_config(cfg, compute_dtype="bfloat16")
    assert d == [], format_diagnostics(d)


@pytest.mark.slow
def test_flagship_corpus_zero_diagnostic_both_dtypes():
    from paddle_tpu.analysis.numerics_lint import lint_numerics_package

    for cfg in sorted(os.listdir(CONFIGS)):
        if not cfg.endswith(".py"):
            continue
        for dt in (None, "bfloat16"):
            d = lint_numerics_config(
                os.path.join(CONFIGS, cfg), compute_dtype=dt
            )
            assert d == [], (cfg, dt, format_diagnostics(d))
    for dt in (None, "bfloat16"):
        d = lint_numerics_package(compute_dtype=dt)
        assert d == [], (dt, format_diagnostics(d))


# ---------------------------------------------------------------------------
# satellites: StatSet non-finite bucket + bench non-finite guard
# ---------------------------------------------------------------------------


def test_statset_observe_nonfinite_goes_to_own_bucket():
    from paddle_tpu.utils.timers import StatSet

    s = StatSet()
    s.observe("num/x", 2.0)
    s.observe("num/x", float("nan"))
    s.observe("num/x", float("inf"))
    s.observe("num/x", 4.0)
    row = s.summary()["num/x"]
    assert row["count"] == 2 and row["nonfinite"] == 2
    assert row["avg"] == 3.0 and row["max"] == 4.0  # unpoisoned
    assert np.isfinite(row["total"])


def test_bench_nonfinite_metric_is_hard_regression():
    import bench

    prior = {"m": [("r01", 10.0)]}
    f = bench.regression_fields("m", float("nan"), "tok/s", prior)
    assert f["regressed_vs_best"] is True and f["non_finite"] is True
    # a NaN with NO history still hard-fails (the silent-pass case)
    f2 = bench.regression_fields("fresh", float("inf"), "ms", {})
    assert f2["regressed_vs_best"] is True
    # finite values keep the old behavior
    f3 = bench.regression_fields("m", 10.0, "tok/s", prior)
    assert not f3.get("non_finite") and f3["regressed_vs_best"] is False


def test_bench_guard_line_reports_non_finite_separately():
    import bench

    results = [
        {"metric": "ok", "value": 1.0, "regressed_vs_best": False},
        {"metric": "bad", "value": float("nan"), "regressed_vs_best": True,
         "non_finite": True},
        {"metric": "slow", "value": 1.0, "regressed_vs_best": True,
         "best_prior": 2.0},
    ]
    guard = bench.build_guard(results)
    assert [g["metric"] for g in guard["non_finite"]] == ["bad"]
    assert [g["metric"] for g in guard["regressed"]] == ["slow"]
