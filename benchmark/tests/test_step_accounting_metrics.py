"""The six step-accounting readers (PR 26) on hand-built traces with known
answers: the host spans of `trainer.SGD.train` (step > feed_wait, train_step,
block_fetch) and the scopes of the jitted step (optimizer:<method>,
guard:sentinel, attgru_core)."""

import pytest

import refsteps
import trace_reduce as T

PLANE = "/device:TPU:0"
DEC = "jit(step)/jit(main)/jvp(recurrent_group:decoder)/"
META = {PLANE: {
    "scan.1": {"tf_op": DEC + "attgru_core/while/body/dot_general:", "hlo_category": "loop fusion"},
    "scan.bwd": {"tf_op": "jit(step)/jit(main)/transpose(jvp(recurrent_group:decoder))/"
                          "attgru_core/tbh,tbg->hg/dot_general:", "hlo_category": "convolution fusion"},
    "proj.1": {"tf_op": DEC + "tbd,dg->tbg/dot_general:", "hlo_category": "convolution fusion"},
    "out.1": {"tf_op": DEC + "fc:dec_out/dot_general:", "hlo_category": "convolution fusion"},
    "adam.1": {"tf_op": "jit(step)/jit(main)/optimizer:adam/mul:", "hlo_category": "loop fusion"},
    "keep.1": {"tf_op": "jit(step)/jit(main)/guard:sentinel/select_n:", "hlo_category": "loop fusion"},
    "copy.1": {"tf_op": "", "hlo_category": "data formatting"},
    "split.1": {"tf_op": "jit(_threefry_split)/threefry2x32:", "hlo_category": "loop fusion"},
}}


def reader(name):
    return refsteps.load_by_name("layer_metrics", name).read


def device_trace(host=None):
    """Two steps of 10 ms in a window of 25 ms."""
    ops = []
    for t in (0.000, 0.015):
        ops += [(t, t + 0.003, "scan.1"), (t + 0.003, t + 0.004, "proj.1"),
                (t + 0.004, t + 0.006, "out.1"), (t + 0.006, t + 0.0075, "scan.bwd"),
                (t + 0.0075, t + 0.0085, "adam.1"), (t + 0.0085, t + 0.009, "keep.1"),
                (t + 0.009, t + 0.00925, "copy.1"), (t + 0.00925, t + 0.0095, "split.1")]
    modules = [(0.000, 0.010, "jit_step(1)"), (0.015, 0.025, "jit_step(1)")]
    return T.Trace({PLANE: {"ops": ops, "modules": modules}}, META, host or {})


def ctx_of(trace):
    lo, hi = trace.window(PLANE)
    return {"trace": trace, "plane": PLANE, "window_s": hi - lo, "traced_steps": trace.steps(PLANE)}


def test_device_readers_on_known_operations():
    ctx = ctx_of(device_trace())
    assert reader("optimizer_share")(ctx) == pytest.approx(100 * 0.002 / 0.025)
    # the copy XLA inserted and the rng split's own program carry no type:name scope
    assert reader("unscoped_device_share")(ctx) == pytest.approx(100 * 0.001 / 0.025)
    assert reader("attgru_core_ms")(ctx) == pytest.approx(4.5)


def test_attgru_core_stays_in_the_scan_roofline_denominator():
    """An operation under recurrent_group:decoder/attgru_core is counted by
    attgru_core_ms AND still by attgru_scan_roofline's predicate (innermost
    type:name scope is the recurrent_group: the core's scope has no colon)."""
    trace = device_trace()
    tf_op = META[PLANE]["scan.bwd"]["tf_op"]
    assert "attgru_core" in tf_op
    assert T.scopes_of(tf_op)[-1] == "recurrent_group:decoder"
    scan_s = trace.seconds_where(
        PLANE, lambda n, tf_op, c: (T.scopes_of(tf_op) or [""])[-1].startswith("recurrent_group:"))
    assert scan_s == pytest.approx(2 * (0.003 + 0.001 + 0.0015))  # core fwd, projection, core bwd
    flops = type("F", (), {"kernels": staticmethod(lambda cfg, lens: {"attgru_scan": (1e9, 1e3)})})
    ctx = dict(ctx_of(trace), steps=[{"lens": None}] * 2, cfg={}, chips=1, flops=flops,
               peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    assert reader("attgru_scan_roofline")(ctx) == pytest.approx(100 * 2e-3 / scan_s)


def test_a_program_without_the_scopes_reads_nothing():
    """The parent commit: no optimizer: scope, no attgru_core, no step or
    feed_wait span.  The readers return None and do not raise."""
    meta = {PLANE: {k: dict(v, tf_op=v["tf_op"].replace("attgru_core/", "")
                            .replace("optimizer:adam/", "").replace("guard:sentinel/", ""))
                    for k, v in META[PLANE].items()}}
    trace = device_trace({"train_step": [(0.0, 0.001)], "block_fetch": [(0.001, 0.010)]})
    trace.meta = meta
    ctx = ctx_of(trace)
    for name in ("optimizer_share", "attgru_core_ms", "feed_wait_ms", "step_host_self_ms"):
        assert reader(name)(ctx) is None
    assert reader("dispatch_ms")(ctx) == pytest.approx(1.0)
    # the update and the guard were unscoped there
    assert reader("unscoped_device_share")(ctx) == pytest.approx(100 * 0.004 / 0.025)


def test_host_readers_partition_the_step():
    host = {
        # step 0: children back to back, 2 ms of self time at the end
        # step 1: children with gaps between them (self time in three pieces)
        # then the iteration that found the pass exhausted: a feed_wait alone
        "step": [(0.000, 0.012), (0.012, 0.030), (0.030, 0.031)],
        "feed_wait": [(0.000, 0.001), (0.013, 0.016), (0.030, 0.031)],
        "train_step": [(0.001, 0.004), (0.017, 0.021)],
        "block_fetch": [(0.004, 0.010), (0.022, 0.028)],
        "feed": [(0.002, 0.006), (0.018, 0.022)],  # on the prefetch thread: no child of step
    }
    ctx = ctx_of(device_trace(host))
    assert reader("feed_wait_ms")(ctx) == pytest.approx((1 + 3 + 1) / 3)
    assert reader("dispatch_ms")(ctx) == pytest.approx(3.5)
    # step 0: 12 - (1 + 3 + 6) = 2; step 1: 18 - (3 + 4 + 6) = 5
    assert reader("step_host_self_ms")(ctx) == pytest.approx(3.5)


def test_a_step_with_no_children_is_all_self_time():
    host = {"step": [(0.0, 0.004)], "train_step": [(0.0, 0.0)]}
    assert reader("step_host_self_ms")(ctx_of(device_trace(host))) == pytest.approx(4.0)


def test_no_feed_wait_span_reads_none():
    host = {"step": [(0.0, 0.010)], "train_step": [(0.001, 0.004)], "block_fetch": [(0.004, 0.009)]}
    ctx = ctx_of(device_trace(host))
    assert reader("feed_wait_ms")(ctx) is None
    assert reader("step_host_self_ms")(ctx) == pytest.approx(2.0)
