"""Start-up accounts for itself (ISSUE 38): ``Tracer.complete`` and the
per-ring count of dropped events, the process's one jax.monitoring listener
(utils/compile_cache.py: ``jit_trace`` / ``jit_lower`` / ``jit_compile`` in
the ring, ``jit/*`` in ``global_stats``, the INFO line on a slow miss), and
the start-up layer's spans (import, init, parameters_create, trainer_build
and its children, train > train_prepare).  Structure and counts only; the
two boots on one cache directory (``cache`` = miss, then hit) are
tests/test_warm_boot.py's."""

import importlib
import json
import time

import pytest

from paddle_tpu import obs
from paddle_tpu.obs import merge as obs_merge
from paddle_tpu.obs.tracer import Tracer
from paddle_tpu.utils import compile_cache
from paddle_tpu.utils.timers import global_stats

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

JIT_STATS = ("jit/trace", "jit/lower", "jit/compile", "jit/cache_hit", "jit/cache_miss")
# what ONE iteration of the stepwise loop emits, in order (PR 26's list; the
# run-ahead loop fetches the step before the one it dispatched)
STEADY_ITERATION = [
    ("B", "step"), ("B", "feed_wait"), ("E", "feed_wait"), ("B", "train_step"),
    ("E", "train_step"), ("B", "block_fetch"), ("E", "block_fetch"), ("E", "step"),
]


class SetClock:
    """A clock a test sets by hand."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _events(tracer):
    return [e for e in tracer.events() if e["ph"] != "M"]


def _jit_counts():
    return {k: global_stats.count(k) for k in JIT_STATS}


@pytest.fixture()
def ring(monkeypatch):
    """The program emits through ``obs.span`` / ``obs.instant`` /
    ``obs.complete``: point all three at a private Tracer on the real
    monotonic clock, so a ``jit_*`` interval (jax's own seconds) can be laid
    against the spans around it."""
    t = Tracer(clock=time.monotonic, ring_events=4096)
    t.set_recording(True)
    for name in ("span", "instant", "complete"):
        monkeypatch.setattr(obs, name, getattr(t, name))
    return t


# ---------------------------------------------------------------------------
# Tracer.complete and the count of dropped events
# ---------------------------------------------------------------------------

def test_complete_is_one_x_event_that_began_seconds_ago():
    clock = SetClock(100.0)
    t = Tracer(clock=clock, ring_events=16)
    with t.span("outer", cat="setup"):
        clock.t = 103.0
        t.complete("jit_compile", "jit", 2.5, fun="jit(step)", cache="miss")
        clock.t = 104.0
    b, x, e = _events(t)  # time-sorted: the interval lies inside the span
    assert (b["ph"], x["ph"], e["ph"]) == ("B", "X", "E")
    assert x["ts"] == pytest.approx(100.5e6) and x["dur"] == pytest.approx(2.5e6)
    assert b["ts"] <= x["ts"] and x["ts"] + x["dur"] <= e["ts"]
    assert x["name"] == "jit_compile" and x["cat"] == "jit"
    assert x["args"] == {"fun": "jit(step)", "cache": "miss"}
    assert "dur" not in b and "dur" not in e


def test_complete_disarmed_emits_nothing():
    t = Tracer(clock=SetClock(), ring_events=16)
    t.set_recording(False)
    t.complete("import", "setup", 1.0)
    assert _events(t) == []


@pytest.mark.parametrize("how", ["dump", "flight_dump"])
def test_complete_is_exported_as_chromes_x(tmp_path, how):
    t = Tracer(clock=SetClock(50.0), ring_events=16)
    t._export_dir = str(tmp_path)
    t.complete("import", "setup", 0.25)
    path = t.dump() if how == "dump" else t.flight_dump("test")
    with open(path) as f:
        obj = json.load(f)
    assert obs_merge.validate_trace(obj) == []
    (x,) = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert x["name"] == "import" and x["dur"] == pytest.approx(0.25e6)
    assert x["ts"] == pytest.approx(49.75e6)


def test_merge_passes_x_through_with_its_length():
    a = Tracer(clock=SetClock(10.0), ring_events=16)
    a.complete("jit_trace", "jit", 0.5, fun="step")
    b = Tracer(clock=SetClock(20.0), ring_events=16)
    b.pid = a.pid + 1
    b.instant("elastic/lease", cat="trainer")
    merged = obs_merge.merge_traces([a.trace_object(), b.trace_object()], reference_pid=b.pid)
    assert obs_merge.validate_trace(merged) == []
    (x,) = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    shift = merged["otherData"]["offsets_us"][str(a.pid)]
    assert x["dur"] == pytest.approx(0.5e6) and x["ts"] == pytest.approx(9.5e6 + shift)
    assert x["args"] == {"fun": "step"}


def test_validate_wants_a_length_on_every_x():
    bad = {"traceEvents": [{"ph": "X", "ts": 1, "pid": 1, "tid": 1, "name": "a"}]}
    assert any("X without a numeric dur" in p for p in obs_merge.validate_trace(bad))


def test_a_ring_counts_what_it_drops():
    import threading

    t = Tracer(clock=SetClock(), ring_events=4)
    for i in range(4):
        t.instant(f"ev{i}")
    assert t.evicted() == 0  # full, nothing lost yet
    t.instant("ev4")
    t.complete("x", "jit", 0.1)
    assert t.evicted() == 2 and len(_events(t)) == 4

    other = threading.Thread(target=lambda: t.instant("elsewhere"))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    assert t.evicted(other.ident) == 0 and t.evicted() == 2  # a count a ring
    t.reset()
    assert t.evicted() == 0


# ---------------------------------------------------------------------------
# the listener, fed by hand
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heard, want", [
    ([], {"cache": "off"}),
    ([REQUEST], {"cache": "miss"}),
    ([REQUEST, HIT, (LOAD, 0.04)], {"cache": "hit", "load_s": 0.04}),
])
def test_jit_compile_says_what_the_cache_answered(ring, heard, want):
    before = _jit_counts()
    for item in heard:
        if isinstance(item, tuple):
            compile_cache._on_duration(*item)
        else:
            compile_cache._on_event(item)
    compile_cache._on_duration(COMPILE, 0.2, fun_name="jit(step)")
    (x,) = _events(ring)
    assert (x["ph"], x["name"], x["cat"]) == ("X", "jit_compile", "jit")
    assert x["args"] == dict(want, fun="jit(step)") and x["dur"] == pytest.approx(0.2e6)
    after = _jit_counts()
    grew = {k: after[k] - before[k] for k in JIT_STATS}
    assert grew == {"jit/trace": 0, "jit/lower": 0, "jit/compile": 1,
                    "jit/cache_hit": int(want["cache"] == "hit"),
                    "jit/cache_miss": int(want["cache"] == "miss")}
    # the answer belonged to that compile: the next one starts from nothing
    compile_cache._on_duration(COMPILE, 0.1, fun_name="jit(other)")
    assert _events(ring)[-1]["args"] == {"fun": "jit(other)", "cache": "off"}


def test_only_the_outermost_trace_or_lowering_is_recorded(ring):
    """A function called inside a trace is traced inside it, and a lowering
    traces helpers of its own: jax announces each start, the listener counts
    the open ones, and one interval a program reaches the ring."""
    before = _jit_counts()
    compile_cache._on_start(TRACE, 0.0, fun_name="step")
    for inner in ("sigmoid", "add"):
        compile_cache._on_start(TRACE, 0.0, fun_name=inner)
        compile_cache._on_duration(TRACE, 0.001, fun_name=inner)
    compile_cache._on_duration(TRACE, 0.5, fun_name="step")
    compile_cache._on_start(LOWER, 0.0, fun_name="jit(step)")
    compile_cache._on_start(TRACE, 0.0, fun_name="bitwise_xor")
    compile_cache._on_duration(TRACE, 0.001, fun_name="bitwise_xor")
    compile_cache._on_duration(LOWER, 0.2, fun_name="jit(step)")
    assert [(e["name"], e["args"]["fun"]) for e in _events(ring)] == [
        ("jit_trace", "step"), ("jit_lower", "jit(step)")]
    after = _jit_counts()
    assert after["jit/trace"] - before["jit/trace"] == 1
    assert after["jit/lower"] - before["jit/lower"] == 1
    totals = global_stats.summary()
    assert totals["jit/trace"]["max"] >= 0.5 and totals["jit/lower"]["max"] >= 0.2


@pytest.mark.parametrize("heard, seconds, lines", [
    ([REQUEST], 2.0, 1),        # the 259 MB executable that compiled in every run
    ([REQUEST], 0.5, 0),        # a miss, but a quick one
    ([REQUEST, HIT], 2.0, 0),   # a slow load is not a compile
    ([], 2.0, 0),               # no cache was asked
])
def test_a_slow_miss_logs_one_line(ring, caplog, heard, seconds, lines):
    with caplog.at_level("INFO", logger="paddle_tpu.compile"):
        for event in heard:
            compile_cache._on_event(event)
        compile_cache._on_duration(COMPILE, seconds, fun_name="jit(step)")
    got = [r.getMessage() for r in caplog.records if r.name == "paddle_tpu.compile"]
    assert len(got) == lines
    if lines:
        assert "jit(step)" in got[0] and "2.00 s" in got[0]


def test_events_the_listener_does_not_know_leave_nothing(ring):
    before = _jit_counts()
    compile_cache._on_event("/jax/compilation_cache/tasks_using_cache")
    compile_cache._on_duration("/jax/compilation_cache/compile_time_saved_sec", 3.0)
    compile_cache._on_start("/jax/core/compile/backend_compile_duration", 0.0)
    assert _events(ring) == [] and _jit_counts() == before


# ---------------------------------------------------------------------------
# the listener, fed by jax
# ---------------------------------------------------------------------------

def _fresh_program():
    import jax

    return jax.jit(lambda x: x * 3 + 1)


def test_a_new_program_leaves_three_intervals_and_a_cached_call_none(ring):
    import jax.numpy as jnp

    x = jnp.ones(7)  # its own programs compile before the count starts
    f = _fresh_program()
    ring.reset()
    before = _jit_counts()
    f(x).block_until_ready()
    first = [(e["name"], e["args"]["fun"]) for e in _events(ring)]
    assert [n for n, _ in first] == ["jit_trace", "jit_lower", "jit_compile"]
    assert all("<lambda>" in fun for _, fun in first)
    assert _events(ring)[-1]["args"]["cache"] in ("hit", "miss", "off")
    grew = {k: _jit_counts()[k] - before[k] for k in JIT_STATS[:3]}
    assert grew == {"jit/trace": 1, "jit/lower": 1, "jit/compile": 1}
    steady = _jit_counts()
    for _ in range(10):
        f(x).block_until_ready()
    assert len(_events(ring)) == 3 and _jit_counts() == steady


def test_recorder_off_counts_and_emits_nothing(ring):
    import jax.numpy as jnp

    x = jnp.ones(5)
    ring.set_recording(False)
    ring.reset()
    before = global_stats.count("jit/compile")
    _fresh_program()(x).block_until_ready()
    assert _events(ring) == []
    assert global_stats.count("jit/compile") == before + 1  # the table survives the ring


def test_importing_paddle_tpu_again_counts_nothing_twice(ring):
    import jax.numpy as jnp

    import paddle_tpu

    assert compile_cache.install_jit_listener() is False
    importlib.reload(paddle_tpu)
    assert [e["name"] for e in _events(ring)] == ["import"]
    x = jnp.ones(3)
    ring.reset()
    before = global_stats.count("jit/compile")
    _fresh_program()(x).block_until_ready()
    assert [e["name"] for e in _events(ring)] == ["jit_trace", "jit_lower", "jit_compile"]
    assert global_stats.count("jit/compile") == before + 1


# ---------------------------------------------------------------------------
# the start-up layer's spans around a toy trainer
# ---------------------------------------------------------------------------

def _tiny_cost():
    import paddle_tpu as paddle
    from paddle_tpu.core.topology import reset_auto_names

    reset_auto_names()
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(4))
    y = paddle.layer.data(name="y", type=paddle.data_type.dense_vector(1))
    pred = paddle.layer.fc(input=x, size=1, act=paddle.activation.Linear())
    return paddle.layer.square_error_cost(input=pred, label=y)


def _tiny_reader(n_batches):
    import numpy as np

    import paddle_tpu as paddle

    def samples():
        rng = np.random.RandomState(0)
        for _ in range(n_batches * 4):
            xv = rng.randn(4).astype(np.float32)
            yield xv, np.array([xv.sum()], np.float32)

    return paddle.batch(samples, 4)


def _tree(tracer):
    """The calling thread's spans, [(name, cat, begin, end, args, parent)] in
    begin order with X events as leaves, parent = index of the span open at
    the event's END (None at top level)."""
    import threading

    tid = threading.get_ident()
    out, stack = [], []
    for e in tracer.events():
        if e["ph"] == "M" or e["tid"] != tid:
            continue
        if e["ph"] == "B":
            out.append([e["name"], e["cat"], e["ts"], None, e.get("args", {}),
                        stack[-1] if stack else None])
            stack.append(len(out) - 1)
        elif e["ph"] == "E":
            assert out[stack[-1]][0] == e["name"]
            out[stack.pop()][3] = e["ts"]
    assert not stack
    spans = list(out)
    for e in tracer.events():
        if e["ph"] == "X" and e["tid"] == tid:
            end = e["ts"] + e["dur"]
            inside = [i for i, s in enumerate(spans) if s[2] <= end <= s[3]]
            out.append([e["name"], e["cat"], e["ts"], end, e.get("args", {}),
                        max(inside, key=lambda i: spans[i][2]) if inside else None])
    return out


def _children(tree, parent, cat=None):
    return [s[0] for s in tree if s[5] == parent and (cat is None or s[1] == cat)]


def _index(tree, name, nth=0):
    return [i for i, s in enumerate(tree) if s[0] == name][nth]


def test_the_toy_trainers_ring_nests_set_up(ring):
    import paddle_tpu as paddle

    paddle.init(seed=0)
    cost = _tiny_cost()
    parameters = paddle.parameters.create(cost, seed=0)
    trainer = paddle.trainer.SGD(cost=cost, parameters=parameters,
                                 update_equation=paddle.optimizer.Adam(learning_rate=0.05))
    trainer.train(_tiny_reader(3), num_passes=2)
    tree = _tree(ring)
    top = [(s[0], s[1]) for s in tree if s[5] is None and s[1] != "jit"]
    assert top == [("init", "setup"), ("parameters_create", "setup"),
                   ("trainer_build", "setup"), ("train", "trainer")]
    # the network came with the parameters: the trainer compiled none
    build = _index(tree, "trainer_build")
    assert _children(tree, build, "setup") == ["make_train_step", "make_eval_step", "optimizer_init"]
    train = _index(tree, "train")
    assert tree[train][4] == {"passes": 2}
    kids = _children(tree, train)
    assert kids[0] == "train_prepare" and set(kids[1:]) == {"step"}
    assert kids.count("step") == 2 * (3 + 1)  # a pass's batches and its exhausted iteration
    assert tree[_index(tree, "train_prepare")][1] == "setup"
    # the first train_step holds the step's trace, lowering and compile; no other does
    first = _index(tree, "train_step")
    held = [(s[0], s[4]["fun"]) for s in tree if s[5] == first]
    of_the_step = [n for n, fun in held if fun in ("step", "jit(step)")]
    assert of_the_step == ["jit_trace", "jit_lower", "jit_compile"]
    later = [i for i, s in enumerate(tree) if s[0] == "train_step"][1:]
    assert later and not [s for s in tree if s[5] in later]


def test_a_trainer_that_builds_its_network_says_so(ring):
    import paddle_tpu as paddle

    cost = _tiny_cost()
    paddle.trainer.SGD(cost=cost, update_equation=paddle.optimizer.Adam(learning_rate=0.05))
    tree = _tree(ring)
    build = _index(tree, "trainer_build")
    assert _children(tree, build, "setup") == [
        "compile_network", "parameters_create", "make_train_step", "make_eval_step",
        "optimizer_init"]


def test_a_second_train_on_the_same_shapes_is_silent_and_steady(ring):
    """Ten steady steps: the listener hears nothing (no ``jit_*`` event, the
    ``jit/*`` table unchanged) and every iteration emits exactly the events it
    emitted before this PR."""
    import threading

    import paddle_tpu as paddle

    cost = _tiny_cost()
    trainer = paddle.trainer.SGD(cost=cost, parameters=paddle.parameters.create(cost, seed=0),
                                 update_equation=paddle.optimizer.Adam(learning_rate=0.05))
    trainer.train(_tiny_reader(2), num_passes=1)  # warms the step and the feed's programs
    ring.reset()
    before = _jit_counts()
    trainer.train(_tiny_reader(10), num_passes=1, async_load_data=False)
    assert _jit_counts() == before
    tid = threading.get_ident()
    mine = [(e["ph"], e["name"]) for e in _events(ring) if e["tid"] == tid]
    assert not [n for _, n in mine if n.startswith("jit_")]
    assert mine[:3] == [("B", "train"), ("B", "train_prepare"), ("E", "train_prepare")]
    assert mine[-1] == ("E", "train")
    body = [x for x in mine[3:-1] if x[1] != "feed"]  # the inline feed's own span
    # the first iteration has nothing to fetch yet; the last finds the pass
    # exhausted, dispatches nothing and fetches the last step's cost
    first, steady, last = body[:6], body[6:-6], body[-6:]
    assert first == [x for x in STEADY_ITERATION if x[1] != "block_fetch"]
    assert last == [x for x in STEADY_ITERATION if x[1] != "train_step"]
    assert steady == STEADY_ITERATION * 9


def test_train_prepare_closes_when_train_raises(ring):
    import paddle_tpu as paddle

    cost = _tiny_cost()
    trainer = paddle.trainer.SGD(cost=cost, parameters=paddle.parameters.create(cost, seed=0),
                                 update_equation=paddle.optimizer.Adam(learning_rate=0.05))
    ring.reset()
    with pytest.raises(ValueError, match="resume=True requires checkpoint_dir"):
        trainer.train(_tiny_reader(1), resume=True)
    assert [(e["ph"], e["name"]) for e in _events(ring)] == [
        ("B", "train"), ("B", "train_prepare"), ("E", "train_prepare"), ("E", "train")]


def test_compile_meter_reads_the_programs_counters():
    import jax.numpy as jnp

    import chip_smoke

    meter = chip_smoke.CompileMeter()
    x = jnp.ones(9)
    c0, s0, h0 = meter.snapshot()
    _fresh_program()(x).block_until_ready()
    c1, s1, h1 = meter.snapshot()
    assert c1 == c0 + 1 and s1 > s0 and h1 in (h0, h0 + 1)
