"""The six start-up readers (PR 38) on hand-made rings with known answers:
what `startup_ring.parts` takes of the program's span ring (events as
`paddle_tpu.obs.tracer.events()` gives them: ts and dur in microseconds) and
what it leaves."""

import pytest

import refsteps
import startup_ring

NAMES = ("startup_import_s", "startup_build_s", "startup_trace_lower_s", "startup_compile_s",
         "startup_cache_misses", "startup_programs")


def span(name, t0, t1, cat="setup", **args):
    b = {"ph": "B", "ts": t0 * 1e6, "tid": 1, "name": name, "cat": cat}
    if args:
        b["args"] = args
    return [b, {"ph": "E", "ts": t1 * 1e6, "tid": 1, "name": name, "cat": cat}]


def x(name, t0, t1, cat="jit", **args):
    return [{"ph": "X", "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6, "tid": 1, "name": name,
             "cat": cat, "args": args}]


def program(fun, t0, trace, lower, compile_, cache="hit"):
    """One program's three intervals, back to back from t0."""
    a, b, c = t0 + trace, t0 + trace + lower, t0 + trace + lower + compile_
    return (x("jit_trace", t0, a, fun=fun) + x("jit_lower", a, b, fun=f"jit({fun})")
            + x("jit_compile", b, c, fun=f"jit({fun})", cache=cache))


def ring_of(*groups):
    return sorted((e for g in groups for e in g), key=lambda e: e["ts"])


def set_up():
    """import 0-1; init 1-1.1; parameters_create 2-3 holding one program
    (0.1 + 0.1 + 0.2); the harness's draw 3.2-3.8 between the program's spans;
    trainer_build 4-5 over its children, optimizer_init holding a program
    (0.05 + 0.05 + 0.1, a miss); train 6-9 (train_prepare 6-6.2, the step's
    program 6.3: 1.0 + 0.5 + 0.4 with two inner traces and a load inside the
    trace); the harness's norms 9.1-9.3; a second train 10-11 for the checked
    steps (train_prepare 10-10.1); the window's train from 12, with a compile
    it should not have; the reference's programs from 20."""
    return ring_of(
        x("import", 0.0, 1.0, cat="setup"),
        span("init", 1.0, 1.1),
        span("parameters_create", 2.0, 3.0), program("_normal", 2.2, 0.1, 0.1, 0.2),
        program("draw", 3.2, 0.1, 0.2, 0.3, cache="miss"),
        span("trainer_build", 4.0, 5.0), span("make_train_step", 4.1, 4.2),
        span("make_eval_step", 4.2, 4.3), span("optimizer_init", 4.4, 4.9),
        program("zeros_like", 4.5, 0.05, 0.05, 0.1, cache="miss"),
        span("train", 6.0, 9.0, cat="trainer", passes=1), span("train_prepare", 6.0, 6.2),
        span("step", 6.2, 8.5, cat="trainer"), span("train_step", 6.25, 8.3, cat="trainer"),
        program("step", 6.3, 1.0, 0.5, 0.4),
        x("jit_trace", 6.4, 6.5, fun="sigmoid"), x("jit_trace", 6.6, 6.9, fun="scan_body"),
        x("jit_compile", 7.0, 7.1, fun="jit(iota)", cache="hit"),
        program("_norms", 9.1, 0.05, 0.05, 0.1),
        span("train", 10.0, 11.0, cat="trainer", passes=1), span("train_prepare", 10.0, 10.1),
        span("train", 12.0, 15.0, cat="trainer", passes=1), span("train_prepare", 12.0, 12.5),
        program("late", 12.6, 0.1, 0.1, 0.1, cache="miss"),
        program("reference", 20.0, 1.0, 1.0, 5.0, cache="miss"),
    )


@pytest.fixture()
def ring(monkeypatch):
    held = {"events": set_up(), "evicted": 0}
    monkeypatch.setattr(startup_ring, "program_ring", lambda: (held["events"], held["evicted"]))
    return held


def read(name):
    return refsteps.load_by_name("layer_metrics", name).read({})


def test_the_six_readers_on_a_known_set_up(ring):
    assert read("startup_import_s") == pytest.approx(1.0)
    # init 0.1 + parameters_create 1.0 + trainer_build 1.0 + train_prepare 0.2 + 0.1,
    # less the 0.4 and 0.2 of jit inside them
    assert read("startup_build_s") == pytest.approx(2.4 - 0.4 - 0.2)
    # _normal 0.2, zeros_like 0.1, step 1.0 + 0.5 with its inner traces counted
    # once and the 0.1 load inside its trace left to the compiles
    assert read("startup_trace_lower_s") == pytest.approx(0.2 + 0.1 + 1.5 - 0.1)
    assert read("startup_compile_s") == pytest.approx(0.2 + 0.1 + 0.4 + 0.1)
    assert read("startup_cache_misses") == 1  # zeros_like; the draw's is the harness's
    assert read("startup_programs") == 4  # _normal, zeros_like, step, iota


def test_the_four_times_are_disjoint_parts_of_the_program_s_spans(ring):
    found = startup_ring.parts()
    own = 0.1 + 1.0 + 1.0 + 3.0 + 1.0  # init, parameters_create, trainer_build, two trains
    parts = sum(found[k] for k in NAMES[1:4])
    assert parts == pytest.approx(2.4 - 0.6 + 1.7 + 0.8) and parts < own


def test_the_cut_is_the_last_train(ring):
    """Without the window's train the ring ends in the checked steps' train:
    that one is then the last, and what begins after it is left out."""
    with_window = startup_ring.parts()
    ring["events"] = [e for e in ring["events"] if e["ts"] < 12e6]
    found = startup_ring.parts()
    assert found["startup_build_s"] == pytest.approx(with_window["startup_build_s"] - 0.1)
    assert found["startup_programs"] == 4


def test_the_reference_s_later_programs_are_ignored(ring):
    before = startup_ring.parts()
    ring["events"] = ring["events"] + program("more", 30.0, 2.0, 2.0, 9.0, cache="miss")
    assert startup_ring.parts() == before


@pytest.mark.parametrize("name", NAMES)
def test_none_without_setup_spans(ring, name):
    """The parent's tree: step spans and nothing of cat `setup`."""
    ring["events"] = [e for e in ring["events"] if e.get("cat") == "trainer"]
    assert ring["events"] and read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_none_after_a_wrap(ring, name):
    """A ring that has dropped events may have dropped set-up's: a truncated
    set-up must never be read as a short one."""
    ring["evicted"] = 1
    assert read(name) is None


@pytest.mark.parametrize("name", NAMES)
def test_none_where_the_program_keeps_no_count(monkeypatch, name):
    monkeypatch.setattr(startup_ring, "program_ring", lambda: None)
    assert read(name) is None


def test_program_ring_reads_the_calling_thread_s_ring():
    import threading

    from paddle_tpu.obs import tracer

    tracer.reset()  # other tests' trainers may have turned the ring over
    tracer.complete("import", "setup", 0.5)
    other = threading.Thread(target=lambda: tracer.instant("elsewhere"))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    events, evicted = startup_ring.program_ring()
    assert evicted == tracer.evicted()
    assert any(e["name"] == "import" and e["ph"] == "X" for e in events)
    assert all(e["tid"] == threading.get_ident() and e["ph"] != "M" for e in events)
    assert isinstance(startup_ring.parts()["startup_import_s"], float)


def test_a_span_left_open_is_left_out():
    events = span("init", 1.0, 2.0) + [{"ph": "B", "ts": 3e6, "tid": 1, "name": "train",
                                        "cat": "trainer"}]
    assert [(n, s, e) for n, _, s, e, _ in startup_ring.intervals(events)] == [
        ("init", pytest.approx(1.0), pytest.approx(2.0))]


def test_seconds_outside():
    assert startup_ring.seconds_outside([(0, 10)], [(2, 3), (2.5, 4), (9, 12)]) == pytest.approx(7.0)
    assert startup_ring.seconds_outside([(0, 4), (2, 6)], []) == pytest.approx(6.0)
    assert startup_ring.seconds_outside([], [(0, 1)]) == 0
