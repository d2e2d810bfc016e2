"""Obs plane (ISSUE 13): span tracer, trace merge, flight recorder,
Prometheus metrics export, the shared --stats-out writer, and the A205
monotonic-clock self-lint rule.

The cross-process acceptance drill (a traced scenario producing ONE
merged timeline from >= 2 processes / >= 3 planes) lives in
tests/test_obs_e2e.py (slow, `make trace-demo`)."""

import json
import os

import pytest

from paddle_tpu import obs
from paddle_tpu.obs import merge as obs_merge
from paddle_tpu.obs.tracer import Tracer
from paddle_tpu.utils import flags


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test sees a recording, export-less singleton and default
    flags; nothing leaks between tests."""
    obs.tracer.reset()
    obs.tracer.set_recording(True)
    obs.tracer._export_dir = None
    obs.tracer.set_annotation_factory(None)
    yield
    obs.tracer.reset()
    obs.tracer.set_recording(True)
    obs.tracer._export_dir = None
    obs.tracer.set_annotation_factory(None)
    flags.reset_flags()


class FakeClock:
    def __init__(self, t0=100.0):
        self.t = t0

    def __call__(self):
        self.t += 0.001
        return self.t


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_trace_event_schema_roundtrip(tmp_path):
    t = Tracer(clock=FakeClock(), ring_events=128)
    with t.span("train_step", cat="trainer", p=0, b=3):
        t.instant("serving/submit", cat="serving", req="r1", deadline_s=0.5)
        with t.span("rpc_call:get_task", cat="rpc", rpc="a-1"):
            pass
    path = t.dump(str(tmp_path / "trace.json"))
    with open(path) as f:
        obj = json.load(f)
    assert obs_merge.validate_trace(obj) == []
    evs = [e for e in obj["traceEvents"] if e["ph"] != "M"]
    # required keys on every event
    for ev in evs:
        for k in ("ph", "ts", "pid", "tid", "name"):
            assert k in ev, ev
    # begin/end pairing, args well-formed, correlation ids intact
    assert [e["ph"] for e in evs] == ["B", "i", "B", "E", "E"]
    sub = next(e for e in evs if e["name"] == "serving/submit")
    assert sub["args"] == {"req": "r1", "deadline_s": 0.5}
    assert sub["cat"] == "serving"
    rpc_b = next(e for e in evs if e["name"] == "rpc_call:get_task")
    assert rpc_b["args"]["rpc"] == "a-1"
    # timestamps are strictly increasing with the injected monotonic clock
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts) and ts[0] < ts[-1]
    # trace context rides otherData
    other = obj["otherData"]
    assert other["pid"] == os.getpid()
    assert other["role"] == "proc"
    assert other["trace_id"]
    assert "mono_us" in other["clock_anchor"]


def test_ring_buffer_wraps_to_last_n():
    t = Tracer(clock=FakeClock(), ring_events=8)
    for i in range(50):
        t.instant(f"ev{i}")
    evs = [e for e in t.events() if e["ph"] != "M"]
    assert len(evs) == 8  # bounded memory: capacity holds
    assert [e["name"] for e in evs] == [f"ev{i}" for i in range(42, 50)]


def test_disarmed_recorder_emits_nothing():
    t = Tracer(clock=FakeClock(), ring_events=8)
    t.set_recording(False)
    with t.span("x"):
        t.instant("y")
    assert [e for e in t.events() if e["ph"] != "M"] == []
    t.set_recording(True)
    t.instant("z")
    assert len([e for e in t.events() if e["ph"] != "M"]) == 1


def test_annotation_factory_nests_spans():
    entered = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("in", self.name))

        def __exit__(self, *a):
            entered.append(("out", self.name))

    t = Tracer(clock=FakeClock())
    t.set_annotation_factory(Ann)
    with t.span("step"):
        pass
    assert entered == [("in", "step"), ("out", "step")]
    # disarmed recording skips the annotation too (zero-cost contract)
    t.set_recording(False)
    with t.span("step2"):
        pass
    assert len(entered) == 2


def test_validate_catches_mispairing_and_missing_keys():
    bad = {"traceEvents": [
        {"ph": "B", "ts": 1, "pid": 1, "tid": 1, "name": "a"},
        {"ph": "E", "ts": 2, "pid": 1, "tid": 1, "name": "b"},
        {"ph": "i", "ts": 3, "pid": 1, "name": "c"},  # no tid
        {"ph": "i", "ts": 4, "pid": 1, "tid": 1, "name": "d", "args": 7},
    ]}
    problems = obs_merge.validate_trace(bad)
    assert any("closes B" in p for p in problems)
    assert any("missing key 'tid'" in p for p in problems)
    assert any("args is not an object" in p for p in problems)
    assert obs_merge.validate_trace({"traceEvents": []}) == []


def test_validate_tolerates_ring_wrap_and_mid_span_dump():
    """The two EXPECTED pairing artifacts must not fail validation:
    leading orphan Es (the ring dropped their Bs at wrap) and trailing
    unclosed Bs (a flight dump fired mid-span)."""
    t = Tracer(clock=FakeClock(), ring_events=3)
    with t.span("outer"):
        with t.span("inner"):
            pass
    # ring of 3 kept [E inner, ...]: B outer evicted -> leading orphan E
    assert obs_merge.validate_trace(t.trace_object()) == []
    t2 = Tracer(clock=FakeClock(), ring_events=64)
    with t2.span("outer"):
        with t2.span("inner"):
            obj = t2.trace_object()  # dump mid-span: two unclosed Bs
    assert obs_merge.validate_trace(obj) == []


# ---------------------------------------------------------------------------
# merge: clock-skew alignment
# ---------------------------------------------------------------------------

def _synthetic_process(pid, role, skew_us, rpc_ids, client, extra=()):
    """A trace whose clock runs ``skew_us`` ahead of process 1's."""
    base = 1_000_000.0 + skew_us
    evs = []
    for i, rid in enumerate(rpc_ids):
        t0 = base + 1000 * i
        if client:
            evs.append({"ph": "B", "ts": t0, "pid": pid, "tid": 1,
                        "name": "rpc_call:get_task", "cat": "rpc",
                        "args": {"rpc": rid}})
            evs.append({"ph": "E", "ts": t0 + 40, "pid": pid, "tid": 1,
                        "name": "rpc_call:get_task", "cat": "rpc"})
        else:
            evs.append({"ph": "B", "ts": t0 + 15, "pid": pid, "tid": 1,
                        "name": "rpc:get_task", "cat": "master",
                        "args": {"rpc": rid}})
            evs.append({"ph": "E", "ts": t0 + 25, "pid": pid, "tid": 1,
                        "name": "rpc:get_task", "cat": "master"})
    evs.extend(extra)
    return {
        "traceEvents": evs,
        "otherData": {
            "pid": pid, "role": role, "trace_id": "t0",
            # wall anchors deliberately COARSE (500us off) so the test
            # proves the rpc pairs refine past them
            "clock_anchor": {"mono_us": base, "wall_us": 2_000_000.0 + 500},
        },
    }


def test_merge_aligns_known_skew_via_rpc_pairs():
    rpc_ids = [f"1-{i}" for i in range(9)]
    skew = 123_456.0
    a = _synthetic_process(1, "worker", 0.0, rpc_ids, client=True)
    b = _synthetic_process(2, "master", skew, rpc_ids, client=False)
    merged = obs_merge.merge_traces([a, b], reference_pid=1)
    off = merged["otherData"]["offsets_us"]
    assert off["1"] == 0.0
    # recovered within a fraction of the (symmetric) exchange window
    assert abs(off["2"] + skew) < 25.0
    # after alignment every server-handling span sits INSIDE its client
    # exchange span on the unified clock
    evs = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    by_rpc = {}
    for e in evs:
        rid = (e.get("args") or {}).get("rpc")
        if rid is not None:
            by_rpc.setdefault(rid, {})[e["name"]] = e["ts"]
    for rid, d in by_rpc.items():
        assert d["rpc_call:get_task"] < d["rpc:get_task"]
    assert merged["otherData"]["rpc_pair_edges"] == {"1->2": 9}
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)


def test_merge_wall_anchor_fallback_without_rpc_pairs():
    # two processes that never talked: only the wall anchors align them
    a = _synthetic_process(1, "serve", 0.0, [], client=True,
                           extra=[{"ph": "i", "ts": 1_000_100.0, "pid": 1,
                                   "tid": 1, "name": "x", "cat": "serving"}])
    b = _synthetic_process(2, "trainer", 50_000.0, [], client=False,
                           extra=[{"ph": "i", "ts": 1_050_100.0, "pid": 2,
                                   "tid": 1, "name": "y", "cat": "trainer"}])
    merged = obs_merge.merge_traces([a, b], reference_pid=1)
    off = merged["otherData"]["offsets_us"]
    # anchor math: dw_a = 2e6+500 - 1e6; dw_b = 2e6+500 - 1.05e6
    assert abs(off["2"] + 50_000.0) < 1.0
    evs = {e["name"]: e["ts"] for e in merged["traceEvents"]
           if e["ph"] != "M"}
    assert abs(evs["x"] - evs["y"]) < 1.0  # simultaneous events align


def test_merge_dir_and_cli(tmp_path):
    t1 = Tracer(clock=FakeClock(10.0), ring_events=64)
    t1.role = "serve"
    t1.instant("serving/submit", cat="serving", req="r1")
    t1.dump(str(tmp_path / "trace-serve-1.json"))
    t2 = Tracer(clock=FakeClock(20.0), ring_events=64)
    t2.role = "worker"
    t2.pid = t1.pid + 1  # distinct synthetic process
    t2.instant("elastic/lease", cat="trainer", task=0)
    t2.dump(str(tmp_path / "trace-worker-2.json"))
    merged, out = obs_merge.merge_dir(str(tmp_path))
    assert os.path.exists(out)
    assert len(merged["otherData"]["merged_pids"]) == 2
    # the CLI face over the same files
    from paddle_tpu.cli import main as cli_main

    rc = cli_main(["trace", "validate", out])
    assert rc == 0
    rc = cli_main(["trace", "merge", "--dir", str(tmp_path),
                   "--out", str(tmp_path / "m2.json")])
    assert rc == 0 and os.path.exists(tmp_path / "m2.json")


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_dump_on_scheduler_crash_guard(tmp_path):
    from paddle_tpu.serving import Request, ServingScheduler

    flags.set_flag("trace_dir", str(tmp_path))

    class BrokenEngine:
        max_slots = 2
        n_prefilling = 0
        n_free_slots = 2
        src_vocab = 50
        default_max_new_tokens = 4
        trace_counts = {}

        def __init__(self):
            self._reqs = []

        @property
        def n_live(self):
            return len(self._reqs)

        def max_src_tokens(self):
            return 64

        def admit(self, waiting):
            self._reqs.extend(waiting)
            return list(waiting)

        def step(self):
            raise RuntimeError("boom: engine corrupted")

        def outstanding_requests(self):
            return list(self._reqs)

        def preempt(self):
            return self._reqs.pop() if self._reqs else None

        def cancel(self, r):
            if r in self._reqs:
                self._reqs.remove(r)
                return True
            return False

        def cancel_by_id(self, rid):
            return None

    sched = ServingScheduler(BrokenEngine(), queue_limit=0,
                             default_deadline_s=0.0)
    r = sched.submit(Request([1, 2, 3]))
    assert r.wait(20.0), "crash guard must finalize the stranded request"
    assert r.status == "closed" and "crashed" in (r.error or "")
    sched.close()
    flight = tmp_path / f"flight-{os.getpid()}.json"
    assert flight.exists(), "crash guard must leave a postmortem"
    obj = json.loads(flight.read_text())
    assert "serving-crash-guard" in obj["otherData"]["reason"]
    names = [e["name"] for e in obj["traceEvents"]]
    assert "serving/submit" in names  # the last events show the lead-in


def test_flight_dump_on_chaos_fire(tmp_path):
    """A firing chaos point dumps the postmortem once per arming (the
    kill -9 SIGKILL variant — the dump must land BEFORE the process dies
    — is drilled in tests/test_obs_e2e.py with a real subprocess)."""
    from paddle_tpu.robustness import chaos

    flags.set_flag("trace_dir", str(tmp_path))
    obs.instant("train_step", cat="trainer", b=1)
    chaos.arm("nan_batch")
    try:
        assert chaos.fire("nan_batch")
        assert chaos.fire("nan_batch")  # fires again, dumps only once
    finally:
        chaos.disarm()
    flight = tmp_path / f"flight-{os.getpid()}.json"
    assert flight.exists()
    obj = json.loads(flight.read_text())
    assert obj["otherData"]["reason"] == "chaos:nan_batch@1"
    assert any(e["name"] == "train_step" for e in obj["traceEvents"])


def test_flight_dump_on_sentinel_divergence(tmp_path):
    from paddle_tpu.robustness.sentinel import DivergenceSentinel

    flags.set_flag("trace_dir", str(tmp_path))
    obs.instant("train_step", cat="trainer", b=0)
    s = DivergenceSentinel(skip_limit=2)
    assert s.observe(1.0, healthy=False) == "skip"
    assert s.observe(1.0, healthy=False) == "diverged"
    flight = tmp_path / f"flight-{os.getpid()}.json"
    assert flight.exists()
    obj = json.loads(flight.read_text())
    assert "sentinel-divergence" in obj["otherData"]["reason"]


# ---------------------------------------------------------------------------
# RPC correlation (client + server halves in one process)
# ---------------------------------------------------------------------------

def test_rpc_spans_share_correlation_id(tmp_path):
    from paddle_tpu import master

    d = str(tmp_path / "rio")
    os.makedirs(d)
    from paddle_tpu.io import recordio

    recordio.write_records(
        os.path.join(d, "a.rio"), iter([b"x"] * 4), max_chunk_records=2
    )
    svc = master.Service(chunks_per_task=2, snapshot_path=None)
    srv = master.Server(svc)
    try:
        cli = master.Client(srv.address)
        cli.set_dataset([os.path.join(d, "*.rio")])
        assert cli._call("stats")["n_todo"] >= 1
        cli.close()
    finally:
        srv.close()
    evs = [e for e in obs.tracer.events() if e["ph"] == "B"]
    calls = {
        (e["args"] or {}).get("rpc")
        for e in evs if e["name"].startswith("rpc_call:")
    }
    handles = {
        (e["args"] or {}).get("rpc")
        for e in evs if e["name"].startswith("rpc:") and e["args"]
    }
    shared = (calls & handles) - {None}
    assert shared, (calls, handles)  # both halves carry the same rpc id


# ---------------------------------------------------------------------------
# metrics export
# ---------------------------------------------------------------------------

_PROM_LINE = __import__("re").compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (-?(?:[0-9.eE+-]+|inf|nan))$"
)


def _parse_prometheus(text):
    samples = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        samples[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return samples


def test_prometheus_exposition_parses(tmp_path):
    from paddle_tpu.obs.metrics import (
        register_gauge, render_prometheus, unregister_gauge,
    )
    from paddle_tpu.utils.timers import StatSet

    stats = StatSet()
    stats.incr("serving/completed", 5)
    stats.incr("serving/shed", 2)
    stats.observe('lock_held/master.Service._lock "x"', 0.25)
    register_gauge("paddle_tpu_serving_queue_depth", lambda: 3,
                   "queued requests")
    register_gauge("paddle_tpu_dead_gauge", lambda: 1 / 0, "must be skipped")
    try:
        text = render_prometheus(stats)
    finally:
        unregister_gauge("paddle_tpu_serving_queue_depth")
        unregister_gauge("paddle_tpu_dead_gauge")
    samples = _parse_prometheus(text)
    assert samples["paddle_tpu_serving_queue_depth"] == 3.0
    assert not any("dead_gauge" in k for k in samples)
    assert samples[
        'paddle_tpu_serving_requests_total{status="served"}'] == 5.0
    assert samples[
        'paddle_tpu_serving_requests_total{status="shed"}'] == 2.0
    assert samples[
        'paddle_tpu_serving_requests_total{status="timeout"}'] == 0.0
    # label escaping: the quoted stat name survives
    assert any("lock_held" in k and '\\"x\\"' in k for k in samples)
    assert "# HELP paddle_tpu_serving_queue_depth queued requests" in text
    assert "# TYPE paddle_tpu_serving_requests_total counter" in text


def test_metrics_exporter_file_and_http(tmp_path):
    import urllib.request

    from paddle_tpu.obs.metrics import MetricsExporter
    from paddle_tpu.utils.timers import StatSet

    stats = StatSet()
    stats.incr("serving/completed", 7)
    out = tmp_path / "metrics.prom"
    with MetricsExporter(path=str(out), port=0, period_s=30.0,
                         stats=stats) as exp:
        assert exp.write_once()
        samples = _parse_prometheus(out.read_text())
        assert samples[
            'paddle_tpu_serving_requests_total{status="served"}'] == 7.0
        assert exp.port and exp.port > 0
        with urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/metrics", timeout=10
        ) as resp:
            assert resp.status == 200
            body = resp.read().decode()
        assert _parse_prometheus(body)[
            'paddle_tpu_serving_requests_total{status="served"}'] == 7.0
    # closed: the endpoint is gone
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/metrics", timeout=2
        )


def test_scheduler_registers_slo_gauges(tmp_path):
    """The PR-12 SLO variables are live gauges while a scheduler exists,
    and unregister on close."""
    from paddle_tpu.obs.metrics import render_prometheus
    from paddle_tpu.serving import ServingScheduler

    class IdleEngine:
        max_slots = 2
        n_live = 0
        n_prefilling = 0
        n_free_slots = 2
        src_vocab = 50
        default_max_new_tokens = 4
        trace_counts = {}

        class pages:
            n_used = 3

        def max_src_tokens(self):
            return 64

        def admit(self, waiting):
            return []

        def step(self):
            return []

        def outstanding_requests(self):
            return []

        def cancel_by_id(self, rid):
            return None

    sched = ServingScheduler(IdleEngine(), queue_limit=0,
                             default_deadline_s=0.0)
    try:
        samples = _parse_prometheus(render_prometheus())
        assert samples["paddle_tpu_serving_queue_depth"] == 0.0
        assert samples["paddle_tpu_serving_pages_in_use"] == 3.0
        assert "paddle_tpu_serving_predicted_wait_seconds" in samples
        # a SECOND scheduler takes the names over; closing the OLD one
        # must not tear the new one's gauges down (ownership check)
        eng2 = IdleEngine()
        eng2.pages = type("P", (), {"n_used": 9})
        sched2 = ServingScheduler(eng2, queue_limit=0,
                                  default_deadline_s=0.0)
        try:
            assert _parse_prometheus(render_prometheus())[
                "paddle_tpu_serving_pages_in_use"] == 9.0
            sched.close()
            assert _parse_prometheus(render_prometheus())[
                "paddle_tpu_serving_pages_in_use"] == 9.0
        finally:
            sched2.close()
    finally:
        sched.close()
    samples = _parse_prometheus(render_prometheus())
    assert "paddle_tpu_serving_queue_depth" not in samples


# ---------------------------------------------------------------------------
# the shared --stats-out writer
# ---------------------------------------------------------------------------

def test_write_stats_json_atomic_append_and_unwritable(tmp_path, capsys):
    p = tmp_path / "stats.json"
    assert obs.write_stats_json(str(p), {"a": 1})
    assert json.loads(p.read_text()) == {"a": 1}
    assert obs.write_stats_json(str(p), {"a": 2})  # replace, not append
    assert json.loads(p.read_text()) == {"a": 2}
    ap = tmp_path / "log.jsonl"
    obs.write_stats_json(str(ap), {"n": 1}, append=True)
    obs.write_stats_json(str(ap), {"n": 2}, append=True)
    assert [json.loads(l) for l in ap.read_text().splitlines()] == [
        {"n": 1}, {"n": 2},
    ]
    # uniform unwritable-path behavior: warn + False, never raise
    bad = str(tmp_path / "no" / "such" / "dir" / "s.json")
    assert obs.write_stats_json(bad, {"a": 1}) is False
    assert obs.write_stats_json(bad, {"a": 1}, append=True) is False
    assert "unwritable" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# satellites: StatSet column alignment + A205 self-lint rule
# ---------------------------------------------------------------------------

def test_statset_print_aligns_long_names(capsys):
    from paddle_tpu.utils.timers import StatSet

    s = StatSet()
    s.incr("feed")
    s.observe("lock_held/master.Server._conns_lock-and-then-some", 0.5)
    out = s.print_all_status()
    capsys.readouterr()
    lines = out.splitlines()
    # every row (header included) lays the same columns: equal lengths
    assert len({len(ln) for ln in lines}) == 1
    assert lines[0].rstrip().endswith("max_ms")
    # numeric columns still right-aligned after the longest name
    for ln in lines[1:]:
        assert not ln.startswith(" ")


def _lint_obs_source(tmp_path, src):
    from paddle_tpu.analysis.ast_rules import lint_file

    d = tmp_path / "paddle_tpu" / "obs"
    d.mkdir(parents=True, exist_ok=True)
    p = d / "mod.py"
    p.write_text(src)
    return lint_file(str(p), root=str(tmp_path))


def test_a205_flags_wall_clock_in_obs(tmp_path):
    diags = _lint_obs_source(
        tmp_path, "import time\nts = time.time()\n"
    )
    assert [d.rule for d in diags] == ["A205"]
    diags = _lint_obs_source(
        tmp_path, "import time\nts = time.time_ns()\n"
    )
    assert [d.rule for d in diags] == ["A205"]


def test_a205_sees_through_aliases(tmp_path):
    # `from time import time` and `import time as t` must not slip past
    # the ban; `from time import monotonic` stays legal
    diags = _lint_obs_source(
        tmp_path, "from time import time\nts = time()\n"
    )
    assert [d.rule for d in diags] == ["A205"]
    diags = _lint_obs_source(
        tmp_path, "import time as t\nts = t.time()\n"
    )
    assert [d.rule for d in diags] == ["A205"]
    assert _lint_obs_source(
        tmp_path, "from time import monotonic\nts = monotonic()\n"
    ) == []


def test_a205_pragma_requires_justification(tmp_path):
    ok = (
        "import time\n"
        "anchor = time.time()  # obs: allow-wall-clock merge anchor only\n"
        "mono = time.monotonic()\n"
    )
    assert _lint_obs_source(tmp_path, ok) == []
    empty = (
        "import time\n"
        "anchor = time.time()  # obs: allow-wall-clock\n"
    )
    diags = _lint_obs_source(tmp_path, empty)
    assert [d.rule for d in diags] == ["A205"]
    assert "justification" in diags[0].message


def test_a205_does_not_fire_outside_obs(tmp_path):
    from paddle_tpu.analysis.ast_rules import lint_file

    d = tmp_path / "paddle_tpu" / "reader"
    d.mkdir(parents=True)
    p = d / "mod.py"
    p.write_text("import time\nts = time.time()\n")
    assert [x.rule for x in lint_file(str(p), root=str(tmp_path))] == []


def test_obs_package_lints_clean():
    """The new plane passes its own rules: A-rules (incl. A205) over
    paddle_tpu/obs/ report nothing."""
    import paddle_tpu
    from paddle_tpu.analysis.ast_rules import lint_file

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(paddle_tpu.__file__)
    ))
    obs_dir = os.path.join(root, "paddle_tpu", "obs")
    diags = []
    for fn in sorted(os.listdir(obs_dir)):
        if fn.endswith(".py"):
            diags.extend(lint_file(os.path.join(obs_dir, fn), root=root))
    assert diags == [], [str(d) for d in diags]


def test_prometheus_per_class_ledger_series():
    """The class-labeled requests_total series: the scheduler's
    serving/class/<class>/<status> counters render as labeled series of
    the same family, all statuses included (served too)."""
    from paddle_tpu.obs.metrics import render_prometheus
    from paddle_tpu.utils.timers import StatSet

    stats = StatSet()
    stats.incr("serving/class/p0/served", 3)
    stats.incr("serving/class/p2/shed", 2)
    stats.incr("serving/class/p2/served", 1)
    samples = _parse_prometheus(render_prometheus(stats))
    assert samples[
        'paddle_tpu_serving_requests_total{class="p0",status="served"}'
    ] == 3.0
    assert samples[
        'paddle_tpu_serving_requests_total{class="p2",status="shed"}'
    ] == 2.0
    assert samples[
        'paddle_tpu_serving_requests_total{class="p2",status="served"}'
    ] == 1.0


# ---------------------------------------------------------------------------
# step accounting (ISSUE 26): the trainer loop's five spans, the slow-step
# record, and the scopes of the jitted step.  Counts and structure only: the
# clock is a private Tracer's, so no timing is asserted.
# ---------------------------------------------------------------------------

_STEP_CHILDREN = ("feed_wait", "train_step", "block_fetch")
_N_BATCHES = 6


@pytest.fixture()
def private_tracer(monkeypatch):
    """trainer/sgd.py emits through ``obs.span`` / ``obs.instant``: point
    both at a Tracer with a fake clock (1 ms a reading) that a test can
    push forward."""
    clock = FakeClock()
    t = Tracer(clock=clock, ring_events=4096)
    monkeypatch.setattr(obs, "span", t.span)
    monkeypatch.setattr(obs, "instant", t.instant)
    t.clock = clock
    return t


def _tiny_trainer(optimizer=None):
    import paddle_tpu as paddle
    from paddle_tpu.core.topology import reset_auto_names

    reset_auto_names()
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(4))
    y = paddle.layer.data(name="y", type=paddle.data_type.dense_vector(1))
    pred = paddle.layer.fc(input=x, size=1, act=paddle.activation.Linear())
    cost = paddle.layer.square_error_cost(input=pred, label=y)
    return paddle.trainer.SGD(
        cost=cost,
        parameters=paddle.parameters.create(cost, seed=0),
        update_equation=optimizer or paddle.optimizer.Adam(learning_rate=0.05),
    )


def _tiny_reader(on_batch=None):
    """_N_BATCHES batches of 4 rows; on_batch(i) runs as batch i is read."""
    import numpy as np

    import paddle_tpu as paddle

    def samples():
        rng = np.random.RandomState(0)
        for i in range(_N_BATCHES * 4):
            if on_batch is not None and i % 4 == 0:
                on_batch(i // 4)
            xv = rng.randn(4).astype(np.float32)
            yield xv, np.array([xv.sum()], np.float32)

    return paddle.batch(samples, 4)


def _spans_by_thread(tracer):
    """{tid: [(name, begin_us, end_us, args, depth, parent index)]} from the
    ring's B/E events, in begin order."""
    out = {}
    for ev in tracer.events():
        if ev["ph"] == "M":
            continue
        spans, stack = out.setdefault(ev["tid"], ([], []))
        if ev["ph"] == "B":
            stack.append(len(spans))
            spans.append([ev["name"], ev["ts"], None, ev.get("args", {}),
                          len(stack) - 1, stack[-2] if len(stack) > 1 else None])
        elif ev["ph"] == "E":
            top = spans[stack.pop()]
            assert top[0] == ev["name"]  # properly nested
            top[2] = ev["ts"]
    assert all(not stack for _, stack in out.values())
    return {tid: spans for tid, (spans, _) in out.items()}


@pytest.mark.parametrize("depth", ["one_in_flight", "none_in_flight"])
@pytest.mark.parametrize("async_load_data", [True, False])
def test_step_span_partitions_every_iteration(private_tracer, tmp_path, async_load_data, depth):
    import paddle_tpu as paddle

    ended = []
    trainer = _tiny_trainer()
    # with a checkpoint_dir every step is settled in the iteration that
    # dispatched it; without, in the next one (trainer.SGD.train's docstring)
    ahead = depth == "one_in_flight"
    trainer.train(
        _tiny_reader(), num_passes=1, async_load_data=async_load_data,
        checkpoint_dir=None if ahead else str(tmp_path / "ck"),
        event_handler=lambda e: ended.append(e.batch_id)
        if isinstance(e, paddle.event.EndIteration) else None,
    )
    assert ended == list(range(_N_BATCHES))
    by_thread = _spans_by_thread(private_tracer)
    loop = next(s for s in by_thread.values() if any(x[0] == "step" for x in s))
    steps = [i for i, s in enumerate(loop) if s[0] == "step"]
    # one `step` per iteration, each a child of the call's one `train` span
    # (PR 38), back to back in batch order; the last is the iteration that
    # found the pass exhausted
    assert [loop[i][3]["b"] for i in steps] == list(range(_N_BATCHES + 1))
    (train,) = [i for i, s in enumerate(loop) if s[0] == "train"]
    assert loop[train][4] == 0 and loop[train][3] == {"passes": 1}
    assert all(loop[i][5] == train and loop[i][3]["p"] == 0 for i in steps)
    for i in steps:
        name, t0, t1, args, _, _ = loop[i]
        kids = [s for s in loop if s[5] == i]
        b = args["b"]
        # every iteration that dispatches holds one feed_wait and one
        # train_step of its own batch, and one block_fetch: of its own step at
        # depth 0, of the step before under run-ahead (none in the first);
        # the exhausted iteration dispatches nothing and fetches the last
        # step's cost if that is still in flight
        fetched = b - 1 if ahead else b
        want = [("feed_wait", b)]
        if b < _N_BATCHES:
            want.append(("train_step", b))
        if 0 <= fetched < _N_BATCHES:
            want.append(("block_fetch", fetched))
        assert [(k[0], k[3]["b"]) for k in kids] == want
        if b < _N_BATCHES:
            assert kids[1][3]["p"] == args["p"]
        # children lie inside the parent, in order, without overlap: what
        # they leave uncovered is the step's self time, and the four add up
        edges = [t0] + [t for k in kids for t in (k[1], k[2])] + [t1]
        assert edges == sorted(edges)
        covered = sum(k[2] - k[1] for k in kids)
        self_us = sum(b - a for a, b in zip(edges[::2], edges[1::2]))
        assert covered + self_us == pytest.approx(t1 - t0)
        assert self_us > 0  # handlers and judge_step ran under no child
    # the staging work: inside feed_wait on the trainer thread when the feed
    # is synchronous, on the prefetch thread (no child of step) when not
    feeds = [(tid, s) for tid, spans in by_thread.items() for s in spans if s[0] == "feed"]
    assert len(feeds) == _N_BATCHES
    for tid, s in feeds:
        if async_load_data:
            assert by_thread[tid] is not loop and s[5] is None
        else:
            assert by_thread[tid] is loop and loop[s[5]][0] == "feed_wait"


def test_a_step_that_saves_is_fetched_before_the_next_dispatch(private_tracer, tmp_path):
    """A batch-period save reads the parameters its step left: that step's
    block_fetch comes BEFORE the next train_step, in the same iteration,
    and the iteration still holds at most one of each child."""
    _tiny_trainer().train(_tiny_reader(), num_passes=1, save_dir=str(tmp_path),
                          saving_period_by_batches=3)
    loop = next(s for s in _spans_by_thread(private_tracer).values()
                if any(x[0] == "step" for x in s))
    got = {s[3]["b"]: [(k[0], k[3]["b"]) for k in loop if k[5] == i]
           for i, s in enumerate(loop) if s[0] == "step"}
    assert got[2] == [("feed_wait", 2), ("train_step", 2), ("block_fetch", 1)]
    assert got[3] == [("feed_wait", 3), ("block_fetch", 2), ("train_step", 3)]  # batch 3 saved
    assert got[4] == [("feed_wait", 4), ("train_step", 4), ("block_fetch", 3)]
    assert got[_N_BATCHES] == [("feed_wait", _N_BATCHES), ("block_fetch", _N_BATCHES - 1)]
    drains = [e["args"] for e in private_tracer.events() if e["name"] == "run_ahead_drain"]
    assert drains == [{"p": 0, "b": 2}]  # the save after the last batch drained nothing early


@pytest.mark.parametrize("phase", ["feed_wait_ms", "self_ms"])
def test_slow_step_emits_one_record_with_its_phases(private_tracer, caplog, phase):
    import paddle_tpu as paddle
    from paddle_tpu.utils.timers import global_stats

    clock = private_tracer.clock
    slow_batch = 4

    def stall(i):
        if i == slow_batch:
            clock.t += 0.5  # half a second, inside whatever span is open

    def handler(e):
        if phase == "self_ms" and isinstance(e, paddle.event.EndIteration):
            stall(e.batch_id)

    before = global_stats.count("slow_steps")
    with caplog.at_level("WARNING", logger="paddle_tpu.trainer"):
        _tiny_trainer().train(
            _tiny_reader(stall if phase == "feed_wait_ms" else None),
            num_passes=1, async_load_data=False, event_handler=handler,
        )
    slow = [e for e in private_tracer.events() if e["name"] == "slow_step"]
    assert len(slow) == 1 and slow[0]["ph"] == "i" and slow[0]["cat"] == "trainer"
    args = slow[0]["args"]
    assert set(args) == {"p", "b", "fetched", "ms", "feed_wait_ms", "dispatch_ms",
                         "fetch_ms", "self_ms"}
    # batch 4 is read in the iteration that dispatches it, while step 3 is in
    # flight; its EndIteration comes one iteration later, once step 5 is
    # dispatched: the record names the iteration AND the step it fetched
    b = slow_batch if phase == "feed_wait_ms" else slow_batch + 1
    assert (args["p"], args["b"], args["fetched"]) == (0, b, b - 1)
    parts = ("feed_wait_ms", "dispatch_ms", "fetch_ms", "self_ms")
    assert sum(args[k] for k in parts) == pytest.approx(args["ms"])
    assert args[phase] >= 500 and args["ms"] - args[phase] < 50  # the phase that held it
    assert global_stats.count("slow_steps") == before + 1
    lines = [r.getMessage() for r in caplog.records if "slow_step" in r.getMessage()]
    assert len(lines) == 1 and f"batch {b}:" in lines[0] and f"of batch {b - 1}," in lines[0]


def test_a_stall_in_the_last_fetch_of_a_pass_is_recorded(private_tracer):
    """The iteration that finds the pass exhausted dispatches nothing, and
    waits for the last step's cost: a stall there is a slow_step too."""
    import paddle_tpu as paddle

    clock = private_tracer.clock

    def handler(e):
        if isinstance(e, paddle.event.EndIteration) and e.batch_id == _N_BATCHES - 1:
            clock.t += 0.5

    _tiny_trainer().train(_tiny_reader(), num_passes=1, event_handler=handler)
    slow = [e["args"] for e in private_tracer.events() if e["name"] == "slow_step"]
    assert [(a["b"], a["fetched"], a["dispatch_ms"]) for a in slow] == [
        (_N_BATCHES, _N_BATCHES - 1, 0.0)]


def test_steady_run_emits_no_slow_step(private_tracer):
    from paddle_tpu.utils.timers import global_stats

    before = global_stats.count("slow_steps")
    trainer = _tiny_trainer()
    trainer.train(_tiny_reader(), num_passes=2)
    assert not [e for e in private_tracer.events() if e["name"] == "slow_step"]
    assert global_stats.count("slow_steps") == before
    # every iteration that dispatched or fetched joined the history: one a
    # batch, and the one at each pass's end that fetched the last cost
    assert len(trainer._step_ms) == 2 * (_N_BATCHES + 1)


def test_slow_step_needs_the_recorder(private_tracer):
    """Disarmed, the spans read no clock: nothing is judged or counted."""
    private_tracer.set_recording(False)
    trainer = _tiny_trainer()
    trainer.train(_tiny_reader(), num_passes=1)
    assert not trainer._step_ms
    assert [e for e in private_tracer.events() if e["ph"] != "M"] == []


def _scope_names(lowered):
    import re

    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _bare(names):
    """The operations of the step that run outside every type:name scope of
    the program (trace_reduce.SCOPE's form)."""
    import re

    return [n for n in names if n.startswith("jit(step)/")
            and not re.search(r"[A-Za-z_0-9]+:[A-Za-z_0-9.@]+", n)]


def test_jitted_step_runs_under_optimizer_guard_and_data_scopes():
    import jax
    import numpy as np

    trainer = _tiny_trainer()
    feeder = trainer._make_feeder(None)
    batch = feeder([(np.ones(4, np.float32), np.ones(1, np.float32))] * 4)
    # a dense slot on the narrow-dtype wire: the feed transform has work to do
    batch["x"] = type(batch["x"])(
        np.asarray(batch["x"].data, np.uint8), batch["x"].lengths)
    p = trainer.parameters
    names = _scope_names(trainer._train_step.lower(
        p.params, p.state, trainer._opt_state, batch, jax.random.PRNGKey(0)))
    for scope in ("optimizer:adam/", "guard:sentinel/", "data:x)", "fc:", "square_error:"):
        assert any(scope in n for n in names), scope
    # the selects that skip a bad step are the roots of the update's fused
    # kernels: they carry the optimizer's scope inside the guard's
    assert any("guard:sentinel/optimizer:adam/" in n for n in names)
    assert not _bare(names)


def test_classification_error_metric_runs_under_an_evaluator_scope():
    """The default classification-error metric (an argmax over the whole
    vocabulary) is device time too: it has a scope, and with it the whole
    seq2seq step has no operation outside one."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.topology import reset_auto_names
    from paddle_tpu.models.seq2seq import seq2seq_cost

    reset_auto_names()
    cost, _ = seq2seq_cost(13, 13, word_dim=5, hidden_dim=4)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=paddle.parameters.create(cost, seed=0),
        update_equation=paddle.optimizer.Adam(learning_rate=1e-3))
    rng = np.random.RandomState(0)
    rows = [tuple(list(rng.randint(2, 13, n)) for n in (4, 5, 5)) for _ in range(4)]
    batch = trainer._make_feeder({"src_word": 0, "trg_word": 1, "trg_next": 2})(rows)
    p = trainer.parameters
    names = _scope_names(trainer._train_step.lower(
        p.params, p.state, trainer._opt_state, batch, jax.random.PRNGKey(0)))
    assert any("evaluator:classification_error." in n for n in names)
    assert any("/attgru_core/" in n for n in names)
    assert not _bare(names)


def test_attention_gru_core_scope_in_forward_and_backward():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle  # noqa: F401  (registers layers)
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology, reset_auto_names
    from paddle_tpu.models.seq2seq import seq2seq_cost

    reset_auto_names()
    cost, _ = seq2seq_cost(13, 13, word_dim=5, hidden_dim=4)
    net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
    params, state = net.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    lens = jnp.asarray(rng.randint(2, 7, 4), jnp.int32)
    batch = {k: SeqTensor(jnp.asarray(rng.randint(2, 13, (4, 6)), jnp.int32), lens)
             for k in ("src_word", "trg_word", "trg_next")}

    def loss(p):
        return net.cost(p, batch, state=state, train=True)[0]

    names = _scope_names(jax.jit(jax.grad(loss)).lower(params))
    core = [n for n in names if "/attgru_core/" in n]
    fwd = [n for n in core if "transpose(" not in n]
    bwd = [n for n in core if "transpose(" in n]
    assert fwd and bwd
    # under the decoder's layer scope, and itself no type:name scope, so the
    # layer stays the operations' innermost one
    assert all("recurrent_group:decoder" in n.split("/attgru_core/")[0] for n in core)
    # mixed precision's casts of a layer's weights sit BESIDE the layer's
    # scope (cast:<layer>), not inside it: the readers that time a layer by
    # its scope (attention_roofline matches the scope anywhere in the name)
    # read the layer alone
    casts = [n for n in names if "cast:" in n]
    assert any("cast:decoder" in n for n in casts)
    assert any("cast:enc_fw" in n and "transpose(" in n for n in casts)
    assert not [n for n in casts if "recurrent_group:" in n or "gru:" in n or "fc:" in n]


def _hybrid_step_names():
    """The names in the lowered train step of a toy hybrid decoder (pattern
    MEM*E, bfloat16 compute), a run of the same network's forward pass, and
    the operations' names in the COMPILED step: a jitted function that several
    layers share is lowered once, its operations named from its own entry on,
    and XLA puts each call site's scopes before them where it inlines it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core.compiler import get_default_compute_dtype, set_default_compute_dtype
    from paddle_tpu.core.topology import reset_auto_names
    from paddle_tpu.models.hybrid_lm import hybrid_lm_cost

    reset_auto_names()
    before = get_default_compute_dtype()
    set_default_compute_dtype(jnp.bfloat16)
    try:
        cost, _ = hybrid_lm_cost(
            "MEM*E", 50, 16, mamba_heads=4, mamba_head_dim=8, mamba_groups=2, state_size=4,
            chunk_size=4, attn_heads=4, attn_kv_heads=2, attn_head_dim=8, num_experts=8,
            experts_per_token=3, expert_hidden=12, shared_hidden=20, experts_held=(2, 4))
        trainer = paddle.trainer.SGD(
            cost=cost, parameters=paddle.parameters.create(cost, seed=0),
            update_equation=paddle.optimizer.Adam(learning_rate=1e-3))
    finally:
        set_default_compute_dtype(before)
    rng = np.random.RandomState(0)
    rows = [tuple(list(rng.randint(2, 50, 10)) for _ in range(2)) for _ in range(3)]
    batch = trainer._make_feeder({"word": 0, "next_word": 1})(rows)
    p = trainer.parameters
    import re

    lowered = trainer._train_step.lower(p.params, p.state, trainer._opt_state, batch, jax.random.PRNGKey(0))
    outs, _ = trainer.network.apply(p.params, batch, state=p.state, train=True)
    return _scope_names(lowered), outs, set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))


@pytest.fixture(scope="module")
def hybrid_step():
    return _hybrid_step_names()


def test_hybrid_decoder_step_has_no_operation_outside_a_scope(hybrid_step):
    names, _, _ = hybrid_step
    for scope in ("mamba2:l0_mamba", "rms_norm:l0_norm", "moe_topk:l1_moe",
                  "multi_head_attention:l3_attn", "cast:l0_mamba", "optimizer:adam/"):
        assert any(scope in n for n in names), scope
    # the one operation outside a scope is no layer's: the zeros that
    # jax.grad itself writes for the experts' correction bias, which steers
    # the choice alone and so has no gradient (a constant, folded by XLA)
    assert _bare(names) == ["jit(step)/broadcast_in_dim"]


@pytest.mark.parametrize("inner,layer", [
    ("ssd_scan", "mamba2:"), ("moe_route", "moe_topk:"),
    ("moe_experts", "moe_topk:"), ("moe_shared", "moe_topk:"),
])
def test_hybrid_decoder_inner_scopes_stay_within_their_layer(hybrid_step, inner, layer):
    """Forward and backward: a scope without a colon, so the layer stays the
    operations' innermost `type:name` scope (what `ssd_scan_roofline` and
    `moe_experts_roofline` read beside `ssm_layers_share`, `moe_layers_share`)."""
    names, _, _ = hybrid_step
    mine = [n for n in names if f"/{inner}/" in n]
    assert [n for n in mine if "transpose(" not in n], inner
    # the route's choice has no gradient of its own; the rest run both ways
    assert inner == "moe_route" or [n for n in mine if "transpose(" in n], inner
    assert all(layer in n.split(f"/{inner}/")[0] for n in mine)


def test_expert_layer_counters_ride_the_aux_outputs(hybrid_step):
    """`<name>@rows_held`, `<name>@rows_over_bound` and `<name>@rows_dropped`,
    an int32 [B, 1] row each as `@aux_loss`: rows computed here, the passes
    beyond the first they took, and none dropped, in every layer."""
    import numpy as np

    from paddle_tpu.layers.moe import held_rows_bound

    _, outs, _ = hybrid_step
    for name in ("l1_moe", "l4_moe"):
        held, over, dropped = (np.asarray(outs[f"{name}@{k}"].data)
                               for k in ("rows_held", "rows_over_bound", "rows_dropped"))
        for counter in (held, over, dropped):
            assert counter.shape == (3, 1) and counter.dtype == np.int32
            assert (counter == counter[0, 0]).all()
        assert 0 < held[0, 0] <= 3 * 10 * 3  # tokens x choices
        # 2 of 8 experts held: passes of 48 rows over the 90 pairs
        bound = held_rows_bound(3 * 10, 3, 2, 8)
        assert bound == 48 and over[0, 0] == max(-(-held[0, 0] // bound) - 1, 0)
        assert (dropped == 0).all()


def test_the_held_experts_passes_run_under_the_layers_scopes(hybrid_step):
    """The loop over the passes beyond the first, forward and backward, and
    what runs inside it: `moe_topk:<name>` then `moe_experts`, as the
    operations of the first pass (what `moe_layers_share` and
    `moe_experts_roofline` read).  The passes are two jitted functions that
    the expert layers share (`_forward`, `_backward`: lowered once, called
    under each layer's scopes), so the layers' names are read where XLA has
    inlined them: in the compiled step, every layer's own."""
    names, _, compiled = hybrid_step
    for layer in ("l1_moe", "l4_moe"):
        assert f"jit(step)/jvp(moe_topk:{layer})/moe_experts/jit(_forward)" in names
        assert f"jit(step)/transpose(jvp(moe_topk:{layer}))/moe_experts/jit(_backward)" in names
    assert {"while/body/ragged_dot_general", "while/body/transpose(jvp())/ragged_dot_general"} <= names
    loops = [n for n in compiled if n.endswith("/while") or "/while/body/" in n]
    mine = [n for n in loops if "/moe_experts/" in n]
    assert [n for n in mine if "transpose(" in n] and [n for n in mine if "transpose(" not in n]
    assert all("moe_topk:" in n.split("/moe_experts/")[0] for n in mine)
    assert {n.split("moe_topk:")[1][:6] for n in mine} == {"l1_moe", "l4_moe"}
