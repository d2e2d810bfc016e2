"""The trace reduction against hand-made intervals and against a small
recorded trace (two NMT bs-512 steps cut from a TPU v5e trace of PR 24's
probe) whose answers were computed by an independent parse of the file."""

import json
import os

import pytest

import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "nmt_two_steps")


def test_union_and_gaps_by_hand():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert T.union_seconds(iv) == pytest.approx(3.0)
    assert T.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert T.gaps(iv, 0.25, 3.5) == [(2.0, 3.0)]
    assert T.union_seconds([]) == 0.0


def test_scopes_of_a_name_stack():
    s = "jit(step)/transpose(jvp(recurrent_group:decoder))/while/body/fc:dec_out/dot_general:"
    assert T.scopes_of(s) == ["recurrent_group:decoder", "fc:dec_out"]
    assert T.scopes_of("jit(step)/mul") == []


def test_idle_gaps_are_labelled_by_the_trainers_thread_first():
    # two steps on the device with 30 ms between them; the trainer waits 18 ms
    # for its batch, then dispatches for 10 ms; the prefetch thread's `feed`
    # lies over the whole stretch, and is the label only where it is alone
    ops = [(0.000, 0.100, "a"), (0.130, 0.230, "a"), (0.250, 0.300, "a")]
    host = {"block_fetch": [(0.010, 0.101)], "feed_wait": [(0.101, 0.119)],
            "train_step": [(0.119, 0.129)], "feed": [(0.095, 0.135), (0.225, 0.255)]}
    trace = T.Trace({"/device:TPU:0": {"ops": ops, "modules": [(0.0, 0.3, "jit_step")]}}, {}, host)
    labels = dict(trace.idle_gaps("/device:TPU:0"))
    assert labels == {"feed_wait": pytest.approx(0.030), "feed": pytest.approx(0.020)}
    # a trace from before `feed_wait` existed still gets its three labels
    del host["feed_wait"]
    assert dict(trace.idle_gaps("/device:TPU:0")) == {
        "train_step": pytest.approx(0.030), "feed": pytest.approx(0.020)}


def test_recorded_trace_matches_the_independent_answers():
    with open(os.path.join(DATA, "answers.json")) as f:
        want = json.load(f)
    trace = T.Trace.from_file(T.find_xplane(DATA))
    plane = trace.fullest()
    assert plane == "/device:TPU:0"
    assert trace.steps(plane) == want["steps"]
    lo, hi = trace.window(plane)
    assert hi - lo == pytest.approx(want["window_s"], rel=2e-4)
    assert trace.busy_seconds(plane) == pytest.approx(want["busy_s"], rel=2e-4)
    scan = trace.seconds_where(
        plane, lambda n, tf_op, c: (T.scopes_of(tf_op) or [""])[-1].startswith("recurrent_group:"))
    assert scan == pytest.approx(want["scan_s"], rel=2e-4)
    assert len(trace.host["feed"]) == want["feed_spans"]
    assert sum(e - s for s, e in trace.host["feed"]) == pytest.approx(want["feed_s"], rel=2e-4)
    # nothing ran between the two programs but the host: the gaps are labelled
    labels = dict(trace.idle_gaps(plane))
    assert sum(labels.values()) == pytest.approx(want["window_s"] - want["busy_s"], abs=2e-5)
    # containers (the scan's `while`) are not counted beside their bodies
    meta = trace.meta[plane]
    names = {n for _, _, n in trace.devices[plane]["ops"]}
    assert not any(meta[n].get("hlo_category") in T.CONTAINERS for n in names)
    top = trace.top_operations(plane, 3)
    assert top[0][0].startswith("recurrent_group:decoder")
