"""The FLOP and byte functions of `ouro-2.6b` against a hand count at toy
widths and against ISSUE 36's arithmetic at the cell's size; the three
per-layer readers this configuration brings, on a made-up trace with the new
scopes and on one without them; and the two faults the reference can plant
in itself, under the rehearsal's limits; and the reference's scan over the
passes against the same cost with the passes as a Python loop."""

import json
import os

import jax
import numpy as np
import pytest

import refsteps
import run
import trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "ouro-2.6b"


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_hand_count_at_toy_widths():
    f = refsteps.load_by_name("flops", NAME)
    cfg = {"hidden_size": 4, "intermediate_size": 6, "num_attention_heads": 2, "head_dim": 3,
           "vocab_size": 11, "num_hidden_layers": 2, "total_ut_steps": 3}
    lens = {"len": np.array([3, 2])}
    d, f_, hd, t = 4, 6, 6, 5
    pairs = 3 * 4 / 2 + 2 * 3 / 2
    att = t * 4 * 2 * d * hd + pairs * 2 * 2 * hd  # four projections; QK^T and AV over the causal pairs
    mlp = t * 3 * 2 * d * f_  # gate, up, down
    head = t * (2 * d * 11 + 2 * d)  # the output matrix and the exit gate, on EVERY pass
    assert f.train_step_flops(cfg, lens) == pytest.approx(3 * 3 * (2 * (att + mlp) + head))
    assert f.layer_parameters(cfg) == 4 * d * hd + 3 * d * f_
    flops, nbytes = f.kernels(cfg, lens)["attention"]
    assert flops == pytest.approx(3 * 3 * 2 * att)
    assert nbytes == pytest.approx(3 * 3 * 2 * (2 * t * d + 4 * d * hd) * 2)


def test_the_cells_step_is_issue_36s_count():
    f = refsteps.load_by_name("flops", NAME)
    cfg, lens = _cfg(), {"len": np.full(2, 2048)}
    assert f.layer_parameters(cfg) == pytest.approx(51.38e6, rel=1e-3)
    assert f.parameters(cfg) == pytest.approx(612.4e6, rel=1e-3)
    assert f.parameters(dict(cfg, num_hidden_layers=48)) == pytest.approx(2.67e9, rel=5e-3)
    # the weights' products: 6 x 4,096 tokens x (4 x 8 x 51.38 M + 4 x 100.7 M) = 50 TFLOP; + 3.3 of scores
    step = f.train_step_flops(cfg, lens)
    assert step == pytest.approx(53.6e12, rel=0.01)
    scores = 3 * 4 * 8 * 2 * (2048 * 2049 / 2) * 2 * 2 * 2048
    assert scores == pytest.approx(3.3e12, rel=0.01)
    assert step - scores == pytest.approx(6 * 4096 * (4 * 8 * 51.38e6 + 4 * 100.67e6), rel=1e-3)
    # a predicted token costs four passes: 13.1 GFLOP of model work
    assert step / 4096 == pytest.approx(13.1e9, rel=0.01)
    # the attention layers whole are bound by operations on a v5e, not bytes
    flops, nbytes = f.kernels(cfg, lens)["attention"]
    assert flops == pytest.approx(16.5e12, rel=0.01) and flops / 197e12 > nbytes / 819e9


def _ctx(ops):
    """A context as metrics_loader.read_all builds it, over a made-up trace:
    ops = [(seconds, event name, tf_op)] back to back inside one step."""
    events, meta, at = [], {}, 0.0
    for i, (seconds, name, tf_op) in enumerate(ops):
        name = f"{name}.{i}"
        events.append((at, at + seconds, name))
        meta[name] = {"tf_op": tf_op, "hlo_category": "fusion"}
        at += seconds
    plane = "/device:TPU:0"
    trace = trace_reduce.Trace({plane: {"ops": events, "modules": [(0.0, 1.0, "jit_step")]}}, {plane: meta}, {})
    return {"trace": trace, "plane": plane, "window_s": 1.0, "traced_steps": 1,
            "steps": [{"items": 4096, "lens": {"len": np.full(2, 2048)}}], "cfg": _cfg(), "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "flops": refsteps.load_by_name("flops", NAME)}


def _read(metric, ctx):
    return refsteps.load_by_name("layer_metrics", metric).read(ctx)


def test_the_new_readers_on_a_trace_with_the_new_scopes():
    step = "jit(step)/jit(main)/"
    fwd, bwd = step + "jvp(layer_loop:ut)/while/body/", step + "transpose(jvp(layer_loop:ut))/while/body/"
    ctx = _ctx([
        (0.10, "%fusion", fwd + "checkpoint/multi_head_attention:l0_attn/dot_general"),
        (0.02, "%fusion", fwd + "checkpoint/multi_head_attention:l0_attn/rope/mul"),
        (0.08, "%fusion", fwd + "checkpoint/fc:l0_gate/dot_general"),
        (0.10, "%fusion", bwd + "checkpoint/rematted_computation/multi_head_attention:l3_attn/dot_general"),
        (0.20, "%fusion", bwd + "checkpoint/multi_head_attention:l3_attn/dot_general"),
        (0.10, "%fusion", bwd + "checkpoint/fc:l3_down/dot_general"),
        (0.12, "%fusion", step + "jvp(looped_exit_cost:lm_cost)/while/body/checkpoint/fc:lm_out/dot_general"),
        (0.03, "%fusion", step + "transpose(jvp(looped_exit_cost:lm_cost))/while/body/checkpoint/reduce_sum"),
        (0.05, "%fusion", step + "optimizer:adam/mul"),
    ])
    assert _read("loop_layers_share", ctx) == pytest.approx(60.0)
    assert _read("exit_cost_share", ctx) == pytest.approx(15.0)
    f, b = ctx["flops"].kernels(ctx["cfg"], ctx["steps"][0]["lens"])["attention"]
    # 0.42 s under multi_head_attention:*, the recomputed forward's 0.10 among them: time, not work
    assert _read("loop_attention_roofline", ctx) == pytest.approx(100 * (f / 197e12) / 0.42)
    assert _read("loop_attention_roofline", ctx) < 100.0


@pytest.mark.parametrize("metric", ["loop_layers_share", "exit_cost_share", "loop_attention_roofline"])
def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(metric):
    ctx = _ctx([(0.5, "%fusion", "jit(step)/jit(main)/jvp(fc:dec_out)/dot_general")])
    assert _read(metric, ctx) is None


@pytest.mark.parametrize("fault", ["three_passes", "no_entropy_term"])
def test_the_planted_faults_read_as_not_correct(fault):
    _, cell, cfg, mix, limits = run.load_cell("ouro-train-2k", rehearsal=True)
    ref = run.reference_readings(cell, cfg, mix, seed=4)
    bad = run.reference_readings(cell, dict(cfg, reference_fault=fault), mix, seed=4)
    ok, compared, _ = run.decide_correct(bad, ref, limits)
    assert not ok, compared


def test_the_references_scan_over_the_passes_is_a_python_loop_over_them(monkeypatch):
    """The reference runs its passes as a `lax.scan` (its docstring says why),
    the form the program has too: the same `block_cost` with the scan swapped
    for a Python loop over the passes gives the cost and every leaf's
    gradient, so the scan's transpose sums the passes' gradients as the
    unrolled sum does."""
    import traffic

    _, _, cfg, mix, _ = run.load_cell("ouro-train-2k", rehearsal=True)
    kind = traffic.kind(mix)
    batch = kind.as_arrays(kind.make_corpus(dict(mix, corpus_batches=1), cfg, 7)[0])
    w = run.drawn_from(cfg, 7)()
    block_cost = refsteps.load_by_name("reference", cfg["reference"]).make_block_cost(cfg)
    grad = jax.value_and_grad(lambda w_: block_cost(w_, batch, refsteps.mm_float32))
    scanned, scanned_g = grad(w)

    def loop(body, carry, xs, length):
        assert xs is None and length == cfg["total_ut_steps"]
        calls.append(length)
        outs = []
        for _ in range(length):
            carry, out = body(carry, None)
            outs.append(out)
        return carry, jax.tree.map(lambda *a: jax.numpy.stack(a), *outs)

    calls = []
    monkeypatch.setattr(jax.lax, "scan", loop)
    looped, looped_g = grad(w)
    assert calls == [cfg["total_ut_steps"]]
    assert float(looped) == pytest.approx(float(scanned), rel=1e-6)
    for name in w:
        gap = refsteps.leaf_norm(looped_g[name], scanned_g[name]) / refsteps.leaf_norm(looped_g[name])
        assert gap < 1e-5, (name, gap)
    assert refsteps.leaf_norm(scanned_g["gate.w"]) > 0 and refsteps.leaf_norm(scanned_g["l0.attn.wq"]) > 0


def test_the_benchmark_declares_the_cell_and_its_three_metrics():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == "ouro-train-2k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "tokens-2048-b2", 1)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == _cfg()["reduced"] == ["num_hidden_layers"]
    mine = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == ["ouro-train-2k"]}
    assert sorted(mine) == ["exit_cost_share", "loop_attention_roofline", "loop_layers_share"]
    assert all(m["moves"] == "train_throughput" and m["source"] == "device_trace" for m in mine.values())
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1 and len(bench["workloads"]) == 6
