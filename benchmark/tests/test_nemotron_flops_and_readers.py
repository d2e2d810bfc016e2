"""The FLOP and byte functions of `nemotron3-nano-30b-a3b` against a hand
count at toy widths and against ISSUE 29's arithmetic at the cell's size; the
four per-layer readers this configuration brings, on a made-up trace with the
new scopes and on one without them (what a parent commit's run gives)."""

import json
import os

import numpy as np
import pytest

import refsteps
import trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "nemotron3-nano-30b-a3b"


def _cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_hand_count_at_toy_widths():
    f = refsteps.load_by_name("flops", NAME)
    cfg = {"hybrid_override_pattern": "ME*", "hidden_size": 4, "vocab_size": 11,
           "mamba_num_heads": 2, "mamba_head_dim": 3, "n_groups": 1, "ssm_state_size": 5, "conv_kernel": 4,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3,
           "n_routed_experts": 2, "n_routed_experts_published": 8, "num_experts_per_tok": 3,
           "moe_intermediate_size": 6, "moe_shared_expert_intermediate_size": 7}
    lens = {"len": np.array([3, 2])}
    d, t = 4, 5
    inner, conv = 6, 6 + 2 * 5
    scan = 2 * 4 * 3 * 5
    mamba = t * (2 * d * (2 * inner + 2 * 5 + 2) + 2 * inner * d + 2 * 4 * conv + scan)
    pairs = 3 * 4 / 2 + 2 * 3 / 2
    att = t * (2 * 2 * d * 12 + 2 * 2 * d * 6) + pairs * 2 * 2 * 12
    rows = t * 3 * 2 / 8
    routed = rows * 2 * 2 * d * 6
    experts = t * 2 * d * 8 + t * 2 * 2 * d * 7 + routed
    out = t * 2 * d * 11
    assert f.train_step_flops(cfg, lens) == pytest.approx(3 * (mamba + att + experts + out))
    k = f.kernels(cfg, lens)
    assert k["ssd_scan"][0] == pytest.approx(3 * t * scan)
    assert k["ssd_scan"][1] == pytest.approx(3 * t * ((2 * 6 + 2 * 5) * 2 + 2 * 4))
    assert k["moe_experts"][0] == pytest.approx(3 * routed)
    assert k["moe_experts"][1] == pytest.approx(3 * (2 * 2 * d * 6 + rows * 2 * d) * 2)


def test_the_cells_step_is_issue_29s_count():
    # forward, a token: Mamba-2 4 x ~80 M, expert layers 4 x 48 M, attention
    # 64 M, output matrix 88 M; 8.2 TFLOP a step of 4,096 tokens
    f = refsteps.load_by_name("flops", NAME)
    cfg, lens = _cfg(), {"len": np.full(2, 2048)}
    assert f.train_step_flops(cfg, lens) == pytest.approx(8.2e12, rel=0.02)
    assert f._mamba_forward(cfg) == pytest.approx(81e6, rel=0.03)
    assert f._expert_layer_forward(cfg, 1.0) == pytest.approx(48e6, rel=0.01)
    assert f._attention_forward(cfg, lens) / 4096 == pytest.approx(64e6, rel=0.01)
    scan_flops, scan_bytes = f.kernels(cfg, lens)["ssd_scan"]
    assert scan_flops == 3 * 4 * 4096 * 64 * 4 * 64 * 128
    # bound by bytes on a v5e: 1.0 GB a step against 0.10 TFLOP
    assert scan_bytes / 819e9 > scan_flops / 197e12


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
    ctx = _ctx([
        (0.10, "%fusion", step + "jvp(mamba2:l0_mamba)/dot_general"),
        (0.05, "%fusion", step + "jvp(mamba2:l0_mamba)/ssd_scan/dot_general"),
        (0.05, "%fusion", step + "transpose(jvp(mamba2:l2_mamba))/ssd_scan/mul"),
        (0.04, "%fusion", step + "jvp(moe_topk:l1_moe)/moe_route/sort"),
        (0.02, "%fusion", step + "jvp(moe_topk:l1_moe)/moe_experts/gather"),
        # XLA:TPU's own kernel of a ragged_dot: renamed, its name stack gone
        (0.03, "%ragged-dot-none", "ragged-dot-none"),
        (0.01, "%fusion", step + "jvp(moe_topk:l1_moe)/moe_shared/dot_general"),
        (0.30, "%fusion", step + "jvp(multi_head_attention:l7_attn)/dot_general"),
    ])
    assert _read("ssm_layers_share", ctx) == pytest.approx(20.0)
    assert _read("moe_layers_share", ctx) == pytest.approx(10.0)
    scan_flops, scan_bytes = ctx["flops"].kernels(ctx["cfg"], ctx["steps"][0]["lens"])["ssd_scan"]
    assert _read("ssd_scan_roofline", ctx) == pytest.approx(100 * (scan_bytes / 819e9) / 0.10)
    f, b = ctx["flops"].kernels(ctx["cfg"], ctx["steps"][0]["lens"])["moe_experts"]
    assert _read("moe_experts_roofline", ctx) == pytest.approx(100 * max(f / 197e12, b / 819e9) / 0.05)


@pytest.mark.parametrize("metric", ["ssm_layers_share", "moe_layers_share", "ssd_scan_roofline",
                                    "moe_experts_roofline"])
def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(metric):
    ctx = _ctx([(0.5, "%fusion", "jit(step)/jit(main)/jvp(fc:dec_out)/dot_general")])
    assert _read(metric, ctx) is None
