"""Finds each per-layer metric's reader by its name in BENCHMARK.json
(`layer_metrics/<name>.py`, a function `read(ctx)`), hands every reader the
same context, and leaves out of the line what a reader finds nothing to
read for (None).  Also the table of peaks and the `breakdown`."""

import json
import os
import re

import refsteps
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark/peaks.json has no peaks for device kind {device_kind!r}")
    return table[device_kind]


def _collapse(label):
    """enc3_att -> encN_att, so the breakdown's lines are kinds of layer."""
    return re.sub(r"(?<=[a-z])\d+(?=_|\b)", "N", label)


def _merge(pairs, n=10):
    acc = {}
    for k, v in pairs:
        k = _collapse(k)
        acc[k] = acc.get(k, 0.0) + v
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def read_all(bench, cell, cfg, trace_dir, steps, peaks, counters):
    """-> (metrics, device fields busy_s/window_s, breakdown)."""
    trace = trace_reduce.Trace.from_file(trace_reduce.find_xplane(trace_dir))
    plane = trace.fullest()
    planes = [p for p in trace.devices if trace.devices[p]["modules"]]
    n = trace.steps(plane)
    lo, hi = trace.window(plane)
    ctx = {
        "trace": trace, "plane": plane, "window_s": hi - lo, "traced_steps": n,
        "steps": steps[:n], "cfg": cfg, "chips": cell["chips"], "peaks": peaks,
        "counters": counters,
        "flops": refsteps.load_by_name("flops", cfg["flops"]),
    }
    metrics = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = refsteps.load_by_name("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "busy_s": sum(trace.busy_seconds(p) for p in planes) / len(planes),
        "window_s": sum(trace.window(p)[1] - trace.window(p)[0] for p in planes) / len(planes),
    }
    breakdown = {
        "device_ops": _merge(trace.top_operations(plane, 10 ** 6)),
        "idle_gaps": _merge(trace.idle_gaps(plane)),
    }
    return metrics, device, breakdown


def roofline_share(ctx, kernel, seconds):
    """Least time for the kernel's operations and bytes over the traced
    steps, per chip, as a percentage of the device seconds it took."""
    if not seconds:
        return None
    flops = nbytes = 0.0
    for step in ctx["steps"]:
        f, b = ctx["flops"].kernels(ctx["cfg"], step["lens"])[kernel]
        flops += f
        nbytes += b
    least = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"]) / ctx["chips"]
    return 100.0 * least / seconds
