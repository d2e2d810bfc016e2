"""Times the Mamba-2 chunked scan alone on the chip: `ops/ssd.ssd_scan` on
XLA's operations against the Pallas kernels of `ops/ssd_pallas.py` over the
widths of their head block, forward alone and forward with the backward, at
the shapes of the two hybrid cells.  It decides `ops/ssd_pallas.BLOCK_LANES`;
its table lives in PERF.md section 6.  Run on a host with a TPU:

    python3 scripts/ssd_sweep.py [--shapes nemotron,granite]
        [--lanes 256,512,1024] [--calls 10] [--reps 5] [--out FILE]

`nemotron`: 2 rows of 2,048 tokens, 8 groups of 8 heads of 64, state 128,
chunks of 128; `granite`: the same rows, 1 group of 64 heads, chunks of 256.
bfloat16 x, b, c; float32 dt and a.  One JSON line a reading on stdout (and
all of them in `--out`, where given): ms of one call on the host's clock
(the median of `reps` batches of `calls` calls), ms of its device
operations and of the dearest of them from a profile of one more batch, and
the kernels' largest difference from XLA's result as a share of XLA's
largest entry.  `--rehearse` runs tiny shapes in interpret mode on any
backend: it checks the script and times nothing.  The program does not
import this file.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp

from grouped_product_sweep import device_ms

# (B, T, G, R, P, N, chunk)
SHAPES = {"nemotron": (2, 2048, 8, 8, 64, 128, 128), "granite": (2, 2048, 1, 64, 64, 128, 256)}
TINY = {"nemotron": (2, 40, 2, 4, 64, 8, 8), "granite": (2, 40, 1, 8, 64, 8, 16)}


def operands(shape, seed=0):
    b, t, g, r, p, n, _ = shape
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, t, g, r, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, g, r)) - 4.0)
    a = -jnp.exp(0.3 * jax.random.normal(k[2], (g, r)))
    bb = jax.random.normal(k[3], (b, t, g, n), jnp.bfloat16)
    cc = jax.random.normal(k[4], (b, t, g, n), jnp.bfloat16)
    dy = jax.random.normal(k[5], (b, t, g, r, p), jnp.bfloat16)
    return x, dt, a, bb, cc, dy


def scan(shape, kernels, backward, interpret):
    from paddle_tpu.ops.ssd import ssd_scan

    chunk = shape[-1]

    def f(x, dt, a, bb, cc, dy):
        y, vjp = jax.vjp(lambda *z: ssd_scan(*z, chunk=chunk, kernels=kernels, interpret=interpret),
                         x, dt, a, bb, cc)
        return (y, *vjp(dy)) if backward else (y,)
    return jax.jit(f)


def reading(fn, args, calls, reps, trace_dir):
    jax.block_until_ready(fn(*args))

    def batch():
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)

    host = []
    for _ in range(reps):
        t0 = time.perf_counter()
        batch()
        host.append((time.perf_counter() - t0) / calls * 1e3)
    out = {"host_ms": round(statistics.median(host), 4)}
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            batch()
        ops = {op: ms / calls for op, ms in device_ms(trace_dir).items()}
        top = max(ops, key=ops.get, default="")
        out.update(device_ms=round(sum(ops.values()), 4), top=top, top_ms=round(ops.get(top, 0.0), 4))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="nemotron,granite")
    ap.add_argument("--lanes", default="256,512,1024", help="widths of the kernels' head block, in lanes")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--trace-dir", default=".bench_trace/ssd_sweep")
    ap.add_argument("--out", default="", help="a file for the readings too")
    args = ap.parse_args()
    from paddle_tpu.ops import ssd_pallas

    table = TINY if args.rehearse else SHAPES
    trace_dir = "" if args.rehearse else args.trace_dir
    lines = []
    for name in args.shapes.split(","):
        shape = table[name]
        ops = operands(shape)
        variants = [("xla", None)] + [("kernels", int(w)) for w in args.lanes.split(",")]
        want = {}
        for path, lanes in variants:
            if lanes is not None:
                ssd_pallas.BLOCK_LANES = lanes  # read when the kernels are traced
                jax.clear_caches()
            kernels = path == "kernels"
            for backward in (False, True):
                fn = scan(shape, kernels, backward, args.rehearse and kernels)
                line = {"shape": name, "path": path, "lanes": lanes,
                        "pass": "fwd+bwd" if backward else "fwd",
                        **reading(fn, ops, 1 if args.rehearse else args.calls,
                                  1 if args.rehearse else args.reps, trace_dir)}
                got = [v.astype(jnp.float32) for v in fn(*ops)]
                if not kernels:
                    want[backward] = got
                line["max_diff"] = [round(float(jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) + 1e-30)), 5)
                                    for g, w in zip(got, want[backward])]
                print(json.dumps(line), flush=True)
                lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write("\n".join(json.dumps(line) for line in lines) + "\n")


if __name__ == "__main__":
    main()
