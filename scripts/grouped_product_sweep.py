"""Times the held experts' grouped products alone on the chip: rows sorted by
expert times each expert's matrix, forward, row gradient and weight gradient,
XLA:TPU's rewrite of `jax.lax.ragged_dot` against the Pallas grouped product
jax ships (`jax.experimental.pallas.ops.tpu.megablox`: `gmm`, `tgmm`) over its
tilings, and against the program's own kernels (`ops/grouped_product.py`) over
the bytes their matrix block may have.  It decides the path `layers/moe.py`
`_grouped_dot` takes and the tiles of `ops/grouped_product.py`; its table
lives in `layers/moe.py` and PERF.md section 6 (PR 39).

    chiprun -- python3 scripts/grouped_product_sweep.py [--shapes w1,w2]
        [--ops fwd,dlhs,drhs] [--routings even,drawn,full,starved]
        [--paths xla,megablox,own] [--tiles 256x2688x512,...] [--blocks 12,6,3]

Shapes are `nemotron-train-2k`'s: a pass of `held_rows_bound` = 3,072 rows
over 8 held experts, `w1` [3072, 2688] x [8, 2688, 1856] and `w2` [3072, 1856]
x [8, 1856, 2688], bfloat16 in and out, float32 sums.  Routings: `even` (192
rows an expert: the first step's expectation, half the bound), `drawn` (1,536
rows dealt unevenly, 120-260 an expert, so groups straddle row tiles), `full`
(384 an expert: a pass of a layer whose load has drifted over the bound),
`starved` (0-16 rows an expert: a layer the router has left).  One JSON line a
reading on stdout, all of them in `chiprun_out/grouped_product_sweep.json`.
The program does not import this file.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm  # the kernels; the package's `gmm` is the custom-VJP wrapper

# (rows, K, N, groups)
SHAPES = {"w1": (3072, 2688, 1856, 8), "w2": (3072, 1856, 2688, 8)}
ROUTINGS = {
    "even": (192,) * 8,
    "drawn": (205, 131, 260, 178, 120, 243, 166, 233),
    "full": (384,) * 8,
    "starved": (0, 16, 3, 0, 9, 16, 1, 12),
}
# (tm, tk, tn) as `gmm` / `tgmm` read them: the row tile, and the tiles of the
# product's own K and N (for `dlhs` those are the forward's N and K)
TILES = {
    "fwd": ["128x128x128", "64xKx1024", "128xKx512", "128xKx1024", "256xKx512", "256xKx1024",
            "512xKx512", "256x512x512", "512x512x1024"],
    "drhs": ["128x128x128", "128xKx512", "128xKx1024", "128x512x512", "128x896x1024",
             "256x512x1024", "256x1024x1024", "512x1024x1024", "512x512x512"],
}
TILES["dlhs"] = TILES["fwd"]

_INDEPENDENT = 4  # products of one jitted call, each on operands of its own


def _tiling(spec, k, n):
    return tuple({"K": k, "N": n}.get(s) or int(s) for s in spec.split("x"))


def xla_product(op):
    def dot(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)

    if op == "fwd":
        return lambda x, w, g, sizes: dot(x, w, sizes)
    if op == "dlhs":
        return lambda x, w, g, sizes: jax.vjp(lambda x_: dot(x_, w, sizes), x)[1](g)[0]
    return lambda x, w, g, sizes: jax.vjp(lambda w_: dot(x, w_, sizes), w)[1](g)[0]


def megablox_product(op, tiling, interpret=False):
    if op == "fwd":
        return lambda x, w, g, sizes: gmm(x, w, sizes, x.dtype, tiling, interpret=interpret)
    if op == "dlhs":
        return lambda x, w, g, sizes: gmm(g, w, sizes, x.dtype, tiling, transpose_rhs=True,
                                               interpret=interpret)
    return lambda x, w, g, sizes: tgmm(x.swapaxes(0, 1), g, sizes, w.dtype, tiling,
                                            interpret=interpret)


def own_product(op, block_mib, interpret=False):
    """The program's kernels with `block_mib` MiB for the matrix block."""
    from paddle_tpu.ops import grouped_product as gp

    def with_block(f):
        def product(x, w, g, sizes):
            gp._BLOCK_BYTES = int(block_mib * 2 ** 20)  # read when the kernel is traced
            return f(x, w, g, sizes)
        return product

    dot = lambda x, w, sizes: gp.grouped_dot(x, w, sizes, interpret)
    if op == "fwd":
        return with_block(lambda x, w, g, sizes: dot(x, w, sizes))
    if op == "dlhs":
        return with_block(lambda x, w, g, sizes: jax.vjp(lambda x_: dot(x_, w, sizes), x)[1](g)[0])
    return with_block(lambda x, w, g, sizes: jax.vjp(lambda w_: dot(x, w_, sizes), w)[1](g)[0])


def operands(shape, dtype):
    """`_INDEPENDENT` sets of rows, matrices and row gradients, each an array
    of its own (a slice of one array would be copied before a kernel reads it)."""
    m, k, n, groups = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 3 * _INDEPENDENT).reshape(3, _INDEPENDENT, -1)
    xs = [jax.random.normal(key, (m, k), jnp.float32).astype(dtype) for key in keys[0]]
    gs = [jax.random.normal(key, (m, n), jnp.float32).astype(dtype) for key in keys[1]]
    ws = [(jax.random.normal(key, (groups, k, n), jnp.float32) / k ** 0.5).astype(dtype) for key in keys[2]]
    return xs, ws, gs


def device_ms(trace_dir):
    """-> {operation: ms} from the newest profile under `trace_dir`: the first
    device plane's `XLA Ops` line, operations that differ only in XLA's
    numbering (`gmm.2`, `gmm.3`) under one name."""
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
               key=os.path.getmtime)
    by_name = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                name = re.sub(r"\.\d+$", "", event.name.split(" = ")[0].lstrip("%"))
                by_name[name] = by_name.get(name, 0.0) + event.duration_ns / 1e6
    return by_name


def time_product(product, args, routings, calls, reps, trace_dir):
    """-> {routing: [ms of ONE product on the host's clock, ms of its device
    operations, ms of the dearest of them (the kernel), that one's name, the
    next four]}.
    The host's: the median over `reps` batches of `calls` back-to-back calls of
    a jitted function that holds `_INDEPENDENT` products on operands of their
    own (so XLA merges none and no 0.2 ms kernel waits for its dispatch); the
    device's: from a profile of one more batch, which also counts what
    surrounds the kernel (the tiles' metadata, a transposed operand)."""
    @jax.jit
    def many(xs, ws, gs, sizes):
        return tuple(product(x, w, g, sizes) for x, w, g in zip(xs, ws, gs))

    def batch(sizes):
        for _ in range(calls):
            r = many(*args, sizes)
        jax.block_until_ready(r)

    out = {}
    for name, sizes in routings.items():
        sizes = jnp.asarray(sizes, jnp.int32)
        jax.block_until_ready(many(*args, sizes))
        readings = []
        for _ in range(reps):
            t0 = time.perf_counter()
            batch(sizes)
            readings.append((time.perf_counter() - t0) / (calls * _INDEPENDENT) * 1e3)
        out[name] = [round(statistics.median(readings), 4)]
        if trace_dir:
            with jax.profiler.trace(trace_dir):
                batch(sizes)
            ops = {op: ms / (calls * _INDEPENDENT) for op, ms in device_ms(trace_dir).items()}
            kernel = max(ops, key=ops.get, default="")
            out[name] += [round(sum(ops.values()), 4), round(ops.get(kernel, 0.0), 4), kernel,
                          {op: round(ms, 4) for op, ms in sorted(ops.items(), key=lambda kv: -kv[1])[1:5]}]
    return out


def agree(product, reference, args, sizes, op):
    """Largest difference from XLA's product over the rows (or experts) the
    routing defines, as a share of the largest reference entry."""
    live = int(sum(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    a, b = (f(args[0][0], args[1][0], args[2][0], sizes).astype(jnp.float32)
            for f in (product, reference))
    if op != "drhs":
        a, b = a[:live], b[:live]
    return round(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), 5)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="w1,w2")
    ap.add_argument("--ops", default="fwd,dlhs,drhs")
    ap.add_argument("--routings", default="even,drawn,full,starved")
    ap.add_argument("--paths", default="xla,megablox,own")
    ap.add_argument("--blocks", default="12,6,3", help="MiB of the own kernels' matrix block, one variant each")
    ap.add_argument("--tiles", default="", help="tmxtkxtn[,...] (K, N stand for the whole dimension): these, not the script's lists")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes in interpret mode on any backend: checks the script, times nothing")
    ap.add_argument("--trace-dir", default=".bench_trace/grouped_product_sweep",
                    help="where each reading's profile goes (overwritten); '' for the host's clock alone")
    ap.add_argument("--out", default="chiprun_out/grouped_product_sweep.json")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"grouped_product_sweep times the chip; jax.devices()[0] is {dev.platform!r}")
    shapes = {"w1": (256, 256, 128, 4), "w2": (256, 128, 256, 4)} if args.rehearse else SHAPES
    routings = ({"even": (32,) * 4, "starved": (0, 5, 1, 0)} if args.rehearse
                else {r: ROUTINGS[r] for r in args.routings.split(",")})
    dtype = jnp.float32 if args.rehearse else jnp.bfloat16
    rows = []
    for shape_name in args.shapes.split(","):
        shape = shapes[shape_name]
        m, k, n, _ = shape
        ops_args = operands(shape, dtype)
        for op in args.ops.split(","):
            pk, pn = (n, k) if op == "dlhs" else (k, n)  # the product's own K and N
            specs = args.tiles.split(",") if args.tiles else TILES[op]
            if args.rehearse:
                specs = ["128x128x128", "128xKxN"]
            paths = args.paths.split(",")
            variants = [("xla", "", xla_product(op))] + [
                ("megablox", spec, megablox_product(op, _tiling(spec, pk, pn), args.rehearse))
                for spec in specs if "megablox" in paths] + [
                ("own", f"{mib}MiB", own_product(op, float(mib), args.rehearse))
                for mib in args.blocks.split(",") if "own" in paths]
            for path, spec, product in variants:
                jax.clear_caches()  # the own kernels' jitted calls do not key on their block's bytes
                row = {"shape": shape_name, "m": m, "k": k, "n": n, "op": op, "path": path,
                       "tiles": spec, "device": dev.device_kind}
                try:
                    row["ms"] = time_product(product, ops_args, routings, args.calls, args.reps,
                                             "" if args.rehearse else args.trace_dir)
                    if path != "xla":
                        jax.clear_caches()
                        row["diff"] = agree(product, variants[0][2], ops_args,
                                            next(iter(routings.values())), op)
                except Exception as e:  # a tiling the compiler refuses is a reading too
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
