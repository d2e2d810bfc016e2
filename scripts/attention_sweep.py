"""Times the attention CORE alone on the chip: forward + backward of
softmax(QK^T)V from q, k, v [B, T, H, dh] bfloat16 to the same layout, every
transpose or repeat a path needs counted to it.  It decides
`layers/attention.py` `_FLASH_FROM_KEYS` and `ops/pallas_attention.py`
`auto_blocks`; PERF.md section 6 (PR 35) holds its table.

    chiprun -- python3 scripts/attention_sweep.py [--paths dense,blocked,...]
        [--shapes cell,threshold,guard|all] [--blocks 256x512,512x512]

Paths: `dense` (the layer's own `_dense_core`), `blocked`
(`flash_attention_diff` at `auto_blocks`, or at each of `--blocks`), and two
yardsticks that ship with jax: `jax_flash`
(`jax.experimental.pallas.ops.tpu.flash_attention`) and `splash`
(`...splash_attention`, fused backward).  One JSON line a reading on stdout,
all of them in `chiprun_out/attention_sweep.json`.  The program does not
import this file.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp

from paddle_tpu.layers.attention import _dense_core
from paddle_tpu.ops import pallas_attention as fa

# (B, T, H, kv heads, dh, causal)
CELL = [(8, 1024, 8, 8, 64, False), (8, 1024, 8, 8, 64, True)]
THRESHOLD = [(b, t, 8, 8, 64, c) for b, t in ((32, 256), (16, 512), (4, 2048))
             for c in (False, True)]
GUARD = [(2, 2048, 32, 2, 128, True)]
SHAPES = {"cell": CELL, "threshold": THRESHOLD, "guard": GUARD,
          "all": CELL + THRESHOLD + GUARD}


def _repeat(x, group):
    return x if group == 1 else jnp.repeat(x, group, axis=2)


def dense_path(causal, t):
    def core(q, k, v):
        b, _, h, dh = q.shape
        mask = jnp.ones((b, t), jnp.float32)  # the layer always adds its key mask
        return _dense_core(q, k, v, mask, causal).reshape(b, t, h, dh)
    return core


def blocked_path(causal, t, blocks=None):
    bq, bk = blocks or fa.auto_blocks(t, causal)

    def core(q, k, v):
        group = q.shape[2] // k.shape[2]
        lengths = jnp.full((q.shape[0],), t, jnp.int32)
        return fa.flash_attention_diff(q, _repeat(k, group), _repeat(v, group),
                                       lengths, causal, bq, bk, False)
    return core


def jax_flash_path(causal, t, block=512):
    from jax.experimental.pallas.ops.tpu import flash_attention as jf

    blk = min(block, t)
    sizes = jf.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk, block_q_dkv=blk,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)

    def core(q, k, v):
        b, _, h, dh = q.shape
        group = h // k.shape[2]
        seg = jnp.ones((b, t), jnp.int32)  # key lengths become segment ids
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, _repeat(k, group), _repeat(v, group)))
        out = jf.flash_attention(qt, kt, vt, segment_ids=jf.SegmentIds(q=seg, kv=seg),
                                 causal=causal, sm_scale=1.0 / math.sqrt(dh), block_sizes=sizes)
        return jnp.swapaxes(out, 1, 2)
    return core


def splash_path(causal, t, h, block=512):
    from jax.experimental.pallas.ops.tpu import splash_attention as sp

    blk = min(block, t)
    sizes = sp.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=min(blk, 256),
                          block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=min(blk, 256),
                          use_fused_bwd_kernel=True)
    one = sp.CausalMask((t, t)) if causal else sp.FullMask((t, t))
    kernel = sp.make_splash_mha_single_device(sp.MultiHeadMask([one] * h), block_sizes=sizes)

    def core(q, k, v):
        b, _, _, dh = q.shape
        group = h // k.shape[2]
        seg = jnp.ones((b, t), jnp.int32)
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, _repeat(k, group), _repeat(v, group)))
        qt = (qt * (1.0 / math.sqrt(dh))).astype(qt.dtype)
        out = jax.vmap(lambda q_, k_, v_, s_: kernel(q_, k_, v_, sp.SegmentIds(q=s_, kv=s_)))(
            qt, kt, vt, seg)
        return jnp.swapaxes(out, 1, 2)
    return core


def time_core(core, shape, calls, reps):
    """Median ms of one forward + backward over `reps` batches of `calls`
    back-to-back calls (the device is the bound: a call takes 1-5 ms, its
    dispatch under 0.1), and the readings' spread."""
    b, t, h, kvh, dh, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, g = (jax.random.normal(kk, (b, t, h, dh), jnp.float32).astype(jnp.bfloat16)
            for kk in keys[:2])
    k, v = (jax.random.normal(kk, (b, t, kvh, dh), jnp.float32).astype(jnp.bfloat16)
            for kk in keys[2:])

    @jax.jit
    def fb(q, k, v, g):
        out, vjp = jax.vjp(core, q, k, v)
        return (out, *vjp(g))

    def median_ms(f, *args):
        jax.block_until_ready(f(*args))
        readings = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(calls):
                r = f(*args)
            jax.block_until_ready(r)
            readings.append((time.perf_counter() - t0) / calls * 1e3)
        med = statistics.median(readings)
        return round(med, 4), round((max(readings) - min(readings)) / med, 4)

    ms, spread = median_ms(fb, q, k, v, g)
    fwd_ms, _ = median_ms(jax.jit(core), q, k, v)
    return {"ms": ms, "spread": spread, "fwd_ms": fwd_ms}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--paths", default="dense,blocked,jax_flash,splash")
    ap.add_argument("--shapes", default="all")
    ap.add_argument("--blocks", default="", help="bqxbk[,bqxbk...]: the blocked path at each, not at auto_blocks")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--out", default="chiprun_out/attention_sweep.json")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"attention_sweep times the chip; jax.devices()[0] is {dev.platform!r}")
    blocks = [tuple(int(n) for n in s.split("x")) for s in args.blocks.split(",") if s]
    rows = []
    for shape in [sh for name in args.shapes.split(",") for sh in SHAPES[name]]:
        b, t, h, kvh, dh, causal = shape
        for path in args.paths.split(","):
            if path == "dense":
                variants = [("", dense_path(causal, t))]
            elif path == "blocked":
                variants = [(f"{bq}x{bk}", blocked_path(causal, t, (bq, bk)))
                            for bq, bk in blocks if t % bq == 0 and t % bk == 0] or \
                           [("auto", blocked_path(causal, t))]
            elif path == "jax_flash":
                variants = [(str(n), jax_flash_path(causal, t, n)) for n in (256, 512)]
            elif path == "splash":
                variants = [(str(n), splash_path(causal, t, h, n)) for n in (512, 1024)]
            else:
                sys.exit(f"unknown path {path!r}")
            for tag, core in variants:
                row = {"B": b, "T": t, "H": h, "kvH": kvh, "dh": dh, "causal": causal,
                       "path": path, "variant": tag, "device": dev.device_kind}
                try:
                    row.update(time_core(core, shape, args.calls, args.reps))
                except Exception as e:  # a path that cannot take a shape is a reading too
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
