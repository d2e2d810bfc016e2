"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py          # from the repo root, no arguments

One process drives the two main paths once, through the entry points a user
calls, at the full width of the attention-GRU NMT flagship (word 512, hidden
512, vocab 30,000; random seeded weights), plus the legs that are cheap to
know now (flash attention, ResNet-50, data parallelism when more than one
chip is visible).  It refuses to run unless
``jax.devices()[0].platform == "tpu"`` and never sets ``JAX_PLATFORMS`` or
``XLA_FLAGS`` itself.  It prints one JSON line per leg, goes on to the next
leg when one fails, exits non-zero if any failed, and ends with a summary
line (which legs passed) and then, as the last line, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Numbers: ``compile_s`` is trace + lowering + XLA compile time inside the leg
as ``jax.monitoring`` reports it (``cache_hits`` says how many of the leg's
``compiles`` the persistent compile cache answered); ``step_ms`` and
``request_ms`` are host wall clock around work that ends in a device->host
fetch or ``block_until_ready``; ``peak_bytes_in_use`` is
``device.memory_stats()`` — a high-water mark of the whole process, so it
only grows from leg to leg.

Each leg is a function whose sizes are arguments: ``tests/test_chip_smoke.py``
calls the same functions at toy sizes on the CPU.  ``__main__`` has one mode:
full size, chip required.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

NMT_FEEDING = {"src_word": 0, "trg_word": 1, "trg_next": 2}


class CompileMeter:
    """This process's XLA compiles, read off the program's own counters: the
    one jax.monitoring listener is ``paddle_tpu.utils.compile_cache``'s,
    installed when ``paddle_tpu`` is imported, and it keeps ``jit/trace``,
    ``jit/lower``, ``jit/compile`` and ``jit/cache_hit`` in ``global_stats``."""

    def __init__(self) -> None:
        import paddle_tpu  # noqa: F401  (installs the listener)

    def snapshot(self) -> Tuple[int, float, int]:
        """-> (backend compiles, loads from the cache among them; seconds of
        trace + lowering + compile; persistent-cache hits)."""
        from paddle_tpu.utils.timers import global_stats

        stats = global_stats.summary()
        seconds = sum(
            stats.get("jit/" + phase, {}).get("total", 0.0)
            for phase in ("trace", "lower", "compile")
        )
        return (
            global_stats.count("jit/compile"), seconds,
            global_stats.count("jit/cache_hit"),
        )


def device_fields() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
    }


def result_line(ok: bool) -> Dict[str, Any]:
    """The last line of standard output.  The driver reads it and takes
    exactly these keys: anything else to report goes on an earlier line."""
    import jax

    devs = jax.devices()
    return {
        "ok": bool(ok),
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
    }


def _memory(device=None) -> Dict[str, Optional[int]]:
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use": stats.get("bytes_in_use"),
    }


def _median_ms(seconds: List[float]) -> float:
    return round(float(np.median(seconds)) * 1e3, 3)


# ---------------------------------------------------------------------------
# nmt_train (and data_parallel, which is nmt_train under a mesh)
# ---------------------------------------------------------------------------


def nmt_corpus(n: int, vocab: int, min_len: int, max_len: int, seed: int):
    """Seeded (src, trg_word, trg_next) triples.  Token ids follow a
    truncated geometric law over the whole vocabulary, so a few steps of
    training have something to learn (the unigram distribution) and the
    cost can be asserted to fall."""
    rng = np.random.RandomState(seed)

    def ids(length: int) -> List[int]:
        return (2 + np.minimum(rng.geometric(0.02, size=length), vocab - 3)).tolist()

    out = []
    for _ in range(n):
        src = ids(int(rng.randint(min_len, max_len + 1)))
        trg = ids(int(rng.randint(min_len, max_len + 1)))
        out.append((src, [0] + trg[:-1], trg))
    return out


def nmt_train(
    meter: CompileMeter,
    vocab: int = 30000,
    word_dim: int = 512,
    hidden_dim: int = 512,
    batch_size: int = 128,
    n_batches: int = 8,
    passes: int = 3,
    min_len: int = 8,
    max_len: int = 50,
    mesh=None,
):
    """``trainer.SGD.train`` on ``seq2seq_cost`` over a small seeded corpus
    repeated for ``passes`` passes — DataFeeder, shard_batch,
    make_train_step, the public path.  Returns (report, trained
    parameters)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.core.topology import reset_auto_names
    from paddle_tpu.models.seq2seq import seq2seq_cost

    paddle.init(compute_dtype="bfloat16", seed=0)
    reset_auto_names()
    cost, _ = seq2seq_cost(vocab, vocab, word_dim=word_dim, hidden_dim=hidden_dim)
    parameters = paddle.parameters.create(cost, seed=0)
    trainer = paddle.trainer.SGD(
        cost=cost,
        parameters=parameters,
        update_equation=paddle.optimizer.Adam(learning_rate=5e-3),
        mesh=mesh,
    )
    corpus = nmt_corpus(batch_size * n_batches, vocab, min_len, max_len, seed=0)
    reader = paddle.batch(lambda: iter(corpus), batch_size)

    costs: List[float] = []
    step_s: List[List[float]] = [[] for _ in range(passes)]
    compiles_after_pass: List[int] = []
    shapes_after_pass: List[int] = []
    t_begin = [0.0]

    def on_event(e) -> None:
        if isinstance(e, paddle.event.BeginIteration):
            t_begin[0] = time.perf_counter()
        elif isinstance(e, paddle.event.EndIteration):
            # EndIteration follows the trainer's host fetch of the cost, so
            # the step that produced it has finished on the device
            step_s[e.pass_id].append(time.perf_counter() - t_begin[0])
            costs.append(float(e.cost))
        elif isinstance(e, paddle.event.EndPass):
            compiles_after_pass.append(meter.snapshot()[0])
            shapes_after_pass.append(trainer.compile_cache.n_shapes)

    trainer.train(reader, num_passes=passes, event_handler=on_event,
                  feeding=NMT_FEEDING)
    jax.block_until_ready(trainer.parameters.params)

    assert len(costs) == passes * n_batches, (len(costs), passes, n_batches)
    assert np.isfinite(costs).all(), f"non-finite cost: {costs}"
    # the cost is a sum over each sequence's tokens, so it is compared on
    # the same batch: every batch of the last pass against the first pass
    first, last = costs[:n_batches], costs[-n_batches:]
    assert all(b < a for a, b in zip(first, last)), (
        f"cost did not fall on every batch: {first} -> {last}"
    )
    # no compile after the first pass: by the trainer's own shape counter
    # and by jax's count of backend compiles
    assert shapes_after_pass[-1] == shapes_after_pass[0], shapes_after_pass
    assert compiles_after_pass[-1] == compiles_after_pass[0], compiles_after_pass
    report = {
        "steps": len(costs),
        "first_cost": round(first[0], 4),
        "last_cost": round(last[0], 4),  # the same batch, `passes` - 1 later
        "batch_shapes": shapes_after_pass[-1],
        "step_ms": _median_ms([s for p in step_s[1:] for s in p]),
        "first_pass_s": round(sum(step_s[0]), 3),
    }
    if mesh is not None:
        report.update(_check_data_parallel(trainer, corpus[:batch_size], mesh))
    return report, trainer.parameters


def _check_data_parallel(trainer, data_batch, mesh) -> Dict[str, Any]:
    """The batch and the step's outputs are laid out over every device of
    the mesh, each device holding its share of the rows, and every device
    has bytes in use."""
    import jax

    from paddle_tpu.parallel.mesh import shard_batch

    devices = set(mesh.devices.flat)
    n = len(devices)
    # what SGD.train stages for every batch (its _stage closure)
    staged = shard_batch(trainer._make_feeder(NMT_FEEDING)(data_batch), mesh)
    for leaf in jax.tree_util.tree_leaves(staged):
        assert leaf.sharding.device_set == devices, leaf.sharding
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        assert rows == {leaf.shape[0] // n}, (leaf.shape, rows)
    # the step's outputs: parameters come back replicated on every device
    for leaf in jax.tree_util.tree_leaves(trainer.parameters.params):
        assert leaf.sharding.device_set == devices, leaf.sharding
        assert leaf.sharding.is_fully_replicated, leaf.sharding
    in_use = [_memory(d)["bytes_in_use"] for d in mesh.devices.flat]
    assert all(b is None or b > 0 for b in in_use), in_use
    return {"mesh_devices": n, "bytes_in_use_per_device": in_use}


# ---------------------------------------------------------------------------
# nmt_serve
# ---------------------------------------------------------------------------


def nmt_serve(
    meter: CompileMeter,
    parameters,
    vocab: int = 30000,
    word_dim: int = 512,
    hidden_dim: int = 512,
    max_length: int = 50,
    n_requests: int = 16,
    min_len: int = 8,
    max_len: int = 50,
) -> Dict[str, Any]:
    """The trained parameters behind ``ServingEngine`` + ``ServingScheduler``:
    ``n_requests`` seeded prompts submitted together; the tokens of the
    shortest and the longest prompt must equal the generator's one-shot
    path (``engine.reference_decode``) on the same device."""
    from paddle_tpu.core.batch import DEFAULT_LADDER, ladder_len
    from paddle_tpu.models.seq2seq import Seq2SeqGenerator
    from paddle_tpu.serving import Request, ServingEngine, ServingScheduler
    from paddle_tpu.serving.scheduler import status_counts

    gen = Seq2SeqGenerator(
        parameters, vocab, vocab, word_dim=word_dim, hidden_dim=hidden_dim,
        max_length=max_length,
    )
    engine = ServingEngine(gen, max_slots=n_requests, max_new_tokens=max_length)
    rng = np.random.RandomState(1)
    prompts = [
        rng.randint(2, vocab, size=rng.randint(min_len, max_len + 1)).tolist()
        for _ in range(n_requests)
    ]

    def serve_wave():
        """Submit every prompt at once; (finalized requests, seconds)."""
        reqs = [Request(p) for p in prompts]
        t0 = time.perf_counter()
        with ServingScheduler(engine) as sched:
            for r in reqs:
                sched.submit(r)
            for r in reqs:
                assert r.wait(900), f"request {r.req_id} not finalized in 900 s"
        return reqs, time.perf_counter() - t0

    requests, first_wave_s = serve_wave()
    ledger = status_counts(requests)
    assert ledger["served"] == n_requests and sum(ledger.values()) == n_requests, ledger
    assert all(r.tokens for r in requests), [len(r.tokens or ()) for r in requests]
    summary = engine.summary()
    # bounded shapes: slot/group rungs are powers of two up to max_slots,
    # source extents are ladder rungs up to the longest prompt's
    n_b = len({1 << i for i in range(n_requests.bit_length())})
    n_s = sum(1 for r in DEFAULT_LADDER if r <= ladder_len(max_len))
    assert 1 <= summary["prefill_shapes"] <= n_b * n_s, summary
    assert 1 <= summary["decode_shapes"] <= n_b * n_s, summary

    # a second wave of the same prompts: steady request time, no compile
    compiles_before = meter.snapshot()[0]
    again, wave_s = serve_wave()
    assert meter.snapshot()[0] == compiles_before, "second wave compiled"
    assert [r.tokens for r in again] == [r.tokens for r in requests], (
        "the same prompts decoded differently the second time"
    )

    for i in (int(np.argmin([len(p) for p in prompts])),
              int(np.argmax([len(p) for p in prompts]))):
        ref = engine.reference_decode(prompts[i], max_length)
        got = list(requests[i].tokens)
        if got != ref:
            at = next(
                (j for j, (a, b) in enumerate(zip(got, ref)) if a != b),
                min(len(got), len(ref)),
            )
            raise AssertionError(
                f"prompt {i} ({len(prompts[i])} tokens): serving and the "
                f"one-shot path part at position {at} of {len(got)}/{len(ref)}"
                f": {got[at:at + 4]} vs {ref[at:at + 4]}"
            )
    return {
        "served": ledger["served"],
        "tokens": sum(len(r.tokens) for r in requests),
        "prefill_shapes": summary["prefill_shapes"],
        "decode_shapes": summary["decode_shapes"],
        "trace_counts": summary["trace_counts"],
        "first_wave_s": round(first_wave_s, 3),
        "request_ms": round(wave_s / n_requests * 1e3, 3),
        "request_latency_ms": _median_ms([r.t_done - r.t_submit for r in again]),
    }


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

# Flash against dense, both with bfloat16 operands and float32 softmax
# statistics: they differ by where p and ds are rounded to bfloat16 (2^-8 =
# 3.9e-3 relative a rounding) and by summation order.  A cost sums B*T
# per-token terms (8192 at the compared shape), so independent roundings
# average down to ~4e-5 (measured on the chip: 2e-5); 1e-3 leaves room for
# roundings that do not average.  At random weights the cost barely depends
# on what attention computes, so this bound is kept tight and the kernels
# are ALSO compared directly: an output or gradient element is a sum over T
# rounded products and gets 4e-2 of the tensor's largest magnitude, ~10 ulps
# (measured on the chip: 4e-3 to 9e-3); a wrong mask, scale or block index
# is an error of order 1.
FLASH_COST_RTOL = 1e-3
FLASH_GRAD_RTOL = 4e-2


def flash_kernels(b: int, t: int, h: int, dh: int, interpret: bool = False) -> Dict[str, Any]:
    """``ops/pallas_attention`` forward and backward against dense attention
    on the same device, causal and not, bfloat16, with key padding."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_attention as fa

    def dense(q, k, v, lengths, causal):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(dh)
        mask = jnp.arange(t)[None, :] < lengths[:, None]  # [B, Tk]
        mask = mask[:, None, None, :]
        if causal:
            mask = mask & jnp.tril(jnp.ones((t, t), bool))[None, None]
        w = jax.nn.softmax(jnp.where(mask, s, fa.NEG_INF), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)

    bq, bk = fa.auto_blocks(t)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (
        jax.random.normal(kk, (b, t, h, dh), jnp.float32).astype(jnp.bfloat16)
        for kk in keys
    )
    lengths = jnp.asarray([t] + [t - t // 4] * (b - 1), jnp.int32)
    def out_and_grads(attention):
        def run(q, k, v, g):
            out, vjp = jax.vjp(attention, q, k, v)
            return dict(zip(("out", "dq", "dk", "dv"), (out, *vjp(g))))

        return jax.jit(run)(q, k, v, g)

    worst = {"out": 0.0, "dq": 0.0, "dk": 0.0, "dv": 0.0}
    for causal in (False, True):
        got = out_and_grads(lambda q, k, v: fa.flash_attention_diff(
            q, k, v, lengths, causal, bq, bk, interpret))
        want = out_and_grads(lambda q, k, v: dense(q, k, v, lengths, causal))
        for name in got:
            a = np.asarray(got[name], np.float32)
            r = np.asarray(want[name], np.float32)
            assert np.isfinite(a).all(), f"{name} not finite (causal={causal})"
            err = float(np.max(np.abs(a - r)) / max(np.max(np.abs(r)), 1e-6))
            assert err <= FLASH_GRAD_RTOL, (
                f"flash {name} vs dense: {err:.3e} of the largest magnitude "
                f"> {FLASH_GRAD_RTOL} (T={t}, causal={causal})"
            )
            worst[name] = max(worst[name], err)
    return {"kernel_shape": [b, t, h, dh], "blocks": [bq, bk],
            "kernel_max_rel_err": {k: round(e, 5) for k, e in worst.items()}}


def flash_train(
    vocab: int = 32000,
    d_model: int = 512,
    n_heads: int = 8,
    n_layers: int = 6,
    d_ff: int = 2048,
    shapes=((16, 512), (2, 4096)),
    steps: int = 3,
) -> Dict[str, Any]:
    """Transformer train steps with ``use_pallas_attention`` on.  The
    kernels must be IN the program — 2 ``tpu_custom_call`` per attention
    layer (forward, the one fused backward) in the compiled step — so a silent
    dense path fails the leg; the costs at the first shape must agree with the same
    steps run dense.  The first shape lies BELOW the 1,024 keys from which
    the layer takes the kernels whatever the flag says: there the flag still
    decides, and switching it off gives the dense steps to compare with."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology, reset_auto_names
    from paddle_tpu.models.transformer import transformer_cost
    from paddle_tpu.trainer.step import make_train_step
    from paddle_tpu.utils.flags import set_flag

    def run_steps(use_flash: bool, b: int, t: int, n: int):
        """(costs of n steps, custom calls in the compiled step, step s)."""
        set_flag("use_pallas_attention", use_flash)
        try:
            reset_auto_names()
            cost, _ = transformer_cost(vocab, vocab, d_model, n_heads, n_layers, d_ff)
            net = CompiledNetwork(Topology([cost]), compute_dtype=jnp.bfloat16)
            params, state = net.init(jax.random.PRNGKey(0))
            opt = paddle.optimizer.Adam(learning_rate=1e-4)
            opt_state = opt.init(params)
            rng = np.random.RandomState(0)
            lens = jnp.full((b,), t, jnp.int32)
            batch = {
                name: SeqTensor(
                    jnp.asarray(rng.randint(1, vocab, size=(b, t)), jnp.int32), lens
                )
                for name in ("src_word", "trg_word", "trg_next")
            }
            key = jax.random.PRNGKey(1)
            lowered = make_train_step(net, opt, mesh=None).lower(
                params, state, opt_state, batch, key
            )
            step = lowered.compile()
            # counted in the COMPILED program: lowered, the layers of one
            # shape share one jitted kernel call, which XLA inlines a layer
            n_calls = step.as_text().count("tpu_custom_call")
        finally:
            set_flag("use_pallas_attention", False)
        costs, secs = [], []
        for _ in range(n):
            t0 = time.perf_counter()
            params, state, opt_state, metrics = step(params, state, opt_state, batch, key)
            costs.append(float(jax.block_until_ready(metrics["cost"])))
            secs.append(time.perf_counter() - t0)
        return costs, n_calls, secs

    n_attention = 3 * n_layers  # encoder self + decoder self + cross
    report: Dict[str, Any] = {"shapes": []}
    for i, (b, t) in enumerate(shapes):
        costs, n_calls, secs = run_steps(True, b, t, steps)
        assert n_calls == 2 * n_attention, (
            f"B={b} T={t}: {n_calls} tpu_custom_call in the compiled step, "
            f"want {2 * n_attention} (2 per attention layer) — the flash "
            "kernel is not in the program"
        )
        assert np.isfinite(costs).all(), f"B={b} T={t}: costs {costs}"
        entry = {"B": b, "T": t, "custom_calls": n_calls,
                 "costs": [round(c, 4) for c in costs],
                 "step_ms": _median_ms(secs[1:] or secs)}
        if i == 0:
            # the same steps dense: the first cost checks the forward
            # kernel, the later ones what the backward kernels fed Adam
            dense_costs, dense_calls, _ = run_steps(False, b, t, steps)
            assert dense_calls == 0, dense_calls
            rel = max(abs(f - d) / abs(d) for f, d in zip(costs, dense_costs))
            assert rel <= FLASH_COST_RTOL, (
                f"B={b} T={t}: flash costs {costs} vs dense {dense_costs}: "
                f"{rel:.3e} > {FLASH_COST_RTOL}"
            )
            entry["dense_costs"] = [round(c, 4) for c in dense_costs]
            entry["cost_max_rel_diff"] = round(rel, 6)
        report["shapes"].append(entry)
    report["step_ms"] = report["shapes"][0]["step_ms"]
    return report


def flash_attention() -> Dict[str, Any]:
    """The kernels against dense at the long shape, then in the train step."""
    return {**flash_kernels(2, 4096, 8, 64), **flash_train()}


# ---------------------------------------------------------------------------
# resnet50_train
# ---------------------------------------------------------------------------


def resnet50_train(
    meter: CompileMeter,
    depth: int = 50,
    class_num: int = 1000,
    img_size: int = 224,
    batch_size: int = 64,
    steps: int = 3,
) -> Dict[str, Any]:
    """The BASELINE headline model through ``trainer.SGD``: another feed and
    state path (dense images in uint8 range, BN state, conv fusions)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.core.topology import reset_auto_names
    from paddle_tpu.models.resnet import resnet_cost

    paddle.init(compute_dtype="bfloat16", seed=0)
    reset_auto_names()
    cost, _ = resnet_cost(depth=depth, class_num=class_num, img_size=img_size)
    parameters = paddle.parameters.create(cost, seed=0)
    trainer = paddle.trainer.SGD(
        cost=cost,
        parameters=parameters,
        update_equation=paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9),
    )
    rng = np.random.RandomState(0)
    samples = [
        (rng.randint(0, 256, size=3 * img_size * img_size).astype(np.float32),
         int(rng.randint(class_num)))
        for _ in range(batch_size)
    ]
    costs: List[float] = []
    secs: List[float] = []
    compiles_after_step: List[int] = []
    t_begin = [0.0]

    def on_event(e) -> None:
        if isinstance(e, paddle.event.BeginIteration):
            t_begin[0] = time.perf_counter()
        elif isinstance(e, paddle.event.EndIteration):
            secs.append(time.perf_counter() - t_begin[0])
            costs.append(float(e.cost))
            compiles_after_step.append(meter.snapshot()[0])

    bn_before = jax.tree_util.tree_map(np.asarray, trainer.parameters.state)
    # one batch a pass: `steps` passes over the same images
    trainer.train(paddle.batch(lambda: iter(samples), batch_size),
                  num_passes=steps, event_handler=on_event)
    jax.block_until_ready(trainer.parameters.params)
    assert len(costs) == steps and np.isfinite(costs).all(), costs
    assert compiles_after_step[-1] == compiles_after_step[0], compiles_after_step
    bn_moved = any(
        not np.array_equal(a, np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(bn_before),
                        jax.tree_util.tree_leaves(trainer.parameters.state))
    )
    assert bn_moved, "batch-norm state did not move"
    return {"steps": steps, "costs": [round(c, 4) for c in costs],
            "step_ms": _median_ms(secs[1:] or secs)}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_leg(name: str, meter: CompileMeter, fn: Callable[[], Any]) -> Tuple[bool, Any]:
    """Run one leg, print its JSON line; (ok, what the leg returned)."""
    c0, s0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    value, line = None, {"leg": name, "ok": True}
    try:
        value = fn()
        line.update(value[0] if isinstance(value, tuple) else value)
    except Exception as e:  # a failed leg is reported; the next leg still runs
        import traceback

        traceback.print_exc()
        line.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
    c1, s1, h1 = meter.snapshot()
    line.update(
        compile_s=round(s1 - s0, 3), compiles=c1 - c0, cache_hits=h1 - h0,
        wall_s=round(time.perf_counter() - t0, 3), **_memory(), **device_fields(),
    )
    print(json.dumps(line), flush=True)
    return line["ok"], value


def main() -> int:
    try:
        import jax

        devs = jax.devices()
    except Exception as e:
        print(f"chip_smoke: jax found no usable backend: {e}", file=sys.stderr)
        return 2
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; jax.devices()[0] is platform "
            f"{devs[0].platform!r} ({devs[0].device_kind!r}, {len(devs)} "
            f"device(s), jax {jax.__version__})",
            file=sys.stderr,
        )
        return 2
    try:
        from paddle_tpu.utils.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: run from the root of a checkout: {e}", file=sys.stderr)
        return 2

    cache_dir = configure_compile_cache()
    print(json.dumps({"leg": "start", "compile_cache_dir": cache_dir,
                      "memory_stats": devs[0].memory_stats(),
                      **device_fields()}), flush=True)
    return run_all(CompileMeter())


def run_all(meter: CompileMeter) -> int:
    """Every leg at full size, the summary line, the result line; the exit
    code."""
    import jax

    from paddle_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    results: Dict[str, bool] = {}

    results["nmt_train"], trained = run_leg(
        "nmt_train", meter, lambda: nmt_train(meter))

    def serve():
        assert trained is not None, "no trained parameters: nmt_train failed"
        return nmt_serve(meter, trained[1])

    results["nmt_serve"], _ = run_leg("nmt_serve", meter, serve)
    results["resnet50_train"], _ = run_leg(
        "resnet50_train", meter, lambda: resnet50_train(meter))
    # after the legs that need less memory: peak_bytes_in_use is a
    # high-water mark of the process, and this leg's is the highest
    results["flash_attention"], _ = run_leg(
        "flash_attention", meter, flash_attention)
    if len(devs) > 1:
        def data_parallel():
            report, _ = nmt_train(meter, mesh=make_mesh(data=len(devs)))
            # bfloat16 compute, float32 cost: sharding the batch changes the
            # order of the float32 sum over tokens and XLA's fusion choices,
            # not the per-row arithmetic
            one = trained[0]["first_cost"] if trained is not None else None
            assert one is not None, "no one-chip first cost to compare with"
            rel = abs(report["first_cost"] - one) / abs(one)
            assert rel <= 1e-3, (
                f"first cost {report['first_cost']} on {len(devs)} chips vs "
                f"{one} on one: {rel:.3e} > 1e-3"
            )
            return {**report, "one_chip_first_cost": one,
                    "first_cost_rel_diff": round(rel, 6)}

        results["data_parallel"], _ = run_leg("data_parallel", meter, data_parallel)

    ok = all(results.values())
    print(json.dumps({"leg": "summary", "ok": ok, "legs": results,
                      **device_fields()}), flush=True)
    print(json.dumps(result_line(ok)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
