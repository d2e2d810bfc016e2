"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file found by its
name: configs/<config>.json, traffic/<mix>.json, traffic_kinds/<kind>.py,
models/<config>.py, reference/<config>.py, flops/<config>.py,
limits/<workload>.json, layer_metrics/<metric>.py.  The last line of standard
output is the result.

Order of a run: set-up (data, weights, trainer, the first steps through
`trainer.train`, whose readings `correct` is decided from) -> the window
(`trainer.train` again on the same trainer, until the time is up) -> read the
device's memory -> free the program -> the plain reference follows the same
first steps -> compare.  From the trainer's construction to the end of the
window the device holds the program's arrays and none of the benchmark's; the
first line printed says what it held (`bytes_in_use`).

`--rehearsal 1` is for the CPU: toy widths from rehearsal/<config>.json and
the toy mix, platform stamped "cpu", no time, rate or share printed.
"""

import time

T_START = time.time()  # the start of the process, as near as Python allows: for `wall_s`

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

EXIT_NO_DEVICE = 2
EXIT_NO_PROGRAM = 3


def _args(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--rehearsal", type=int, default=0)
    return p.parse_args(argv)


def load_cell(workload, rehearsal=False):
    """-> (benchmark, cell, configuration, mix, limits) from the data files.
    A rehearsal reads toy widths from rehearsal/<config>.json, which may name
    its toy mix under "mix" (a kind of traffic other than sentence pairs has
    one of its own); `rehearsal-ragged` otherwise."""
    import traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    path = os.path.join(ROOT, entry["file"])
    if rehearsal:
        path = os.path.join(HERE, "rehearsal", os.path.basename(entry["file"]))
    with open(path) as f:
        cfg = json.load(f)
    if rehearsal:  # toy widths read otherwise than the cell's own
        mix = traffic.load_mix(cfg.get("mix", "rehearsal-ragged"))
        return bench, cell, cfg, mix, cfg["limits"]
    mix = traffic.load_mix(cell["traffic"])
    with open(os.path.join(HERE, "limits", workload + ".json")) as f:
        limits = json.load(f)["limits"]
    return bench, cell, cfg, mix, limits


def place_compile_cache(jax):
    """Inside the checkout at a fixed path, unless whoever runs us placed it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    # programs that compile in under a second are most of a warm set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Program:
    """The system under test, built once: `trainer.SGD` on the configuration's
    topology with the benchmark's weights.  Set-up's first steps and the
    window both go through `self.train`, i.e. `trainer.train`."""

    def __init__(self, cfg, draw, chips):
        import jax
        import paddle_tpu as paddle
        from paddle_tpu.core.topology import reset_auto_names

        import refsteps
        import weights as W

        self.paddle = paddle
        self.cfg = cfg
        self.param_map = W.expand_param_map(cfg)
        paddle.init(compute_dtype=cfg["compute_dtype"], seed=0)
        reset_auto_names()
        cost, self.feeding = refsteps.load_by_name("models", cfg["model"]).build(cfg)
        parameters = paddle.parameters.create(cost, seed=0)
        tree = W.to_program_tree(draw(), self.param_map)
        have = jax.tree_util.tree_map(lambda x: x.shape, parameters.params)
        if jax.tree_util.tree_map(lambda x: x.shape, tree) != have:
            raise SystemExit("the configuration's param_map does not cover the program's parameters")
        parameters.params = tree
        mesh = None
        if chips > 1:
            from paddle_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(data=chips, devices=jax.devices()[:chips])
        opt = cfg["optimizer"]
        self.trainer = paddle.trainer.SGD(
            cost=cost, parameters=parameters, mesh=mesh,
            update_equation=paddle.optimizer.Adam(
                learning_rate=opt["learning_rate"], beta1=opt["beta1"],
                beta2=opt["beta2"], epsilon=opt["epsilon"]),
        )

    def train(self, reader, on_step):
        """One call of the public training loop; on_step(cost) after each
        step's cost has been fetched (EndIteration)."""
        end = self.paddle.event.EndIteration

        def handler(e):
            if isinstance(e, end):
                on_step(float(e.cost))

        self.trainer.train(reader, num_passes=1, event_handler=handler,
                           feeding=self.feeding)

    def adam_first_moment(self):
        import weights as W

        return W.from_program_tree(self.trainer._opt_state["m"], self.param_map)

    def parameters(self):
        import weights as W

        return W.from_program_tree(self.trainer.parameters.params, self.param_map)


def first_steps(program, batches, draw, beta1):
    """Set-up's checked steps, through the window's own call and feed.
    -> the program's readings: each step's loss, the first gradient per
    leaf (Adam's first moment after one step / (1 - beta1)) with its norm,
    and the norm of each leaf's change after the last step.

    While the program steps the device holds nothing of the benchmark's: its
    draw went into the program, the first gradient waits on the host, and the
    weights the change is taken from are drawn again (`draw()`, the same
    program, so the same bits) after the last step."""
    import jax
    import numpy as np

    import refsteps

    losses = []
    program.train(lambda: iter(batches[:1]), losses.append)
    grad = {k: np.asarray(x) / np.float32(1.0 - beta1)
            for k, x in program.adam_first_moment().items()}
    if len(batches) > 1:
        program.train(lambda: iter(batches[1:]), losses.append)
    # one set of the benchmark's on the device at a time: the first gradient
    # for its norms, then the second draw for the change
    grad_norms = refsteps.leaf_norms(grad)
    weights = draw()
    # with a mesh the program's leaves are replicated over it; the benchmark's
    # own sit on the first device
    after = {k: jax.device_put(v, weights[k].sharding)
             for k, v in program.parameters().items()}
    return {"losses": losses, "grad": grad, "grad_norms": grad_norms,
            "change_norms": refsteps.leaf_norms(after, weights)}


class GcWatch:
    """Records the interpreter's garbage collections (start, seconds,
    generation), so that a long gap between two steps can be laid at the
    collector's door or not.  Reads only; changes nothing of the collector."""

    def __init__(self):
        self.events, self._t = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.events.append((self._t, time.perf_counter() - self._t, info["generation"]))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def drawn_from(cfg, seed):
    """-> draw(): the benchmark's weights for this configuration and seed,
    made on the device at every call."""
    import refsteps
    import weights as W

    ref_mod = refsteps.load_by_name("reference", cfg["reference"])
    draw = W.drawer(ref_mod.param_shapes(cfg))
    return lambda: draw(seed)


def program_readings(cell, cfg, mix, seed):
    """Builds the program from the seed and drives it through the checked
    first steps alone: what `calibrate.py` and the tests read."""
    import traffic

    corpus = traffic.kind(mix).make_corpus(
        dict(mix, corpus_batches=mix["checked_steps"]), cfg, seed)
    draw = drawn_from(cfg, seed)
    program = Program(cfg, draw, cell["chips"])
    return first_steps(program, corpus, draw, cfg["optimizer"]["beta1"])


def window(program, corpus, seconds):
    """Cycles the corpus through `trainer.train` until the time is up.
    -> (t0, [completion time of each step], [cost of each step])."""
    times, costs = [], []
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def reader():
        i = 0
        while time.perf_counter() < deadline:
            yield corpus[i % len(corpus)]
            i += 1

    def on_step(cost):
        times.append(time.perf_counter())
        costs.append(cost)

    program.train(reader, on_step)
    return t0, times, costs


def memory_peak_bytes(jax, chips):
    """Peak on the fullest chip.  On this runtime `peak_bytes_in_use` counts
    live arrays and leaves out the executables' scratch, which
    `peak_bytes_reserved` holds (probe, PERF.md): the peak is their sum."""
    peaks = []
    for d in jax.devices()[:chips]:
        s = d.memory_stats() or {}
        peaks.append(int(s.get("peak_bytes_in_use", 0)) + int(s.get("peak_bytes_reserved", 0)))
    return max(peaks)


def bytes_in_use(jax, chips):
    """What the fullest chip holds in live arrays now (None where the backend
    does not say, as the CPU's)."""
    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    return max(int(s["bytes_in_use"]) for s in stats) if all(stats) else None


def percentile(values, q):
    """Nearest-rank percentile over all the values."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def reference_readings(cell, cfg, mix, seed, precision="float32", fault=None, watch=None):
    """The plain reference follows the same first steps from the same weights
    (made again from the seed); -> its losses, gradient norms and changes."""
    import refsteps
    import traffic

    ref_mod = refsteps.load_by_name("reference", cfg["reference"])
    kind = traffic.kind(mix)
    corpus = kind.make_corpus(dict(mix, corpus_batches=mix["checked_steps"]), cfg, seed)
    return refsteps.ReferenceRun(
        ref_mod.make_block_cost(cfg), cfg["optimizer"], mix["reference_block_rows"],
        precision=precision, fault=fault, chips=cell["chips"], cell=cell["name"], watch=watch,
    ).run(drawn_from(cfg, seed), [kind.as_arrays(b) for b in corpus])


def decide_correct(got, ref, limits):
    """-> (correct, {number: [value, limit]}, notes)."""
    import refsteps

    numbers, notes = refsteps.compare(got, ref)
    compared = {k: [numbers[k], limits[k]] for k in sorted(limits) if limits[k] is not None}
    ok = all(v <= lim for v, lim in compared.values())
    notes["reference_losses"] = ref["losses"]
    notes["program_losses"] = got["losses"]
    return ok, compared, notes


def main(argv=None):
    args = _args(argv)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("benchmark/run.py: no program beside the benchmark (paddle_tpu/ is missing)",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    rehearsal = bool(args.rehearsal)
    bench, cell, cfg, mix, limits = load_cell(args.workload, rehearsal)
    chips = cell["chips"]

    import jax

    place_compile_cache(jax)
    devices = jax.devices()
    platform = devices[0].platform
    if (platform != "tpu" and not rehearsal) or len(devices) < chips:
        print(f"benchmark/run.py: cell {cell['name']} needs {chips} TPU chip(s); "
              f"jax found {len(devices)} x {platform}", file=sys.stderr)
        return EXIT_NO_DEVICE

    import metrics_loader
    import traffic
    from meter import CompileMeter

    meter = CompileMeter()
    peaks = None if rehearsal else metrics_loader.load_peaks(devices[0].device_kind)

    # ---- set-up -----------------------------------------------------------
    # `setup_s` runs from here, the device runtime up: Python, `import jax`
    # and the TPU runtime's own start took 8 to 16 s from run to run of one
    # tree (PERF.md), more than the whole of the set-up that follows, and no
    # change to the program can move them.  They are reported beside it.
    t_setup = time.time()
    parts = {"imports_and_devices": t_setup - T_START}

    def part(name, t=[t_setup]):
        parts[name], t[0] = time.time() - t[0], time.time()

    kind = traffic.kind(mix)
    corpus = kind.make_corpus(mix, cfg, args.seed)
    part("corpus")
    # what the fullest chip holds in live arrays, at five moments of the run
    # and at the reference's fullest: the harness's budget (PERF.md section 4)
    held = {}
    draw = drawn_from(cfg, args.seed)
    program = Program(cfg, draw, chips)
    part("weights_and_trainer")
    held["after_program"] = bytes_in_use(jax, chips)
    checked = corpus[: mix["checked_steps"]]
    got = first_steps(program, checked, draw, cfg["optimizer"]["beta1"])
    part("first_steps")
    held["after_checked_steps"] = bytes_in_use(jax, chips)
    items_per_batch = [kind.items(b) for b in corpus]
    compiles_before = meter.snapshot()
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    seconds = args.seconds
    if args.trace:
        import shutil
        from paddle_tpu import obs

        seconds = min(seconds, mix["trace_seconds"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python frames slow the host and fill the file
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        # as utils/profiler.start does: the program's obs spans ride the trace
        obs.tracer.set_annotation_factory(jax.profiler.TraceAnnotation)
    setup_s = time.time() - t_setup

    # ---- the window -------------------------------------------------------
    with GcWatch() as collections:
        t0, times, costs = window(program, corpus, seconds)
    if args.trace:
        obs.tracer.set_annotation_factory(None)
        jax.profiler.stop_trace()
    compiles_in_window = meter.snapshot()[0] - compiles_before[0]
    peak_bytes = memory_peak_bytes(jax, chips)
    held["after_window"] = bytes_in_use(jax, chips)

    attempted = len(costs)
    failed = sum(1 for c in costs if c != c or c in (float("inf"), float("-inf")))
    window_s = (times[-1] - t0) if times else float("nan")
    items = sum(items_per_batch[i % len(corpus)] for i in range(attempted))
    gaps = [b - a for a, b in zip([t0] + times[:-1], times)]

    # ---- free the program, then the reference -----------------------------
    del program
    gc.collect()
    held["before_reference"] = held["reference_most"] = bytes_in_use(jax, chips)

    def watch():
        now = bytes_in_use(jax, chips)
        if now is not None:
            held["reference_most"] = max(held["reference_most"], now)

    t_ref = time.perf_counter()
    ref = reference_readings(cell, cfg, mix, args.seed, watch=watch)
    reference_s = time.perf_counter() - t_ref
    held["after_reference"] = bytes_in_use(jax, chips)
    ok, compared, notes = decide_correct(got, ref, limits)
    ok = ok and failed == 0 and attempted > 0

    device = {"platform": "cpu" if rehearsal else platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    metrics, breakdown = {}, None
    if not rehearsal and attempted:
        if args.trace:
            steps = [{"items": items_per_batch[i % len(corpus)],
                      "lens": kind.lengths(corpus[i % len(corpus)])}
                     for i in range(attempted)]
            metrics, dev, breakdown = metrics_loader.read_all(
                bench, cell, cfg, trace_dir, steps, peaks,
                counters={"compiles_in_window": compiles_in_window})
            device.update(dev)
        else:
            metrics = {
                "train_throughput": {"value": items / window_s, "unit": "items/s"},
                "step_p95_ms": {"value": percentile(gaps, 95) * 1e3, "unit": "ms"},
                "setup_s": {"value": setup_s, "unit": "s"},
            }
    line = {"workload": cell["name"], "seed": args.seed, "steps": attempted,
            "item": cfg["item"], "items": items, "compiles": meter.snapshot()[0],
            "cache_hits": meter.snapshot()[1], "compiles_in_window": compiles_in_window,
            "bytes_in_use": held, "notes": notes}
    if not rehearsal:  # a CPU run prints no time
        ordered = sorted(gaps)
        line["gap_ms"] = {q: ordered[min(len(ordered) - 1, q * len(ordered) // 100)] * 1e3
                          for q in (0, 25, 50, 75, 95, 99, 100)} if gaps else {}
        # the longest gaps, each with the step it ended and the seconds of
        # garbage collection that fell inside it, oldest generation first
        ends = [t0] + times
        longest = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:6]
        line["longest_gaps"] = [
            {"step": i, "ms": gaps[i] * 1e3,
             "gc_ms": {str(g): sum(d for s, d, gen in collections.events
                                   if gen == g and ends[i] <= s < ends[i + 1]) * 1e3
                       for g in (2, 1, 0)}}
            for i in longest]
        line["gc_in_window"] = {
            str(g): [sum(1 for e in collections.events if e[2] == g),
                     sum(e[1] for e in collections.events if e[2] == g) * 1e3]
            for g in (0, 1, 2)}
        line.update(setup_parts_s=parts, reference_s=reference_s, wall_s=time.time() - T_START)
    print(json.dumps(line))
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value:.6g} limit {limit:.6g}", file=sys.stderr)
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
