"""The ``paddle train`` command-line face.

The reference's primary entry point is a command line
(``paddle/trainer/TrainerMain.cpp:32-65``): ``paddle_trainer --config=...
--save_dir=... --num_passes=...`` wrapped by the ``paddle`` shell script
(``paddle/scripts/submit_local.sh.in``), with ``--job`` selecting
train / test / time / checkgrad (TrainerBenchmark.cpp:71 for ``time``).
This module is that face over the TPU-native stack: ``paddle-tpu train
--config=conf.py`` (or ``python -m paddle_tpu train ...``) runs any v1
config file unmodified — parse → compile → jitted-step pass loop, with
``pass-%05d/`` checkpoint dirs exactly like the reference trainer writes.

Flags mirror the reference gflags (Flags.cpp) in ``--name=value`` form;
argparse also accepts ``--name value``.
"""

from __future__ import annotations

import argparse
import json
import logging
import re
import os
import sys
import time
from typing import List, Optional

import numpy as np


def _build_train_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="paddle-tpu train",
        description="Train/test/time a v1 config file "
        "(reference paddle_trainer, TrainerMain.cpp).",
    )
    ap.add_argument("--config", required=True, help="v1 config file (.py/.conf)")
    ap.add_argument(
        "--config_args", default="",
        help="comma-separated key=value pairs passed to the config "
        "(get_config_arg)",
    )
    ap.add_argument(
        "--job", default="train",
        choices=["train", "test", "time", "checkgrad"],
        help="one of (train, test, time, checkgrad) — TrainerMain.cpp:51-62",
    )
    ap.add_argument("--save_dir", default=None, help="write pass-%%05d/ checkpoints here")
    ap.add_argument("--num_passes", type=int, default=1)
    ap.add_argument("--start_pass", type=int, default=0)
    ap.add_argument(
        "--init_model_path", default=None,
        help="load initial parameters from this pass dir (ParamUtil.cpp)",
    )
    ap.add_argument("--saving_period", type=int, default=1)
    ap.add_argument("--saving_period_by_batches", type=int, default=0)
    ap.add_argument("--batch_size", type=int, default=0,
                    help="override the config's settings(batch_size=...)")
    ap.add_argument("--log_period", type=int, default=None)
    ap.add_argument("--dot_period", type=int, default=1,
                    help="print a '.' every N batches (reference TrainerInternal)")
    ap.add_argument("--show_parameter_stats_period", type=int, default=None)
    ap.add_argument("--test_period", type=int, default=50,
                    help="--job=time: number of timed batches "
                    "(TrainerBenchmark.cpp:79)")
    ap.add_argument("--feed_data", action="store_true",
                    help="--job=time: refetch a fresh batch every timed step "
                    "instead of reusing one (TrainerBenchmark.cpp:80-83)")
    ap.add_argument("--seed", type=int, default=None)
    # accepted for surface compatibility; the platform comes from jax and
    # is logged when the job starts
    ap.add_argument("--use_tpu", type=_flag_bool, default=True, nargs="?", const=True)
    ap.add_argument("--use_gpu", type=_flag_bool, default=False, nargs="?", const=True)
    ap.add_argument("--trainer_count", type=int, default=1,
                    help="data-parallel width: N > 1 trains on an N-way "
                    "data mesh over the first N devices (the reference's "
                    "meaning of the flag) and fails when jax sees fewer")
    ap.add_argument("--async_load_data", type=_flag_bool, default=True)
    ap.add_argument(
        "--cache_pass_in_mem", type=_flag_bool, default=False, nargs="?",
        const=True,
        help="device-resident pass cache: epoch 1 captures the staged "
        "batches on device, later epochs replay them with zero H2D "
        "traffic (the TPU-native CacheType.CACHE_PASS_IN_MEM; "
        "@provider(cache=...) configs enable this without the flag)",
    )
    ap.add_argument(
        "--data_echo_factor", type=int, default=None,
        help="train each epoch-1 batch N times (data echo) to amortize "
        "its host->device transfer; needs the pass cache enabled",
    )
    ap.add_argument(
        "--checkpoint_dir", default=None,
        help="fault-tolerance plane (robustness/): write full-state "
        "checkpoints (params + optimizer state + RNG + pass/batch "
        "position) here every --checkpoint_period_batches batches and at "
        "pass boundaries; enables divergence auto-rollback and "
        "preemption-safe shutdown (SIGTERM -> final checkpoint + "
        "PREEMPTED marker)",
    )
    ap.add_argument(
        "--checkpoint_period_batches", type=int, default=None,
        help="full-state checkpoint cadence in batches (default: the "
        "checkpoint_period_batches flag); each checkpoint is the rollback "
        "anchor and the kill -9 resume point",
    )
    ap.add_argument(
        "--resume", action="store_true",
        help="restore the latest good checkpoint from --checkpoint_dir "
        "(walking past torn ones) and continue mid-pass where the "
        "interrupted run stopped",
    )
    ap.add_argument(
        "--chaos", default=None,
        help="arm chaos fault points, e.g. 'nan_batch@5,kill@12' "
        "(robustness/chaos.py; testing only)",
    )
    return ap


def _flag_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).lower() in ("1", "true", "yes")


def _echo(msg: str) -> None:
    print(msg, flush=True)


def _load_init_model(trainer, path: str) -> None:
    """--init_model_path: a pass dir (params.tar and/or v1 per-parameter
    binaries), a merged-model bundle (merge_model output), or a bare
    params.tar."""
    import tarfile

    from paddle_tpu import checkpoint as ckpt

    if os.path.isdir(path):
        ckpt.load_parameter_dir(trainer.parameters, path)
    else:
        # a merge_model bundle is a tar with a manifest + nested params.tar;
        # a bare params.tar has no manifest
        is_bundle = False
        try:
            with tarfile.open(path, "r:*") as tf:
                is_bundle = any(
                    m.name.endswith("manifest.json") for m in tf.getmembers()
                )
        except tarfile.ReadError:
            pass
        if is_bundle:
            from paddle_tpu.utils.model_tools import load_merged_model

            load_merged_model(path, trainer.parameters)
        else:
            with open(path, "rb") as f:
                trainer.parameters.from_tar(f)
    trainer._reshard_after_restore()


def _make_trainer(parsed, seed: int, mesh=None):
    from paddle_tpu import parameters as v2_params
    from paddle_tpu import trainer as v2_trainer
    from paddle_tpu.v1_compat import make_optimizer

    params = v2_params.create(parsed.topology, seed=seed)
    return v2_trainer.SGD(
        cost=parsed.topology,
        parameters=params,
        update_equation=make_optimizer(parsed.settings),
        evaluators=list(parsed.evaluators),
        seed=seed,
        mesh=mesh,
    )


def _trainer_count_mesh(trainer_count: int, batch_size: int):
    """The data mesh ``--trainer_count N`` asks for (None for N <= 1).
    Raises ValueError when this process cannot give N devices their share
    of every batch — a device count that is silently dropped is the
    multi-chip form of a CPU fallback."""
    if trainer_count <= 1:
        return None
    import jax

    from paddle_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < trainer_count:
        raise ValueError(
            f"--trainer_count={trainer_count} needs that many devices; jax "
            f"sees {len(devs)} ({devs[0].platform} {devs[0].device_kind!r})"
        )
    if batch_size % trainer_count:
        raise ValueError(
            f"batch_size {batch_size} does not split over "
            f"--trainer_count={trainer_count} devices"
        )
    return make_mesh(data=trainer_count, devices=devs[:trainer_count])


# The reference trainer's registered gflags this CLI doesn't implement
# (paddle/utils/Flags.cpp + paddle/trainer/*.cpp DEFINE_*): a train.sh line
# that works against paddle_trainer must not die here — these specific names
# are accepted-and-ignored with a note.  Anything NOT in this set (typos,
# stray tokens) stays a hard error.
_IGNORED_REFERENCE_FLAGS = {
    "average_test_period", "beam_size", "checkgrad_eps", "comment",
    "distribute_test", "enable_parallel_vector", "gpu_id",
    "load_missing_parameter_strategy", "loadsave_parameters_in_pserver",
    "local", "log_period_server", "nics", "num_gradient_servers",
    "parallel_nn", "port", "ports_num", "ports_num_for_sparse",
    "prev_batch_state", "rdma_tcp", "save_only_one", "show_layer_stat",
    "start_pserver", "test_all_data_in_one_period", "test_pass",
    "test_wait", "trainer_id", "use_old_updater", "with_cost",
}


# the subset of ignored flags that take a VALUE (gflags string/int/double
# definitions per the reference Flags.cpp/trainer flags) — only these may
# consume a separate following token; the boolean remainder never does.
# NB test_wait and enable_parallel_vector LOOK boolean but are DEFINE_int32
# (Trainer.cpp:70, Flags.cpp:62).
_VALUE_REFERENCE_FLAGS = {
    "average_test_period", "beam_size", "checkgrad_eps", "comment",
    "enable_parallel_vector", "gpu_id", "load_missing_parameter_strategy",
    "log_period_server", "nics", "num_gradient_servers", "port",
    "ports_num", "ports_num_for_sparse", "rdma_tcp", "test_pass",
    "test_wait", "trainer_id",
}


def _ignored_flag_name(token: str):
    """The _IGNORED_REFERENCE_FLAGS entry this token spells, or None.
    Accepts --name, --name=value, and the gflags --no<bool> negation."""
    if not token.startswith("-"):
        return None
    name = token.lstrip("-").split("=", 1)[0]
    if name in _IGNORED_REFERENCE_FLAGS:
        return name
    if name.startswith("no") and name[2:] in _IGNORED_REFERENCE_FLAGS:
        return name[2:]
    return None


def cmd_train(argv: List[str]) -> int:
    args, unknown = _build_train_parser().parse_known_args(argv)
    ignored, fatal = [], []
    i = 0
    while i < len(unknown):
        u = unknown[i]
        name = _ignored_flag_name(u)
        if name is not None:
            ignored.append(u)
            # gflags separate-value form (`--gpu_id -1`, `--nics eth0`):
            # only VALUE-taking flags consume the next token, and only when
            # the value wasn't already attached with '='.  The token must
            # neither be a key=value (a stray `batch_size=32` after a
            # boolean stays fatal) nor LOOK like a flag itself (`--nics
            # --nolocall` must not eat the typo) — negative numbers like
            # `-1` are values, dash-then-letter is a flag.
            nxt = unknown[i + 1] if i + 1 < len(unknown) else None
            looks_like_flag = bool(
                nxt and re.match(r"--?[A-Za-z]", nxt)
            )
            if (
                "=" not in u
                and not u.lstrip("-").startswith("no")
                and name in _VALUE_REFERENCE_FLAGS
                and nxt is not None
                and "=" not in nxt
                and not looks_like_flag
            ):
                ignored.append(nxt)
                i += 1
        else:
            fatal.append(u)
        i += 1
    if ignored:
        print(
            f"note: ignoring reference trainer flags {ignored}",
            file=sys.stderr,
        )
    if fatal:
        print(
            f"error: unrecognized arguments {fatal} (not reference trainer "
            "flags; see `paddle-tpu train --help`)",
            file=sys.stderr,
        )
        return 2
    from paddle_tpu import event as v2_event
    from paddle_tpu import minibatch
    from paddle_tpu.utils import flags as _flags
    from paddle_tpu.v1_compat import make_config_reader, parse_config

    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    from paddle_tpu import obs as _obs

    _obs.tracer.configure(role="trainer")
    if args.log_period is not None:
        _flags.set_flag("log_period", args.log_period)
    if args.show_parameter_stats_period is not None:
        _flags.set_flag(
            "show_parameter_stats_period", args.show_parameter_stats_period
        )
    if args.seed is not None:
        _flags.set_flag("seed", args.seed)
    if args.cache_pass_in_mem:
        _flags.set_flag("cache_pass_in_mem", True)
    if args.data_echo_factor is not None:
        _flags.set_flag("data_echo_factor", args.data_echo_factor)
    if args.chaos:
        from paddle_tpu.robustness import chaos as _chaos

        _chaos.arm(args.chaos)
    _flags.set_flag("trainer_count", args.trainer_count)
    seed = _flags.get_flag("seed")

    config_path = os.path.abspath(args.config)
    config_dir = os.path.dirname(config_path)
    parsed = parse_config(config_path, args.config_args)
    if args.batch_size:
        # write the override back BEFORE building the optimizer: the
        # 'manual' LR schedule converts its sample boundaries through
        # settings.batch_size (reference numSamplesProcessed counts real
        # samples)
        parsed.settings.batch_size = args.batch_size
    batch_size = parsed.settings.batch_size
    try:
        mesh = _trainer_count_mesh(args.trainer_count, batch_size)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    import jax

    d0 = jax.devices()[0]
    _echo(
        f"devices: {jax.device_count()} x {d0.platform} {d0.device_kind!r}; "
        + (f"training on a {args.trainer_count}-way data mesh"
           if mesh is not None else "training on one device")
    )
    trainer = _make_trainer(parsed, seed, mesh=mesh)

    if args.init_model_path:
        _load_init_model(trainer, args.init_model_path)
    elif args.start_pass > 0 and args.save_dir:
        # resume from the last completed pass (reference ParamUtil
        # loadParametersWithPath from save_dir/pass-%05d)
        trainer.load_pass(args.save_dir, args.start_pass - 1)

    if args.job == "train":
        return _job_train(args, parsed, trainer, batch_size, config_dir, v2_event, minibatch, make_config_reader)
    if args.job == "test":
        return _job_test(args, parsed, trainer, batch_size, config_dir, minibatch, make_config_reader)
    if args.job == "time":
        return _job_time(args, parsed, trainer, batch_size, config_dir, minibatch, make_config_reader)
    if args.job == "checkgrad":
        return _job_checkgrad(args, parsed, trainer, batch_size, config_dir, minibatch, make_config_reader)
    raise AssertionError(args.job)


def _job_train(args, parsed, trainer, batch_size, config_dir,
               v2_event, minibatch, make_config_reader) -> int:
    # batching honors the bucketing flags (use_bucketing /
    # bucketing_token_budget): reference configs get length-bucketed
    # token-budget feeding with zero config edits
    from paddle_tpu.v1_compat import make_batched_reader
    test_reader = None
    has_test = (
        parsed.test_data is not None
        or (parsed.data_sources is not None and parsed.data_sources.test_list)
    )
    if has_test:
        try:
            test_reader = make_config_reader(parsed, config_dir, train=False)
        except (ValueError, FileNotFoundError) as e:
            _echo(f"test data declared but unavailable ({e}); skipping eval")

    dot = max(args.dot_period, 0)
    t0 = time.time()

    def handler(ev) -> None:
        if isinstance(ev, v2_event.EndIteration):
            if dot and (ev.batch_id + 1) % dot == 0:
                sys.stdout.write(".")
                sys.stdout.flush()
        elif isinstance(ev, v2_event.EndPass):
            sys.stdout.write("\n")
            _echo(
                f"Pass {ev.pass_id}: mean cost "
                f"{ev.evaluator.get('mean_cost', float('nan')):.6f} "
                f"({time.time() - t0:.1f}s elapsed)"
            )
            for k, v in sorted(ev.evaluator.items()):
                if k != "mean_cost":
                    _echo(f"  {k} = {v}")
            if test_reader is not None:
                res = trainer.test(
                    reader=minibatch.batch(test_reader, batch_size),
                    feeding=parsed.feeding,
                )
                _echo(f"Test with Pass {ev.pass_id}: cost {res.cost:.6f}")
                for k, v in sorted(res.metrics.items()):
                    _echo(f"  {k} = {v}")

    trainer.train(
        reader=make_batched_reader(parsed, config_dir, batch_size, train=True),
        num_passes=args.num_passes,
        event_handler=handler,
        feeding=parsed.feeding,
        save_dir=args.save_dir,
        saving_period=args.saving_period,
        saving_period_by_batches=args.saving_period_by_batches or None,
        start_pass=args.start_pass,
        async_load_data=args.async_load_data,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_period_batches=args.checkpoint_period_batches,
        resume=args.resume,
    )
    if getattr(trainer, "preempted", False):
        _echo(
            f"PREEMPTED: state checkpointed under {args.checkpoint_dir}; "
            "restart with --resume to continue"
        )
        return 75  # EX_TEMPFAIL: restart me
    return 0


def _job_test(args, parsed, trainer, batch_size, config_dir,
              minibatch, make_config_reader) -> int:
    """--job=test (reference Tester.cpp): evaluate the loaded model on the
    config's test stream (train stream when no test stream is declared)."""
    try:
        reader = make_config_reader(parsed, config_dir, train=False)
    except (ValueError, FileNotFoundError):
        _echo("no test data declared; evaluating on the train stream")
        reader = make_config_reader(parsed, config_dir, train=True)
    res = trainer.test(
        reader=minibatch.batch(reader, batch_size), feeding=parsed.feeding
    )
    _echo(f"Test cost {res.cost:.6f}")
    for k, v in sorted(res.metrics.items()):
        _echo(f"  {k} = {v}")
    return 0


def _job_time(args, parsed, trainer, batch_size, config_dir,
              minibatch, make_config_reader) -> int:
    """--job=time (TrainerBenchmark.cpp:30-90): 10 burn-in steps on one
    batch, then ``--test_period`` timed steps; prints the StatSet table the
    reference prints via globalStat.printSegTimerStatus()."""
    import jax

    from paddle_tpu.parallel.mesh import shard_batch
    from paddle_tpu.utils.timers import global_stats, stat_timer

    from paddle_tpu.v1_compat import make_batched_reader

    # honors use_bucketing: --job=time measures the bucketed feed when the
    # flag is on (the per-bucket dispatch counters land in the StatSet table
    # this job prints)
    batch_reader = make_batched_reader(parsed, config_dir, batch_size, train=True)
    batches = batch_reader()
    feeder = trainer._make_feeder(parsed.feeding)

    def next_batch():
        nonlocal batches
        with stat_timer("GetData"):
            try:
                raw = next(batches)
            except StopIteration:
                batches = batch_reader()
                raw = next(batches)
            return shard_batch(feeder(raw), trainer.mesh)

    # --cache_pass_in_mem (or a CACHE_PASS_IN_MEM provider): stage the timed
    # batches once, seal the device-resident cache, and feed every timed
    # step from its replay — the timing then measures the compute-bound
    # cached-epoch regime instead of the H2D wire
    from paddle_tpu.utils.flags import get_flag as _get_flag

    cached_iter = None
    if _get_flag("cache_pass_in_mem") or getattr(
        batch_reader, "cache_pass_in_mem", False
    ):
        from paddle_tpu.reader.pass_cache import PassCache

        # timing feed: no echo (every timed step must be a distinct
        # dispatch); shuffle/budget/seed follow the shared flag contract
        cache = PassCache.from_flags(batch_reader, echo_factor=1)
        # stage at most ONE pass (never wrap the reader around: re-staged
        # duplicates would multiply the pass's real HBM cost), capped at
        # the timed-step count
        for raw in batch_reader():
            with stat_timer("GetData"):
                cache.observe(shard_batch(feeder(raw), trainer.mesh))
            if not cache.active or cache.n_batches >= max(args.test_period, 1):
                break
        cache.seal()
        if cache.ready:
            cached_iter = cache.stream()
            _echo(f"pass cache: {cache.summary()}")

    batch = next(cached_iter) if cached_iter is not None else next_batch()
    params, state = trainer.parameters.params, trainer.parameters.state
    opt_state = trainer._opt_state
    rng = jax.random.PRNGKey(0)

    def one_step(params, state, opt_state, batch, rng):
        rng, step_rng = jax.random.split(rng)
        params, state, opt_state, metrics = trainer._train_step(
            params, state, opt_state, batch, step_rng
        )
        return params, state, opt_state, metrics, rng

    _echo("Burning time...")
    for _ in range(10):
        params, state, opt_state, metrics, rng = one_step(
            params, state, opt_state, batch, rng
        )
    # host sync before the clock starts
    float(np.asarray(metrics["cost"]))
    _echo("Burning time end.")

    n = 0
    t0 = time.time()
    for _ in range(max(args.test_period, 1)):
        if args.feed_data:
            batch = (
                next(cached_iter) if cached_iter is not None else next_batch()
            )
        with stat_timer("FwdBwd"):
            params, state, opt_state, metrics, rng = one_step(
                params, state, opt_state, batch, rng
            )
        n += 1
    float(np.asarray(metrics["cost"]))
    dt = time.time() - t0
    global_stats.print_all_status()  # prints the StatSet table itself
    _echo(
        f"{n} batches of {batch_size}: {dt * 1000 / n:.3f} ms/batch, "
        f"{n * batch_size / dt:.1f} samples/sec"
    )
    global_stats.reset()
    return 0


def _job_checkgrad(args, parsed, trainer, batch_size, config_dir,
                   minibatch, make_config_reader) -> int:
    """--job=checkgrad (Trainer::checkGradient, Trainer.cpp): compare the
    VJP gradient of the total cost against a central finite difference of
    the directional derivative, per parameter tensor.  Runs the graph in
    float64 — the reference gets its fd accuracy from the double-precision
    build (WITH_DOUBLE); in f32 the forward noise (~1e-4 relative for an
    800-wide MLP) swamps any usable eps."""
    import jax

    jax.config.update("jax_enable_x64", True)
    from paddle_tpu.parallel.mesh import shard_batch

    reader = make_config_reader(parsed, config_dir, train=True)
    raw = next(minibatch.batch(reader, min(batch_size, 8))())
    feeder = trainer._make_feeder(parsed.feeding)
    batch = shard_batch(feeder(raw), trainer.mesh)

    def _f64(x):
        arr = np.asarray(x)
        return arr.astype(np.float64) if np.issubdtype(arr.dtype, np.floating) else arr

    batch = jax.tree.map(_f64, batch)
    net = trainer.network
    state = trainer.parameters.state
    rng = jax.random.PRNGKey(0)
    out_names = list(net.topology.output_names)

    def total_cost(params):
        outs, _ = net.apply(params, batch, state=state, train=True, rng=rng)
        total = 0.0
        for name in out_names:
            v = outs[name]
            arr = v.data if hasattr(v, "data") else v
            total = total + arr.astype("float64").mean()
        return total

    def loss(params) -> float:
        return float(np.asarray(total_cost(params)))

    base = jax.tree.map(_f64, trainer.parameters.params)
    grads = jax.grad(total_cost)(base)

    # Directional derivative per parameter tensor, the reference's scheme
    # (perturb the whole parameter by a random delta, compare the cost
    # change against <grad, delta>).
    rng_np = np.random.RandomState(0)
    worst = 0.0
    failed = []
    eps = 1e-5
    for pname, g in sorted(grads.items()):
        for wname, gval in sorted(g.items()):
            gval = np.asarray(gval, np.float64)
            w0 = np.asarray(base[pname][wname], np.float64)
            d = rng_np.standard_normal(w0.shape)
            d /= max(np.linalg.norm(d), 1e-12)
            pert = dict(base)
            pert[pname] = dict(base[pname])
            pert[pname][wname] = w0 + eps * d
            lp = loss(pert)
            pert[pname][wname] = w0 - eps * d
            lm = loss(pert)
            fd = (lp - lm) / (2 * eps)
            an = float((gval * d).sum())
            denom = max(abs(fd), abs(an), 1e-8)
            rel = abs(fd - an) / denom
            worst = max(worst, rel)
            if rel > 1e-3:
                failed.append((f"{pname}.{wname}", an, fd, rel))
    if failed:
        for name, an, fd, rel in failed:
            _echo(f"FAIL {name}: analytic {an:.6g} vs fd {fd:.6g} (rel {rel:.3g})")
        return 1
    _echo(f"checkgrad PASSED ({len(grads)} parameters, worst rel err {worst:.3g})")
    return 0


# ---------------------------------------------------------------------------
# non-train subcommands (submit_local.sh.in:114-135)
# ---------------------------------------------------------------------------

def cmd_version(argv: List[str]) -> int:
    import jax

    import paddle_tpu

    print(f"paddle-tpu {paddle_tpu.__version__}, running on")
    print(f"    jax: {jax.__version__}")
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"    devices: unavailable ({e})", file=sys.stderr)
        return 1
    print(f"    platform: {devs[0].platform}")
    print(f"    device_kind: {devs[0].device_kind}")
    print(f"    device_count: {len(devs)}")
    print(f"    devices: {[str(d) for d in devs]}")
    return 0


def cmd_dump_config(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(prog="paddle-tpu dump_config")
    ap.add_argument("config")
    ap.add_argument("--config_args", default="")
    args = ap.parse_args(argv)
    from paddle_tpu.utils.model_tools import dump_config

    print(dump_config(args.config, args.config_args))
    return 0


def cmd_make_diagram(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(prog="paddle-tpu make_diagram")
    ap.add_argument("config")
    ap.add_argument("dot_file")
    ap.add_argument("--config_args", default="")
    args = ap.parse_args(argv)
    from paddle_tpu.utils.model_tools import make_diagram
    from paddle_tpu.v1_compat import parse_config

    parsed = parse_config(os.path.abspath(args.config), args.config_args)
    make_diagram(parsed.topology, args.dot_file)
    print(f"wrote {args.dot_file}")
    return 0


def cmd_merge_model(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(prog="paddle-tpu merge_model")
    ap.add_argument("--model_dir", required=True, help="a pass-%%05d dir")
    ap.add_argument("--config_file", required=True)
    ap.add_argument("--model_file", required=True, help="output bundle path")
    ap.add_argument("--config_args", default="")
    args = ap.parse_args(argv)
    from paddle_tpu import checkpoint as ckpt
    from paddle_tpu import parameters as v2_params
    from paddle_tpu.utils.model_tools import merge_model
    from paddle_tpu.v1_compat import parse_config

    parsed = parse_config(os.path.abspath(args.config_file), args.config_args)
    params = v2_params.create(parsed.topology)
    ckpt.load_parameter_dir(params, args.model_dir)
    merge_model(params, args.model_file)
    print(f"wrote {args.model_file}")
    return 0


def cmd_plotcurve(argv: List[str]) -> int:
    from paddle_tpu.utils.plotcurve import main as plot_main

    return plot_main(argv)


def cmd_serve(argv: List[str]) -> int:
    """``paddle-tpu serve`` — the TPU-native serving plane over the NMT
    flagship (serving/): request queue + continuous batching + block-paged
    decode cache, with the production SLO surface (deadlines, bounded
    queue, shedding, chunked prefill).  Requests come from ``--requests``
    (one line of space-separated source token ids each) or ``--synthetic
    N``; arrivals follow the open-loop generator at ``--rate`` req/s.
    Prints one JSON line per completed request and a final summary line
    with the DISJOINT status ledger (served / shed / rejected / timeout /
    unfinished — the Gemma-on-TPU serving metric set plus the overload
    taxonomy).  SIGTERM drains gracefully: stop admitting, finish every
    in-flight request, exit 0 (the PreemptionGuard contract the trainer
    already honors); a second signal still kills."""
    import json as _json
    import time as _time

    ap = argparse.ArgumentParser(
        prog="paddle-tpu serve",
        description="continuous-batching serving plane (serving/engine.py)",
    )
    ap.add_argument("--model", default="",
                    help="trained parameter tar (paddle-tpu train "
                    "--save_dir output); random seeded weights when empty")
    ap.add_argument("--src-vocab", type=int, default=1000)
    ap.add_argument("--trg-vocab", type=int, default=1000)
    ap.add_argument("--word-dim", type=int, default=128)
    ap.add_argument("--hidden-dim", type=int, default=128)
    ap.add_argument("--max-length", type=int, default=32,
                    help="compiled decode ceiling (Seq2SeqGenerator)")
    ap.add_argument("--max-new-tokens", type=int, default=None)
    ap.add_argument("--max-slots", type=int, default=None)
    ap.add_argument("--hbm-budget-mb", type=int, default=None)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request end-to-end deadline; infeasible "
                    "requests are SHED at admission (default: the "
                    "serving_default_deadline_s flag; 0 = none)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound on queued-ahead-of-admission requests; "
                    "beyond it submits are REJECTED immediately (default: "
                    "the serving_queue_limit flag; 0 = unbounded)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    help="chunked prefill bound (default: the "
                    "serving_prefill_chunk_tokens flag; 0 = whole-prompt "
                    "prefill)")
    ap.add_argument("--prefix-cache", action="store_true", default=None,
                    help="arm copy-on-write prompt-prefix sharing (default: "
                    "the serving_prefix_cache flag)")
    ap.add_argument("--spec-decode", action="store_true", default=None,
                    help="arm n-gram speculative decoding (default: the "
                    "serving_spec_decode flag)")
    ap.add_argument("--drain-timeout-s", type=float, default=60.0,
                    help="graceful-drain budget after SIGTERM/SIGINT")
    ap.add_argument("--requests", default="",
                    help="file of requests (space-separated src ids/line)")
    ap.add_argument("--synthetic", type=int, default=16,
                    help="generate N random requests when --requests is empty")
    ap.add_argument("--prefix-pool", type=int, default=0,
                    help="share prompt prefixes across synthetic requests: "
                    "draw from a seeded pool of N prefixes "
                    "(reader/loadgen.PrefixMixer) — the realistic workload "
                    "for the serving_prefix_cache COW sharing path; 0 = "
                    "fully independent prompts")
    ap.add_argument("--prefix-frac", type=float, default=0.5,
                    help="fraction of synthetic requests that start with a "
                    "pool prefix (only with --prefix-pool)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate (req/s); 0 = submit all "
                    "immediately")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "uniform", "burst"],
                    help="open-loop arrival process (reader/loadgen.py)")
    ap.add_argument("--sessions", type=int, default=0,
                    help="stamp synthetic requests with session ids drawn "
                    "from a pool of N sessions (PrefixMixer.session_of — "
                    "the fleet router's affinity key); 0 = session-less")
    ap.add_argument("--priority-every", type=int, default=0,
                    help="stamp every Nth request interactive class p0 and "
                    "the rest batch class p2 (per-class SLO admission, "
                    "serving/scheduler.py); 0 = everything default class p1")
    ap.add_argument("--record-trace", default="", metavar="TRACE",
                    help="record the offered workload to a replayable "
                    ".ptt request-lifecycle trace (robustness/traces.py): "
                    "arrival offsets, ids, full source ids, deadlines, "
                    "sessions, priority classes")
    ap.add_argument("--replay", default="", metavar="TRACE",
                    help="REPLAY a recorded .ptt trace instead of offering "
                    "synthetic load: the recorded arrival clock, prompts, "
                    "ids, deadlines, sessions and priorities are "
                    "reproduced bit-for-bit (--synthetic/--rate/--arrival "
                    "are ignored)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--register", default="",
                    help="run as a FLEET ENGINE: register with the router "
                    "at host:port (serving/router.py) and serve requests "
                    "over the typed wire RPC instead of a local workload; "
                    "SIGTERM drains and deregisters")
    ap.add_argument("--engine-id", default="",
                    help="engine identity on the router's lease plane "
                    "(default: engine-<pid>; only with --register)")
    ap.add_argument("--engine-port", type=int, default=0,
                    help="data-plane listen port (0 = ephemeral; only "
                    "with --register)")
    ap.add_argument("--timeout-s", type=float, default=120.0)
    ap.add_argument("--stats-out", default="",
                    help="write the summary JSON here too")
    ap.add_argument("--trace-dir", default=None,
                    help="arm Chrome-trace span export to this directory "
                    "(default: the trace_dir flag / PADDLE_TPU_TRACE_DIR)")
    ap.add_argument("--metrics-out", default=None,
                    help="periodic Prometheus-text metrics snapshot file "
                    "(obs/metrics.py; default: the metrics_out flag)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics on http://127.0.0.1:<port> "
                    "(default: the metrics_port flag; 0 = off)")
    args = ap.parse_args(argv)

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import obs as _obs

    _obs.tracer.configure(role="serve", trace_dir=args.trace_dir)
    from paddle_tpu.core.topology import reset_auto_names
    from paddle_tpu.models.seq2seq import Seq2SeqGenerator, seq2seq_cost
    from paddle_tpu.reader.loadgen import OpenLoopLoadGen
    from paddle_tpu.robustness.preemption import PreemptionGuard
    from paddle_tpu.serving import Request, ServingEngine, ServingScheduler

    reset_auto_names()
    cost, _ = seq2seq_cost(
        args.src_vocab, args.trg_vocab,
        word_dim=args.word_dim, hidden_dim=args.hidden_dim,
    )
    params = paddle.parameters.create(cost, seed=args.seed)
    if args.model:
        with open(args.model, "rb") as f:
            params.init_from_tar(f)
    gen = Seq2SeqGenerator(
        params, args.src_vocab, args.trg_vocab,
        word_dim=args.word_dim, hidden_dim=args.hidden_dim,
        max_length=args.max_length,
    )
    engine = ServingEngine(
        gen,
        max_slots=args.max_slots,
        hbm_budget_mb=args.hbm_budget_mb,
        max_new_tokens=args.max_new_tokens,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        prefix_cache=args.prefix_cache,
        spec_decode=args.spec_decode,
    )

    if args.register:
        return _serve_as_fleet_engine(args, engine)

    session_of = None
    replay_trace = None
    sources = []
    if args.replay:
        # the recorded day IS the workload: prompts/ids/deadlines/
        # sessions/priorities all come from the trace records
        from paddle_tpu.robustness.traces import read_trace

        replay_trace = read_trace(args.replay)
    elif args.requests:
        with open(args.requests) as f:
            sources = [
                [int(t) for t in line.split()] for line in f if line.strip()
            ]
    elif args.prefix_pool > 0:
        from paddle_tpu.reader.loadgen import PrefixMixer

        mixer = PrefixMixer(
            args.src_vocab, pool_size=args.prefix_pool,
            prefix_frac=args.prefix_frac, seed=args.seed,
            sessions=args.sessions,
        )
        sources = [mixer.source(i) for i in range(args.synthetic)]
        if args.sessions > 0:
            session_of = mixer.session_of
    else:
        rng = np.random.RandomState(args.seed)
        sources = [
            rng.randint(2, args.src_vocab, size=rng.randint(3, 24)).tolist()
            for _ in range(args.synthetic)
        ]
    if args.sessions > 0 and session_of is None and replay_trace is None:
        # no prefix pool to correlate with: sessions spread round-robin
        session_of = lambda i: f"sess{i % args.sessions}"  # noqa: E731
    priority_of = None
    if args.priority_every > 0 and replay_trace is None:
        priority_of = (
            lambda i: 0 if i % args.priority_every == 0 else 2
        )

    done = []

    def on_done(r):
        done.append(r)
        print(_json.dumps({
            "req": r.req_id,
            "status": r.status,
            "tokens": r.tokens,
            "error": r.error,
            "latency_ms": round((r.t_done - r.t_submit) * 1e3, 3),
        }), flush=True)

    deadline_s = args.deadline_s
    if replay_trace is not None:
        # replay: every request carries the RECORDED identity — ids,
        # deadlines, sessions, priority classes.  The live flags must
        # not re-derive any of it (the loadgen's stamp-if-absent
        # contract keeps recorded values authoritative).
        reqs = [
            Request(
                list(rec["src"]), rec.get("mnt"),
                req_id=str(rec["id"]), callback=on_done,
                deadline_s=rec.get("dl"), session_id=rec.get("sess"),
                priority=rec.get("prio"),
            )
            for rec in replay_trace.requests()
        ]
    else:
        reqs = [
            Request(src, callback=on_done, deadline_s=deadline_s)
            for src in sources
        ]
    drained_clean = None
    t0 = _time.perf_counter()
    # live metrics export (obs/metrics.py): the SLO gauges the scheduler
    # registers (queue depth, pages in use, predicted wait) + the StatSet
    # ledger, as Prometheus text — file snapshot and/or localhost endpoint
    from paddle_tpu.obs.metrics import MetricsExporter
    from paddle_tpu.utils import flags as _serve_flags

    # --metrics-port 0 forces the endpoint OFF even when the metrics_port
    # flag/env is set (the help's "0 = off"); unset falls through to the
    # flag; a positive port wins outright
    metrics = MetricsExporter(
        path=args.metrics_out,
        port=(None if args.metrics_port is None
              else (args.metrics_port if args.metrics_port > 0 else -1)),
    ) if (
        args.metrics_out or args.metrics_port
        or _serve_flags.get_flag("metrics_out")
        or _serve_flags.get_flag("metrics_port")
    ) else None
    if metrics is not None and metrics.port:
        _echo(f"metrics: http://127.0.0.1:{metrics.port}/metrics")
    writer = None
    if args.record_trace:
        from paddle_tpu.robustness.traces import TraceWriter

        writer = TraceWriter(args.record_trace, meta={
            "cmd": "serve", "seed": args.seed, "rate": args.rate,
            "arrival": args.arrival,
        })
    with PreemptionGuard() as guard:
        sched = ServingScheduler(
            engine, queue_limit=args.queue_limit,
            default_deadline_s=(
                args.deadline_s if args.deadline_s is not None else None
            ),
        )

        def _submit(r):
            # record AFTER the loadgen stamped deadline/session/priority
            # (run() stamps before calling submit), so the trace carries
            # the values the scheduler actually saw
            if writer is not None:
                writer.record_request(r)
            return sched.submit(r)

        try:
            submitted = []
            if replay_trace is not None:
                from paddle_tpu.robustness.traces import TraceReplayLoadGen

                it = iter(reqs)
                submitted = TraceReplayLoadGen(
                    replay_trace,
                    request_factory=lambda rec: next(it),
                ).run(
                    _submit, stop=lambda: guard.triggered,
                    cancel=lambda rid, reason: sched.cancel(
                        rid, reason or "timeout: canceled"),
                )
            elif args.rate > 0:
                submitted = OpenLoopLoadGen(
                    args.rate, len(reqs), lambda i: reqs[i],
                    seed=args.seed, process=args.arrival,
                    session_of=session_of, priority_of=priority_of,
                ).run(_submit, stop=lambda: guard.triggered)
            else:
                for i, r in enumerate(reqs):
                    if guard.triggered:
                        break
                    if session_of is not None:
                        r.session_id = session_of(i)
                    if priority_of is not None:
                        pri = priority_of(i)
                        if pri is not None:
                            r.priority = int(pri)
                    _submit(r)
                    submitted.append(r)
            if guard.triggered:
                # graceful drain: stop admitting, finish what's in flight,
                # leave the untransmitted tail of the schedule unsubmitted
                _echo("draining: SIGTERM/SIGINT — finishing in-flight "
                      f"requests ({len(submitted)} submitted)")
                drained_clean = sched.drain(args.drain_timeout_s)
                reqs = list(submitted)
            else:
                wait_deadline = _time.perf_counter() + args.timeout_s
                for r in reqs:
                    # bounded poll; past the deadline, done() costs zero per
                    # remaining request instead of a full wait() quantum
                    while not r.done():
                        if guard.triggered or (
                            _time.perf_counter() > wait_deadline
                        ):
                            break
                        r.wait(0.2)
                    if guard.triggered:
                        break
                if guard.triggered:
                    drained_clean = sched.drain(args.drain_timeout_s)
        finally:
            sched.close()
            if writer is not None:
                writer.close()
            if metrics is not None:
                metrics.close()
    from paddle_tpu.serving import percentile, status_counts

    # the status ledger is judged AFTER close() (which finalizes every
    # outstanding request), so categories are DISJOINT and sum to total
    wall = _time.perf_counter() - t0
    by_status = status_counts(reqs)
    ok = [r for r in reqs if r.status == "served"]
    tpots = [
        (r.t_done - r.t_admit) / len(r.tokens)
        for r in ok if r.tokens and r.t_admit is not None
    ]

    def pct(xs, p):
        v = percentile(xs, p)
        return None if v is None else round(v * 1e3, 3)

    summary = {
        "served": by_status["served"],
        "shed": by_status["shed"],
        "rejected": by_status["rejected"],
        "timeout": by_status["timeout"],
        "unfinished": by_status["closed"],
        "drained_clean": drained_clean,
        "wall_s": round(wall, 3),
        "sustained_req_per_sec": round(len(ok) / wall, 3) if wall > 0 else None,
        "p50_token_ms": pct(tpots, 0.50),
        "p99_token_ms": pct(tpots, 0.99),
        "engine": engine.summary(),
    }
    class_labels = sorted({r.class_label for r in reqs})
    if len(class_labels) > 1:
        # per-class status ledger — the p0-stays-served-while-p2-sheds
        # evidence the per-class admission plane exists to produce
        summary["classes"] = {
            c: status_counts([r for r in reqs if r.class_label == c])
            for c in class_labels
        }
    if replay_trace is not None:
        summary["replayed_trace"] = args.replay
    if writer is not None:
        summary["recorded_trace"] = args.record_trace
    print(_json.dumps(summary), flush=True)
    if args.stats_out:
        _obs.write_stats_json(args.stats_out, summary)
    _obs.tracer.dump()  # per-process trace file (no-op without trace_dir)
    if drained_clean is not None:
        # SIGTERM path: exit 0 iff the drain finished every in-flight
        # request (no 'closed' stragglers) — the graceful-exit contract
        return 0 if (drained_clean and not by_status["closed"]) else 1
    return 0 if (ok and not by_status["closed"]) else 1


def _serve_as_fleet_engine(args, engine) -> int:
    """The `paddle-tpu serve --register host:port` mode: this process is
    one FLEET ENGINE — a ServingScheduler wrapped in an EngineAgent that
    registers on the router's heartbeat-lease plane and serves requests
    arriving over the typed wire RPC (serving/router.py).  No local
    workload; SIGTERM drains the scheduler, deregisters, exits 0 on a
    clean drain — the rolling-restart contract."""
    import json as _json
    import os as _os
    import time as _time

    from paddle_tpu import obs as _obs
    from paddle_tpu.obs.metrics import MetricsExporter
    from paddle_tpu.robustness.preemption import PreemptionGuard
    from paddle_tpu.serving import EngineAgent, ServingScheduler
    from paddle_tpu.utils import flags as _serve_flags

    host, _, port = args.register.rpartition(":")
    if not host or not port.isdigit():
        print(f"--register wants host:port, got {args.register!r}",
              file=sys.stderr)
        return 2
    engine_id = args.engine_id or f"engine-{_os.getpid()}"
    metrics = MetricsExporter(
        path=args.metrics_out,
        port=(None if args.metrics_port is None
              else (args.metrics_port if args.metrics_port > 0 else -1)),
    ) if (
        args.metrics_out or args.metrics_port
        or _serve_flags.get_flag("metrics_out")
        or _serve_flags.get_flag("metrics_port")
    ) else None
    drained_clean = False
    with PreemptionGuard() as guard:
        sched = ServingScheduler(
            engine, queue_limit=args.queue_limit,
            default_deadline_s=args.deadline_s,
        )
        agent = EngineAgent(
            sched, engine_id, (host, int(port)),
            address=("127.0.0.1", args.engine_port),
        )
        # the harness parses this line for identity + data-plane port
        print(_json.dumps({
            "engine_id": engine_id,
            "data_plane": list(agent.address),
            "router": [host, int(port)],
        }), flush=True)
        try:
            while not guard.triggered:
                _time.sleep(0.1)
            _echo(f"draining: engine {engine_id} finishing in-flight work")
            drained_clean = sched.drain(args.drain_timeout_s)
        finally:
            agent.close()
            sched.close()
            if metrics is not None:
                metrics.close()
    summary = {
        "engine_id": engine_id,
        "drained_clean": drained_clean,
        "engine": engine.summary(),
    }
    print(_json.dumps(summary), flush=True)
    if args.stats_out:
        _obs.write_stats_json(args.stats_out, summary)
    _obs.tracer.dump()
    return 0 if drained_clean else 1


def cmd_route(argv: List[str]) -> int:
    """``paddle-tpu route`` — the serving-fleet router frontend
    (serving/router.py): admission (deadlines, bounded queue, shed) +
    least-predicted-wait dispatch with prefix/session affinity over the
    engines registered on its heartbeat-lease plane (`paddle-tpu serve
    --register`).  With ``--synthetic N`` it also DRIVES an open-loop
    workload through the fleet and prints the per-request lines + final
    summary (the `paddle-tpu serve` report shape, one tier up); with
    ``--synthetic 0`` it routes for external clients until SIGTERM."""
    import json as _json
    import time as _time

    ap = argparse.ArgumentParser(
        prog="paddle-tpu route",
        description="SLO-aware affinity-routing fleet frontend "
                    "(serving/router.py)",
    )
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="router RPC port (0 = ephemeral, printed on the "
                    "ready line)")
    ap.add_argument("--journal", default="",
                    help="append-only JSON-lines routing journal; restart "
                    "with the predecessor's journal to refuse re-serving "
                    "its finalized request ids (HA failover)")
    ap.add_argument("--lease-timeout-s", type=float, default=None,
                    help="engine heartbeat lease (default: the "
                    "router_lease_timeout_s flag)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound on requests inside admission+dispatch "
                    "(default: the router_queue_limit flag; 0 = unbounded)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="default per-request deadline stamped at the "
                    "frontend (default: the serving_default_deadline_s "
                    "flag; 0 = none)")
    ap.add_argument("--no-affinity", action="store_true",
                    help="disable prefix/session affinity (pure "
                    "least-predicted-wait) — the A/B lever for the "
                    "prefix-hit-rate comparison")
    ap.add_argument("--affinity-slack-s", type=float, default=None)
    ap.add_argument("--stats-poll-s", type=float, default=None)
    ap.add_argument("--expect-engines", type=int, default=0,
                    help="wait until N engines hold live leases before "
                    "offering traffic")
    ap.add_argument("--expect-timeout-s", type=float, default=30.0)
    ap.add_argument("--synthetic", type=int, default=0,
                    help="drive N open-loop synthetic requests through the "
                    "fleet; 0 = daemon mode (route for external clients "
                    "until SIGTERM)")
    ap.add_argument("--src-vocab", type=int, default=1000)
    ap.add_argument("--max-new-tokens", type=int, default=None)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate (req/s); 0 = submit all "
                    "immediately")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "uniform", "burst"])
    ap.add_argument("--prefix-pool", type=int, default=0,
                    help="share prompt prefixes across synthetic requests "
                    "(reader/loadgen.PrefixMixer) — what affinity routing "
                    "concentrates per engine")
    ap.add_argument("--prefix-frac", type=float, default=0.5)
    ap.add_argument("--sessions", type=int, default=0,
                    help="stamp session ids from a pool of N "
                    "(PrefixMixer.session_of) — the affinity key")
    ap.add_argument("--priority-every", type=int, default=0,
                    help="stamp every Nth synthetic request interactive "
                    "class p0 and the rest batch class p2; 0 = all p1")
    ap.add_argument("--record-trace", default="", metavar="TRACE",
                    help="record the fleet workload to a replayable .ptt "
                    "request-lifecycle trace (robustness/traces.py)")
    ap.add_argument("--replay", default="", metavar="TRACE",
                    help="replay a recorded .ptt trace through the fleet "
                    "instead of synthetic load (recorded arrivals/ids/"
                    "deadlines/sessions/priorities; --synthetic/--rate "
                    "are ignored; recorded cancels are dropped — the "
                    "fleet client has no cancel RPC)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout-s", type=float, default=120.0,
                    help="wait budget for the synthetic workload")
    ap.add_argument("--stats-out", default="",
                    help="write the summary JSON here too")
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--metrics-out", default=None,
                    help="periodic Prometheus snapshot: fleet gauges "
                    "(paddle_tpu_fleet_engines, per-engine queue depth/"
                    "pages/predicted wait) + the fleet request ledger")
    ap.add_argument("--metrics-port", type=int, default=None)
    args = ap.parse_args(argv)

    import numpy as np

    from paddle_tpu import obs as _obs

    _obs.tracer.configure(role="route", trace_dir=args.trace_dir)
    from paddle_tpu.obs.metrics import MetricsExporter
    from paddle_tpu.reader.loadgen import OpenLoopLoadGen, PrefixMixer
    from paddle_tpu.robustness.preemption import PreemptionGuard
    from paddle_tpu.serving import FleetClient, Request, Router
    from paddle_tpu.serving import percentile, status_counts
    from paddle_tpu.utils import flags as _route_flags

    metrics = MetricsExporter(
        path=args.metrics_out,
        port=(None if args.metrics_port is None
              else (args.metrics_port if args.metrics_port > 0 else -1)),
    ) if (
        args.metrics_out or args.metrics_port
        or _route_flags.get_flag("metrics_out")
        or _route_flags.get_flag("metrics_port")
    ) else None
    if metrics is not None and metrics.port:
        _echo(f"metrics: http://127.0.0.1:{metrics.port}/metrics")

    router = Router(
        address=(args.host, args.port),
        journal_path=args.journal or None,
        lease_timeout_s=args.lease_timeout_s,
        queue_limit=args.queue_limit,
        default_deadline_s=args.deadline_s,
        affinity=False if args.no_affinity else None,
        affinity_slack_s=args.affinity_slack_s,
        stats_poll_s=args.stats_poll_s,
    )
    # the harness parses this line for the routing address
    print(_json.dumps({"router": list(router.address)}), flush=True)
    rc = 0
    t0 = _time.perf_counter()
    try:
        with PreemptionGuard() as guard:
            if args.expect_engines > 0:
                deadline = _time.perf_counter() + args.expect_timeout_s
                while (len(router.live_engines()) < args.expect_engines
                       and _time.perf_counter() < deadline
                       and not guard.triggered):
                    _time.sleep(0.05)
                live = len(router.live_engines())
                if live < args.expect_engines:
                    _echo(f"only {live}/{args.expect_engines} engines "
                          "registered before the deadline")
                    return 1
                _echo(f"fleet ready: {live} engine(s)")
            if args.synthetic <= 0 and not args.replay:
                # daemon mode: route until SIGTERM
                while not guard.triggered:
                    _time.sleep(0.1)
                return 0
            mixer = PrefixMixer(
                args.src_vocab,
                pool_size=max(1, args.prefix_pool),
                prefix_frac=args.prefix_frac if args.prefix_pool > 0 else 0.0,
                seed=args.seed, sessions=args.sessions,
            )
            t0 = _time.perf_counter()

            done = []

            def on_done(r):
                done.append(r)
                print(_json.dumps({
                    "req": r.req_id,
                    "status": r.status,
                    "tokens": r.tokens,
                    "error": r.error,
                    "latency_ms": round((r.t_done - r.t_submit) * 1e3, 3),
                }), flush=True)

            replay_trace = None
            if args.replay:
                from paddle_tpu.robustness.traces import read_trace

                replay_trace = read_trace(args.replay)
                reqs = [
                    Request(
                        list(rec["src"]), rec.get("mnt"),
                        req_id=str(rec["id"]), callback=on_done,
                        deadline_s=rec.get("dl"),
                        session_id=rec.get("sess"),
                        priority=rec.get("prio"),
                    )
                    for rec in replay_trace.requests()
                ]
            else:
                reqs = [
                    Request(
                        mixer.source(i), args.max_new_tokens,
                        req_id=f"route-{args.seed}-{i}", callback=on_done,
                        deadline_s=args.deadline_s,
                    )
                    for i in range(args.synthetic)
                ]
            priority_of = None
            if args.priority_every > 0 and replay_trace is None:
                priority_of = (
                    lambda i: 0 if i % args.priority_every == 0 else 2
                )
            writer = None
            if args.record_trace:
                from paddle_tpu.robustness.traces import TraceWriter

                writer = TraceWriter(args.record_trace, meta={
                    "cmd": "route", "seed": args.seed, "rate": args.rate,
                    "arrival": args.arrival,
                })
            fc = FleetClient(router.address)

            def _submit(r):
                if writer is not None:
                    writer.record_request(r)
                return fc.submit(r)

            try:
                if replay_trace is not None:
                    from paddle_tpu.robustness.traces import (
                        TraceReplayLoadGen,
                    )

                    it = iter(reqs)
                    TraceReplayLoadGen(
                        replay_trace,
                        request_factory=lambda rec: next(it),
                    ).run(_submit, stop=lambda: guard.triggered)
                elif args.rate > 0:
                    OpenLoopLoadGen(
                        args.rate, len(reqs), lambda i: reqs[i],
                        seed=args.seed, process=args.arrival,
                        session_of=mixer.session_of,
                        priority_of=priority_of,
                    ).run(_submit, stop=lambda: guard.triggered)
                else:
                    for i, r in enumerate(reqs):
                        if guard.triggered:
                            break
                        sid = mixer.session_of(i)
                        if sid is not None:
                            r.session_id = sid
                        if priority_of is not None:
                            pri = priority_of(i)
                            if pri is not None:
                                r.priority = int(pri)
                        _submit(r)
                wait_deadline = _time.perf_counter() + args.timeout_s
                for r in reqs:
                    while not r.done():
                        if guard.triggered or (
                            _time.perf_counter() > wait_deadline
                        ):
                            break
                        r.wait(0.2)
                    if guard.triggered:
                        break
            finally:
                fc.close()
                if writer is not None:
                    writer.close()
    finally:
        fleet = router.fleet_stats()
        router.close()
        if metrics is not None:
            metrics.close()
    wall = _time.perf_counter() - t0
    by_status = status_counts(r for r in reqs if r.done())
    ok = [r for r in reqs if r.status == "served"]
    lats = sorted(
        (r.t_done - r.t_submit) * 1e3
        for r in ok if r.t_done is not None and r.t_submit is not None
    )

    def pct(p):
        v = percentile(lats, p)
        return None if v is None else round(v, 3)

    summary = {
        "served": by_status["served"],
        "shed": by_status["shed"],
        "rejected": by_status["rejected"],
        "timeout": by_status["timeout"],
        "unfinished": len(reqs) - sum(by_status.values()),
        "wall_s": round(wall, 3),
        "sustained_req_per_sec": (
            round(len(ok) / wall, 3) if wall > 0 else None
        ),
        "p50_latency_ms": pct(0.50),
        "p95_latency_ms": pct(0.95),
        "p99_latency_ms": pct(0.99),
        "fleet": fleet,
    }
    class_labels = sorted({r.class_label for r in reqs})
    if len(class_labels) > 1:
        summary["classes"] = {
            c: status_counts(r for r in reqs if r.class_label == c)
            for c in class_labels
        }
    print(_json.dumps(summary), flush=True)
    if args.stats_out:
        _obs.write_stats_json(args.stats_out, summary)
    _obs.tracer.dump()
    return rc if (ok or (args.synthetic <= 0 and not args.replay)) else 1


def cmd_scenario(argv: List[str]) -> int:
    """``paddle-tpu scenario`` — the production-gate scenario harness
    (robustness/scenarios.py): run named mixed-traffic/chaos scenarios
    and print one JSON metrics line each (p50/p95/p99, goodput under the
    SLO, shed/reject/timeout counts, recovery-time-after-fault).  Exit 0
    only when every requested scenario passed its gates."""
    ap = argparse.ArgumentParser(
        prog="paddle-tpu scenario",
        description="mixed-traffic SLO + chaos scenario harness "
        "(robustness/scenarios.py)",
    )
    ap.add_argument("--name", action="append", default=[],
                    help="scenario to run (repeatable); see --list")
    ap.add_argument("--all-fast", action="store_true",
                    help="run every fast (in-process) scenario")
    ap.add_argument("--list", action="store_true", dest="list_",
                    help="list known scenarios and exit")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="end-to-end SLO override (default: the "
                    "scenario_slo_ms flag, else derived from measurement)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=None,
                    help="scratch dir for fleet scenarios (default: a "
                    "temp dir)")
    ap.add_argument("--out", default="",
                    help="append one JSON line per scenario here too")
    ap.add_argument("--trace", action="store_true",
                    help="run with span tracing armed and merge every "
                    "process's trace file into ONE Perfetto-loadable "
                    "timeline per scenario (obs/; subprocess fleets "
                    "inherit the trace dir through the environment)")
    ap.add_argument("--trace-dir", default=None,
                    help="where the per-process + merged trace files land "
                    "(default: a temp dir; implies --trace)")
    args = ap.parse_args(argv)

    from paddle_tpu.robustness import scenarios as _sc

    if args.list_:
        for n in sorted(_sc.FAST_SCENARIOS):
            print(f"{n}  (fast)")
        for n in sorted(_sc.SLOW_SCENARIOS):
            print(f"{n}  (slow: spawns a worker fleet)")
        return 0
    names = list(args.name)
    if args.all_fast:
        names.extend(n for n in _sc.FAST_SCENARIOS if n not in names)
    if not names:
        print("error: give --name (repeatable), --all-fast, or --list",
              file=sys.stderr)
        return 2
    trace_dir = None
    if args.trace or args.trace_dir:
        import tempfile

        from paddle_tpu import obs as _obs

        trace_dir = args.trace_dir or tempfile.mkdtemp(
            prefix="paddle-tpu-trace-"
        )
        os.makedirs(trace_dir, exist_ok=True)
        os.environ.setdefault("PADDLE_TPU_TRACE_ID", _obs.tracer.trace_id)
    failed = []
    for name in names:
        kw = {"seed": args.seed}
        if args.slo_ms is not None:
            kw["slo_ms"] = args.slo_ms
        if name in _sc.SLOW_SCENARIOS:
            import tempfile

            kw["workdir"] = args.workdir or tempfile.mkdtemp(
                prefix=f"paddle-tpu-scenario-{name}-"
            )
        if trace_dir is not None:
            from paddle_tpu import obs as _obs
            from paddle_tpu.utils import flags as _flags

            # one subdirectory PER scenario, and the parent rings reset:
            # otherwise scenario N's merged timeline would accumulate
            # scenarios 1..N-1's events and dead workers' trace files
            sdir = os.path.join(trace_dir, name)
            os.makedirs(sdir, exist_ok=True)
            _flags.set_flag("trace_dir", sdir)
            # subprocess fleets (the elastic workers a scenario spawns)
            # arm through the environment, sharing this trace id
            os.environ["PADDLE_TPU_TRACE_DIR"] = sdir
            _obs.tracer.reset()
            _obs.tracer.configure(role="serve", trace_dir=sdir)
        res = _sc.run_scenario(name, **kw)
        res.pop("_requests", None)
        if trace_dir is not None:
            from paddle_tpu.obs.merge import merge_dir

            _obs.tracer.dump()
            merged, mpath = merge_dir(
                os.path.join(trace_dir, name),
                out_path=os.path.join(trace_dir, f"merged-{name}.json"),
            )
            res["trace"] = {
                "merged": mpath,
                "events": sum(
                    1 for e in merged["traceEvents"] if e.get("ph") != "M"
                ),
                "pids": merged["otherData"]["merged_pids"],
                "planes": sorted({
                    e.get("cat") for e in merged["traceEvents"]
                    if e.get("ph") != "M" and e.get("cat")
                }),
            }
        line = json.dumps(res)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        if not res.get("passed"):
            failed.append(name)
    if failed:
        print(f"SCENARIO FAILURES: {failed}", file=sys.stderr)
    return 1 if failed else 0


def cmd_trace(argv: List[str]) -> int:
    """``paddle-tpu trace`` — the span-timeline tooling (obs/):

    * ``merge --dir D [--out F]`` — zip the per-process
      ``trace-<role>-<pid>.json`` files a launcher/scenario run left
      behind into ONE Chrome-trace timeline (opens directly in Perfetto),
      clock-skew aligned via the RPC plane's request/response pairs
      (wall-anchor fallback for processes that never talked);
    * ``validate F`` — schema-check a trace file (required event keys,
      begin/end pairing, well-formed args); exit 0 iff valid.

    One JSON summary line per run (event counts, pids, planes, applied
    per-process clock offsets)."""
    ap = argparse.ArgumentParser(
        prog="paddle-tpu trace",
        description="merge/validate span-timeline files (paddle_tpu/obs)",
    )
    ap.add_argument("action", choices=["merge", "validate"])
    ap.add_argument("paths", nargs="*",
                    help="validate: trace file(s); merge: explicit trace "
                    "files instead of --dir")
    ap.add_argument("--dir", default=None,
                    help="merge: directory of trace-*.json files")
    ap.add_argument("--out", default=None,
                    help="merge: merged timeline path "
                    "(default <dir>/merged.json)")
    args = ap.parse_args(argv)

    from paddle_tpu.obs import merge as _merge

    if args.action == "validate":
        if not args.paths:
            print("error: validate needs trace file path(s)",
                  file=sys.stderr)
            return 2
        bad = 0
        for p in args.paths:
            problems = _merge.validate_trace(_merge.load_trace(p))
            print(json.dumps({
                "file": p, "valid": not problems,
                "problems": problems[:20],
            }))
            bad += bool(problems)
        return 1 if bad else 0

    if args.paths:
        merged = _merge.merge_traces(
            [_merge.load_trace(p) for p in args.paths]
        )
        out = args.out or "merged.json"
        with open(out, "w") as f:
            json.dump(merged, f)
    elif args.dir:
        merged, out = _merge.merge_dir(args.dir, out_path=args.out)
    else:
        print("error: merge needs --dir or trace file paths",
              file=sys.stderr)
        return 2
    other = merged["otherData"]
    print(json.dumps({
        "merged": out,
        "events": sum(
            1 for e in merged["traceEvents"] if e.get("ph") != "M"
        ),
        "pids": other["merged_pids"],
        "roles": other["roles"],
        "offsets_us": other["offsets_us"],
        "rpc_pair_edges": other["rpc_pair_edges"],
    }))
    return 0


def cmd_worker(argv: List[str]) -> int:
    """``paddle-tpu worker`` — one elastic trainer process (scale-out
    plane, trainer/elastic.py): leases data-shard tasks from the master,
    contributes deterministic per-task gradients, reduces at pass fences,
    writes its sharded-checkpoint shard."""
    from paddle_tpu.trainer import elastic

    return elastic.main(argv)


def cmd_master(argv: List[str]) -> int:
    """``paddle-tpu master`` — one HA master candidate for the elastic
    cluster plane: campaigns for the file lease under --dir, serves the
    task queues when leader (publishing its endpoint for HAClient
    discovery), hot-stands-by otherwise.  Runs until SIGTERM/SIGINT."""
    import signal

    ap = argparse.ArgumentParser(
        prog="paddle-tpu master",
        description="HA master candidate (worker registry + shard leases "
        "+ pass fences; master.py/master_ha.py)",
    )
    ap.add_argument("--dir", required=True,
                    help="shared discovery/lease/snapshot directory")
    ap.add_argument("--patterns", required=True,
                    help="comma-separated recordio globs to partition")
    ap.add_argument("--chunks-per-task", type=int, default=8)
    ap.add_argument("--timeout-s", type=float, default=60.0,
                    help="per-task shard-lease timeout")
    ap.add_argument("--worker-timeout-s", type=float, default=10.0,
                    help="worker registry heartbeat-lease timeout")
    ap.add_argument("--failure-max", type=int, default=3)
    ap.add_argument("--lease-timeout", type=float, default=5.0,
                    help="leader-election lease timeout (master_ha)")
    ap.add_argument("--no-journal", action="store_true",
                    help="legacy debounced-snapshot persistence instead of "
                    "the fsync'd journal (standbys then take over cold)")
    ap.add_argument("--journal-compact-every", type=int, default=512,
                    help="journal records between snapshot compactions")
    ap.add_argument("--no-journal-fsync", action="store_true",
                    help="skip the per-record fsync (drills/benches only: "
                    "a kill -9 may then lose acked records)")
    ap.add_argument("--stats-out", default=None,
                    help="append one JSON line here each time THIS "
                    "candidate assumes leadership (warm/cold, replayed "
                    "records, takeover span) — the failover drill reads it")
    ap.add_argument("--chaos", default=None,
                    help="arm chaos points in THIS candidate, e.g. "
                    "'kill_master@8' or 'net_partition@40' (env "
                    "PADDLE_TPU_CHAOS also works)")
    ap.add_argument("--rpc-max-message-mb", type=int, default=None,
                    help="override the rpc_max_message_mb flag: hard "
                    "bound on one wire frame, enforced on send AND recv "
                    "(master_wire.py)")
    args = ap.parse_args(argv)

    from paddle_tpu import obs as _obs
    from paddle_tpu.master_ha import HAMaster

    if args.rpc_max_message_mb is not None:
        from paddle_tpu.utils import flags as _flags

        _flags.set_flag("rpc_max_message_mb", args.rpc_max_message_mb)

    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(message)s"
    )
    _obs.tracer.configure(role="master")
    if args.chaos:
        from paddle_tpu.robustness import chaos as _chaos

        _chaos.arm(args.chaos)
    ha = HAMaster(
        args.dir,
        [p for p in args.patterns.split(",") if p],
        lease_timeout=args.lease_timeout,
        chunks_per_task=args.chunks_per_task,
        timeout_s=args.timeout_s,
        worker_timeout_s=args.worker_timeout_s,
        failure_max=args.failure_max,
        auto_rotate=False,  # elastic workers fence their pass boundaries
        journal=not args.no_journal,
        journal_fsync=not args.no_journal_fsync,
        journal_compact_every=args.journal_compact_every,
    )
    stop = {"flag": False}

    def _sig(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    ha.start()
    _echo(f"master candidate {ha.owner_id} campaigning in {args.dir}")
    announced = False
    while not stop["flag"]:
        if ha.fatal is not None:
            _echo(f"FATAL {ha.fatal}")
            ha.stop()
            return 1
        # snapshot the server ref: the HA thread nulls it on step-down
        # between the leader check and the address read
        srv = ha.server
        if ha.is_leader.is_set() and srv is not None and not announced:
            host, port = srv.address
            _echo(f"LEADER {host}:{port}")
            if args.stats_out and ha.last_takeover is not None:
                # advisory (obs.write_stats_json warns instead of raising):
                # an unwritable path must not crash the just-elected leader
                # — every candidate shares the flag, so it would crash-loop
                # the cluster
                _obs.write_stats_json(
                    args.stats_out,
                    {"owner": ha.owner_id, **ha.last_takeover},
                    append=True,
                )
            announced = True
        elif not ha.is_leader.is_set():
            announced = False
        time.sleep(0.2)  # lock: allow[C306] CLI supervision loop: wall-clock by design, driven end-to-end by the failover drills
    ha.stop()
    return 0


def _donation_audit_builders():
    """T106 over the shipped step builders: trace make_train_step and
    make_multi_train_step on a probe MLP and audit that every large
    carried buffer (params/opt-state) is donated.  Pure host-side tracing
    — no compile, no FLOPs."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.analysis.trace_lint import donation_audit
    from paddle_tpu.core.batch import SeqTensor
    from paddle_tpu.core.compiler import CompiledNetwork
    from paddle_tpu.core.topology import Topology, reset_auto_names
    from paddle_tpu.trainer.step import make_multi_train_step, make_train_step

    reset_auto_names()
    x = paddle.layer.data("x", paddle.data_type.dense_vector(64))
    h = paddle.layer.fc(x, size=256, act=paddle.activation.Relu())
    pred = paddle.layer.fc(h, size=10, act=paddle.activation.Softmax())
    y = paddle.layer.data("y", paddle.data_type.integer_value(10))
    cost = paddle.layer.classification_cost(input=pred, label=y)
    net = CompiledNetwork(Topology([cost]))
    opt = paddle.optimizer.Adam(learning_rate=1e-2)
    params, state = net.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    batch = {
        "x": SeqTensor(jnp.zeros((8, 64), jnp.float32)),
        "y": SeqTensor(jnp.zeros((8,), jnp.int32)),
    }
    rng = jax.random.PRNGKey(0)
    k = 4
    stacked = jax.tree_util.tree_map(
        lambda v: jnp.stack([v] * k), batch
    )
    diags = []
    diags += donation_audit(
        make_train_step(net, opt, mesh=None),
        params, state, opt_state, batch, rng,
        source="trainer/step.py:make_train_step",
    )
    diags += donation_audit(
        make_multi_train_step(net, opt, k, mesh=None),
        params, state, opt_state, stacked, rng,
        source="trainer/step.py:make_multi_train_step",
    )
    print(
        f"donation audit: 2 step builders traced, {len(diags)} T106 "
        "finding(s)"
    )
    return diags


def cmd_lint(argv: List[str]) -> int:
    """``paddle-tpu lint`` — static analysis (analysis/):

    * no --config: AST self-lint over the paddle_tpu package source
      (+ any --extra files), rules A### — including A206, the wire-codec
      hygiene rule: raw ``pickle.loads`` / bare ``Connection.recv()``
      deserialization outside master_wire.py is forbidden
      (``# wire: allow[A206] <why>`` escapes a genuinely-local read);
    * --config=conf.py: parse the v1 config and graph-lint its topology
      (rules G###) with layer + config provenance;
    * --journal=master_journal-000001.log: verify a master journal file —
      framing/CRC (J001), unknown record types (J002, the version-skew
      hard error), sequence monotonicity (J003), torn tail (J004);
    * --donation: buffer-donation audit (rule T106) over the shipped step
      builders — trace make_train_step / make_multi_train_step / the
      whole-pass epoch program on a probe network and flag any large
      carried buffer that would be copied instead of donated;
    * --concurrency: lock-discipline lint (rules C###) over the package
      source — guarded-field consistency, static lock-order cycles,
      blocking-under-lock, thread-leak and injectable-clock checks
      (the static leg of the concurrency plane; the runtime leg is
      PADDLE_TPU_LOCK_SANITIZER=1 on the chaos drills);
    * --protocol: protocol-conformance lint (rules P###) over the
      distributed planes (master RPC/journal/wire + serving fleet) —
      RPC whitelist vs handler vs wire-universe conformance (P501),
      journal record/replay/compaction coverage (P502), status-ledger
      exhaustiveness (P503), lease/fence monotonicity (P504), timeout
      completeness (P505); ``# proto: allow[P###] <why>`` escapes an
      intentional finding (skips the self-lint);
    * --numerics: precision-flow lint (rules N###) over the compiled
      train-step jaxprs — low-precision accumulation, master-precision
      escapes, unguarded domain hazards, overflowing mask literals,
      sub-f32 psums, convert churn.  Alone it lints the package step
      builders over probe topologies; with --config it lints each
      config's REAL train step; --compute-dtype/--master-dtype pick the
      precision plan (the bf16 flagship leg of ``make lint``), and
      --certify prints the per-layer precision certificate
      (analysis.certify_precision_plan — the ROADMAP item 2 gate; the
      runtime leg is PADDLE_TPU_NUM_SANITIZER=1 on the chaos drills).

    Exit 0 only when no diagnostics fire (``make lint``'s contract)."""
    ap = argparse.ArgumentParser(
        prog="paddle-tpu lint",
        description="config-time graph lint + package self-lint "
        "(the reference config_parser's config_assert plane)",
    )
    ap.add_argument("--config", action="append", default=[],
                    help="v1 config file to graph-lint (repeatable; one "
                    "process lints the whole corpus; skips the self-lint)")
    ap.add_argument("--config_args", default="",
                    help="comma-separated key=value pairs for the config(s)")
    ap.add_argument("--extra", action="append", default=[],
                    help="extra .py files to self-lint (e.g. bench.py)")
    ap.add_argument("--journal", action="append", default=[],
                    help="master journal file to verify (repeatable; "
                    "rules J###; skips the self-lint)")
    ap.add_argument("--donation", action="store_true",
                    help="audit the shipped step builders' buffer donation "
                    "(rule T106; skips the self-lint)")
    ap.add_argument("--concurrency", action="store_true",
                    help="lock-discipline lint (rules C###) over the "
                    "package source (skips the self-lint)")
    ap.add_argument("--numerics", action="store_true",
                    help="precision-flow lint (rules N###) over the "
                    "compiled train-step jaxprs: package probes, or each "
                    "--config's real step (skips the self-lint)")
    ap.add_argument("--protocol", action="store_true",
                    help="protocol-conformance lint (rules P###) over the "
                    "distributed planes: RPC surface vs handlers vs wire "
                    "universe, journal record/replay/compaction coverage, "
                    "status-ledger exhaustiveness, lease/fence "
                    "monotonicity, timeout completeness (skips the "
                    "self-lint)")
    ap.add_argument("--compute-dtype", default=None,
                    help="numerics: compute dtype of the precision plan "
                    "(e.g. bfloat16; default f32)")
    ap.add_argument("--master-dtype", default=None,
                    help="numerics: master/param dtype of the plan "
                    "(default float32)")
    ap.add_argument("--certify", action="store_true",
                    help="numerics + --config: print the per-layer "
                    "precision certificate for the dtype plan")
    ap.add_argument("--min-severity", default=None,
                    choices=["info", "warning", "error"],
                    help="only report findings at or above this severity")
    args = ap.parse_args(argv)

    from paddle_tpu import analysis

    diags = []
    if args.journal:
        from paddle_tpu import master_journal as _mj

        for jpath in args.journal:
            for f in _mj.verify_journal(jpath):
                diags.append(analysis.Diagnostic(
                    rule=f["rule"],
                    severity=analysis.Severity[f["severity"].upper()],
                    message=f["message"],
                    source=jpath,
                ))
    if args.donation:
        diags.extend(_donation_audit_builders())
    if args.concurrency:
        from paddle_tpu.analysis.concurrency_lint import (
            lint_concurrency_package,
        )

        diags.extend(lint_concurrency_package(extra_paths=args.extra))
    if args.protocol:
        from paddle_tpu.analysis.protocol_lint import lint_protocol_package

        diags.extend(lint_protocol_package())
    if args.numerics:
        from paddle_tpu.analysis.numerics_lint import (
            certify_precision_plan,
            lint_numerics_config,
            lint_numerics_package,
        )

        if args.certify and not args.config:
            print("error: --certify needs --config (a certificate is "
                  "per-topology; the package probes have none)",
                  file=sys.stderr)
            return 2
        if args.config:
            from paddle_tpu.v1_compat import parse_config

            for cfg in args.config:
                if len(args.config) > 1 or args.certify:
                    print(f"numerics-lint {cfg} "
                          f"(compute={args.compute_dtype or 'float32'})")
                if args.certify:
                    # ONE trace: the certificate already carries every
                    # (pragma-filtered) N-rule finding for this plan, and
                    # a REJECT must fail the exit-code contract
                    parsed = parse_config(
                        os.path.abspath(cfg), args.config_args
                    )
                    from paddle_tpu.v1_compat import make_optimizer

                    try:
                        opt = make_optimizer(parsed.settings)
                    except Exception:  # exotic settings: the Adam probe
                        opt = None
                    cert = certify_precision_plan(parsed.topology, {
                        "compute_dtype": args.compute_dtype,
                        "master_dtype": args.master_dtype,
                    }, optimizer=opt)
                    print(cert.format())
                    diags.extend(cert.diagnostics)
                else:
                    diags.extend(lint_numerics_config(
                        cfg, args.config_args,
                        compute_dtype=args.compute_dtype,
                        master_dtype=args.master_dtype,
                    ))
        else:
            diags.extend(lint_numerics_package(
                compute_dtype=args.compute_dtype,
                master_dtype=args.master_dtype,
            ))
    if args.config and not args.numerics:
        from paddle_tpu.v1_compat import parse_config

        for cfg in args.config:
            if len(args.config) > 1:
                print(f"graph-lint {cfg}")
            try:
                parsed = parse_config(os.path.abspath(cfg), args.config_args)
            except analysis.DiagnosticError as e:
                # build-time findings (duplicate names, feed-slot errors)
                # report like any other lint result, not as a traceback —
                # re-homed onto this config so the merged report attributes
                # them to the right file
                import dataclasses as _dc

                diags.extend(
                    _dc.replace(d, source=cfg) for d in e.diagnostics
                )
                continue
            diags.extend(analysis.lint_parsed(parsed))
    if not (args.config or args.journal or args.donation
            or args.concurrency or args.numerics or args.protocol):
        diags = analysis.lint_package(extra_paths=args.extra)

    if args.min_severity:
        floor = analysis.Severity[args.min_severity.upper()]
        diags = [d for d in diags if d.severity >= floor]

    print(analysis.format_diagnostics(diags))
    return 1 if diags else 0


def cmd_explore(argv: List[str]) -> int:
    """Deterministic interleaving explorer over the distributed planes.

    Drives the REAL state machines (serving router, journaled master,
    HA lease file) in-process on a virtual clock with a simulated
    transport, searching event interleavings for protocol-invariant
    violations (double-serve, epoch-fence breach, recovery infidelity).

    * default: seeded-random exploration (``--schedules`` independent
      schedules; schedule i draws from ``Random(f"{seed}:{i}")``, so
      any run replays exactly).
    * --dfs-depth N: additionally sweep every interleaving up to depth
      N (bounded DFS, first ``--dfs-branch`` enabled events per state).
    * --plant NAME: plant a known bug (canary) to prove the harness
      detects, shrinks, and replays — e.g. ``double_serve``.
    * --replay SPEC.json: re-run a shrunk violation spec; exit 0 iff
      the violation reproduces (the regression-test contract).

    Exit code: 0 = clean (or replay reproduced), 1 = violation found
    (or replay failed to reproduce).  A found violation is ddmin-shrunk
    to a minimal replayable spec, printed, and written to ``--out``.
    """
    ap = argparse.ArgumentParser(prog="paddle-tpu explore",
                                 description=cmd_explore.__doc__)
    ap.add_argument("--model", default="router",
                    choices=["router", "master", "ha"],
                    help="which state machine to drive (default router)")
    ap.add_argument("--schedules", type=int, default=200,
                    help="number of seeded-random schedules (default 200)")
    ap.add_argument("--seed", type=int, default=0,
                    help="batch seed; schedule i uses Random(f'{seed}:{i}')")
    ap.add_argument("--max-events", type=int, default=14,
                    help="events per random schedule (default 14)")
    ap.add_argument("--dfs-depth", type=int, default=0,
                    help="also run bounded DFS to this depth (0 = skip)")
    ap.add_argument("--dfs-branch", type=int, default=5,
                    help="DFS branch limit per state (default 5)")
    ap.add_argument("--plant", default=None,
                    help="plant a known bug as a harness canary "
                    "(e.g. double_serve)")
    ap.add_argument("--replay", default=None, metavar="SPEC",
                    help="re-run a shrunk violation spec JSON file")
    ap.add_argument("--out", default=None, metavar="SPEC",
                    help="write the shrunk violation spec here")
    args = ap.parse_args(argv)

    import json
    import logging
    import tempfile

    from paddle_tpu.analysis.interleave import (
        dfs_explore, explore_schedules, make_model, replay_spec,
    )

    # fault injection makes the router log every simulated transport
    # failure — noise at batch scale, so keep only real errors
    logging.getLogger("paddle_tpu").setLevel(logging.ERROR)

    if args.replay:
        with open(args.replay, "r", encoding="utf-8") as fh:
            spec = json.load(fh)
        out = replay_spec(spec)
        if out["reproduced"]:
            print(f"reproduced ({out['applied']} events applied):")
            for v in out["violations"]:
                print(f"  {v}")
            return 0
        print(f"spec did NOT reproduce ({out['applied']} events applied, "
              "no violation)", file=sys.stderr)
        return 1

    workdir = tempfile.mkdtemp(prefix="paddle-tpu-explore-")
    model = make_model(args.model, workdir, planted=args.plant)
    try:
        res = explore_schedules(model, schedules=args.schedules,
                                seed=args.seed, max_events=args.max_events)
        if not res["violation_found"] and args.dfs_depth > 0:
            dres = dfs_explore(model, depth=args.dfs_depth,
                               branch_limit=args.dfs_branch)
            print(f"dfs: {dres['paths_run']} paths to depth "
                  f"{args.dfs_depth}")
            if dres["violation_found"]:
                res = {"violation_found": True,
                       "schedules_run": res["schedules_run"],
                       "spec": dres["spec"]}
        if not res["violation_found"]:
            print(f"clean: {res['schedules_run']} schedules on model "
                  f"{args.model!r} (seed {args.seed}), no violation")
            return 0
        spec = res["spec"]
        print(f"VIOLATION on model {args.model!r} after "
              f"{res['schedules_run']} schedules, shrunk to "
              f"{len(spec['events'])} events:")
        for v in spec["violations"]:
            print(f"  {v}")
        print(json.dumps(spec, indent=2, sort_keys=True))
        if args.out:
            with open(args.out, "w", encoding="utf-8") as fh:
                json.dump(spec, fh, indent=2, sort_keys=True)
            print(f"spec written to {args.out} "
                  f"(replay: paddle-tpu explore --replay {args.out})")
        return 1
    finally:
        model.close()


def cmd_fuzz(argv: List[str]) -> int:
    """Coverage-guided chaos-composition fuzzer (robustness/fuzz.py).

    Samples seeded COMPOSITIONS of the existing fault vocabulary —
    arrival process x rate factor, serve-plane chaos (nan_request,
    serve_slow_client), network emulation (delay/drop/dup/corrupt/
    partition), training chaos (worker_hang), torn checkpoints — as
    declarative specs, runs each cocktail against the REAL serving/
    training/checkpoint planes in-process, and checks the invariant
    set (disjoint status ledger, bit-identical training params, journal
    lint, page/thread leaks, armed-chaos consultation, checkpoint
    restore past torn artifacts).

    * default: ``--count`` seeded compositions; composition i draws
      from ``Random(f"{seed}:{i}")``, so any run replays exactly.
    * --plant NAME: plant a known bug (canary) to prove the harness
      detects, shrinks, and replays — e.g. ``ledger_skew``.
    * --replay SPEC.json: re-run a shrunk violation spec; exit 0 iff
      the violation reproduces (the regression-test contract, shared
      with ``paddle-tpu explore``).

    Exit code: 0 = clean (or replay reproduced), 1 = violation found
    (or replay failed to reproduce).  A found violation is ddmin-shrunk
    to a minimal replayable spec, printed, and written to ``--out``.
    """
    ap = argparse.ArgumentParser(prog="paddle-tpu fuzz",
                                 description=cmd_fuzz.__doc__)
    ap.add_argument("--count", type=int, default=25,
                    help="number of seeded compositions (default 25)")
    ap.add_argument("--seed", type=int, default=0,
                    help="batch seed; composition i uses "
                    "Random(f'{seed}:{i}')")
    ap.add_argument("--requests", type=int, default=16,
                    help="serving requests offered per composition")
    ap.add_argument("--plant", default=None,
                    help="plant a known bug as a harness canary "
                    "(e.g. ledger_skew)")
    ap.add_argument("--no-shrink", action="store_true",
                    help="skip ddmin shrinking of a found violation")
    ap.add_argument("--replay", default=None, metavar="SPEC",
                    help="re-run a shrunk violation spec JSON file")
    ap.add_argument("--out", default=None, metavar="SPEC",
                    help="write the shrunk violation spec here")
    args = ap.parse_args(argv)

    import json
    import logging
    import tempfile

    from paddle_tpu.robustness import fuzz as _fz

    # fault cocktails make every plane log its injected failures —
    # noise at batch scale, so keep only real errors
    logging.getLogger("paddle_tpu").setLevel(logging.ERROR)

    workdir = tempfile.mkdtemp(prefix="paddle-tpu-fuzz-")
    if args.replay:
        spec = _fz.load_spec(args.replay)
        out = _fz.replay_fuzz_spec(spec, workdir=workdir)
        if out["reproduced"]:
            print("reproduced:")
            for v in out["violations"]:
                print(f"  {v}")
            return 0
        print("spec did NOT reproduce (clean run, no violation)",
              file=sys.stderr)
        return 1

    res = _fz.fuzz_batch(
        count=args.count, seed=args.seed, workdir=workdir,
        planted=args.plant, shrink=not args.no_shrink,
        n_requests=args.requests, log=lambda m: _echo(f"fuzz: {m}"),
    )
    if not res["violation_found"]:
        print(f"clean: {res['compositions_run']} compositions "
              f"(seed {args.seed}), no violation")
        return 0
    spec = res["spec"]
    print(f"VIOLATION after {res['compositions_run']} compositions, "
          f"shrunk to {len(spec['items'])} item(s):")
    for v in spec["violations"]:
        print(f"  {v}")
    print(json.dumps(spec, indent=2, sort_keys=True))
    if args.out:
        _fz.save_spec(spec, args.out)
        print(f"spec written to {args.out} "
              f"(replay: paddle-tpu fuzz --replay {args.out})")
    return 1


_COMMANDS = {
    "train": cmd_train,
    "version": cmd_version,
    "dump_config": cmd_dump_config,
    "make_diagram": cmd_make_diagram,
    "merge_model": cmd_merge_model,
    "plotcurve": cmd_plotcurve,
    "lint": cmd_lint,
    "explore": cmd_explore,
    "fuzz": cmd_fuzz,
    "serve": cmd_serve,
    "route": cmd_route,
    "scenario": cmd_scenario,
    "trace": cmd_trace,
    "worker": cmd_worker,
    "master": cmd_master,
}


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: paddle-tpu <command> [<flags>]")
        print("commands:")
        print("    train             train/test/time a v1 config (--job=...)")
        print("    version           print version + device info")
        print("    dump_config       print the resolved topology of a config")
        print("    make_diagram      write a Graphviz diagram of a config")
        print("    merge_model       bundle config + parameters into one file")
        print("    plotcurve         plot training curves from a log")
        print("    lint              static analysis: graph-lint a config, or")
        print("                      self-lint the package source")
        print("    explore           interleaving explorer: drive the real")
        print("                      router/master/HA state machines on a")
        print("                      virtual clock, hunt protocol-invariant")
        print("                      violations, shrink + replay specs")
        print("    fuzz              chaos-composition fuzzer: seeded fault")
        print("                      cocktails (arrival x chaos x netem x")
        print("                      torn checkpoints) vs the invariant set;")
        print("                      shrink + replay violation specs")
        print("    serve             continuous-batching serving plane over")
        print("                      the NMT flagship (request queue + paged")
        print("                      decode cache, SLO admission/shedding,")
        print("                      SIGTERM graceful drain); --register")
        print("                      joins a fleet router as one engine")
        print("    route             serving-fleet frontend: SLO admission +")
        print("                      least-predicted-wait affinity routing")
        print("                      over registered engines (lease plane,")
        print("                      idempotent ledger, rolling restart)")
        print("    scenario          production-gate scenario harness: mixed")
        print("                      traffic + chaos under load, SLO metrics")
        print("    trace             merge/validate span-timeline files: zip")
        print("                      per-process traces into one Perfetto")
        print("                      timeline (clock-skew aligned via RPC)")
        print("    master            run an HA master candidate (elastic")
        print("                      scale-out: registry + shard leases)")
        print("    worker            run one elastic trainer process against")
        print("                      a master discovery directory")
        return 0 if argv else 1
    cmd, rest = argv[0], argv[1:]
    if cmd not in _COMMANDS:
        print(f"unknown command {cmd!r}; try 'paddle-tpu --help'", file=sys.stderr)
        return 1
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    return _COMMANDS[cmd](rest)


if __name__ == "__main__":
    sys.exit(main())
