"""The training driver — ``paddle.v2.trainer.SGD`` surface (reference:
python/paddle/v2/trainer.py:24-177) over the jitted step.

Differences from the reference by design: one fused XLA step replaces the
forwardBackward + per-parameter updater loop; data parallelism is the mesh
`data` axis (gradients psum over ICI) instead of MultiGradientMachine threads
or remote parameter servers — `is_local` is accepted for API compatibility
but there is nothing remote to talk to.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import os
import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from paddle_tpu import event as v2_event
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.core.topology import LayerOutput, Topology
from paddle_tpu.optimizer import Optimizer
from paddle_tpu.parameters import Parameters, create_from_network
from paddle_tpu.parallel.mesh import DATA_AXIS, get_default_mesh, shard_batch
from paddle_tpu.reader.feeder import DataFeeder
from paddle_tpu.trainer.evaluators import default_metrics_fn
from paddle_tpu.trainer.step import make_eval_step, make_train_step

_log = logging.getLogger("paddle_tpu.trainer")
from paddle_tpu import obs as _obs
from paddle_tpu.utils.timers import global_stats, stat_timer


def _batch_rows(batch) -> int:
    """Sample count of a staged batch (any slot's leading dim).  Cost and
    metric aggregation weight by this: with the bucketed feed, batch sizes
    vary ~32x across length rungs, and an unweighted mean-over-batches would
    give a long-sequence sample many times the weight of a short one."""
    for t in batch.values():
        data = t.data if hasattr(t, "data") else t
        return int(data.shape[0])
    return 1


# the slow-step record: a step is slow when it took over _SLOW_STEP_RATIO
# times the median of the trainer's last _SLOW_STEP_WINDOW steps AND at
# least _SLOW_STEP_MIN_MS over it (a 3 ms step after 1 ms ones is noise)
_SLOW_STEP_WINDOW = 64
_SLOW_STEP_RATIO = 2.0
_SLOW_STEP_MIN_MS = 50.0


_STEP_CHILDREN = ("feed_wait", "train_step", "block_fetch")


@contextlib.contextmanager
def _step_span(pass_id: int, bid: int, recent: collections.deque):
    """The parent span ``step`` of ONE iteration of the stepwise loop, from
    before the batch is taken to after the iteration's last bookkeeping.

    Yields a dict the loop hangs its child spans on
    (``with _obs.span(...) as phase["feed_wait"]`` / ``"train_step"`` /
    ``"block_fetch"``), at most one of each; what of the iteration they do
    not cover — handlers, judge_step, compile_cache.observe, recovery
    bookkeeping — is its self time, so the four numbers partition it by
    construction.  The loop keeps one step in flight where it may (see
    ``SGD.train``): the iteration that dispatches batch ``bid`` then fetches
    the cost of the step BEFORE it, whose id the loop leaves under
    ``phase["fetched"]``; the first iteration of a pass fetches nothing, and
    the one that finds the pass exhausted dispatches nothing and fetches the
    last step's cost.

    On exit (``continue``/``return`` included) the iteration's length joins
    ``recent`` and, where it stands out of their median, one ``slow_step``
    instant lands in the always-on ring with the four phases and the id of
    the step ``fetch_ms`` waited for, beside a ``slow_steps`` count and a
    log line — the record a ``--trace 0`` job leaves of WHERE a stall sat.
    An iteration that neither dispatched nor fetched (a ``feed_wait`` alone:
    the pass was exhausted with nothing in flight) is skipped.
    Rides the spans' own clock readings; disarmed, nothing is judged."""
    phase: Dict[str, Any] = {}
    with _obs.span("step", cat="trainer", p=pass_id, b=bid) as whole:
        yield phase
    spans = [whole] + [phase.get(k, (0.0, 0.0)) for k in _STEP_CHILDREN]
    if any(t is None or t[1] is None for t in spans):
        return  # recorder off (or switched mid-step)
    if "train_step" not in phase and "block_fetch" not in phase:
        return  # nothing was dispatched or fetched
    ms, wait_ms, dispatch_ms, fetch_ms = ((t[1] - t[0]) * 1e3 for t in spans)
    median = statistics.median(recent) if recent else None
    recent.append(ms)
    if (
        median is None
        or ms <= _SLOW_STEP_RATIO * median
        or ms - median < _SLOW_STEP_MIN_MS
    ):
        return
    fetched = phase.get("fetched")
    parts = dict(
        ms=ms, feed_wait_ms=wait_ms, dispatch_ms=dispatch_ms,
        fetch_ms=fetch_ms, self_ms=ms - wait_ms - dispatch_ms - fetch_ms,
    )
    _obs.instant(
        "slow_step", cat="trainer", p=pass_id, b=bid, fetched=fetched, **parts
    )
    global_stats.incr("slow_steps")
    _log.warning(
        "slow_step pass %d batch %d: %.1f ms against a median of %.1f "
        "(feed_wait %.1f, dispatch %.1f, fetch %.1f of batch %s, self %.1f)",
        pass_id, bid, ms, median, wait_ms, dispatch_ms, fetch_ms, fetched,
        parts["self_ms"],
    )


@dataclasses.dataclass
class _IssuedStep:
    """What a dispatched step leaves for the host to settle once its cost
    is fetched: its place, what of the step's outputs the bookkeeping reads,
    and what falls due at it.  ``rows`` is the count, not the batch, so the
    batch's buffers can go while the step is in flight."""

    pass_id: int
    bid: int
    count: int  # the trainer's step count with this step applied
    rows: int
    is_live: bool  # taken from the reader, not from a rollback's replay
    metrics: Dict[str, Any]
    health: Any
    grad_norm: Any
    saves: bool  # save_dir + saving_period_by_batches fall due at it
    shows_stats: bool  # show_parameter_stats_period falls due at it

    @property
    def reads_params(self) -> bool:
        """Settling it reads the parameters as THIS step left them, which
        the next dispatch donates: it is settled before that dispatch."""
        return self.saves or self.shows_stats


class SGD:
    """paddle.v2.trainer.SGD(cost, parameters, update_equation, ...)"""

    def __init__(
        self,
        cost,
        parameters: Optional[Parameters] = None,
        update_equation: Optional[Optimizer] = None,
        extra_layers: Optional[Sequence[LayerOutput]] = None,
        is_local: bool = True,  # kept for surface compat; always "local"
        mesh=None,
        seed: int = 0,
        evaluators: Optional[Sequence] = None,
    ):
        # obs: the start-up layer's span over everything a trainer builds
        # before its first batch; children compile_network (where this
        # builds one), make_train_step, make_eval_step, optimizer_init
        with _obs.span("trainer_build", cat="setup"):
            self._build(
                cost, parameters, update_equation, extra_layers, mesh, seed,
                evaluators,
            )

    def _build(
        self, cost, parameters, update_equation, extra_layers, mesh, seed,
        evaluators,
    ) -> None:
        self.evaluators = list(evaluators or [])
        self._seed = seed  # also keys the pass-cache replay shuffle
        if isinstance(cost, Topology) and not extra_layers and not self.evaluators:
            # e.g. a v1_compat parse_config result's topology
            self.topology = cost
        else:
            if isinstance(cost, Topology):
                outputs: List[LayerOutput] = list(cost.outputs)
            elif isinstance(cost, LayerOutput):
                outputs = [cost]
            else:
                outputs = list(cost)
            if extra_layers:
                outputs += list(extra_layers)
            for ev in self.evaluators:
                outputs += list(ev.layers)
            self.topology = Topology(outputs)
        if parameters is not None and not hasattr(parameters, "network"):
            # the reference's static Parameters.from_tar(f) returns a
            # topology-free bag (DetachedParameters); build real params
            # for THIS topology and merge the values in by name
            detached = parameters
            parameters = create_from_network(self._compile_network(), seed)
            detached.merge_into(parameters)
        # Structural comparison (serialize covers types/sizes/attrs) — name
        # tuples alone would wrongly reuse a different network whose layers
        # happen to share auto-names.
        if parameters is not None and (
            parameters.network.topology.serialize() == self.topology.serialize()
            # a shared network must not have its mesh clobbered: reuse only
            # when the meshes agree (another trainer may be using it)
            and (mesh is None or parameters.network.mesh in (None, mesh))
        ):
            self.network = parameters.network
            self.parameters = parameters
        else:
            self.network = self._compile_network()
            if parameters is not None:
                # Same cost graph extended with evaluators/extra layers is
                # fine (the extras are param-free); parameters built for a
                # DIFFERENT network are not — catch it here instead of a
                # shape/KeyError mid-step.
                stale = [
                    n for n in parameters.params if n not in self.topology.layers
                ]
                if stale:
                    raise ValueError(
                        f"parameters were created for a different topology: "
                        f"param layers {stale} do not exist in this trainer's "
                        f"network"
                    )
            self.parameters = parameters or create_from_network(self.network, seed)
        assert update_equation is not None, "update_equation (an Optimizer) is required"
        self.optimizer = update_equation
        self.mesh = mesh if mesh is not None else get_default_mesh()
        self._metrics_fn = self._build_metrics_fn()
        from paddle_tpu.parallel.sharding import has_model_sharding, shard_params

        # mesh-aware layers (ring attention) trace against the trainer's
        # mesh, scoped to THIS network — no process-global publishing, so
        # two trainers with different meshes stay isolated.  A meshless
        # trainer reusing a meshed network ADOPTS that mesh rather than
        # clobbering it with None.
        if self.mesh is not None:
            self.network.mesh = self.mesh
        elif self.network.mesh is not None:
            self.mesh = self.network.mesh
        self._model_sharded = has_model_sharding(
            self.network, self.parameters.params, self.mesh
        )
        if self._model_sharded:
            # Row/column-shard the flagged tables over the model axis before
            # optimizer state is created so its slots inherit the placement.
            self.parameters.params = shard_params(
                self.network, self.parameters.params, self.mesh
            )
        # Static pruning hooks: masks from initial magnitudes, applied to
        # the initial values and after every update (StaticPruningHook).
        from paddle_tpu.trainer.step import apply_prune_masks, build_prune_masks

        self._prune_masks = build_prune_masks(self.network, self.parameters.params)
        if self._prune_masks:
            self.parameters.params = apply_prune_masks(
                self.parameters.params, self._prune_masks
            )
        with _obs.span("make_train_step", cat="setup"):
            self._train_step = make_train_step(
                self.network, self.optimizer, self.mesh, self._metrics_fn,
                infer_param_shardings=self._model_sharded,
                prune_masks=self._prune_masks,
            )
        with _obs.span("make_eval_step", cat="setup"):
            self._eval_step = make_eval_step(
                self.network, self.mesh, self._metrics_fn,
                infer_param_shardings=self._model_sharded,
            )
        with _obs.span("optimizer_init", cat="setup"):
            self._opt_state = self.optimizer.init(self.parameters.params)
        self._rng = jax.random.PRNGKey(seed + 1)
        self._step_count = 0
        # lengths (ms) of the last steps: the slow-step record's yardstick
        self._step_ms: collections.deque = collections.deque(
            maxlen=_SLOW_STEP_WINDOW
        )
        self._pass_cache = None  # set per train() call when caching is on
        self._pass_cache_reader = None  # the reader the cache was built for
        # Per-bucket dispatch accounting: every train/eval batch's shape
        # signature is observed here (core.compiler.CompileShapeCache), so
        # the StatSet plane carries compile hit/miss counters and a bounded-
        # shape check is one property read away.  With the bucketing feed on
        # (use_bucketing flag / DataFeeder(ladder=...)) misses stay bounded
        # by the shape-ladder size; an unbucketed variable-length feed shows
        # its per-shape recompiles here instead of as silent latency.
        from paddle_tpu.core.compiler import CompileShapeCache

        self.compile_cache = CompileShapeCache("train_step")
        self._eval_cache = CompileShapeCache("eval_step")
        # dynamic-width (batch-wide trans) weights resolve exactly ONCE, at
        # the first batch this trainer ever sees; a later batch-size change
        # must fail loudly, never silently re-draw trained weights
        self._width_resolved = not self.network.has_dynamic_widths

    # ------------------------------------------------------------------
    def _compile_network(self) -> CompiledNetwork:
        with _obs.span("compile_network", cat="setup"):
            return CompiledNetwork(self.topology)

    def _build_metrics_fn(self):
        default = default_metrics_fn(self.topology)
        if not self.evaluators:
            return default
        from paddle_tpu.evaluator import combined_update

        ev_update = combined_update(self.evaluators)

        def metrics(outs):
            m = default(outs) if default else {}
            m.update(ev_update(outs))
            return m

        return metrics

    def _split_metrics(self, metrics):
        """(plain scalar metrics, evaluator accumulators) from a step result."""
        scalars, accums = {}, {}
        for k, v in metrics.items():
            if k.startswith("ev:"):
                accums[k] = np.asarray(v)
            elif k != "cost":
                scalars[k] = float(v)
        return scalars, accums

    def _finalize(self, accums):
        from paddle_tpu.evaluator import finalize_all

        return finalize_all(self.evaluators, accums) if self.evaluators else {}

    # ------------------------------------------------------------------
    def _make_feeder(self, feeding) -> DataFeeder:
        # data layers declaring a narrow wire dtype (data_layer(feed_dtype=
        # "uint8")) feed raw and cast+normalize on device (_feed_transform)
        from paddle_tpu.reader.feeder import feed_dtypes_of
        from paddle_tpu.utils import flags as _flags

        # bucketing feed: padded lengths come from the canonical shape
        # ladder instead of multiple-of-8 rounding, completing the contract
        # reader.bucketing packs batches for (bounded jit shapes)
        ladder = None
        if _flags.get_flag("use_bucketing"):
            if self.network.has_dynamic_widths:
                # batch-wide-trans weights pin to the FIRST batch's size and
                # any later batch-size change is a hard XLA shape error; the
                # token-budget batcher varies batch size per rung by design,
                # so the combination can only explode mid-epoch — refuse now
                raise ValueError(
                    "use_bucketing is incompatible with dynamic (batch-wide "
                    "trans) width layers: bucketed batch sizes vary per "
                    "length rung, but these weights train at exactly one "
                    "batch size.  Feed this network with paddle.batch "
                    "(fixed size, drop_last=True) instead."
                )
            from paddle_tpu.core.batch import DEFAULT_LADDER

            ladder = DEFAULT_LADDER
        return DataFeeder(
            self.topology.data_types(), feeding,
            feed_dtypes=feed_dtypes_of(self.topology),
            ladder=ladder,
            row_bound_slots=self.network.row_bound_slots(),
            shards=1 if self.mesh is None else self.mesh.shape.get(DATA_AXIS, 1),
        )

    def _run_train_step(self, params, state, opt_state, batch, rng):
        """The loop's one point of dispatch: the jitted step."""
        return self._train_step(params, state, opt_state, batch, rng)

    def train(
        self,
        reader: Callable,
        num_passes: int = 1,
        event_handler: Optional[Callable] = None,
        feeding=None,
        save_dir: Optional[str] = None,
        saving_period: int = 1,
        saving_period_by_batches: Optional[int] = None,
        start_pass: int = 0,
        show_parameter_stats_period: Optional[int] = None,
        async_load_data: bool = True,
        checkpoint_dir: Optional[str] = None,
        checkpoint_period_batches: Optional[int] = None,
        resume: bool = False,
    ) -> None:
        """Pass loop with the reference trainer's checkpoint cadence: every
        `saving_period` passes (and optionally every `saving_period_by_batches`
        batches) write pass-%05d under save_dir; `start_pass` resumes numbering
        (reference: Trainer.cpp:454-488, flags saving_period /
        saving_period_by_batches / start_pass).

        One step in flight, and the order of events.  An iteration takes
        the next batch and dispatches its step BEFORE it waits for the cost
        of the step before, so the device finds step N+1 queued when step N
        ends; the loop never holds more than one unsettled step.  The
        contract: ``BeginIteration(N)`` precedes N's dispatch;
        ``EndIteration(N)`` comes once N's cost is on the host, in order,
        exactly once, possibly after ``BeginIteration(N+1)``; every
        ``EndIteration`` of a pass precedes its ``EndPass``.  Each step
        computes the bits it would compute alone (the same jitted function
        on the same arguments).  Where settling step N reads or publishes
        the parameters as N left them — which N+1's dispatch donates — N
        is settled first: always with a ``checkpoint_dir`` (rollback
        windows, periodic checkpoints, the preemption guard, ``resume``'s
        trajectory) or the numerics sanitizer armed, and at each step at
        which ``save_dir`` + ``saving_period_by_batches`` or
        ``show_parameter_stats_period`` falls due (StatSet
        ``run_ahead_steps`` counts the steps dispatched behind an unsettled
        one, ``run_ahead_drains`` those settled early for what was due).
        ``trainer.parameters`` is published at pass ends, checkpoints and
        saves only: a handler that reads it mid-pass sees the last
        published, since donated, buffers.

        async_load_data (reference TrainData(async_load_data=...) +
        DataProvider.h's double-buffer queue): run the host-side feed —
        converters, sharding, the device_put issue — on a background thread
        so batch N+1's host→device transfer overlaps step N's compute.
        JAX's async dispatch handles the device side; this hides the host
        side.  The reader runs up to 3 batches ahead of the consuming step;
        set False for inline single-thread feeding if the reader mutates
        state the training loop observes (or isn't thread-compatible).

        Device-resident pass cache (the TPU-native CACHE_PASS_IN_MEM,
        reference PyDataProvider2.cpp:69): when the reader was built from
        ``@provider(cache=CacheType.CACHE_PASS_IN_MEM)`` (the factory tags
        it) or the ``cache_pass_in_mem`` flag is on, epoch 1's staged
        batches stay on device (reader/pass_cache.py: HBM-budgeted, wire
        dtype preserved, optional ``data_echo_factor`` echo) and every
        later pass replays them with a seed-reproducible on-device shuffle
        — zero H2D traffic, no per-batch Python feed.  A pass that blows
        the HBM budget falls back to streaming with a warning.

        Fault tolerance (robustness/): with ``checkpoint_dir`` set, the
        trainer writes full-state checkpoints (params + optimizer state +
        RNG + pass/batch position) every ``checkpoint_period_batches``
        batches (None = the flag) and at every pass boundary.  The
        divergence sentinel (``divergence_sentinel`` flag, fused into the
        jitted step) skips non-finite steps on device; when it declares
        divergence (skip streak or EMA loss spike), the trainer rolls back
        to the last-good checkpoint and applies the master's ``failure_max``
        discipline to the offending data window — retry from the retained
        batches, then quarantine and continue.  SIGTERM/SIGINT trigger a
        synchronous final checkpoint + ``PREEMPTED`` marker and return
        (``self.preempted`` is True); ``resume=True`` restores the latest
        good checkpoint (walking past torn ones) and skips the interrupted
        pass's already-consumed batches, so with a deterministic streamed
        reader the resumed trajectory matches an uninterrupted run
        bit-for-bit.  (A ``cache_pass_in_mem`` run resumes from the
        checkpoint but streams its remaining passes — the interrupted
        process's device-resident capture cannot be reconstructed.)

        obs: one span ``train`` (cat ``trainer``, ``passes``) around the
        whole call, over a child ``train_prepare`` (cat ``setup``) from
        entry to the first iteration of the first pass (the feeder, the
        recovery plane, the pass cache, the reader and its prefetch
        thread), then the ``step`` spans of the loop."""
        with _obs.span(
            "train", cat="trainer", passes=num_passes
        ), contextlib.ExitStack() as prepare:
            prepare.enter_context(_obs.span("train_prepare", cat="setup"))
            self._train(
                prepare.close, reader, num_passes, event_handler, feeding,
                save_dir, saving_period, saving_period_by_batches, start_pass,
                show_parameter_stats_period, async_load_data, checkpoint_dir,
                checkpoint_period_batches, resume,
            )

    def _train(
        self, prepared: Callable[[], None], reader, num_passes, event_handler,
        feeding, save_dir, saving_period, saving_period_by_batches,
        start_pass, show_parameter_stats_period, async_load_data,
        checkpoint_dir, checkpoint_period_batches, resume,
    ) -> None:
        """``train``'s body; ``prepared()`` ends the ``train_prepare`` span
        (idempotent: called before each pass's loop, it acts once)."""
        if event_handler is None:
            event_handler = lambda e: None
        import itertools
        from collections import deque
        from contextlib import nullcontext

        from paddle_tpu.reader.prefetch import prefetch
        from paddle_tpu.robustness import chaos as _chaos
        from paddle_tpu.utils import flags as _flags

        if show_parameter_stats_period is None:  # explicit 0 still disables
            show_parameter_stats_period = _flags.get_flag(
                "show_parameter_stats_period"
            )
        log_period = _flags.get_flag("log_period")
        feeder = self._make_feeder(feeding)

        def _stage(data_batch):
            # obs: the STAGE work behind the loop's five spans (step >
            # feed_wait, train_step, block_fetch; see _step_span) — on the
            # prefetch thread when async_load_data is on, so a timeline shows
            # feed overlapping compute (or failing to: feed_wait grows);
            # inside feed_wait on the trainer thread when it is off
            with stat_timer("feed"), _obs.span("feed", cat="trainer"):
                fed = feeder(data_batch)
                if _chaos.fire("nan_batch"):
                    fed = _chaos.poison_batch(fed)
                return shard_batch(fed, self.mesh)

        # -- robustness plane: sentinel + rollback + preemption ----------
        self.preempted = False
        sentinel = None
        if _flags.get_flag("divergence_sentinel"):
            from paddle_tpu.robustness.sentinel import DivergenceSentinel

            sentinel = DivergenceSentinel.from_flags()
        # numerics sanitizer (analysis/num_sanitizer.py): armed via the
        # num_sanitizer flag / PADDLE_TPU_NUM_SANITIZER=1 it keeps a host
        # copy of each step's inputs and, when a step is sentinel-flagged,
        # re-executes it eqn-by-eqn to name the first non-finite-producing
        # op in a flight-recorder postmortem.  Unarmed: num_san stays
        # None and this loop is untouched (zero overhead, zero captures).
        num_san = None
        if _flags.get_flag("num_sanitizer"):
            from paddle_tpu.analysis.num_sanitizer import NumericsSanitizer

            num_san = NumericsSanitizer.for_trainer(self)
        recovery = manager = None
        if checkpoint_dir:
            from paddle_tpu import checkpoint as _ckpt
            from paddle_tpu.robustness.recovery import RecoveryCoordinator

            manager = _ckpt.CheckpointManager(checkpoint_dir)
            recovery = RecoveryCoordinator.from_flags(
                save_fn=lambda step, extra: self.save_checkpoint(
                    manager, step=step, extra=extra
                ),
                restore_fn=lambda: self._restore_latest_full(manager),
            )
            if checkpoint_period_batches is None:
                checkpoint_period_batches = _flags.get_flag(
                    "checkpoint_period_batches"
                )
            if not resume and manager.latest_step() is not None:
                _log.warning(
                    "checkpoint_dir %s already holds checkpoints from a "
                    "previous run but resume=False — a rollback could "
                    "restore stale state; use a fresh directory or resume",
                    checkpoint_dir,
                )
        elif resume:
            raise ValueError("resume=True requires checkpoint_dir")

        resume_extra = None
        skip_batches = 0
        first_pass = start_pass
        if resume:
            resume_extra = recovery.resume()
            if resume_extra is None:
                _log.warning(
                    "resume: no usable checkpoint under %s; starting fresh",
                    checkpoint_dir,
                )
            else:
                from paddle_tpu.robustness.preemption import clear_marker

                clear_marker(checkpoint_dir)
                first_pass = int(resume_extra.get("pass_id", start_pass))
                skip_batches = int(resume_extra.get("batch_id", -1)) + 1
                _log.info(
                    "resumed at step %d: pass %d, skipping %d already-"
                    "consumed batch(es)",
                    self._step_count, first_pass, skip_batches,
                )

        # epoch-aware feed switch: capture pass 1 into the device-resident
        # cache, replay it for every later pass (per-bucket batches keep
        # their own shapes, so this composes with use_bucketing).  A
        # single-pass run can never replay, so it must not pin the pass in
        # HBM — data echo still applies (it needs the batch in hand, not
        # the cache).
        pass_cache = None
        cache_requested = _flags.get_flag("cache_pass_in_mem") or bool(
            getattr(reader, "cache_pass_in_mem", False)
        )
        if cache_requested and resume_extra is not None and not (
            self._pass_cache is not None and self._pass_cache.ready
        ):
            # a resumed process cannot reconstruct the interrupted run's
            # cache: a mid-pass resume would capture only the pass's TAIL,
            # and even a pass-boundary resume would capture the wrong pass
            # (the original captured pass `first_pass` raw order and
            # replays every later pass shuffled) — stream the remaining
            # passes instead.  The trajectory still continues exactly from
            # the checkpoint, but epoch order past it is the streamed
            # reader's, not the cached replay's.
            _log.warning(
                "pass cache disabled on resume: the interrupted run's "
                "capture cannot be reconstructed mid-stream; streaming "
                "the remaining passes",
            )
            cache_requested = False
        echo_factor = (
            max(int(_flags.get_flag("data_echo_factor")), 1)
            if cache_requested
            else 1
        )
        if cache_requested:
            # the cache lives with its data source (reference
            # CACHE_PASS_IN_MEM keeps the pass for the provider's
            # lifetime): a later train() call with the SAME reader object
            # replays immediately — even its first pass pays zero H2D; a
            # different reader frees the stale pass before any re-capture
            prev = self._pass_cache
            if (
                prev is not None
                and prev.ready
                and self._pass_cache_reader is reader
            ):
                pass_cache = prev
            else:
                if prev is not None:
                    prev.drop()
                if num_passes > 1:
                    from paddle_tpu.reader.pass_cache import PassCache

                    pass_cache = PassCache.from_flags(
                        reader, seed=self._seed, echo_factor=echo_factor
                    )
        elif self._pass_cache is not None:
            # caching switched off since the last call: release the HBM
            self._pass_cache.drop()
        self._pass_cache = pass_cache
        self._pass_cache_reader = reader if pass_cache is not None else None

        def judge_step(step: _IssuedStep, cost: float) -> str:
            """Per-step sentinel judging + report bookkeeping.  Reads the
            pass-local accumulators (pass_costs/pass_weights/pass_accums)
            from the enclosing scope; emits EndIteration; returns the
            sentinel verdict."""
            verdict = "ok"
            if sentinel is not None and step.health is not None:
                healthy = float(step.health) >= 0.5
                if healthy and step.grad_norm is not None:
                    global_stats.observe(
                        "robustness.grad_norm", float(step.grad_norm)
                    )
                verdict = sentinel.observe(cost, healthy)
            if log_period and step.count % log_period == 0:
                _log.info(
                    "pass %d batch %d cost %.6f", step.pass_id, step.bid, cost
                )
            evaluator: Dict[str, float] = {}
            if verdict == "ok":
                pass_costs.append(cost)
                pass_weights.append(step.rows)
                evaluator, accums = self._split_metrics(step.metrics)
                for k, v in accums.items():
                    pass_accums[k] = pass_accums.get(k, 0) + v
                evaluator.update(self._finalize(accums))
            event_handler(
                v2_event.EndIteration(step.pass_id, step.bid, cost, evaluator)
            )
            return verdict

        def publish() -> None:
            """``trainer.parameters`` and the optimizer state become the
            loop's working values: at checkpoints, saves and pass ends, and
            only while no dispatch has donated them."""
            self.parameters.params, self.parameters.state = params, state
            self._opt_state = opt_state

        def settle(step: _IssuedStep, phase: Dict[str, Any]) -> bool:
            """Waits for an issued step's cost (the iteration's
            ``block_fetch``) and does everything that follows from it, in
            the order the loop always had: the kill drill, the parameter
            stats, judge_step -> EndIteration, the sentinel's verdict and
            rollback, the periodic checkpoint, the batch-period save, the
            preemption guard.  Whatever of it reads or publishes
            ``params`` runs only while they are still this step's: with a
            checkpoint_dir or the numerics sanitizer nothing is dispatched
            behind a step before it is settled, and a step that
            ``reads_params`` is settled before the next dispatch.  -> True
            when the job was preempted and ``train`` is to return."""
            nonlocal params, state, opt_state, pass_accums
            nonlocal costs_mark, accums_mark, replay
            pass_id, bid = step.pass_id, step.bid
            with _obs.span(
                "block_fetch", cat="trainer", b=bid
            ) as phase["block_fetch"]:
                cost = float(step.metrics["cost"])
            phase["fetched"] = bid
            if _chaos.fire("kill"):  # hard-preemption drill: no flush
                _chaos.kill_self()
            if step.shows_stats:
                # reference TrainerInternal.cpp:83-110 per-param stats log
                from paddle_tpu.utils.debug import (
                    format_parameter_stats,
                    parameter_stats,
                )

                _log.info(
                    "parameter stats @ step %d:\n%s",
                    step.count,
                    format_parameter_stats(parameter_stats(params)),
                )
            verdict = judge_step(step, cost)
            if num_san is not None and (
                verdict in ("skip", "diverged") or not np.isfinite(cost)
            ):
                # name the op that went non-finite, not just the step
                num_san.postmortem(f"{verdict} at pass {pass_id} batch {bid}")
            if not step.is_live and not replay and recovery is not None:
                recovery.replay_done()  # window re-applied cleanly
            if verdict == "diverged":
                if recovery is None:
                    _log.error(
                        "divergence detected at pass %d batch %d but no "
                        "checkpoint_dir is set — cannot roll back",
                        pass_id, bid,
                    )
                    if sentinel is not None:
                        sentinel.reset()
                else:
                    action, window = recovery.on_divergence()
                    if action != "none":
                        # restore_fn updated self.*; resync the loop's
                        # working refs and drop the undone bookkeeping
                        params = self.parameters.params
                        state = self.parameters.state
                        opt_state = self._opt_state
                        del pass_costs[costs_mark:]
                        del pass_weights[costs_mark:]
                        pass_accums = {
                            k: np.copy(v) for k, v in accums_mark.items()
                        }
                        if sentinel is not None:
                            sentinel.reset()
                        if action == "retry":
                            replay = deque(window)
                return False
            if (
                recovery is not None
                and verdict == "ok"
                and checkpoint_period_batches
                and not recovery.replaying
                and (sentinel is None or sentinel.steady)
                and step.count % checkpoint_period_batches == 0
            ):
                publish()
                recovery.checkpoint(
                    step.count,
                    {
                        "step_count": step.count,
                        "pass_id": pass_id,
                        "batch_id": bid,
                    },
                )
                costs_mark = len(pass_costs)
                accums_mark = {k: np.copy(v) for k, v in pass_accums.items()}
            if step.saves:
                publish()
                self.save_pass(save_dir, pass_id, batch_id=bid + 1)
            if guard is not None and guard.triggered:
                # preemption: finish THIS step's bookkeeping, persist a
                # synchronous final checkpoint + marker, hand back
                publish()
                extra = {
                    "step_count": step.count,
                    "pass_id": pass_id,
                    "batch_id": bid,
                    "preempted": True,
                }
                self.save_checkpoint(manager, step=step.count, extra=extra)
                write_marker(checkpoint_dir, {**extra, "signal": guard.signum})
                self.preempted = True
                _log.warning(
                    "preempted at pass %d batch %d (step %d): state "
                    "checkpointed under %s; restart with resume=True",
                    pass_id, bid, step.count, checkpoint_dir,
                )
                return True
            return False

        # the loop may keep one step in flight unless settling a step reads
        # the parameters as that step left them (rollback windows, periodic
        # checkpoints, the preemption guard and resume's bit-for-bit
        # trajectory; the sanitizer's re-execution): the next dispatch
        # donates them
        run_ahead = recovery is None and num_san is None

        params, state = self.parameters.params, self.parameters.state
        opt_state = self._opt_state
        if recovery is not None:
            from paddle_tpu.robustness.preemption import (
                PreemptionGuard,
                write_marker,
            )

            guard = PreemptionGuard()
            if resume_extra is None and self._width_resolved:
                # rollback needs an anchor before the first batch lands —
                # otherwise an early divergence has nothing to restore.
                # (A dynamic-width network's weight shapes pin to the FIRST
                # batch; anchoring pre-resolution would restore placeholder
                # shapes into a loop that believes widths are resolved, so
                # its anchor waits for the first periodic checkpoint.)
                recovery.checkpoint(
                    self._step_count,
                    {
                        "step_count": self._step_count,
                        "pass_id": first_pass,
                        "batch_id": -1,
                    },
                )
        else:
            guard = None
        with (guard if guard is not None else nullcontext()):
          for pass_id in range(first_pass, start_pass + num_passes):
            skip = skip_batches if pass_id == first_pass else 0
            event_handler(v2_event.BeginPass(pass_id))
            if "pass" in opt_state:
                # pass_manual schedule: the optimizer reads the pass index
                # (reference PassManualLRS calcLearningRate(_, pass)); the
                # value is a traced scalar so updating it never recompiles
                import jax.numpy as jnp

                opt_state = {
                    **opt_state, "pass": jnp.asarray(pass_id, jnp.int32)
                }
            pass_costs: List[float] = []
            pass_weights: List[int] = []
            pass_accums: Dict[str, np.ndarray] = {}
            # rollback bookmarks: the pass report must not double-count a
            # retried window (truncate back to the last checkpoint's mark)
            costs_mark = 0
            accums_mark: Dict[str, np.ndarray] = {}
            if pass_cache is not None and pass_cache.ready:
                # cached pass: device-resident replay, seed-reproducible
                # shuffle, zero H2D — the feeder/prefetcher never runs
                batches = pass_cache.epoch(pass_id)
                if skip:
                    batches = itertools.islice(batches, skip, None)
            else:
                raw = iter(reader())
                if skip:
                    # resume mid-pass: drain the already-consumed batches
                    # without staging them (the reader's own RNG stream
                    # advances exactly as the interrupted run's did)
                    for _ in range(skip):
                        next(raw, None)
                batches = (
                    prefetch(raw, _stage)
                    if async_load_data
                    else map(_stage, raw)
                )
                if pass_cache is not None and pass_cache.active:
                    batches = pass_cache.capture(batches)
                elif echo_factor > 1 and pass_id == first_pass:
                    # single-pass (or overflowed) run with data echo: train
                    # each transferred batch echo_factor times, retain none
                    batches = (
                        b for bb in batches for b in (bb,) * echo_factor
                    )
            live = iter(batches)
            replay = deque()
            batch_id = skip - 1
            in_flight: Optional[_IssuedStep] = None  # dispatched, unsettled
            prepared()
            while True:
                bid = replay[0][1] if replay else batch_id + 1
                with _step_span(pass_id, bid, self._step_ms) as phase:
                    # obs: the trainer's wait for its next batch (with
                    # async_load_data off the feed runs here, inside it)
                    with _obs.span(
                        "feed_wait", cat="trainer", b=bid
                    ) as phase["feed_wait"]:
                        if replay:
                            _, _, batch = replay.popleft()
                            is_live = False
                        else:
                            batch = next(live, None)
                            batch_id = bid
                            is_live = True
                    if in_flight is not None and (
                        batch is None or in_flight.reads_params
                    ):
                        # the last step of the pass; or one whose save or
                        # stats read what the next dispatch would donate
                        if batch is not None:
                            global_stats.incr("run_ahead_drains")
                            _obs.instant(
                                "run_ahead_drain", cat="trainer",
                                p=pass_id, b=in_flight.bid,
                            )
                        if settle(in_flight, phase):
                            return
                        in_flight = None
                    if batch is None:
                        break  # the pass is exhausted, and settled
                    if not self._width_resolved:
                        # fc/matrix-projection weights over a whole-minibatch
                        # trans have a batch-dependent height; the FIRST batch
                        # this trainer sees pins it (resolve_dynamic_widths) —
                        # any later batch-size change hits an XLA shape error
                        # rather than silently re-drawing trained weights
                        self._width_resolved = True
                        params, chg = self.network.resolve_dynamic_widths(
                            params, batch
                        )
                        if chg:  # weight shapes moved: optimizer slots follow
                            opt_state = self.optimizer.init(params)
                    event_handler(v2_event.BeginIteration(pass_id, bid))
                    if self.compile_cache.observe(batch) and self._step_count:
                        # a NEW batch shape after warmup = a jit recompile; say
                        # so at debug level (the hit/miss counters aggregate in
                        # the StatSet table either way)
                        _log.debug(
                            "train batch %d brings new shape (distinct shapes "
                            "now %d)", bid, self.compile_cache.n_shapes,
                        )
                    if is_live and recovery is not None:
                        recovery.record(pass_id, bid, batch)
                    # obs: DISPATCH (issue the async jitted step); the BLOCK
                    # (the host sync on a fetched cost scalar) is settle's —
                    # the split that shows whether a slow step is compute or
                    # host-feed
                    with stat_timer("train_step"), _obs.span(
                        "train_step", cat="trainer", p=pass_id, b=bid,
                    ) as phase["train_step"]:
                        self._rng, step_rng = jax.random.split(self._rng)
                        if num_san is not None:
                            # the dispatch donates params/state/opt-state —
                            # copy the step's inputs out first or there is
                            # nothing left to re-execute when it goes bad
                            num_san.capture(
                                params, state, opt_state, batch, step_rng,
                                where=f"pass {pass_id} batch {bid}",
                            )
                        params, state, opt_state, metrics = self._run_train_step(
                            params, state, opt_state, batch, step_rng
                        )
                    self._step_count += 1
                    issued = _IssuedStep(
                        pass_id, bid, self._step_count, _batch_rows(batch),
                        is_live, metrics,
                        health=metrics.pop("health", None),
                        grad_norm=metrics.pop("grad_norm", None),
                        saves=bool(
                            save_dir
                            and saving_period_by_batches
                            and (bid + 1) % saving_period_by_batches == 0
                        ),
                        shows_stats=bool(
                            show_parameter_stats_period
                            and self._step_count % show_parameter_stats_period
                            == 0
                        ),
                    )
                    if in_flight is not None:
                        # dispatched behind its predecessor, which the
                        # device is still running: wait for that one now
                        global_stats.incr("run_ahead_steps")
                        settling, in_flight = in_flight, issued
                    elif run_ahead:
                        settling, in_flight = None, issued
                    else:
                        settling = issued
                    if settling is not None and settle(settling, phase):
                        return
            publish()  # so checkpoints and test() see the pass's values
            pass_metrics = {
                # per-SAMPLE mean: weight each batch by its row count (batch
                # sizes vary across rungs under the bucketed feed)
                "mean_cost": float(np.average(pass_costs, weights=pass_weights))
                if pass_costs else 0.0
            }
            cc = self.compile_cache
            if cc.n_shapes > 1:
                # per-bucket dispatch table (reference prints its StatSet
                # per log period; shape traffic is the TPU-relevant stat)
                _log.info(
                    "pass %d bucket dispatch: %d distinct batch shapes, "
                    "%d compile misses / %d hits",
                    pass_id, cc.n_shapes, cc.misses, cc.hits,
                )
            pass_metrics.update(self._finalize(pass_accums))
            event_handler(v2_event.EndPass(pass_id, pass_metrics))
            if save_dir and (pass_id + 1 - start_pass) % saving_period == 0:
                self.save_pass(save_dir, pass_id)
            if recovery is not None:
                # pass boundary = a natural last-good anchor; position says
                # "start of the next pass" so resume never re-reads this one
                recovery.checkpoint(
                    self._step_count,
                    {
                        "step_count": self._step_count,
                        "pass_id": pass_id + 1,
                        "batch_id": -1,
                    },
                )
        publish()

    # ------------------------------------------------------------------
    def elastic_model(self, decode):
        """Adapt this trainer to the elastic multi-process protocol
        (trainer/elastic.py): per-task jitted gradient contributions,
        fence-synchronized deterministic reduction, the trainer's own
        optimizer applied to the reduced update, and full-state sharded
        checkpoints.  ``decode(record_bytes) -> feed sample``."""
        from paddle_tpu.trainer.elastic import TrainerTaskModel

        return TrainerTaskModel(self, decode)

    # ------------------------------------------------------------------
    def test(
        self, reader: Callable, feeding=None, async_load_data: bool = True
    ) -> v2_event.TestResult:
        from paddle_tpu.reader.prefetch import prefetch

        feeder = self._make_feeder(feeding)
        costs: List[float] = []
        weights: List[int] = []
        sums: Dict[str, float] = {}
        accum_sums: Dict[str, np.ndarray] = {}
        n = 0.0
        stage = lambda b: shard_batch(feeder(b), self.mesh)
        batches = (
            prefetch(reader(), stage) if async_load_data
            else map(stage, reader())
        )
        for batch in batches:
            if not self._width_resolved:
                # never trained yet: the eval batch pins the dynamic widths
                # (a post-training batch-size change raises a shape error in
                # the step instead — see train())
                self._width_resolved = True
                p2, chg = self.network.resolve_dynamic_widths(
                    self.parameters.params, batch
                )
                if chg:
                    self.parameters.params = p2
                    self._opt_state = self.optimizer.init(p2)
            self._eval_cache.observe(batch)
            metrics = self._eval_step(
                self.parameters.params, self.parameters.state, batch
            )
            rows = _batch_rows(batch)
            costs.append(float(metrics["cost"]))
            weights.append(rows)
            scalars, accums = self._split_metrics(metrics)
            for k, v in scalars.items():
                sums[k] = sums.get(k, 0.0) + v * rows
            for k, v in accums.items():
                accum_sums[k] = accum_sums.get(k, 0) + v
            n += rows
        # per-sample means (batch sizes vary under the bucketed feed)
        avg = {k: v / max(n, 1) for k, v in sums.items()}
        avg.update(self._finalize(accum_sums))
        return v2_event.TestResult(
            avg,
            float(np.average(costs, weights=weights)) if costs else 0.0,
        )

    # ------------------------------------------------------------------
    def save_parameter_to_tar(self, f) -> None:
        self.parameters.to_tar(f)

    def save_pass(self, save_dir: str, pass_id: int, batch_id: Optional[int] = None) -> str:
        """Write pass-%05d/ with params.tar *and* one v1-format binary file
        per parameter (reference pass-%05d dirs, paddle/trainer/ParamUtil.cpp;
        batch checkpoints get a -batch-%d suffix like Trainer.cpp:454-465)."""
        from paddle_tpu import checkpoint as ckpt

        name = f"pass-{pass_id:05d}"
        if batch_id is not None:
            name += f"-batch-{batch_id}"
        d = os.path.join(save_dir, name)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "params.tar"), "wb") as f:
            self.parameters.to_tar(f)
        ckpt.save_parameter_dir(self.parameters, d)
        return d

    def load_pass(self, save_dir: str, pass_id: int) -> None:
        """Resume parameter values from a pass dir (reference
        --init_model_path / --start_pass, Trainer.cpp:224-253)."""
        from paddle_tpu import checkpoint as ckpt

        ckpt.load_parameter_dir(
            self.parameters, os.path.join(save_dir, f"pass-{pass_id:05d}")
        )
        # Restored values land with default placement; re-apply the model-axis
        # sharding (no-op when not model-sharded) so the next step doesn't
        # recompile against replicated tables.
        self._reshard_after_restore()

    # -- full-state checkpoints (params + layer state + optimizer state) --
    def _full_state(self):
        return {
            "params": self.parameters.params,
            "state": self.parameters.state,
            "opt_state": self._opt_state,
            "rng": self._rng,
        }

    def save_checkpoint(
        self,
        manager,
        step: Optional[int] = None,
        async_: bool = False,
        extra: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Write params + optimizer state + counters through a
        checkpoint.CheckpointManager (the Go-pserver-style full checkpoint,
        reference go/pserver/service.go:244-303 — sans pserver).  ``extra``
        merges into the meta's extra dict (the recovery plane stores the
        pass/batch position there)."""
        manager.save(
            step if step is not None else self._step_count,
            self._full_state(),
            extra={"step_count": self._step_count, **(extra or {})},
            async_=async_,
        )

    def _apply_restored(self, tree, extra) -> None:
        self.parameters.params = tree["params"]
        self.parameters.state = tree["state"]
        self._opt_state = tree["opt_state"]
        import jax.numpy as jnp

        self._rng = jnp.asarray(tree["rng"])
        self._step_count = int(extra.get("step_count", self._step_count))
        self._reshard_after_restore()

    def restore_checkpoint(self, manager, step: Optional[int] = None) -> bool:
        """Restore the latest (or given) checkpoint; returns False when the
        directory holds none."""
        if step is None:
            restored = manager.restore_latest(self._full_state())
            if restored is None:
                return False
            _, tree, extra = restored
        else:
            tree, extra = manager.restore(step, self._full_state())
        self._apply_restored(tree, extra)
        return True

    def _restore_latest_full(self, manager) -> Optional[Dict[str, Any]]:
        """restore_checkpoint returning the checkpoint's ``extra`` dict (the
        recovery/resume position plane) — None when nothing restorable; a
        torn/corrupt newest checkpoint falls back to the previous retained
        one inside the manager."""
        restored = manager.restore_latest(self._full_state())
        if restored is None:
            return None
        _, tree, extra = restored
        self._apply_restored(tree, extra)
        return dict(extra)

    def _reshard_after_restore(self) -> None:
        """Checkpoints come back as host arrays; re-apply the model-axis
        placement so the inferred-sharding step doesn't recompile with a
        replicated (possibly OOM-sized) table."""
        if not self._model_sharded:
            return
        from paddle_tpu.parallel.sharding import shard_params

        self.parameters.params = shard_params(
            self.network, self.parameters.params, self.mesh
        )
        param_names = set(self.parameters.params)
        self._opt_state = {
            k: shard_params(self.network, v, self.mesh)
            if isinstance(v, dict) and set(v) <= param_names
            else v
            for k, v in self._opt_state.items()
        }
