"""Device profiling + numeric traps.

* :func:`profile` wraps ``jax.profiler.trace``: every layer already runs
  under ``jax.named_scope("type:name")`` (core/compiler.py), so the
  resulting TensorBoard/Perfetto timeline attributes fused XLA ops back to
  layers — the device-side half of the reference's per-layer
  REGISTER_TIMER_INFO (NeuralNetwork.cpp:247,288).  The step's tail has
  scopes of the same form (``optimizer:<method>``, ``guard:sentinel``,
  trainer/step.py), and ``attgru_core`` marks the decoder recurrence inside
  its layer.  While the profile is active the trainer loop's five host
  spans ride the same timeline: ``step`` (one whole iteration) over
  ``feed_wait``, ``train_step`` (the dispatch) and ``block_fetch`` (the
  way back of a cost: the step's own, or that of the step before where
  the loop keeps one in flight) on the trainer thread, and ``feed`` (the
  staging work) on the prefetch thread.  Host-side timers live in utils/timers.py, eager
  per-layer timing in utils/debug.py.

* :func:`enable_nan_checks` is the FP-trap equivalent (the reference
  installs SIGFPE handlers / CHECKs on nan paths): jax re-runs any
  computation that produced a nan un-jitted and raises with the exact
  primitive — combined with the compiler's layer-context notes the error
  names the offending layer.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax


@contextlib.contextmanager
def profile(logdir: Optional[str] = None) -> Iterator[None]:
    """::

        with paddle.utils.profiler.profile("/tmp/trace"):
            trainer.train(...)

    then `tensorboard --logdir /tmp/trace` (or open the .trace in Perfetto).
    With no argument, the `profile_dir` flag (PADDLE_TPU_PROFILE_DIR) names
    the directory."""
    if logdir is None:
        from paddle_tpu.utils.flags import get_flag

        logdir = get_flag("profile_dir")
        if not logdir:
            raise ValueError(
                "no logdir given and the profile_dir flag is unset"
            )
    # while the device profile is active, every obs host span nests under a
    # jax.profiler.TraceAnnotation of the same name, so the host timeline
    # (obs/tracer.py) and the XLA timeline share a vocabulary.  Injected
    # here so the obs package itself stays jax-free (master.py imports it).
    from paddle_tpu import obs as _obs

    with jax.profiler.trace(logdir):
        _obs.tracer.set_annotation_factory(jax.profiler.TraceAnnotation)
        try:
            yield
        finally:
            _obs.tracer.set_annotation_factory(None)


def start(logdir: str) -> None:
    from paddle_tpu import obs as _obs

    jax.profiler.start_trace(logdir)
    _obs.tracer.set_annotation_factory(jax.profiler.TraceAnnotation)


def stop() -> None:
    from paddle_tpu import obs as _obs

    _obs.tracer.set_annotation_factory(None)
    jax.profiler.stop_trace()


def enable_nan_checks(enable: bool = True) -> None:
    """Trap nans/infs produced by any jitted computation (debug-mode only:
    forces re-execution without jit on failure)."""
    jax.config.update("jax_debug_nans", enable)
