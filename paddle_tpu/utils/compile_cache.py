"""Where jax's persistent compilation cache lives.

The cache directory is part of the cache key, so a directory that moves
never hits.  Entry points that run on the chip (``paddle_tpu/cli.py``
``main``, ``chip_smoke.py``, ``bench.py``) call
:func:`configure_compile_cache` once, before the first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads it itself and this sets no
  directory in code, so whoever runs the program places the cache;
* unset — the fixed ``<checkout>/.jax_cache`` next to the package
  (git-ignored), the same path on every run of the same checkout.

What the cache answered, and what every jitted program cost before it ran.
jax reports each phase of each program it traces, lowers, compiles or loads
through ``jax.monitoring``; :func:`install_jit_listener` (called once, when
``paddle_tpu`` is imported) is the process's ONE listener to them.  For every
program it leaves

* in the span ring (cat ``jit``, :meth:`obs.Tracer.complete`, so each lies
  in time under whatever span is open: ``trainer_build``, the first
  ``train_step`` of a shape): ``jit_trace`` and ``jit_lower`` (the outermost: what
  a function calls is traced inside its trace, and a lowering traces helpers
  of its own), ``jit_compile``, each with ``fun`` = jax's name of the program; ``jit_compile`` also says ``cache``
  = ``hit`` | ``miss`` | ``off`` and, on a hit, ``load_s``;
* in ``global_stats`` (the StatSet table, the Prometheus export): value
  stats ``jit/trace``, ``jit/lower``, ``jit/compile`` (count, total and
  longest seconds), counters ``jit/cache_hit``, ``jit/cache_miss``;
* on the ``paddle_tpu.compile`` logger, one INFO line for every miss that
  took over a second.

jax emits these events on its compile path only, never on the cached
dispatch: in a steady loop the listener is not called.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any

from paddle_tpu import obs
from paddle_tpu.utils.timers import global_stats

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir


_log = logging.getLogger("paddle_tpu.compile")

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_NESTING = tuple(k for k, v in _PHASES.items() if v != "compile")
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
SLOW_MISS_S = 1.0

# what the calling thread's work in progress has said so far.  A compile:
# jax asks the cache (request), may be answered (hit, then the seconds the
# load took), and only then reports the compile's duration, all on one
# thread.  A trace or a lowering: jax announces the phase's start as a
# scalar, and what a traced function calls is traced inside its trace (a
# step's holds thousands; a lowering traces helpers of its own), so `open`
# counts the traces and lowerings in progress and only the outermost is
# recorded, which is all their union is.
_pending = threading.local()
_installed = False


def _on_start(event: str, _value: float, **_: Any) -> None:
    if event in _NESTING:
        _pending.open = getattr(_pending, "open", 0) + 1


def _on_event(event: str, **_: Any) -> None:
    if event == _CACHE_REQUEST:
        _pending.cache, _pending.load_s = "miss", None
    elif event == _CACHE_HIT:
        _pending.cache = "hit"


def _on_duration(event: str, seconds: float, **kw: Any) -> None:
    if event == _CACHE_LOAD:
        _pending.load_s = seconds
        return
    phase = _PHASES.get(event)
    if phase is None:
        return
    if event in _NESTING:
        _pending.open = still_open = max(getattr(_pending, "open", 1) - 1, 0)
        if still_open:
            return
    fun = str(kw.get("fun_name", "?"))
    global_stats.observe("jit/" + phase, seconds)
    if phase != "compile":
        obs.complete("jit_" + phase, "jit", seconds, fun=fun)
        return
    cache = getattr(_pending, "cache", "off")
    args = {"fun": fun, "cache": cache}
    if cache == "hit":
        args["load_s"] = getattr(_pending, "load_s", None)
    _pending.cache = "off"
    if cache != "off":
        global_stats.incr("jit/cache_" + cache)
    obs.complete("jit_compile", "jit", seconds, **args)
    if cache == "miss" and seconds > SLOW_MISS_S:
        _log.info(
            "compiled %s in %.2f s: the persistent cache had no entry "
            "for it", fun, seconds,
        )


def install_jit_listener() -> bool:
    """Registers the listener with ``jax.monitoring``, once a process
    (a second import of ``paddle_tpu`` must count nothing twice).
    -> whether this call installed it."""
    global _installed
    if _installed:
        return False
    import jax

    _installed = True
    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return True
