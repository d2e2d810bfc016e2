"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
early PaddlePaddle (reference: zhoudaqing/Paddle, v1 gserver engine + v2 API),
re-architected on JAX/XLA: topologies compile to single jitted XLA programs,
distribution is a jax.sharding Mesh with ICI collectives (no parameter
server), sequences are padded lax.scan loops.

User surface mirrors ``paddle.v2``::

    import paddle_tpu as paddle
    paddle.init()
    img = paddle.layer.data("pixel", paddle.data_type.dense_vector(784))
    ...
    trainer = paddle.trainer.SGD(cost, parameters, paddle.optimizer.Momentum(...))
    trainer.train(paddle.batch(paddle.dataset.mnist.train(), 128), ...)
"""

from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.monotonic()  # the span `import` runs from this line to the last

from paddle_tpu import activation  # noqa: F401
from paddle_tpu import attr  # noqa: F401
from paddle_tpu import dataset  # noqa: F401
from paddle_tpu import evaluator  # noqa: F401
from paddle_tpu import event  # noqa: F401
from paddle_tpu import layers as layer  # noqa: F401
from paddle_tpu.layers import networks  # noqa: F401
from paddle_tpu import optimizer  # noqa: F401
from paddle_tpu import parallel  # noqa: F401
from paddle_tpu import parameters  # noqa: F401
from paddle_tpu import pooling  # noqa: F401
from paddle_tpu import reader  # noqa: F401
from paddle_tpu import trainer  # noqa: F401
from paddle_tpu.core import data_types as data_type  # noqa: F401
from paddle_tpu.core import topology  # noqa: F401
from paddle_tpu.core.compiler import CompiledNetwork  # noqa: F401
from paddle_tpu.core.topology import Topology  # noqa: F401
from paddle_tpu import master  # noqa: F401
from paddle_tpu.minibatch import batch  # noqa: F401
from paddle_tpu import inference  # noqa: F401
from paddle_tpu import model  # noqa: F401
from paddle_tpu.inference import Inference, infer  # noqa: F401
from paddle_tpu import v1_compat  # noqa: F401
from paddle_tpu import plot  # noqa: F401
from paddle_tpu import image  # noqa: F401
from paddle_tpu import launcher  # noqa: F401
from paddle_tpu.utils import flags  # noqa: F401
from paddle_tpu.utils import profiler  # noqa: F401

__version__ = "0.1.0"


def init(
    use_tpu=None,
    trainer_count=None,
    seed=None,
    compute_dtype=None,
    **kwargs,
) -> None:
    """paddle.init equivalent (reference: paddle/utils/Util.h initMain via
    swig initPaddle).  JAX needs no global init; `use_tpu`/`trainer_count`
    are accepted for config compatibility — device selection and parallelism
    come from the jax platform and the mesh instead.

    compute_dtype: 'bfloat16' enables mixed precision for networks built
    after this call (master params stay float32; see core.compiler).

    Remaining keyword arguments set flags from the global flags plane
    (utils/flags.py — the gflags surface, e.g. check_nans=True,
    log_period=50); unknown names are accepted-and-ignored like the
    reference's tolerant command-line init.
    """
    from paddle_tpu import obs as _obs

    with _obs.span("init", cat="setup"):
        import random

        import numpy as np

        from paddle_tpu.utils import flags as _flags

        # Only arguments the caller actually passed enter the explicit layer —
        # otherwise init()'s python defaults would mask PADDLE_TPU_* env
        # overrides (the documented defaults < env < explicit precedence).
        explicit = {
            k: v
            for k, v in dict(
                use_tpu=use_tpu, trainer_count=trainer_count, seed=seed
            ).items()
            if v is not None
        }
        if "use_tpu" in explicit:
            explicit["use_tpu"] = bool(explicit["use_tpu"])
        _flags.set_flags(**explicit)
        seed_val = _flags.get_flag("seed")
        random.seed(seed_val)
        np.random.seed(seed_val)
        for k, v in kwargs.items():
            try:
                _flags.set_flag(k, v)
            except KeyError:
                pass  # v1 configs pass gpu-era flags; accept silently
        # compute_dtype comes from THIS call's argument, else the flag plane
        # (env PADDLE_TPU_COMPUTE_DTYPE or an explicit flags.set_flag).  init
        # never WRITES the flag: the argument is per-call configuration, so a
        # later bare init() (or set_default_compute_dtype(None)) is not
        # silently overridden by an earlier call's choice.
        dtype_src = (
            compute_dtype
            if compute_dtype is not None
            else _flags.get_flag("compute_dtype")
        )
        if dtype_src:
            from paddle_tpu.core.compiler import set_default_compute_dtype

            set_default_compute_dtype(dtype_src)
        if _flags.get_flag("check_nans"):
            from paddle_tpu.utils.profiler import enable_nan_checks

            enable_nan_checks(True)


def _finish_import() -> None:
    """The process's one jax.monitoring listener (utils/compile_cache.py),
    then the span ``import``: this file from its first line to here, on the
    tracer's own clock."""
    from paddle_tpu import obs as _obs
    from paddle_tpu.utils.compile_cache import install_jit_listener

    install_jit_listener()
    _obs.complete("import", "setup", _time.monotonic() - _IMPORT_T0)


_finish_import()
