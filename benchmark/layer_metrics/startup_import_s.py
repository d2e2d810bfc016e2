"""Seconds of `import paddle_tpu`: the program's span `import` (cat `setup`), the
whole of `paddle_tpu/__init__.py` on the tracer's own clock (startup_ring.py).
jax is imported by run.py before it, so this is the package's own modules."""

import startup_ring


def read(ctx):
    return startup_ring.read("startup_import_s")
