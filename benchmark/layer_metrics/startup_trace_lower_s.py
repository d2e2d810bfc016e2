"""Seconds jax spent tracing and lowering the program's jitted functions before the
window: the union of the `jit_trace` and `jit_lower` intervals inside the
program's own spans, less any compile inside them.  What a warm boot pays that
no compilation cache answers (startup_ring.py)."""

import startup_ring


def read(ctx):
    return startup_ring.read("startup_trace_lower_s")
