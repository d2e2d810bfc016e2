"""Seconds of backend compiles and of loads from the persistent cache before the
window: the union of the `jit_compile` intervals inside the program's own spans
(startup_ring.py)."""

import startup_ring


def read(ctx):
    return startup_ring.read("startup_compile_s")
