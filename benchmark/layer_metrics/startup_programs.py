"""Executables built or loaded before the window: the `jit_compile` events inside
the program's own spans (startup_ring.py)."""

import startup_ring


def read(ctx):
    return startup_ring.read("startup_programs")
