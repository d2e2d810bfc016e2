"""Seconds of the Python that builds the graph, the step functions and the state:
the program's spans `init`, `parameters_create`, `trainer_build` and every
`train_prepare` before the window's `train` (their union), less what of them
jax spent tracing, lowering, compiling or loading a program (startup_ring.py)."""

import startup_ring


def read(ctx):
    return startup_ring.read("startup_build_s")
