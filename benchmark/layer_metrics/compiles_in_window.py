"""Backend compiles (jax.monitoring) between the window's start and its end:
every shape was warmed in set-up, so this should read 0."""


def read(ctx):
    return ctx["counters"].get("compiles_in_window")
