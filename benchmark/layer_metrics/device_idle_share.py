"""1 - union of the device's operation intervals over the traced window, on
the fullest-loaded device."""


def read(ctx):
    if not ctx["window_s"]:
        return None
    busy = ctx["trace"].busy_seconds(ctx["plane"])
    return 100.0 * (1.0 - busy / ctx["window_s"])
