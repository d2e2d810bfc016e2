"""Device time of the looped stack whole (the operations under a
`layer_loop:<name>` scope: the scan over the passes with every inner layer's
forward, the recomputed forward and the backward) over the traced window, on
the fullest-loaded device."""


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: "layer_loop:" in tf_op)
    if not seconds or not ctx["window_s"]:
        return None
    return 100.0 * seconds / ctx["window_s"]
