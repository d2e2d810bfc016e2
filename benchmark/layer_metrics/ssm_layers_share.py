"""Device time of the state-space layers whole (the operations under a
`mamba2:<name>` scope: projections, conv, the scan, the gated norm, forward
and backward) over the traced window, on the fullest-loaded device."""


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: "mamba2:" in tf_op)
    if not seconds or not ctx["window_s"]:
        return None
    return 100.0 * seconds / ctx["window_s"]
