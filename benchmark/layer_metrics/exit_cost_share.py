"""Device time of the loss over the passes (the operations under a
`looped_exit_cost:<name>` scope: every pass's head product, which runs under
the head's own `fc:<head>` scope INSIDE it, the log-softmax and gather, the
exit gate, the exit distribution, forward, recomputed forward and backward)
over the traced window, on the fullest-loaded device."""


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: "looped_exit_cost:" in tf_op)
    if not seconds or not ctx["window_s"]:
        return None
    return 100.0 * seconds / ctx["window_s"]
