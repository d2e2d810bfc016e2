"""Device time of the optimizer's update (the operations under the step's
`optimizer:<method>` scope, trainer/step.py) over the traced window, on the
fullest-loaded device."""


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: "optimizer:" in tf_op)
    if not seconds or not ctx["window_s"]:
        return None
    return 100.0 * seconds / ctx["window_s"]
