"""Device milliseconds a traced step of the decoder recurrence itself: the
operations under the `attgru_core` scope (ops/rnn.py, the forward scan and
the hand-written backward with its post-scan weight gradients), apart from
the projections that `attgru_scan_roofline`'s layer scope also holds."""


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: "attgru_core" in tf_op)
    if not seconds or not ctx["traced_steps"]:
        return None
    return 1e3 * seconds / ctx["traced_steps"]
