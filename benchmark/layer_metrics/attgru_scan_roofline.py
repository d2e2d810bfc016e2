"""The decoder recurrence (ops/rnn.py `_attgru_core`, forward and backward,
with the projections around it): least time for its operations and bytes
over the device time of the operations whose innermost layer scope is the
decoder's recurrent_group."""

import metrics_loader
from trace_reduce import scopes_of


def read(ctx):
    def mine(name, tf_op, category):
        sc = scopes_of(tf_op)
        return bool(sc) and sc[-1].startswith("recurrent_group:")

    seconds = ctx["trace"].seconds_where(ctx["plane"], mine)
    return metrics_loader.roofline_share(ctx, "attgru_scan", seconds)
