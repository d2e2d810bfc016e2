"""The recurrence of the state-space layers alone (ops/ssd.py, forward and
the hand-structured backward, under the scope `ssd_scan`): least time for the
operations and bytes the recurrence states, over the device time of the
operations under that scope, whatever algorithm implements it."""

import metrics_loader


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: "ssd_scan" in tf_op)
    return metrics_loader.roofline_share(ctx, "ssd_scan", seconds)
