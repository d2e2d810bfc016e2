"""All multi_head_attention layers whole (projections, QK^T, softmax, AV),
forward and backward: least time for their operations and bytes over the
device time of the operations under `multi_head_attention:*` scopes,
whatever implements them (dense XLA operations or a custom call)."""

import metrics_loader


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: "multi_head_attention:" in tf_op)
    return metrics_loader.roofline_share(ctx, "attention", seconds)
