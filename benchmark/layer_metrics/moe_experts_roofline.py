"""The held routed experts' grouped products (layers/moe.py, scope
`moe_experts`: gather of the sorted rows, the two products, the weighted
return, forward and backward): least time for the two products over the rows
expected and the held weights once a pass, over the device time of the
operations under that scope."""

import metrics_loader
from expert_ops import in_moe_experts


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: in_moe_experts(name, tf_op))
    return metrics_loader.roofline_share(ctx, "moe_experts", seconds)
