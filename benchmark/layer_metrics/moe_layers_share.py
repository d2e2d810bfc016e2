"""Device time of the expert layers whole (the operations under a
`moe_topk:<name>` scope: routing, the sort, the grouped products of the held
experts, the shared expert, forward and backward) over the traced window,
on the fullest-loaded device."""

from expert_ops import under_expert_layer


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: under_expert_layer(name, tf_op))
    if not seconds or not ctx["window_s"]:
        return None
    return 100.0 * seconds / ctx["window_s"]
