"""Which device operations are the held routed experts' grouped products.

The program traces them under the scope `moe_experts` inside its expert
layer's `moe_topk:<name>`.  XLA:TPU rewrites each `ragged_dot` into a kernel
of its own and names it anew (`ragged-dot-*`, with a `ragged-dot-metadata`
beside it), dropping the program's name stack: those are recognised by that
name (a probe on the chip, PERF.md section 7)."""


def grouped_product(name, tf_op):
    return "ragged-dot" in name or "ragged-dot" in tf_op


def under_expert_layer(name, tf_op):
    return "moe_topk:" in tf_op or grouped_product(name, tf_op)


def in_moe_experts(name, tf_op):
    return "moe_experts" in tf_op or grouped_product(name, tf_op)
