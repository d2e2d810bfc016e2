"""The multi_head_attention layers inside the loop, whole (projections,
rotary, QK^T, softmax, AV), as `attention_roofline` reckons it: least time
for the operations and bytes that the passes x layers applications state
(`flops/<config>.py` `kernels()["attention"]`) over the device time of the
operations under `multi_head_attention:*` scopes in this cell.  The backward
runs every pass's forward a second time: that recomputed forward is time
here and not work, so the reading says what recomputation costs the layer
(a forward is a third of the work: a reading of R% without recomputation
would read about 3/4 R% with it)."""

import metrics_loader


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: "multi_head_attention:" in tf_op)
    return metrics_loader.roofline_share(ctx, "attention", seconds)
