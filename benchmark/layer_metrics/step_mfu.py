"""Model FLOPs of the true tokens of the traced steps (forward + backward, no
recomputation, from the configuration's shapes) over traced seconds x chips x
the chip's peak in the type the configuration computes in."""


def read(ctx):
    if not ctx["traced_steps"] or not ctx["window_s"]:
        return None
    flops = sum(ctx["flops"].train_step_flops(ctx["cfg"], s["lens"]) for s in ctx["steps"])
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
