"""Device time of the leaf operations whose name stack holds no `type:name`
scope of the program (no layer, optimizer or guard), over the traced window,
on the fullest-loaded device: what the trace cannot lay at any layer's door."""

from trace_reduce import scopes_of


def read(ctx):
    if not ctx["window_s"]:
        return None
    seconds = ctx["trace"].seconds_where(
        ctx["plane"], lambda name, tf_op, category: not scopes_of(tf_op))
    return 100.0 * seconds / ctx["window_s"]
