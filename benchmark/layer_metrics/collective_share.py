"""Device time of the collective operations (all-reduce and kin) over the
traced window, on the fullest-loaded device."""

from trace_reduce import COLLECTIVE


def read(ctx):
    seconds = ctx["trace"].seconds_where(
        ctx["plane"],
        lambda name, tf_op, category: bool(COLLECTIVE.search(category) or COLLECTIVE.search(name.split("=", 1)[0])))
    if not seconds or not ctx["window_s"]:
        return None
    return 100.0 * seconds / ctx["window_s"]
