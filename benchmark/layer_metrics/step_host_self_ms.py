"""Host milliseconds of a step that none of its three child spans covers
(event handlers, judge_step, compile_cache.observe, recovery bookkeeping):
the program's obs span `step` minus the parts of it under `feed_wait`,
`train_step` and `block_fetch`, mean over the traced steps.  The iteration
that finds the pass exhausted is a `step` with no `train_step` in it and is
left out."""

CHILDREN = ("feed_wait", "train_step", "block_fetch")


def read(ctx):
    host = ctx["trace"].host
    kids = [iv for name in CHILDREN for iv in host.get(name, ())]
    dispatches = host.get("train_step", ())
    selfs = []
    for s, e in host.get("step", ()):
        if not any(s <= a and b <= e for a, b in dispatches):
            continue
        covered = sum(max(0.0, min(e, b) - max(s, a)) for a, b in kids)
        selfs.append((e - s) - covered)
    if not selfs:
        return None
    return 1e3 * sum(selfs) / len(selfs)
