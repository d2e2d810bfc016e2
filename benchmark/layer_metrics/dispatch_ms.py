"""Host milliseconds to issue one step: the program's obs span `train_step`
(the rng split and the jitted call; on one chip also the batch's
host-to-device copy), as it rides the trace."""


def read(ctx):
    spans = ctx["trace"].host.get("train_step")
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
