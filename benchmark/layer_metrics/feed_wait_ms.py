"""Host milliseconds the trainer waits for its next batch, per step: the
program's obs span `feed_wait` (around `next(live)` in trainer.SGD.train, on
the trainer thread), as it rides the trace.  `feed_ms` is the work on the
prefetch thread; this is what of it the step does not hide."""


def read(ctx):
    spans = ctx["trace"].host.get("feed_wait")
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
