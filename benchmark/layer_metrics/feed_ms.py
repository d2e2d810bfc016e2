"""Host milliseconds of feed work per batch: the program's obs span `feed`
(DataFeeder + shard_batch, on the prefetch thread), as it rides the trace."""


def read(ctx):
    spans = ctx["trace"].host.get("feed")
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(spans)
