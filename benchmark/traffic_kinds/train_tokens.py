"""Traffic kind "train_tokens": a corpus of `corpus_batches` batches of
`batch_size` token rows for a decoder-only language model.  A row is
`row_len` + 1 ids: its inputs and, one position on, the token that follows
each.  Every batch holds the same multiset of row lengths, spread evenly over
`row_len` = [lo, hi] (every row full where lo = hi), in an order drawn from
the seed; ids follow `train_pairs`' truncated geometric law over the
configuration's vocabulary (a slice of the model's where the configuration
holds one).  An item is a predicted token."""

import numpy as np


def make_corpus(mix, cfg, seed):
    """-> list of batches; a batch is a list of (inputs, next tokens) lists
    of ints, as a user's reader yields them."""
    rng = np.random.default_rng(int(seed))
    bs, (lo, hi) = mix["batch_size"], mix["row_len"]
    batches = []
    for _ in range(mix["corpus_batches"]):
        lens = rng.permutation(lo + (np.arange(bs) * (hi - lo + 1)) // bs)
        ids = 2 + np.minimum(rng.geometric(mix["token_p"], size=int(lens.sum()) + bs),
                             cfg["vocab_size"] - 3)
        rows, at = [], 0
        for n in lens.tolist():
            row = ids[at:at + n + 1].tolist()
            at += n + 1
            rows.append((row[:-1], row[1:]))
        batches.append(rows)
    return batches


def as_arrays(batch):
    """One batch as the plain reference takes it: int32 arrays padded with 0
    to the batch's longest row, and the true lengths."""
    longest = max(len(r[0]) for r in batch)
    out = {k: np.zeros((len(batch), longest), np.int32) for k in ("word", "next_word")}
    for i, (inp, nxt) in enumerate(batch):
        out["word"][i, :len(inp)] = inp
        out["next_word"][i, :len(nxt)] = nxt
    out["len"] = np.array([len(r[0]) for r in batch], np.int32)
    return out


def items(batch):
    """What `train_throughput` counts in this batch: predicted tokens."""
    return sum(len(r[1]) for r in batch)


def lengths(batch):
    """The true lengths of a batch's rows, as the FLOP functions take them."""
    return {"len": np.array([len(r[0]) for r in batch], np.int64)}
