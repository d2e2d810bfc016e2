"""Traffic kind "train_pairs": a corpus of `corpus_batches` batches of
`batch_size` (source, target) sentence pairs.  Every batch holds the same
multiset of lengths, spread evenly over [lo, hi], in an order drawn from the
seed, so every step of every seed does the same amount of work on other rows;
token ids follow `chip_smoke.nmt_corpus`'s truncated geometric law over the
vocabulary.  An item is a true (unpadded) target token."""

import numpy as np


def _lengths(rng, n, lo, hi):
    spread = lo + (np.arange(n) * (hi - lo + 1)) // n
    return rng.permutation(spread)


def _ids(rng, n, vocab, p):
    return 2 + np.minimum(rng.geometric(p, size=n), vocab - 3)


def make_corpus(mix, cfg, seed):
    """-> list of batches; a batch is a list of (src, trg_in, trg_next)
    lists of ints, as a user's reader yields them (bos is id 0)."""
    rng = np.random.default_rng(int(seed))
    bs = mix["batch_size"]
    batches = []
    for _ in range(mix["corpus_batches"]):
        s_len = _lengths(rng, bs, *mix["src_len"])
        t_len = _lengths(rng, bs, *mix["trg_len"])
        s_ids = _ids(rng, int(s_len.sum()), cfg["src_vocab_size"], mix["token_p"])
        t_ids = _ids(rng, int(t_len.sum()), cfg["trg_vocab_size"], mix["token_p"])
        rows = []
        so = to = 0
        for sl, tl in zip(s_len.tolist(), t_len.tolist()):
            src = s_ids[so:so + sl].tolist()
            trg = t_ids[to:to + tl].tolist()
            so += sl
            to += tl
            rows.append((src, [0] + trg[:-1], trg))
        batches.append(rows)
    return batches


def as_arrays(batch):
    """One batch as the plain reference takes it: int32 arrays padded with 0
    to the batch's longest row, and the true lengths."""
    def pad(seqs):
        out = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
        for i, s in enumerate(seqs):
            out[i, :len(s)] = s
        return out

    src, trg_in, trg_next = zip(*batch)
    return {
        "src": pad(src), "src_len": np.array([len(s) for s in src], np.int32),
        "trg_in": pad(trg_in), "trg_next": pad(trg_next),
        "trg_len": np.array([len(t) for t in trg_next], np.int32),
    }


def items(batch):
    """What `train_throughput` counts in this batch: true target tokens."""
    return sum(len(r[2]) for r in batch)


def lengths(batch):
    """The true lengths of a batch's rows, as the FLOP functions take them."""
    return {"src_len": np.array([len(r[0]) for r in batch], np.int64),
            "trg_len": np.array([len(r[2]) for r in batch], np.int64)}
