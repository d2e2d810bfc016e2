"""The system under test for `nmt-attgru-512`: the program's own topology,
`models/seq2seq.seq2seq_cost`, at the configuration's widths."""


def build(cfg):
    from paddle_tpu.models.seq2seq import seq2seq_cost

    cost, _ = seq2seq_cost(
        cfg["src_vocab_size"], cfg["trg_vocab_size"],
        word_dim=cfg["word_dim"], hidden_dim=cfg["hidden_dim"],
    )
    return cost, {"src_word": 0, "trg_word": 1, "trg_next": 2}
