"""The system under test for `transformer-base`: the program's own topology,
`models/transformer.transformer_cost`, at the configuration's widths."""


def build(cfg):
    from paddle_tpu.models.transformer import transformer_cost

    cost, _ = transformer_cost(
        cfg["src_vocab_size"], cfg["trg_vocab_size"], cfg["d_model"],
        cfg["num_heads"], cfg["num_layers"], cfg["d_ff"],
    )
    return cost, {"src_word": 0, "trg_word": 1, "trg_next": 2}
