"""The system under test for `ouro-2.6b`: the program's own topology,
`models/looped_lm.looped_lm_cost`, at the configuration's widths."""


def build(cfg):
    from paddle_tpu.models.looped_lm import looped_lm_cost

    cost, _ = looped_lm_cost(
        cfg["vocab_size"], cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_passes=cfg["total_ut_steps"], n_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], intermediate=cfg["intermediate_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"], exit_beta=cfg["exit_beta"],
    )
    return cost, {"word": 0, "next_word": 1}
