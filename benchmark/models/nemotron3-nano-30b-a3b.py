"""The system under test for `nemotron3-nano-30b-a3b`: the program's own
topology, `models/hybrid_lm.hybrid_lm_cost`, at the configuration's widths,
holding the first `n_routed_experts` of the router's experts."""


def build(cfg):
    from paddle_tpu.models.hybrid_lm import hybrid_lm_cost

    cost, _ = hybrid_lm_cost(
        cfg["hybrid_override_pattern"], cfg["vocab_size"], cfg["hidden_size"],
        mamba_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        mamba_groups=cfg["n_groups"], state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        attn_heads=cfg["num_attention_heads"], attn_kv_heads=cfg["num_key_value_heads"],
        attn_head_dim=cfg["head_dim"], num_experts=cfg["n_routed_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"], expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_shared_expert_intermediate_size"],
        experts_held=(0, cfg["n_routed_experts"]),
        routed_scaling=cfg["routed_scaling_factor"], norm_eps=cfg["norm_eps"],
    )
    return cost, {"word": 0, "next_word": 1}
