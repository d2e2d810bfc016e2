"""Decoder-only language model whose stack is described by a pattern string,
one letter a layer, each layer ONE mixer or ONE feed-forward part alone (the
`nemotron_h` family's topology, arXiv:2504.03624):

    M  Mamba-2 mixer             (layers/ssm.py, chunked selective scan)
    *  causal self-attention     (grouped key/value heads, no position code)
    E  experts chosen top-k      (layers/moe.py `moe_topk`, with a shared
                                  expert, over the experts held here)

    x = embedding(ids);  every layer: x = x + mixer(rms_norm(x))
    logits = rms_norm(x) W_out   (untied, no bias)
    cost of a row = sum over its tokens of -log softmax(logits_t)[next_t]

Built from the layer DSL, so `core/compiler.apply` gives every layer its
`type:name` scope: `l<i>_norm`, `l<i>_mamba` / `l<i>_attn` / `l<i>_moe`,
`l<i>_res`, then `final_norm`, `lm_out`, `lm_cost`.  All projections
without bias.  The data slots are `word` (a row's ids) and `next_word` (the
same row one position on): `reader.decorator.next_token_rows` makes both
from rows of T + 1 ids.
"""

from __future__ import annotations

from typing import Optional, Tuple

import paddle_tpu as paddle
from paddle_tpu.core.topology import LayerOutput

L = paddle.layer
A = paddle.activation


def hybrid_lm_cost(
    pattern: str,
    vocab_size: int,
    hidden: int,
    *,
    mamba_heads: int,
    mamba_head_dim: int,
    mamba_groups: int,
    state_size: int,
    conv_kernel: int = 4,
    chunk_size: int = 128,
    attn_heads: int,
    attn_kv_heads: int,
    attn_head_dim: int,
    num_experts: int,
    experts_per_token: int,
    expert_hidden: int,
    shared_hidden: int,
    experts_held: Optional[Tuple[int, int]] = None,
    routed_scaling: float = 1.0,
    norm_eps: float = 1e-5,
) -> Tuple[LayerOutput, LayerOutput]:
    """Training topology for `pattern` (e.g. "MEMEMEM*E") -> (cost, logits)."""
    unknown = set(pattern) - set("M*E")
    if unknown or not pattern:
        raise ValueError(f"hybrid_lm_cost: pattern {pattern!r} has letters other than M, * and E")
    word = L.data("word", paddle.data_type.integer_value_sequence(vocab_size))
    nxt = L.data("next_word", paddle.data_type.integer_value_sequence(vocab_size))
    x = L.embedding(word, size=hidden, name="embed")
    for i, kind in enumerate(pattern):
        h = L.rms_norm(x, epsilon=norm_eps, name=f"l{i}_norm")
        if kind == "M":
            y = L.mamba2(
                h, n_heads=mamba_heads, head_dim=mamba_head_dim, n_groups=mamba_groups,
                state_size=state_size, conv_kernel=conv_kernel, chunk_size=chunk_size,
                epsilon=norm_eps, name=f"l{i}_mamba")
        elif kind == "*":
            y = L.multi_head_attention(
                h, n_heads=attn_heads, n_kv_heads=attn_kv_heads, head_dim=attn_head_dim,
                causal=True, bias_attr=False, name=f"l{i}_attn")
        else:
            y = L.moe_topk(
                h, expert_hidden=expert_hidden, num_experts=num_experts,
                top_k=experts_per_token, experts_held=experts_held,
                shared_hidden=shared_hidden, score_fn="sigmoid", scaling=routed_scaling,
                act=A.Relu2(), name=f"l{i}_moe")
        x = L.addto([x, y], act=A.Identity(), bias_attr=False, name=f"l{i}_res")
    out = L.rms_norm(x, epsilon=norm_eps, name="final_norm")
    logits = L.fc(out, size=vocab_size, act=A.Softmax(), bias_attr=False, name="lm_out")
    cost = L.classification_cost(input=logits, label=nxt, name="lm_cost")
    return cost, logits
