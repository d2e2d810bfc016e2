"""Decoder-only language model whose stack of layers runs `n_passes` times
over its own output with one set of weights (the `ouro` family's topology,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741):

    x^0 = embedding(ids)
    one pass, the same weights in every pass, for each layer l:
        a = x + norm2_l(attention_l(norm1_l(x)))        rotary, causal, no bias
        x = a + norm4_l(down_l(silu(gate_l(h)) * up_l(h))),  h = norm3_l(a)
    x^t = final_norm(x): what pass t + 1 starts from, and what the head reads
    cost = the expected cross entropy over the passes under the exit gate's
    distribution, less beta times its entropy (`layers.looped_exit_cost`)

Built from the layer DSL: `embed`, then `layer_loop:ut` over `l<i>_norm1`,
`l<i>_attn`, `l<i>_norm2`, `l<i>_res1`, `l<i>_norm3`, `l<i>_gate`,
`l<i>_up`, `l<i>_glu`, `l<i>_down`, `l<i>_norm4`, `l<i>_res2` and
`final_norm`, whose parameters nest under `ut/`; then `lm_out`, `exit_gate`
and `lm_cost`, which runs the head and the gate on every pass's output.
The data slots are `word` and `next_word`, as `hybrid_lm`'s.
"""

from __future__ import annotations

from typing import Tuple

import paddle_tpu as paddle
from paddle_tpu.core.topology import LayerOutput

L = paddle.layer
A = paddle.activation


def looped_lm_cost(
    vocab_size: int,
    hidden: int,
    n_layers: int,
    n_passes: int,
    n_heads: int,
    head_dim: int,
    intermediate: int,
    rope_theta: float = 1e6,
    norm_eps: float = 1e-6,
    exit_beta: float = 0.0,
) -> Tuple[LayerOutput, LayerOutput]:
    """Training topology -> (cost, the last pass's logits)."""
    word = L.data("word", paddle.data_type.integer_value_sequence(vocab_size))
    nxt = L.data("next_word", paddle.data_type.integer_value_sequence(vocab_size))

    def fc(x, size, name, act=None):
        return L.fc(x, size=size, act=act or A.Identity(), bias_attr=False, name=name)

    def one_pass(x):
        for i in range(n_layers):
            h = L.rms_norm(x, epsilon=norm_eps, name=f"l{i}_norm1")
            h = L.multi_head_attention(
                h, n_heads=n_heads, head_dim=head_dim, causal=True, bias_attr=False,
                rope_theta=rope_theta, name=f"l{i}_attn")
            h = L.rms_norm(h, epsilon=norm_eps, name=f"l{i}_norm2")
            x = L.addto([x, h], act=A.Identity(), bias_attr=False, name=f"l{i}_res1")
            h = L.rms_norm(x, epsilon=norm_eps, name=f"l{i}_norm3")
            h = L.dotmul_operator(
                fc(h, intermediate, f"l{i}_gate", A.Silu()), fc(h, intermediate, f"l{i}_up"),
                name=f"l{i}_glu")
            h = L.rms_norm(fc(h, hidden, f"l{i}_down"), epsilon=norm_eps, name=f"l{i}_norm4")
            x = L.addto([x, h], act=A.Identity(), bias_attr=False, name=f"l{i}_res2")
        return L.rms_norm(x, epsilon=norm_eps, name="final_norm")

    x = L.layer_loop(one_pass, L.embedding(word, size=hidden, name="embed"), n_passes, name="ut")
    named = paddle.attr.ParamAttr
    logits = L.fc(x, size=vocab_size, act=A.Softmax(), bias_attr=False,
                  param_attr=named(name="lm_out.w0"), name="lm_out")
    gate = L.fc(x, size=1, act=A.Sigmoid(), param_attr=named(name="exit_gate.w0"),
                bias_attr=named(name="exit_gate.b"), name="exit_gate")
    cost = L.looped_exit_cost(x, head=logits, gate=gate, label=nxt, beta=exit_beta, name="lm_cost")
    return cost, logits
