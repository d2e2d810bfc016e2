"""Operations and bytes of `transformer-base`, from shapes alone.

Two flops for a multiply-add; backward = 2x forward, so a training step is
3x the forward count; nothing recomputed is counted, no padded position is
counted, and a causal layer counts only the keys a query may see.  `lens`
gives the true lengths of the step's rows."""

import numpy as np

BF16 = 2


def _mha_forward(d, tq, tk, pairs):
    """Projections (q, o over the queries; k, v over the keys) and the two
    products over the (query, key) pairs that are computed."""
    return (np.sum(tq) * 2 * 2 * d * d + np.sum(tk) * 2 * 2 * d * d
            + np.sum(pairs) * 2 * 2 * d)


def _attention_layers(cfg, lens):
    """[(tq, tk, pairs)] per kind of layer, each per row."""
    s, t = lens["src_len"].astype(np.float64), lens["trg_len"].astype(np.float64)
    return {"enc_self": (s, s, s * s), "dec_self": (t, t, t * (t + 1) / 2),
            "dec_cross": (t, s, t * s)}


def attention_forward_flops(cfg, lens):
    d, n = cfg["d_model"], cfg["num_layers"]
    return float(n * sum(_mha_forward(d, *x) for x in _attention_layers(cfg, lens).values()))


def train_step_flops(cfg, lens):
    d, f, n, v = cfg["d_model"], cfg["d_ff"], cfg["num_layers"], cfg["trg_vocab_size"]
    s, t = float(np.sum(lens["src_len"])), float(np.sum(lens["trg_len"]))
    ffn = n * (s + t) * 2 * 2 * d * f
    out = t * 2 * d * v
    return 3.0 * (attention_forward_flops(cfg, lens) + ffn + out)


def kernels(cfg, lens):
    """name -> (flops, least bytes) of one training step's work in that
    kernel.  attention: all 18 multi_head_attention layers whole, forward
    and backward (projections, QK^T, softmax, AV, output projection: what
    the layer's scope covers, whatever implements it).  Least bytes: the
    layer's input and output [T, d] and its four [d, d] weights once a pass
    in bfloat16, and the same again with their gradients on the way back."""
    d, n = cfg["d_model"], cfg["num_layers"]
    one_pass = 0.0
    for tq, tk, _ in _attention_layers(cfg, lens).values():
        one_pass += n * ((2 * np.sum(tq) + np.sum(tk)) * d + 4 * d * d) * BF16
    return {"attention": (3.0 * attention_forward_flops(cfg, lens), 3.0 * float(one_pass))}
