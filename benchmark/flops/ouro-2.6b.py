"""Operations and bytes of `ouro-2.6b`, from shapes alone.

Two flops for a multiply-add; backward = 2x forward, so a training step is
3x the forward count of R = `total_ut_steps` passes of L layers, R heads and
R exit gates; NOTHING recomputed is counted (the program runs every pass's
forward a second time on the way back: that is time, not model work), no
padded position is counted, and attention counts only the keys a query may
see.  A predicted token is one item however many passes compute it, so the
counts a token are R times a plain decoder's.  `lens` gives the true lengths
of the step's rows."""

import numpy as np

BF16 = 2


def _dims(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return d, f, cfg["num_attention_heads"] * cfg["head_dim"]


def layer_parameters(cfg):
    d, f, hd = _dims(cfg)
    return 4 * d * hd + 3 * d * f


def parameters(cfg):
    """Matrices only, as the issue counts them: the layers, the embedding and
    the head (the norms' gains and the gate are 0.03% more)."""
    return cfg["num_hidden_layers"] * layer_parameters(cfg) + 2 * cfg["vocab_size"] * cfg["hidden_size"]


def _attention_forward(cfg, lens):
    """All rows through ONE application of an attention layer: the four
    projections, QK^T and AV over the causal pairs."""
    d, _, hd = _dims(cfg)
    t = lens["len"].astype(np.float64)
    return float(np.sum(t) * 4 * 2 * d * hd + np.sum(t * (t + 1) / 2) * 2 * 2 * hd)


def _mlp_forward(cfg, tokens):
    d, f, _ = _dims(cfg)
    return tokens * 3 * 2 * d * f


def train_step_flops(cfg, lens):
    tokens = float(np.sum(lens["len"]))
    r, n = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    one_pass = n * (_attention_forward(cfg, lens) + _mlp_forward(cfg, tokens)) + tokens * (2 * d * v + 2 * d)
    return 3.0 * r * one_pass


def kernels(cfg, lens):
    """name -> (flops, least bytes) of one training step's work in that
    kernel.  attention: the R x L applications of multi_head_attention whole,
    forward and backward (projections, rotary, QK^T, softmax, AV, output
    projection: what the layer's scope covers, whatever implements it), as
    `transformer-base`'s entry counts them.  Least bytes: an application's
    input and output [T, d] and its four weights once a pass in bfloat16, and
    the same again with their gradients on the way back."""
    d, _, hd = _dims(cfg)
    apps = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    one_pass = (2 * float(np.sum(lens["len"])) * d + 4 * d * hd) * BF16
    return {"attention": (3.0 * apps * _attention_forward(cfg, lens), 3.0 * apps * one_pass)}
