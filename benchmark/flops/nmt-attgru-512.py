"""Operations and bytes of `nmt-attgru-512`, from shapes alone.

Two flops for a multiply-add; backward = 2x forward, so a training step is
3x the forward count; nothing recomputed is counted, no padded position is
counted.  `lens` gives the true lengths of the step's rows."""

import numpy as np

BF16 = 2  # bytes of the type the configuration computes in


def _sizes(cfg):
    return cfg["word_dim"], cfg["hidden_dim"], cfg["trg_vocab_size"]


def _decoder_scan_forward(cfg, lens):
    """Everything under the decoder's recurrent_group scope but the output
    matrix, per true target token of a row with S true source positions: the
    target-side gate projection ([W] x [W,3H]), state projection and u/r
    gates (h[H] x [H,3H]), scores (S x H), context (S x 2H), context
    projection ([2H] x [2H,3H]), candidate (h[H] x [H,H])."""
    w, h, _ = _sizes(cfg)
    s, t = lens["src_len"].astype(np.float64), lens["trg_len"].astype(np.float64)
    per_token = 2 * w * 3 * h + 2 * h * 3 * h + 2 * s * h + 2 * s * 2 * h + 2 * 2 * h * 3 * h + 2 * h * h
    return float(np.sum(t * per_token))


def train_step_flops(cfg, lens):
    w, h, v = _sizes(cfg)
    s, t = lens["src_len"].astype(np.float64), lens["trg_len"].astype(np.float64)
    rows = len(s)
    enc_token = 2 * (2 * w * 3 * h + 2 * h * 3 * h) + 2 * 2 * h * h  # 2 GRUs + enc_proj
    boot = rows * 2 * 2 * h * h
    dec_token = 2 * h * v  # the output matrix
    forward = (float(np.sum(s)) * enc_token + boot + float(np.sum(t)) * dec_token
               + _decoder_scan_forward(cfg, lens))
    return 3.0 * forward


def kernels(cfg, lens):
    """name -> (flops, least bytes) of one training step's work in that
    kernel.  attgru_scan: forward and backward of the decoder recurrence.
    Least bytes: each operand once a pass (enc [S,2H] and its projection
    [S,H] per row, the target embedding [W] in and the state [H] out per token,
    the five weights), in bfloat16, and the same again with their gradients
    on the way back: 2 passes forward-sized, 1 more for the gradients."""
    _, h, _ = _sizes(cfg)
    s, t = lens["src_len"].astype(np.float64), lens["trg_len"].astype(np.float64)
    one_pass = (float(np.sum(s)) * 3 * h + float(np.sum(t)) * (cfg["word_dim"] + h)
                + (cfg["word_dim"] * 3 * h + h * 3 * h + h + 2 * h * 3 * h + h * h)) * BF16
    return {"attgru_scan": (3.0 * _decoder_scan_forward(cfg, lens), 3.0 * one_pass)}
