"""The FLOP and byte functions against hand counts at one small shape, and
against XLA's count where nothing is padded; the table of peaks."""

import json
import os

import numpy as np
import pytest

import metrics_loader
import refsteps

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_nmt_hand_count():
    f = refsteps.load_by_name("flops", "nmt-attgru-512")
    cfg = {"word_dim": 2, "hidden_dim": 3, "trg_vocab_size": 5}
    lens = {"src_len": np.array([4]), "trg_len": np.array([2])}
    w, h, v, s, t = 2, 3, 5, 4, 2
    enc = s * (2 * (2 * w * 3 * h + 2 * h * 3 * h) + 2 * 2 * h * h)
    boot = 2 * 2 * h * h
    scan = t * (2 * w * 3 * h + 2 * h * 3 * h + 2 * s * h + 2 * s * 2 * h + 2 * 2 * h * 3 * h + 2 * h * h)
    out = t * 2 * h * v
    assert f.train_step_flops(cfg, lens) == pytest.approx(3 * (enc + boot + scan + out))
    kf, kb = f.kernels(cfg, lens)["attgru_scan"]
    assert kf == pytest.approx(3 * scan)
    one = (s * 3 * h + t * (w + h) + (w * 3 * h + h * 3 * h + h + 2 * h * 3 * h + h * h)) * 2
    assert kb == pytest.approx(3 * one)


def test_nmt_true_tokens_at_the_cell_size():
    # ISSUE 24: about 2.0 T a step for 512 rows at lengths 8-50
    f = refsteps.load_by_name("flops", "nmt-attgru-512")
    n = 512
    spread = 8 + (np.arange(n) * 43) // n
    got = f.train_step_flops(_cfg("nmt-attgru-512"), {"src_len": spread, "trg_len": spread})
    assert 1.9e12 < got < 2.1e12


def test_transformer_hand_count():
    f = refsteps.load_by_name("flops", "transformer-base")
    cfg = {"d_model": 4, "d_ff": 6, "num_layers": 1, "trg_vocab_size": 7}
    lens = {"src_len": np.array([3]), "trg_len": np.array([2])}
    d, ff, v, s, t = 4, 6, 7, 3, 2
    proj = lambda tq, tk: 2 * (2 * d * d) * tq + 2 * (2 * d * d) * tk
    pairs = lambda n: n * 2 * 2 * d
    att = (proj(s, s) + pairs(s * s)) + (proj(t, t) + pairs(t * (t + 1) / 2)) + (proj(t, s) + pairs(t * s))
    ffn = (s + t) * 2 * 2 * d * ff
    out = t * 2 * d * v
    assert f.train_step_flops(cfg, lens) == pytest.approx(3 * (att + ffn + out))
    assert f.kernels(cfg, lens)["attention"][0] == pytest.approx(3 * att)


def test_transformer_against_xla_where_nothing_is_padded():
    # XLA's cost_analysis of the program's step at B=128, T=128: 6.23 T
    # (ISSUE 24's AOT compile); it counts the causal layers whole and the
    # elementwise work, so ours reads a little under it
    f = refsteps.load_by_name("flops", "transformer-base")
    full = np.full(128, 128)
    got = f.train_step_flops(_cfg("transformer-base"), {"src_len": full, "trg_len": full})
    assert 0.95 * 6.23e12 < got < 6.23e12


def test_peaks_table():
    peaks = metrics_loader.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        metrics_loader.load_peaks("TPU v99")
