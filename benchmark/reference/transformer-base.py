"""Plain reference of the pre-LN Transformer encoder-decoder (Vaswani et al.
2017, table 3 "base" widths): jax.numpy, float32, dense attention, no kernel.

Nothing here comes from the program: the weights are the benchmark's own
(`benchmark/weights.py`), under this file's argument names; the map from the
program's parameter names to these lives in the configuration's file.

    x = E[ids] * sqrt(d) + PE      (sinusoidal: even channels sin, odd cos)
    encoder layer:  x += MHA(LN(x));            x += FFN(LN(x))
    decoder layer:  y += MHA_causal(LN(y));     y += MHA(LN(y), enc);  y += FFN(LN(y))
    enc = LN(x);  logits = LN(y) W_out + b_out
    MHA(q, kv) = softmax(q W_q (kv W_k)^T / sqrt(d_head), keys past a row's
        length masked out) (kv W_v) W_o + b_o
    FFN(x) = relu(x W_1 + b_1) W_2 + b_2;  LN eps 1e-6
    cost of a row = sum over its true target tokens of -log softmax(logits)[y_t]

Departures from the paper, which are the program's (`models/transformer.py`):
pre-LN with a final LN on each stack, no dropout, no label smoothing, no bias
on the q/k/v projections, unshared embeddings and output matrix.
"""

import math

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    """Argument name -> (shape, init): `normal` is N(0, 1/sqrt(rows))."""
    d, f, n = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    shapes = {"src_emb": ((cfg["src_vocab_size"], d), "normal"),
              "trg_emb": ((cfg["trg_vocab_size"], d), "normal"),
              "out.w": ((d, cfg["trg_vocab_size"]), "normal"),
              "out.b": ((cfg["trg_vocab_size"],), "zeros")}

    def ln(name):
        shapes[name + ".gamma"] = ((d,), "ones")
        shapes[name + ".beta"] = ((d,), "zeros")

    def mha(name):
        for m in ("wq", "wk", "wv", "wo"):
            shapes[f"{name}.{m}"] = ((d, d), "normal")
        shapes[name + ".b"] = ((d,), "zeros")

    def ffn(name):
        shapes[name + ".w1"] = ((d, f), "normal")
        shapes[name + ".b1"] = ((f,), "zeros")
        shapes[name + ".w2"] = ((f, d), "normal")
        shapes[name + ".b2"] = ((d,), "zeros")

    for i in range(n):
        ln(f"enc{i}.ln1"), mha(f"enc{i}.att"), ln(f"enc{i}.ln2"), ffn(f"enc{i}.ffn")
        ln(f"dec{i}.ln1"), mha(f"dec{i}.self"), ln(f"dec{i}.ln2")
        mha(f"dec{i}.cross"), ln(f"dec{i}.ln3"), ffn(f"dec{i}.ffn")
    ln("enc_ln"), ln("dec_ln")
    return shapes


def _ln(w, name, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * w[name + ".gamma"] + w[name + ".beta"]


def _mha(mm, w, name, q_in, kv_in, kv_len, heads, causal):
    b, tq, d = q_in.shape
    tk = kv_in.shape[1]
    dh = d // heads
    q = mm(q_in, w[name + ".wq"]).reshape(b, tq, heads, dh)
    k = mm(kv_in, w[name + ".wk"]).reshape(b, tk, heads, dh)
    v = mm(kv_in, w[name + ".wv"]).reshape(b, tk, heads, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(dh)
    ok = (jnp.arange(tk)[None, :] < kv_len[:, None])[:, None, None, :]
    if causal:
        ok = ok & (jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None])
    a = jax.nn.softmax(jnp.where(ok, s, -1e9), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision="highest").reshape(b, tq, d)
    return mm(o, w[name + ".wo"]) + w[name + ".b"]


def _ffn(mm, w, name, x):
    return mm(jax.nn.relu(mm(x, w[name + ".w1"]) + w[name + ".b1"]),
              w[name + ".w2"]) + w[name + ".b2"]


def _embed(table, ids):
    t, d = ids.shape[1], table.shape[1]
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((t, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div)).at[:, 1::2].set(jnp.cos(pos * div[: d // 2]))
    return jnp.take(table, ids, axis=0) * math.sqrt(d) + pe


def make_block_cost(cfg):
    heads, n = cfg["num_heads"], cfg["num_layers"]

    def block_cost(w, batch, mm):
        """Sum over the block's rows of each row's token-summed cross entropy.
        batch: src, trg_in, trg_next [B, T] int32; src_len, trg_len [B]."""
        src_len, trg_len = batch["src_len"], batch["trg_len"]
        x = _embed(w["src_emb"], batch["src"])
        for i in range(n):
            h = _ln(w, f"enc{i}.ln1", x)
            x = x + _mha(mm, w, f"enc{i}.att", h, h, src_len, heads, False)
            x = x + _ffn(mm, w, f"enc{i}.ffn", _ln(w, f"enc{i}.ln2", x))
        enc = _ln(w, "enc_ln", x)
        y = _embed(w["trg_emb"], batch["trg_in"])
        for i in range(n):
            h = _ln(w, f"dec{i}.ln1", y)
            y = y + _mha(mm, w, f"dec{i}.self", h, h, trg_len, heads, True)
            h = _ln(w, f"dec{i}.ln2", y)
            y = y + _mha(mm, w, f"dec{i}.cross", h, enc, src_len, heads, False)
            y = y + _ffn(mm, w, f"dec{i}.ffn", _ln(w, f"dec{i}.ln3", y))
        logits = mm(_ln(w, "dec_ln", y), w["out.w"]) + w["out.b"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, batch["trg_next"][..., None], axis=-1)[..., 0]
        tmask = jnp.arange(logits.shape[1])[None, :] < trg_len[:, None]
        return jnp.sum(nll * tmask)

    return block_cost
