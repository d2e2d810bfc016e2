"""Plain reference of the attention-GRU encoder-decoder (demo/seqToseq
`gru_encoder_decoder`): jax.numpy, float32, no kernel, no custom gradient.

Nothing here comes from the program: the weights are the benchmark's own
(`benchmark/weights.py`), under this file's argument names; the map from the
program's parameter names to these lives in the configuration's file.

Equations (Paddle-v1 GRU: u, r, c slot order, reset applied before the
candidate GEMM):

    encoder, each direction:  x = E_src[src] W_x;  p = x_t + b
        u = s(p_u + h U_u)   r = s(p_r + h U_r)
        c = tanh(p_c + (r*h) U_c)    h' = (1-u)*h + u*c     (padding: h' = h)
    enc = [fw ; bw],  ep = enc W_ep,  h_0 = tanh(enc[:,0] W_boot + b_boot)
    decoder step t:  a = softmax_S(tanh(ep + h W_sp) . v)  over true source
        ctx = sum_s a_s enc_s;  p = ctx W_ctx + E_trg[y_{t-1}] W_emb + b, GRU as above
        logits = h' W_out + b_out
    cost of a row = sum over its true target tokens of -log softmax(logits)[y_t]
"""

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    """Argument name -> (shape, init): `normal` is N(0, 1/sqrt(rows))."""
    v_s, v_t = cfg["src_vocab_size"], cfg["trg_vocab_size"]
    w, h = cfg["word_dim"], cfg["hidden_dim"]
    shapes = {"src_emb": (v_s, w), "trg_emb": (v_t, w)}
    for d in ("fw", "bw"):
        shapes[f"enc_{d}.w_x"] = (w, 3 * h)
        shapes[f"enc_{d}.u_ur"] = (h, 2 * h)
        shapes[f"enc_{d}.u_c"] = (h, h)
        shapes[f"enc_{d}.b"] = (3 * h,)
    shapes.update({
        "enc_proj.w": (2 * h, h),
        "boot.w": (2 * h, h), "boot.b": (h,),
        "att.w_state": (h, h), "att.v": (h, 1),
        "dec.w_ctx": (2 * h, 3 * h), "dec.w_emb": (w, 3 * h),
        "dec.u_ur": (h, 2 * h), "dec.u_c": (h, h), "dec.b": (3 * h,),
        "out.w": (h, v_t), "out.b": (v_t,),
    })
    return {k: (s, "normal" if len(s) == 2 else "zeros") for k, s in shapes.items()}


def _gru_cell(mm, p, h, u_ur, u_c, keep):
    n = h.shape[-1]
    ur = jax.nn.sigmoid(p[:, : 2 * n] + mm(h, u_ur))
    u, r = ur[:, :n], ur[:, n:]
    c = jnp.tanh(p[:, 2 * n:] + mm(r * h, u_c))
    return jnp.where(keep[:, None], (1.0 - u) * h + u * c, h)


def _gru_seq(mm, x, lengths, w, d, reverse):
    b, t, _ = x.shape
    p = mm(x, w[f"enc_{d}.w_x"]) + w[f"enc_{d}.b"]
    steps = jnp.arange(t)
    h0 = jnp.zeros((b, w[f"enc_{d}.u_c"].shape[0]), jnp.float32)

    def step(h, i):
        h = _gru_cell(mm, p[:, i], h, w[f"enc_{d}.u_ur"], w[f"enc_{d}.u_c"],
                      i < lengths)
        return h, h

    _, hs = jax.lax.scan(step, h0, steps, reverse=reverse)
    return jnp.swapaxes(hs, 0, 1)


def make_block_cost(cfg):
    del cfg  # every size is read from the weights' shapes
    return block_cost


def block_cost(w, batch, mm):
    """Sum over the block's rows of each row's token-summed cross entropy.
    batch: src, trg_in, trg_next [B, T] int32; src_len, trg_len [B]."""
    src, src_len = batch["src"], batch["src_len"]
    x = jnp.take(w["src_emb"], src, axis=0)
    enc = jnp.concatenate(
        [_gru_seq(mm, x, src_len, w, "fw", False),
         _gru_seq(mm, x, src_len, w, "bw", True)], axis=-1)
    ep = mm(enc, w["enc_proj.w"])
    h0 = jnp.tanh(mm(enc[:, 0], w["boot.w"]) + w["boot.b"])
    smask = jnp.arange(src.shape[1])[None, :] < src_len[:, None]

    emb = jnp.take(w["trg_emb"], batch["trg_in"], axis=0)
    pe = mm(emb, w["dec.w_emb"]) + w["dec.b"]
    trg_len = batch["trg_len"]

    def step(h, i):
        sp = mm(h, w["att.w_state"])
        score = mm(jnp.tanh(ep + sp[:, None, :]), w["att.v"])[..., 0]
        a = jax.nn.softmax(jnp.where(smask, score, -1e9), axis=-1) * smask
        ctx = jnp.einsum("bs,bse->be", a, enc, precision="highest")
        p = pe[:, i] + mm(ctx, w["dec.w_ctx"])
        h = _gru_cell(mm, p, h, w["dec.u_ur"], w["dec.u_c"], i < trg_len)
        return h, h

    _, hs = jax.lax.scan(step, h0, jnp.arange(emb.shape[1]))
    logits = mm(jnp.swapaxes(hs, 0, 1), w["out.w"]) + w["out.b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["trg_next"][..., None], axis=-1)[..., 0]
    tmask = jnp.arange(emb.shape[1])[None, :] < trg_len[:, None]
    return jnp.sum(nll * tmask)
