"""Plain reference of the `ouro` looped decoder (ByteDance Ouro-2.6B,
config.json and modeling_ouro.py; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741) for one pipeline stage of it: jax.numpy,
float32 under `jax.default_matmul_precision("highest")`, a Python loop over
the layers inside a scan over the passes, dense attention with explicit
cos/sin tables, no kernel.

Nothing here comes from the program: the weights are the benchmark's own
(`benchmark/weights.py`) under this file's argument names; the map from these
to the program's parameter names lives in the configuration's file.

    x^0 = E[ids];  R = total_ut_steps passes, the SAME weights in every pass
    one pass, for each layer l:
      a = x + N2_l(Attn_l(N1_l(x)));  x = a + N4_l(Wd_l(silu(Wg_l h) * (Wu_l h))),  h = N3_l(a)
      N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * gain;  no bias anywhere
      Attn(h): q, k, v = h Wq, h Wk, h Wv, split into heads of head_dim;
        q, k <- q cos + rotate_half(q) sin (positions 0..T-1, angle
        position * rope_theta^(-2i/head_dim), pairs (i, i + head_dim/2));
        softmax(q k^T / sqrt(head_dim)) under the causal mask, times v; then Wo
    x^t = Nf(x) after the layers of pass t: what the head reads and what
      pass t + 1 starts from
    ce^t_i = -log softmax(x^t_i W_out)[next_i];  g^t_i = sigmoid(w_g . x^t_i + b_g)
    p^1 = g^1;  p^t = g^t prod_{j<t}(1 - g^j);  p^R = prod_{j<R}(1 - g^j)
    cost of a row = sum over its true tokens of [ sum_t p^t ce^t - beta H(p) ],
      H(p) = -sum_t p^t log p^t,  beta = exit_beta

Departures from the published model, all stated in the configuration's file:
depth cut to `num_hidden_layers` of the 48; the objective is the paper's
first-stage one (config.json has no key for it) with beta assumed.  Departure
from "no recomputation" that changes no arithmetic: `jax.checkpoint` around
each layer application and around each pass's head, or one row's 32
applications and four [T, V] log-softmaxes would not fit beside three
float32 copies of the weights.  Departure from a Python loop over the
passes that changes no arithmetic either: the passes are one `lax.scan`
whose body is the Python loop over the layers, the final norm and the head.
Unrolled over the passes too, the gradient is 32 layer applications x
(forward, recomputed forward, backward) of float32 products that the TPU
runs as six bfloat16 passes each: a 259 MB executable that took 124-135 s to
compile and is over the 201 MB that jax's persistent cache takes, so every
run of the cell compiled it again (my chip runs, PR 36; `PERF.md` section 6
has the forms tried).  The cos/sin tables and the mask are made once a block.
The gate's product stays float32 whatever
`mm` is handed in (the control, like fp8 training, keeps such a reduction
to one logit so).

`cfg["reference_fault"]` plants a fault for the calibration of the cell's
limits: "three_passes" runs R - 1 passes in place of R (the last of them
takes the rest of the exit distribution); "no_entropy_term" leaves the
-beta H(p) term out of the cost.
"""

import math

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    """Argument name -> (shape, law): `normal` is N(0, 1/sqrt(rows)), gains
    one, the gate's bias zero."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    shapes = {"embed": ((v, d), "normal"), "out.w": ((d, v), "normal"),
              "final_norm.gamma": ((d,), "ones"),
              "gate.w": ((d, 1), "normal"), "gate.b": ((1,), "zeros")}
    for i in range(cfg["num_hidden_layers"]):
        for k in (1, 2, 3, 4):
            shapes[f"l{i}.norm{k}.gamma"] = ((d,), "ones")
        shapes.update({
            f"l{i}.attn.wq": ((d, hd), "normal"), f"l{i}.attn.wk": ((d, hd), "normal"),
            f"l{i}.attn.wv": ((d, hd), "normal"), f"l{i}.attn.wo": ((hd, d), "normal"),
            f"l{i}.mlp.gate": ((d, f), "normal"), f"l{i}.mlp.up": ((d, f), "normal"),
            f"l{i}.mlp.down": ((f, d), "normal"),
        })
    return shapes


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def _rope_tables(t, dh, theta):
    """cos, sin [T, dh]: the angle of pair i repeated over both its halves."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _attention(cfg, mm, w, name, u, tables):
    """tables: cos, sin [T, dh] and the mask [B, T, T] (true = a key this
    query may see: not after it, not past the row's length)."""
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    b, t, _ = u.shape
    cos, sin, ok = tables
    q, k, v = (mm(u, w[f"{name}.{m}"]).reshape(b, t, h, dh) for m in ("wq", "wk", "wv"))
    q = q * cos[None, :, None, :] + _rotate_half(q) * sin[None, :, None, :]
    k = k * cos[None, :, None, :] + _rotate_half(k) * sin[None, :, None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e9), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return mm(o.reshape(b, t, h * dh), w[name + ".wo"])


def make_block_cost(cfg):
    eps, beta = cfg["rms_norm_eps"], cfg["exit_beta"]
    n_layers, passes = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    fault = cfg.get("reference_fault")
    if fault == "three_passes":
        passes -= 1
    elif fault == "no_entropy_term":
        beta = 0.0

    def layer(name, mm, w, x, tables):
        a = x + _rms_norm(_attention(cfg, mm, w, name + ".attn", _rms_norm(x, w[name + ".norm1.gamma"], eps), tables),
                          w[name + ".norm2.gamma"], eps)
        h = _rms_norm(a, w[name + ".norm3.gamma"], eps)
        y = mm(jax.nn.silu(mm(h, w[name + ".mlp.gate"])) * mm(h, w[name + ".mlp.up"]), w[name + ".mlp.down"])
        return a + _rms_norm(y, w[name + ".norm4.gamma"], eps)

    def head(mm, w, x, nxt):
        """-> ce [B, T], the gate's logit [B, T]."""
        logp = jax.nn.log_softmax(mm(x, w["out.w"]), axis=-1)
        ce = -jnp.take_along_axis(logp, nxt[..., None], axis=-1)[..., 0]
        return ce, (jnp.matmul(x, w["gate.w"]) + w["gate.b"])[..., 0]

    def block_cost(w, batch, mm):
        """Sum over the block's rows of each row's token-summed cost.
        batch: word, next_word [B, T] int32; len [B]."""
        with jax.default_matmul_precision("highest"):
            lens = batch["len"]
            x = jnp.take(w["embed"], batch["word"], axis=0)
            keys = jnp.arange(x.shape[1])
            ok = (keys[None, :] <= keys[:, None])[None] & (keys[None, None, :] < lens[:, None, None])
            tables = (*_rope_tables(x.shape[1], cfg["head_dim"], cfg["rope_theta"]), ok)

            def one_pass(x, _):
                for i in range(n_layers):
                    # one layer's activations at a time on the way back
                    x = jax.checkpoint(lambda w_, x_, i=i: layer(f"l{i}", mm, w_, x_, tables))(w, x)
                x = _rms_norm(x, w["final_norm.gamma"], eps)
                return x, jax.checkpoint(lambda w_, x_: head(mm, w_, x_, batch["next_word"]))(w, x)

            _, (ces, zs) = jax.lax.scan(one_pass, x, None, length=passes)
            # the exit distribution: log(1 - g) as log_sigmoid(-z)
            log_p, stay = [], jnp.zeros_like(zs[0])
            for t in range(passes):
                log_p.append(stay + (jax.nn.log_sigmoid(zs[t]) if t < passes - 1 else 0.0))
                stay = stay + jax.nn.log_sigmoid(-zs[t])
            cost = sum(jnp.exp(lp) * (ces[t] + beta * lp) for t, lp in enumerate(log_p))
            return jnp.sum(cost * (keys[None, :] < lens[:, None]))

    return block_cost
