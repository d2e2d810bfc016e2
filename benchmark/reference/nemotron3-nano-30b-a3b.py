"""Plain reference of the `nemotron_h` hybrid decoder (NVIDIA-Nemotron-3-Nano-
30B-A3B, config.json; Nemotron-H, arXiv:2504.03624; Mamba-2, arXiv:2405.21060)
for one chip's share of it: jax.numpy, float32, no kernel, no chunking.

Nothing here comes from the program: the weights are the benchmark's own
(`benchmark/weights.py`) under this file's argument names; the map from these
to the program's parameter names lives in the configuration's file.

    x = E[ids];  every layer i of the pattern: x = x + mixer_i(rms_norm(x))
    logits = rms_norm(x) W_out;  rms_norm(x) = x / sqrt(mean(x^2) + eps) * gain
    cost of a row = sum over its true tokens of -log softmax(logits_t)[next_t]

    M  [z | xBC | dt] = u W_in (widths inner, inner + 2 G N, H)
       xBC = silu(causal depthwise conv_K(xBC) + bias);  x [H, P], B, C [G, N]
       dt = softplus(dt + dt_bias);  A = -exp(A_log);  a group serves H/G heads
       h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
       y = group_rms_norm(y * silu(z)) * gain (groups of inner/G);  out = y W_out
       The recurrence is computed in its quadratic dual form, a whole row at
       once: y_q = sum_{s<=q} (C_q . B_s) exp(sum_{s<r<=q} dt_r A) dt_s x_s,
       one group of heads at a time so that the [T, T] forms fit.
    *  q, k, v = u W_q, u W_k, u W_v (Hq, Hkv, Hkv heads of dh);  query head j
       reads key/value head j // (Hq / Hkv);  softmax(q k^T / sqrt(dh)) causal;
       out = o W_o.  No positional encoding (the family uses none).
    E  s = sigmoid(u W_r) over ALL the published experts;  the k largest of
       s + b chosen;  w_e = s_e / (sum of the chosen s + 1e-20) * scaling
       E_e(u) = relu(u U_e)^2 V_e;  out = shared(u) + sum over the chosen e
       THAT THIS CHIP HOLDS of w_e E_e(u), as a masked sum over the held.

Departures from the published model, all stated in the configuration's file:
depth cut to one period of the pattern; of the 128 experts the router still
scores, the weights of `n_routed_experts` (the first ones) are held and what
the others would add is left out; the vocabulary is a slice; the correction
bias b is zero and nothing updates it; no auxiliary loss; `rope_theta` and
`partial_rotary_factor` are unused.  The router's product stays float32
whatever `mm` is handed in (the control, like fp8 training, keeps it so).

`cfg["reference_fault"] == "no_routed_experts"` plants a fault for the
calibration of the cell's limits: the held routed experts add nothing.
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return h, p, g, n, h * p


def param_shapes(cfg):
    """Argument name -> (shape, law).  `normal` is N(0, 1/sqrt(rows)); an
    expert-major leaf states its fan-in.  dt_bias -4 and A_log 0 give a step
    size softplus(-4 + N(0, 1)) of 0.018 at the median and 0.03 in the mean
    against A = -1: a half-life of the state of 23 to 38 tokens."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p, g, n, inner = _dims(cfg)
    k, conv = cfg["conv_kernel"], inner + 2 * g * n
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    e, f, s = cfg["n_routed_experts"], cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    shapes = {"embed": ((v, d), "normal"), "out.w": ((d, v), "normal"),
              "final_norm.gamma": ((d,), "ones")}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        shapes[f"l{i}.norm.gamma"] = ((d,), "ones")
        if kind == "M":
            shapes.update({
                f"l{i}.mamba.w_in": ((d, 2 * inner + 2 * g * n + h), "normal"),
                f"l{i}.mamba.conv_w": ((k, conv), "normal"),
                f"l{i}.mamba.conv_b": ((conv,), "zeros"),
                f"l{i}.mamba.dt_bias": ((h,), ("constant", -4.0)),
                f"l{i}.mamba.a_log": ((h,), ("constant", 0.0)),
                f"l{i}.mamba.d": ((h,), "ones"),
                f"l{i}.mamba.norm": ((inner,), "ones"),
                f"l{i}.mamba.w_out": ((inner, d), "normal"),
            })
        elif kind == "*":
            shapes.update({
                f"l{i}.attn.wq": ((d, hq * dh), "normal"),
                f"l{i}.attn.wk": ((d, hkv * dh), "normal"),
                f"l{i}.attn.wv": ((d, hkv * dh), "normal"),
                f"l{i}.attn.wo": ((hq * dh, d), "normal"),
            })
        else:
            shapes.update({
                f"l{i}.moe.router": ((d, cfg["n_routed_experts_published"]), "normal"),
                f"l{i}.moe.router_bias": ((cfg["n_routed_experts_published"],), "zeros"),
                f"l{i}.moe.w1": ((e, d, f), ("normal", d)),
                f"l{i}.moe.w2": ((e, f, d), ("normal", f)),
                f"l{i}.moe.shared_w1": ((d, s), "normal"),
                f"l{i}.moe.shared_w2": ((s, d), "normal"),
            })
    return shapes


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def _mamba(cfg, mm, w, name, u):
    h, p, g, n, inner = _dims(cfg)
    b_, t, _ = u.shape
    z, xbc, dt = jnp.split(mm(u, w[name + ".w_in"]), [inner, 2 * inner + 2 * g * n], axis=-1)
    k = cfg["conv_kernel"]
    past = jnp.concatenate([jnp.zeros((b_, k - 1, xbc.shape[-1]), xbc.dtype), xbc], axis=1)
    conv = w[name + ".conv_b"]
    for j in range(k):
        conv = conv + past[:, j:j + t] * w[name + ".conv_w"][j]
    x, bb, cc = jnp.split(jax.nn.silu(conv), [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt + w[name + ".dt_bias"])  # [B, T, H]
    a = -jnp.exp(w[name + ".a_log"])  # [H]

    def one_group(args):
        """x [B, T, R, P], dt [B, T, R], a [R], b, c [B, T, N] -> y [B, T, R, P]."""
        xg, dtg, ag, bg, cg = args
        cum = jnp.moveaxis(jnp.cumsum(dtg * ag, axis=1), 1, 2)  # [B, R, T]: log-decay up to and with t
        diff = cum[..., :, None] - cum[..., None, :]  # [B, R, Tq, Ts]
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        scores = jnp.einsum("bqn,bsn->bqs", cg, bg, precision=HIGHEST)
        return jnp.einsum("brqs,bsrp->bqrp", scores[:, None] * decay,
                          xg * dtg[..., None], precision=HIGHEST)

    r = h // g
    grouped = (
        jnp.moveaxis(x.reshape(b_, t, g, r, p), 2, 0), jnp.moveaxis(dt.reshape(b_, t, g, r), 2, 0),
        a.reshape(g, r), jnp.moveaxis(bb.reshape(b_, t, g, n), 2, 0),
        jnp.moveaxis(cc.reshape(b_, t, g, n), 2, 0))
    y = jnp.moveaxis(jax.lax.map(jax.checkpoint(one_group), grouped), 0, 2)  # [B, T, G, R, P]
    y = y.reshape(b_, t, h, p) + x.reshape(b_, t, h, p) * w[name + ".d"][:, None]
    y = (y.reshape(b_, t, inner) * jax.nn.silu(z)).reshape(b_, t, g, inner // g)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg["norm_eps"])
    return mm(y.reshape(b_, t, inner) * w[name + ".norm"], w[name + ".w_out"])


def _attention(cfg, mm, w, name, u, lens):
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    b_, t, _ = u.shape
    q = mm(u, w[name + ".wq"]).reshape(b_, t, hkv, hq // hkv, dh)
    k = mm(u, w[name + ".wk"]).reshape(b_, t, hkv, dh)
    v = mm(u, w[name + ".wv"]).reshape(b_, t, hkv, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=HIGHEST) / math.sqrt(dh)
    ok = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None] & (
        jnp.arange(t)[None, None, :] < lens[:, None, None])
    p = jax.nn.softmax(jnp.where(ok[:, None, None], s, -1e9), axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)
    return mm(o.reshape(b_, t, hq * dh), w[name + ".wo"])


def _experts(cfg, mm, w, name, u, fault):
    def ffn(x, up, down):
        return mm(jnp.square(jax.nn.relu(mm(x, up))), down)

    out = ffn(u, w[name + ".shared_w1"], w[name + ".shared_w2"])
    if fault == "no_routed_experts":
        return out
    scores = jax.nn.sigmoid(jnp.matmul(u, w[name + ".router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + w[name + ".router_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    for e in range(cfg["n_routed_experts"]):  # the held experts are the first ones
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1, keepdims=True)
        out = out + w_e * ffn(u, w[name + ".w1"][e], w[name + ".w2"][e])
    return out


def make_block_cost(cfg):
    pattern, eps = cfg["hybrid_override_pattern"], cfg["norm_eps"]
    fault = cfg.get("reference_fault")

    def layer(kind, name, mm, w, x, lens):
        u = _rms_norm(x, w[name + ".norm.gamma"], eps)
        if kind == "M":
            return x + _mamba(cfg, mm, w, name + ".mamba", u)
        if kind == "*":
            return x + _attention(cfg, mm, w, name + ".attn", u, lens)
        return x + _experts(cfg, mm, w, name + ".moe", u, fault)

    def block_cost(w, batch, mm):
        """Sum over the block's rows of each row's token-summed cross entropy.
        batch: word, next_word [B, T] int32; len [B]."""
        lens = batch["len"]
        x = jnp.take(w["embed"], batch["word"], axis=0)
        for i, kind in enumerate(pattern):
            # one layer's activations at a time on the way back
            x = jax.checkpoint(lambda w_, x_, kind=kind, i=i: layer(kind, f"l{i}", mm, w_, x_, lens))(w, x)
        logits = mm(_rms_norm(x, w["final_norm.gamma"], eps), w["out.w"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, batch["next_word"][..., None], axis=-1)[..., 0]
        return jnp.sum(nll * (jnp.arange(logits.shape[1])[None, :] < lens[:, None]))

    return block_cost
