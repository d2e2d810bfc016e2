"""Attention-family layers: multi-head attention, layer norm, positional
encoding — the building blocks of Transformer-base MT (BASELINE.json configs
#5; "new config" stressing the op-graph → HLO lowering, with no reference
implementation to translate).

TPU-native design notes:
  * MHA is two einsums around a masked softmax — XLA fuses the scale/mask/
    softmax chain between the MXU matmuls; heads live in one [B,T,H,dh]
    layout (no per-head loop).
  * Under bf16 mixed precision the softmax and layer-norm statistics compute
    in float32 and cast back: both are cancellation-sensitive reductions.
  * Padding is masked via SeqTensor lengths (keys) and an optional causal
    mask (decoder self-attention) — static shapes, no dynamic slicing.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core import initializers as init
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.layers.base import register_layer
from paddle_tpu.ops import acc_einsum, acc_matmul
from paddle_tpu.utils.timers import global_stats

NEG_INF = -1e9


# ---------------------------------------------------------------------------
# layer_norm
# ---------------------------------------------------------------------------


def layer_norm_init(conf, in_confs, rng):
    d = conf.size
    return {"gamma": init.ones((d,)), "beta": init.zeros((d,))}


@register_layer("layer_norm", init=layer_norm_init, auto_activation=False)
def layer_norm_apply(conf, params, inputs, ctx):
    x = inputs[0]
    eps = conf.attr("epsilon", 1e-6)
    x32 = x.data.astype(jnp.float32)
    # two-pass (subtract-mean-first) variance on purpose: rows are only
    # 512 wide so the second pass is cheap, and the one-pass E[x^2]-E[x]^2
    # form cancels catastrophically for offset-heavy rows (measured zero
    # speedup here, unlike batch_norm's megasample reductions)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["gamma"].astype(jnp.float32) + params["beta"].astype(jnp.float32)
    return x.with_data(y.astype(x.data.dtype))


def rms_normalize(x32, eps):
    """x / sqrt(mean(x^2) + eps) over the last axis, x float32."""
    return x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)


def rms_norm_init(conf, in_confs, rng):
    return {"gamma": init.ones((conf.size,))}


@register_layer("rms_norm", init=rms_norm_init, auto_activation=False)
def rms_norm_apply(conf, params, inputs, ctx):
    """x / sqrt(mean(x^2) + eps) * gamma: no mean subtracted, no shift;
    float32 statistics as layer_norm's."""
    x = inputs[0]
    y = rms_normalize(x.data.astype(jnp.float32), conf.attr("epsilon", 1e-5))
    return x.with_data((y * params["gamma"].astype(jnp.float32)).astype(x.data.dtype))


# ---------------------------------------------------------------------------
# multi-head attention
# ---------------------------------------------------------------------------


def mha_init(conf, in_confs, rng):
    import jax

    d = conf.size
    h, kvh, dh = _head_dims(conf)
    d_in_q = in_confs[0].size
    d_in_kv = in_confs[1].size if len(in_confs) > 1 else d_in_q
    rq, rk, rv, ro = jax.random.split(rng, 4)
    std_q = 1.0 / math.sqrt(d_in_q)
    std_kv = 1.0 / math.sqrt(d_in_kv)
    p = {
        "wq": init.normal(rq, (d_in_q, h * dh), std_q),
        "wk": init.normal(rk, (d_in_kv, kvh * dh), std_kv),
        "wv": init.normal(rv, (d_in_kv, kvh * dh), std_kv),
        "wo": init.normal(ro, (h * dh, d), 1.0 / math.sqrt(h * dh)),
    }
    if conf.bias:
        p["b"] = init.zeros((d,))
    return p


def _head_dims(conf):
    """(query heads, key/value heads, head width).  By default the heads
    divide the layer's size and every query head has key/value heads of its
    own; `head_dim` sets a width apart from the size, `n_kv_heads` makes
    groups of n_heads / n_kv_heads query heads share one key/value head."""
    h = conf.attrs["n_heads"]
    kvh = conf.attr("n_kv_heads") or h
    dh = conf.attr("head_dim") or conf.size // h
    assert conf.attr("head_dim") or conf.size % h == 0, (
        f"{conf.name}: size {conf.size} not divisible by n_heads {h}")
    assert h % kvh == 0, f"{conf.name}: {h} query heads over {kvh} key/value heads"
    return h, kvh, dh


# Keys from which attention with as many queries as keys takes the blocked
# kernels unasked: the smallest count at which they beat the dense path with
# AND without the causal mask (scripts/attention_sweep.py on a v5e, PERF.md
# section 6, PR 35; forward + backward of the core, 8,192 tokens, 8 heads of
# 64; dense / blocked ms): 256 keys 0.57 / 0.64 and 0.56 / 0.60 causal, 512
# keys 0.57 / 0.73 and 1.30 / 0.70, 1,024 keys 2.15 / 1.07 and 3.06 / 0.95,
# 2,048 keys 4.00 / 1.76 and 5.85 / 1.32.  The benchmark's cells lie on both
# sides: 128 keys dense, 1,024 and 2,048 blocked.
_FLASH_FROM_KEYS = 1024


def _flash_asked():
    from paddle_tpu.utils.flags import get_flag

    return bool(get_flag("use_pallas_attention"))


def _blocked_core(mesh, batch):
    """How the blocked kernels enter the program being traced on `mesh`
    (ctx.mesh) -> (wrap, why): `wrap` takes the per-device function of
    (q, k, v, lengths) to the one to call, or is None with the reason.

    XLA does not partition a Mosaic kernel: lowered into a program over more
    than one device (trainer/step.py's data-parallel step is a plain jit
    with a NamedSharding on the batch), jax refuses it outright.  So where
    the mesh holds more than one device the kernels run under a shard_map
    over the batch (DATA_AXIS), every other axis replicated: a batch row's
    attention needs no other row.  Inside a shard_map that already holds
    EVERY axis (the quantized-allreduce step) the program is a device's own
    and the kernels are called as they are; inside one that holds only some
    of them (the pipeline) jax refuses the kernels, and the layer stays
    dense."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.mesh import DATA_AXIS

    held = jax.sharding.get_abstract_mesh()
    if held.manual_axes:
        if set(held.manual_axes) == set(held.axis_names):
            return (lambda f: f), None
        return None, f"the layer is traced inside a shard_map over {held.manual_axes} only"
    if mesh is None or mesh.size == 1:
        return (lambda f: f), None
    n = mesh.shape.get(DATA_AXIS, 1)
    if n == 1 or batch % n:
        return None, (f"the mesh {dict(mesh.shape)} holds {mesh.size} devices and "
                      f"{batch} rows do not split over its {DATA_AXIS!r} axis")
    rows = P(DATA_AXIS)
    return (lambda f: jax.shard_map(f, mesh=mesh, in_specs=(rows,) * 4, out_specs=rows,
                                    check_vma=False)), None


def _dense_core(q, k, v, key_mask, causal):
    """softmax(QK^T)V with the [Tq, Tk] scores held whole: q [B, Tq, h, dh],
    k and v [B, Tk, kvh, dh], key_mask [B, Tk] (1 = a key that exists) or
    None -> [B, Tq, h * dh].  float32 softmax, weights in v's dtype."""
    b, tq, h, dh = q.shape
    tk, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    # Explicit [B, h, T, dh] operands with LEADING batch dims: the
    # score/output einsums and every dot_general their VJP emits then
    # have (b, h) as proper leading batch dimensions, which the TPU
    # layout assignment handles in place.  With h trapped at dim 2
    # ("bqhd,bkhd->bhqk") the backward materialized layout-change
    # copies of every [B,h,T,T]/[B,T,h,dh] grad — measured 9.1 ms of
    # a 36 ms transformer-base step (25% in pure copies).  (Two
    # alternatives measured SLOWER on v5e: a single packed
    # [B,T,3,h,dh]->[3,B,h,T,dh] relayout of the fused QKV — the 5-D
    # transpose tiles worse than three separate ones — and a
    # whole-[T,T]-in-VMEM Pallas kernel with grid (B,) + in-core
    # batched-over-heads dots, which lost ~35% to tiny per-program
    # work at T=64.)
    #
    # Grouped heads (n_kv_heads < n_heads): the query heads that share
    # a key/value head are folded into the query axis, [B, kvh, g*Tq,
    # dh], so the same two einsums run over kvh batch heads and no
    # key or value is repeated.
    if group == 1:
        qh = q.transpose(0, 2, 1, 3)
    else:
        qh = (q.reshape(b, tq, kvh, group, dh).transpose(0, 2, 3, 1, 4)
              .reshape(b, kvh, group * tq, dh))
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    scores = acc_einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(dh)
    scores = scores.astype(jnp.float32)
    if key_mask is not None:
        scores = scores + (1.0 - key_mask)[:, None, None, :] * NEG_INF
    if causal:
        cm = jnp.tril(jnp.ones((tq, tk), jnp.float32))
        if group > 1:
            cm = jnp.tile(cm, (group, 1))
        scores = scores + (1.0 - cm)[None, None, :, :] * NEG_INF
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = acc_einsum("bhqk,bhkd->bhqd", w, vh)
    if group == 1:
        return out.transpose(0, 2, 1, 3).reshape(b, tq, h * dh)
    return (out.reshape(b, kvh, group, tq, dh).transpose(0, 3, 1, 2, 4)
            .reshape(b, tq, h * dh))


def rotary(x, theta):
    """Rotary position code over the head width: x [B, T, heads, dh], the
    positions 0..T-1 of the row, pair (i, i + dh/2) of a head turned by
    position * theta^(-2i/dh) ("rotate-half"), so that a query-key product
    depends on the two positions' difference alone.  Angles and the turn in
    float32, the result back in x's type."""
    t, dh = x.shape[1], x.shape[-1]
    assert dh % 2 == 0, f"rotary needs an even head width, got {dh}"
    inv_freq = jnp.exp(jnp.arange(0, dh, 2, dtype=jnp.float32) * (-math.log(theta) / dh))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [T, dh/2]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


@register_layer("multi_head_attention", init=mha_init, auto_activation=False)
def mha_apply(conf, params, inputs, ctx):
    """inputs: (query, key_value) — pass the same layer twice for
    self-attention.  attrs: n_heads, causal, rope_theta (None: no position
    code in the layer)."""
    q_in = inputs[0]
    kv_in = inputs[1] if len(inputs) > 1 else inputs[0]
    h, kvh, dh = _head_dims(conf)
    causal = conf.attr("causal", False)
    d = h * dh  # the heads' width together; conf.size is the output's

    # self-attention detection by TOPOLOGY, not object identity: the
    # mixed-precision cast rebuilds each input SeqTensor, so `kv_in is
    # q_in` is False in every bf16 step even when both are the same layer
    same_input = len(conf.inputs) == 1 or conf.inputs[0] == conf.inputs[1]
    if same_input:
        # self-attention: one [D, 3D] GEMM instead of three [D, D] — wider
        # N keeps the MXU fuller and the param concat is trace-time cheap
        qkv = acc_matmul(q_in.data, jnp.concatenate(
            [params["wq"], params["wk"], params["wv"]], axis=1
        ))
        q, k, v = jnp.split(qkv, [d, d + kvh * dh], axis=-1)
    else:
        q = acc_matmul(q_in.data, params["wq"])  # [B, Tq, D]
        k = acc_matmul(kv_in.data, params["wk"])  # [B, Tk, D]
        v = acc_matmul(kv_in.data, params["wv"])
    b, tq = q.shape[0], q.shape[1]
    tk = k.shape[1]
    q = q.reshape(b, tq, h, dh)
    k = k.reshape(b, tk, kvh, dh)
    v = v.reshape(b, tk, kvh, dh)
    group = h // kvh
    rope_theta = conf.attr("rope_theta")
    if rope_theta is not None:
        # before the core, so the dense path, the ring and the blocked
        # kernels all take rotated q and k unchanged
        with jax.named_scope("rope"):
            q, k = rotary(q, rope_theta), rotary(k, rope_theta)

    sp_axis = conf.attr("seq_parallel_axis")
    out = None
    # The blocked kernels (ops/pallas_attention.py) keep a block of scores
    # in VMEM between two MXU products: no [T, T] scores, weights or their
    # gradients in HBM.  TPU backend only.  They are taken where the flag
    # asks for them, and from _FLASH_FROM_KEYS keys on whatever the flag
    # says: from there the dense path is bound by those bytes (2.2-2.4 GB a
    # layer at 8 x 8 heads x 1,024^2) and loses in time as well as in memory.
    # The choice is made here, at trace time, and counted here
    # (`attention_blocked_layers` / `attention_dense_layers`).
    # Asked for by the flag, or due by the key count on a TPU, and not
    # usable, the layer computes dense and SAYS so at trace time: a silent
    # dense path would be timed and costed as the kernel.  On a mesh of
    # several devices they run under a shard_map over the batch
    # (_blocked_core).
    from paddle_tpu.ops import pallas_attention as fa

    flash_why = wrap = None
    wanted = _flash_asked()
    if jax.default_backend() != "tpu":
        flash_why = f"the backend is {jax.default_backend()!r}, not 'tpu'"
    elif tq != tk:
        flash_why = f"query length {tq} != key length {tk}"
    elif not fa.supported(tq, dh):
        flash_why = f"T={tq}, head dim {dh} is not a shape the kernel takes"
    elif wanted or tk >= _FLASH_FROM_KEYS:
        wanted = True
        wrap, flash_why = _blocked_core(ctx.mesh, b)
    take_flash = wrap is not None
    if group > 1 and (sp_axis is not None or take_flash):
        # the ring and the kernel take a key/value head a query head
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    if sp_axis is not None and tq == tk:
        # context parallelism: shard T over the mesh axis and run exact
        # ring attention (parallel/ring_attention.py) instead of the dense
        # [T, T] score matrix — the long-context path.  The mesh comes from
        # the owning network (trainer-scoped), falling back to the process
        # default (compiler.py ApplyContext).
        from paddle_tpu.parallel.ring_attention import (
            sequence_parallel_attention,
        )

        mesh = ctx.mesh
        usable = (
            mesh is not None
            and sp_axis in mesh.shape
            and tq % mesh.shape[sp_axis] == 0
        )
        if not usable:
            import warnings

            if mesh is None:
                why = "no mesh is available"
            elif sp_axis not in mesh.shape:
                why = f"the mesh has no {sp_axis!r} axis"
            else:
                why = (
                    f"T={tq} is not divisible by the "
                    f"{mesh.shape[sp_axis]}-way ring"
                )
            warnings.warn(
                f"{conf.name}: seq_parallel_axis={sp_axis!r} requested but "
                f"{why}; falling back to dense O(T^2) attention",
                stacklevel=2,
            )
        else:
            out = sequence_parallel_attention(
                q, k, v, mesh, sp_axis,
                lengths=kv_in.lengths if kv_in.is_seq else None,
                causal=causal,
            ).reshape(b, tq, d)

    if out is None and take_flash:
        global_stats.incr("attention_blocked_layers")
        bq, bk = fa.auto_blocks(tq, causal)
        lengths = kv_in.lengths if kv_in.is_seq else jnp.full((b,), tk, jnp.int32)
        out = wrap(lambda q, k, v, n: fa.flash_attention_diff(q, k, v, n, causal, bq, bk, False))(
            q, k, v, lengths).reshape(b, tq, d)
    elif out is None and wanted:
        import warnings

        due = ("use_pallas_attention is on" if _flash_asked()
               else f"{tk} keys are due the blocked kernels")
        warnings.warn(
            f"{conf.name}: {due} but {flash_why}; computing dense O(T^2) attention",
            stacklevel=2,
        )

    if out is None:
        global_stats.incr("attention_dense_layers")
        out = _dense_core(q, k, v, kv_in.mask(jnp.float32) if kv_in.is_seq else None, causal)

    out = acc_matmul(out, params["wo"])
    if "b" in params:
        out = out + params["b"]
    return SeqTensor(out, q_in.lengths, q_in.sub_lengths)


# ---------------------------------------------------------------------------
# attention-GRU decoder step pattern — the fused-scan matcher
# ---------------------------------------------------------------------------
#
# The v1 NMT decoder idiom (reference trainer_config_helpers networks.py
# simple_attention feeding a gru_step inside a recurrent_group) builds this
# exact step sub-graph:
#
#   expand(memory, enc_proj) -> fc(identity) --\
#                                 enc_proj -----+-> addto(act) -> fc(1,
#   seq_softmax) -> scaling(scores, enc) -> seqpool(sum) = context
#   fc([context, scanned...], 3H, identity) -> gru_step(., memory)
#
# match_attention_gru_step recognizes it structurally (types, wiring, act/
# bias constraints) so recurrent_group can lower the WHOLE step onto the
# fused custom-VJP scan core (ops/rnn.py _attgru_core) with no config edits
# — the op-fusion analogue of the reference's hand-fused per-timestep
# decoder kernels (paddle/cuda/src/hl_cuda_lstm.cu).  Anything that doesn't
# match keeps the generic per-layer scan body.


@dataclasses.dataclass(frozen=True)
class AttentionGRUMatch:
    """Layer names of a matched attention-GRU decoder step."""

    gru: str  # gru_step — the memory link
    in_proj: str  # fc building the 3H gate input from [context, scanned...]
    pool: str  # seqpool(sum) -> context
    scale: str  # scaling(scores, enc)
    scores: str  # fc size-1 sequence_softmax
    hidden: str  # addto(enc_proj, state_proj)
    state_proj: str  # fc over the expanded memory
    expand: str  # expand(memory, enc_proj)
    mem: str  # memory placeholder name
    enc_name: str  # static placeholder: encoded sequence (context values)
    ep_name: str  # static placeholder: encoded projection (score keys)
    ctx_slot: int  # index of the context input within in_proj.inputs
    scan_slots: Tuple[Tuple[int, str], ...]  # (in_proj slot, scan placeholder)
    gate_act: str
    act: str
    att_act: str
    matched: frozenset  # every matched layer name, for body-coverage checks


def _clean(c) -> bool:
    """No dropout / error-clip / dynamic-width on a candidate layer — the
    fused core implements none of them."""
    return (
        c.drop_rate == 0.0
        and not c.attr("error_clip", 0.0)
        and not c.attr("dynamic_width_in")
    )


# The fused backward derives the score activation's derivative with a
# jvp-against-ones (ops/rnn.py _attgru_core_bwd) — exact ONLY for
# elementwise activations.  A non-elementwise act (softmax, ...) on the
# attention hidden layer must fall back to the generic scan, or it would
# match, run, and train with silently wrong gradients.
_ELEMENTWISE_ATT_ACTS = frozenset({
    "", "identity", "linear", "tanh", "sigmoid", "relu", "brelu",
    "stanh", "softrelu", "abs", "square",
})


def _ident_act(c) -> bool:
    return c.act in ("identity", "linear", "")


def match_attention_gru_step(
    layers, mem_conf, scan_names, static_seq_names
) -> Optional[AttentionGRUMatch]:
    """Match the sub-topology rooted at `mem_conf`'s link against the v1
    attention-GRU decoder idiom.  `layers` is the step sub-topology's
    {name: LayerConf}; `scan_names` the scanned placeholder names;
    `static_seq_names` the sequence-valued static placeholder names.
    Returns None on any structural mismatch (callers fall back to the
    generic scan)."""
    if mem_conf.attrs.get("is_seq") or mem_conf.attrs.get("boot_const_id") is not None:
        return None
    link = mem_conf.attrs.get("link") or ""
    gru = layers.get(link)
    if (
        gru is None
        or gru.type != "gru_step"
        or gru.attr("tied_weights", False)
        or not _clean(gru)
        or len(gru.inputs) != 2
        or gru.inputs[1] != mem_conf.name
    ):
        return None
    h = gru.size
    in_proj = layers.get(gru.inputs[0])
    if (
        in_proj is None
        or in_proj.type != "fc"
        or not _ident_act(in_proj)
        or not _clean(in_proj)
        or in_proj.size != 3 * h
    ):
        return None
    # exactly one in_proj input is the pooled context; the rest must be
    # scanned placeholders (their projections hoist out of the scan)
    ctx_slot = None
    scan_slots = []
    for i, nm in enumerate(in_proj.inputs):
        c = layers.get(nm)
        if c is not None and c.type == "seqpool":
            if ctx_slot is not None:
                return None
            ctx_slot = i
        elif nm in scan_names:
            scan_slots.append((i, nm))
        else:
            return None
    if ctx_slot is None or not scan_slots:
        return None
    pool = layers[in_proj.inputs[ctx_slot]]
    if (
        pool.attr("pool_type", "max") != "sum"
        or pool.attr("agg_level", 0) != 0
        or pool.attr("stride", -1) > 0
        or pool.attr("output_max_index", False)
        or not _ident_act(pool)
        or not _clean(pool)
        or len(pool.inputs) != 1
    ):
        return None
    scale = layers.get(pool.inputs[0])
    if (
        scale is None
        or scale.type != "scaling"
        or not _ident_act(scale)
        or not _clean(scale)
        or len(scale.inputs) != 2
    ):
        return None
    scores_name, enc_name = scale.inputs
    if enc_name not in static_seq_names:
        return None
    scores = layers.get(scores_name)
    if (
        scores is None
        or scores.type != "fc"
        or scores.size != 1
        or scores.act != "sequence_softmax"
        or scores.bias
        or not _clean(scores)
        or len(scores.inputs) != 1
    ):
        return None
    hidden = layers.get(scores.inputs[0])
    if (
        hidden is None
        or hidden.type != "addto"
        or hidden.bias
        or not _clean(hidden)
        or len(hidden.inputs) != 2
        or hidden.act not in _ELEMENTWISE_ATT_ACTS
    ):
        return None
    ep_name = state_proj = None
    for nm in hidden.inputs:
        if nm in static_seq_names:
            ep_name = nm
        else:
            state_proj = layers.get(nm)
    if ep_name is None or state_proj is None:
        return None
    if (
        state_proj.type != "fc"
        or not _ident_act(state_proj)
        or not _clean(state_proj)
        or len(state_proj.inputs) != 1
    ):
        return None
    exp = layers.get(state_proj.inputs[0])
    if (
        exp is None
        or exp.type != "expand"
        or exp.attr("expand_level", 0) != 0
        or not _ident_act(exp)
        or not _clean(exp)
        or tuple(exp.inputs) != (mem_conf.name, ep_name)
    ):
        return None
    matched = frozenset(
        (gru.name, in_proj.name, pool.name, scale.name, scores.name,
         hidden.name, state_proj.name, exp.name)
    )
    return AttentionGRUMatch(
        gru=gru.name,
        in_proj=in_proj.name,
        pool=pool.name,
        scale=scale.name,
        scores=scores.name,
        hidden=hidden.name,
        state_proj=state_proj.name,
        expand=exp.name,
        mem=mem_conf.name,
        enc_name=enc_name,
        ep_name=ep_name,
        ctx_slot=ctx_slot,
        scan_slots=tuple(scan_slots),
        gate_act=gru.attr("gate_act", "sigmoid"),
        act=gru.attr("active_type", "tanh"),
        att_act=hidden.act or "identity",
        matched=matched,
    )


# ---------------------------------------------------------------------------
# sinusoidal positional encoding (parameterless)
# ---------------------------------------------------------------------------


@register_layer("pos_encoding", auto_activation=False)
def pos_encoding_apply(conf, params, inputs, ctx):
    x = inputs[0]
    assert x.is_seq and not x.is_nested
    b, t, d = x.data.shape
    scale = conf.attr("emb_scale", 1.0)
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]  # [T, 1]
    div = jnp.exp(
        jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d)
    )
    pe = jnp.zeros((t, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))  # ceil(d/2) even channels
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div[: d // 2]))  # floor(d/2) odd
    out = x.data * jnp.asarray(scale, x.data.dtype) + pe.astype(x.data.dtype)
    return x.with_data(out)
