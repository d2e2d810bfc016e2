"""Mixture-of-Experts layer with EXPERT PARALLELISM over the mesh model
axis.

The 2017 reference predates MoE; this is a first-class TPU-native addition
(task spec: distributed modes incl. expert parallelism are first-class).
Design follows the XLA-friendly capacity-based dispatch of Switch/GShard:
top-1 routing, fixed expert capacity C, one-hot dispatch/combine einsums —
all static shapes, so the whole layer jits into dense MXU work.

Under a mesh whose ``model`` axis is >1, the expert-major tensors
([E, C, D] dispatch buffers and the [E, ...] expert weights) carry
``with_sharding_constraint(P('model', ...))``: XLA's SPMD partitioner
places each expert group on its own devices and inserts the token
all-to-all for dispatch/combine — the hand-written NCCL alltoall of
GPU MoE frameworks becomes two sharding annotations.

The router's load-balancing auxiliary (Switch Transformer eq. 4,
``num_experts * Σ_e fraction_e * prob_e``) is exposed as the aux output
``<name>@aux_loss`` for the cost to pick up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core import initializers as init
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.layers.base import ApplyContext, register_layer
from paddle_tpu.ops import acc_matmul
from paddle_tpu.parallel.mesh import MODEL_AXIS


def moe_init(conf, in_confs, rng):
    d = in_confs[0].size
    e = conf.attr("num_experts")
    h = conf.attr("expert_hidden")
    std = conf.attr("param_std")
    r = jax.random.split(rng, 3)
    # explicit fan-in stds: the default heuristic reads shape[0], which for
    # expert-major [E, D, H] tensors would be 1/sqrt(num_experts)
    p = {
        "router": init.normal(r[0], (d, e), std or init.default_std(d)),
        "w1": init.normal(r[1], (e, d, h), std or init.default_std(d)),
        "w2": init.normal(r[2], (e, h, conf.size), std or init.default_std(h)),
    }
    if conf.bias:
        p["b1"] = init.zeros((e, h))
        p["b2"] = init.zeros((e, conf.size))
    return p


def _expert_sharding(ctx: ApplyContext, conf):
    """NamedSharding for expert-major [E, C, D] buffers when the layer opted
    into model-axis sharding on a >1 model axis, else None."""
    mesh = ctx.mesh
    if (
        mesh is None
        or conf.shard_axis != MODEL_AXIS
        or mesh.shape.get(MODEL_AXIS, 1) <= 1
    ):
        return None
    return NamedSharding(mesh, P(MODEL_AXIS, None, None))


def _valid_tokens(x):
    """[N] float32, 1 at the true positions of a (nested) sequence's
    flattened tokens; None where every row is a token."""
    if x.is_nested:
        return x.sub_mask(jnp.float32).reshape(-1)
    if x.is_seq:
        return x.mask(jnp.float32).reshape(-1)
    return None


@register_layer("moe", init=moe_init, auto_activation=False)
def moe_apply(conf, params, inputs, ctx: ApplyContext):
    from paddle_tpu.ops.activations import get_activation

    x = inputs[0]
    d = x.data.shape[-1]
    e = conf.attr("num_experts")
    f_act = get_activation(conf.attr("active_type", "relu"))
    cap_factor = conf.attr("capacity_factor", 1.25)

    tokens = x.data.reshape(-1, d)  # [N, D]
    n = tokens.shape[0]
    cap = max(int(n / e * cap_factor), 1)

    logits = tokens @ params["router"].astype(tokens.dtype)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [N, E]
    valid = _valid_tokens(x)
    if valid is not None:
        # padded tokens must not consume expert capacity
        gates = gates * valid[:, None]
    top_gate = jnp.max(gates, axis=-1)  # [N]
    top_idx = jnp.argmax(gates, axis=-1)  # [N]
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)
    if valid is not None:
        onehot = onehot * valid[:, None]

    # position of each token within its expert's capacity (exclusive cumsum)
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot  # [N, E]
    keep = (pos < cap).astype(jnp.float32) * onehot
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    dispatch = keep[..., None] * pos_oh  # [N, E, C]
    combine = dispatch * top_gate[:, None, None]

    sh = _expert_sharding(ctx, conf)
    xin = jnp.einsum("nec,nd->ecd", dispatch.astype(tokens.dtype), tokens)
    if sh is not None:
        xin = jax.lax.with_sharding_constraint(xin, sh)
    h = jnp.einsum("ecd,edh->ech", xin, params["w1"])
    if "b1" in params:
        h = h + params["b1"][:, None, :]
    h = f_act(h)
    y = jnp.einsum("ech,ehd->ecd", h, params["w2"])
    if "b2" in params:
        y = y + params["b2"][:, None, :]
    if sh is not None:
        y = jax.lax.with_sharding_constraint(y, sh)
    out = jnp.einsum("nec,ecd->nd", combine.astype(y.dtype), y)  # [N, Dout]

    # Switch load-balance aux: E * sum_e fraction_of_tokens_e * mean_prob_e.
    # Emitted as a per-row [B, 1] tensor where EVERY row equals the scalar
    # aux: the documented pickup (get_output + sum_cost) reduces per ROW
    # (sum_cost sums axis=-1, cost.py) and CompiledNetwork.cost() then takes
    # the batch MEAN — so the effective coefficient is already batch-size
    # invariant (mean of B identical rows = aux).  Do not pre-divide by B.
    denom = jnp.maximum(jnp.sum(onehot), 1.0)
    frac = jnp.sum(onehot, axis=0) / denom
    prob = jnp.sum(gates, axis=0) / denom
    aux = e * jnp.sum(frac * prob)
    ctx.outputs[conf.name + "@aux_loss"] = SeqTensor(
        jnp.broadcast_to(aux, (x.data.shape[0], 1))
    )

    if valid is not None:
        out = out * valid[:, None].astype(out.dtype)
    out = out.reshape(x.data.shape[:-1] + (conf.size,))
    return SeqTensor(out, x.lengths, x.sub_lengths)


# ---------------------------------------------------------------------------
# moe_topk: top-k routing with no capacity, over the experts this chip holds
# ---------------------------------------------------------------------------
#
# The routing of today's large expert models (sigmoid scores over ALL the
# experts, the k largest a token, weights normalised over the chosen, a
# shared expert every token passes) for a chip that holds a RANGE of the
# experts, `experts_held`: the router keeps all its outputs, the choice and
# the normalisation run over all of them, and the layer returns
#
#     shared(x) + sum over the chosen e inside the range of w_e E_e(x)
#
# What the experts held elsewhere would add is left out; summing the routed
# parts of every share (and the shared expert once) gives the whole layer
# (tests/test_hybrid_lm.py).  No token is ever dropped: the (token, choice)
# pairs are sorted by expert, the pairs whose expert is not held sort last,
# and `jax.lax.ragged_dot` multiplies each held expert's rows by its matrix.
# Shapes are static, and sized by the rows that exist rather than by the
# worst case (every token chooses k held experts: N x k rows): the sorted
# rows are worked through in passes of `held_rows_bound` rows, a bound taken
# from the shapes alone, the first pass always and a further one for every
# `held_rows_bound` rows beyond it (`<name>@rows_over_bound` counts those).
# Where the bound is N x k (every expert held, or a handful of tokens) there
# is the one pass and no loop.
#
# A separate layer from `moe` above, whose top-1 softmax routing into
# capacity slots (one-hot dispatch einsums, dropped overflow, the Switch
# auxiliary loss, experts sharded over the mesh) shares no step with this.


def moe_topk_init(conf, in_confs, rng):
    d = in_confs[0].size
    n = conf.attrs["num_experts"]
    lo, hi = conf.attrs["experts_held"]
    h, sh = conf.attrs["expert_hidden"], conf.attr("shared_hidden", 0)
    r = jax.random.split(rng, 5)
    p = {
        "router": init.normal(r[0], (d, n), init.default_std(d)),
        # added to the scores for the choice alone (the bias by which such
        # models balance their experts' load); nothing here updates it
        "router_bias": init.zeros((n,)),
        "w1": init.normal(r[1], (hi - lo, d, h), init.default_std(d)),
        "w2": init.normal(r[2], (hi - lo, h, conf.size), init.default_std(h)),
    }
    if sh:
        p["shared_w1"] = init.normal(r[3], (d, sh), init.default_std(d))
        p["shared_w2"] = init.normal(r[4], (sh, conf.size), init.default_std(sh))
    return p


# The held experts' block works on at most this many times the (token, choice)
# rows a router that spreads its choices evenly sends to the experts held
# here.  Twice: a layer whose load is balanced (what the correction bias is
# for) stays under it step after step, so the block's arrays are sized once,
# from the shapes; a layer that is not pays one more pass of the block for
# each further `held_rows_bound` rows and loses none.
_ROWS_OVER_EVEN_SHARE = 2


def held_rows_bound(n, k, held, num_experts):
    """Rows of one pass of the held experts' block, from the shapes alone:
    the even share of `n * k` pairs that `held` of `num_experts` experts
    draw, times `_ROWS_OVER_EVEN_SHARE`, rounded up to the 512-row tile of
    XLA:TPU's grouped product (to 8 rows below one tile), and never more
    than the pairs there are."""
    pairs = n * k
    want = -(-_ROWS_OVER_EVEN_SHARE * pairs * held // num_experts)
    tile = 512 if want >= 512 else 8
    return min(pairs, -(-want // tile) * tile)


# -- the grouped product: rows sorted by group times each group's matrix ------
#
# One contract, two kernels.  `dot(xs [rows, K], w [groups, K, N], sizes
# [groups] int32) -> [rows, N]` in xs' type with float32 sums; rows past the
# last group are UNDEFINED (whatever the buffer held, NaN included) and so are
# their gradients, which is why `_experts` cuts them out on both sides.
#
# `_xla_dot`: `jax.lax.ragged_dot`, which XLA:TPU rewrites into a kernel of its
# own (`ragged-dot-none`, tiles of 512 x 128 x 128 whatever the rows a group
# holds).  It takes every shape, type and mesh, and is the path of every
# backend but the TPU.
# `grouped_product.grouped_dot`: the Pallas kernels of `ops/grouped_product.py` (the product;
# the row gradient, the same kernel on the matrices read transposed; the
# matrices' gradient), whose grid follows the row tiles that hold rows: an
# empty group costs nothing, a nearly empty layer a handful of tile visits.
#
# Which of the two a layer takes is chosen in `_grouped_dot`, at trace time,
# from what the code can observe, and counted there.
#
# The sweep that chose (scripts/grouped_product_sweep.py; my chip runs, PR 39:
# `chiprun_out/p39_sweep4.json`, the own kernels' `p39_sweep5.json`; one v5e
# chip, bfloat16, a pass of 3,072 rows over 8 held experts, the cell's two
# products: w1 = [3072, 2688] x [8, 2688, 1856], w2 = [3072, 1856] x [8, 1856,
# 2688]).  ms of the kernel alone, from the device's profile, by routing: even
# (192 rows an expert) / full (384) / starved (0-16).  `megablox` is the
# grouped product jax ships (`jax.experimental.pallas.ops.tpu.megablox`), its
# tiles (rows x K x N) of the product at hand, K for the whole dimension:
#
#                    XLA's            megablox         megablox         megablox         megablox         megablox         ops/grouped_
#                    ragged-dot       128 x 128 x 128  512 x 512 x 512* 256 x 512 x 512  256 x K x 1024*  128 x K x 1024*  product.py
#   w1 product       1.00/1.20/0.60   1.13/1.70/0.42   0.42/0.50/0.25   0.33/0.45/0.17   0.20/0.26/0.10   0.14/0.20/0.06   0.18/0.23/0.09
#   w1 row gradient  1.18/1.42/0.71   1.35/2.02/0.51   0.42/0.51/0.25   0.35/0.46/0.17   0.19/0.26/0.10   0.13/0.20/0.05   0.19/0.25/0.09
#   w1 matrices'     1.61/2.13/1.15   2.18/3.28/0.90   0.51/0.60/0.34   0.34/0.43/0.21   0.31/0.39/0.20   0.20/0.27/0.11   0.22/0.28/0.14
#   w2 product       1.18/1.42/0.71   1.52/2.28/0.58   0.39/0.47/0.24   0.32/0.43/0.16   0.21/0.28/0.11   0.18/0.25/0.07   0.18/0.24/0.09
#   w2 row gradient  1.00/1.20/0.60   1.56/2.34/0.59   0.38/0.46/0.23   0.33/0.44/0.17   0.19/0.25/0.09   0.16/0.22/0.07   0.17/0.23/0.09
#   w2 matrices'     1.38/1.71/1.06   2.10/3.16/0.90   0.48/0.57/0.33   0.31/0.40/0.20   0.29/0.37/0.19   0.22/0.29/0.15   0.22/0.29/0.14
#   (* the matrices' gradient at 512 x 512 x 512 as is, else 512 x 512 x 1024 for the products; 256 x 512 x 1024 and
#    256 x 1024 x 1024; 128 x K x 512 for w1, whose 128 x K x 1024 is over the 16 MiB of VMEM a kernel gets unasked)
#
# What it says.  (a) The row tile: 128, in every routing; 256 or 512 rows
# multiply the padding of every group that does not end on a tile, 64 gains
# nothing.  (b) The whole K in one block: the block of a group's matrix then
# stays in VMEM over the row tiles the group spans and is read once, and the
# kernel runs at what the product must read (108 MB in the even routing: 0.13
# ms at 819 GB/s); with K cut every visit reads its [K, tn] column again.
# (c) N as wide as the block's bytes allow (`ops/grouped_product._BLOCK_BYTES`:
# 12 MiB, which holds a whole matrix of the cell; 6 and 3 MiB cost 0.01-0.04
# ms a call).  (d) The shipped kernels' stock tiling loses to XLA's kernel;
# tiled for the shape they win by 5 to 8 times in the even routing, and by
# more where a layer is starved.  (e) The program's own kernels are the same
# layout and as fast in the step; the shipped ones, wired in first, cost the
# cell's warm boot 3.8 s (they work out their tiles' metadata with `jnp.repeat`,
# `histogram` and `roll` inside each jitted kernel, traced and lowered a kernel),
# the own ones 1 s (PERF.md section 6, PR 39).  An operand whose last
# dimension is no multiple of 128 (the 1,856 of w1) is copied into the
# row-major tiling before either path's kernel (0.14 ms alone; in the step the
# cast of the float32 master writes it).


def _xla_dot(xs, w, sizes):
    return jax.lax.ragged_dot(xs, w, sizes, preferred_element_type=xs.dtype)


def _grouped_dot(rows, d, h, dtypes, mesh):
    """-> (dot, why): the grouped product a layer takes for passes of `rows`
    rows between widths d and h and operands of `dtypes`, and where that is
    `_xla_dot`, why.  The kernels where the backend is the TPU, their row
    tile divides the pass (from 512 rows on `held_rows_bound` gives whole
    tiles; the few rows of a small layer keep XLA's), the types are ones
    they take and the program is one device's: XLA partitions no Mosaic
    kernel, and no job runs this layer on a mesh yet.  Counted:
    `moe_grouped_kernel_layers` / `moe_grouped_xla_layers`, one a layer traced."""
    from paddle_tpu.utils.timers import global_stats

    if jax.default_backend() != "tpu":
        why = f"the backend is {jax.default_backend()!r}, not 'tpu'"
    elif mesh is not None and mesh.size > 1:
        why = f"the mesh {dict(mesh.shape)} holds {mesh.size} devices"
    else:
        from paddle_tpu.ops import grouped_product  # Pallas is imported where it is used

        why = grouped_product.supported(rows, d, h, dtypes)
    global_stats.incr("moe_grouped_xla_layers" if why else "moe_grouped_kernel_layers")
    return (_xla_dot if why else grouped_product.grouped_dot), why


def _experts(f_act, xs, w1, w2, sizes, live, dot=_xla_dot):
    """The two grouped products over rows sorted by expert.  Rows past the
    last group are neither computed nor defined: they are cut out on both
    sides of each product, so nothing flows back through them either."""
    hmid = f_act(jnp.where(live, dot(xs, w1, sizes), 0))
    return jnp.where(live, dot(hmid, w2, sizes), 0)


def _further_passes(rows, bound):
    """Passes of `bound` rows beyond the first that `rows` rows take."""
    return jnp.maximum(-(-rows // bound) - 1, 0)


def _passes(k, bound, order, group_sizes):
    """-> (pass_rows, passes) for the sorted pairs cut into passes of `bound`
    rows.  pass_rows(b) gives pass b's pairs, their tokens, which of its
    rows hold a held pair, and each held expert's rows among them; `passes`
    is how many hold any row, and at least one."""
    edges = jnp.concatenate([jnp.zeros((1,), group_sizes.dtype), jnp.cumsum(group_sizes)])
    rows = edges[-1]
    order = jnp.pad(order, (0, -order.shape[0] % bound))

    def pass_rows(b):
        start = b * bound
        pair = jax.lax.dynamic_slice(order, (start,), (bound,))
        live = (start + jnp.arange(bound) < rows)[:, None]
        cut = jnp.clip(edges, start, start + bound)
        return pair, pair // k, live, cut[1:] - cut[:-1]

    return pass_rows, 1 + _further_passes(rows, bound)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _held_experts(f_act, k, bound, dot, tokens, w1, w2, weights, order, group_sizes):
    """sum over a token's held choices e of weights[token, e] E_e(token), [N, D].

    `order` lists the (token, choice) pairs sorted by expert, the held ones
    first; `group_sizes` counts each held expert's; `dot` is the grouped
    product (`_grouped_dot`).  The rows are worked
    through in passes of `bound`: the first always, each further one only
    while rows are left, so the arrays are `bound` rows whatever the
    routing and the cost follows the rows that exist.  A pass gathers its
    tokens' rows, multiplies, and adds each weighted result row to its
    token (a scatter-add of `bound` rows; float32 sums).

    Nothing but the arguments is kept for the way back: each pass is
    computed again there, one at a time.

    The way forward and the way back are jitted functions of their own, so
    the expert layers of one shape share ONE trace and ONE lowering of each
    (XLA inlines them under each layer's scope): a model's start pays the
    Python of one layer's passes and kernels, not of every layer's (PERF.md
    section 6, PR 39)."""
    return _forward(f_act, k, bound, dot, tokens, w1, w2, weights, order, group_sizes)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _forward(f_act, k, bound, dot, tokens, w1, w2, weights, order, group_sizes):
    pass_rows, passes = _passes(k, bound, order, group_sizes)
    wflat = weights.reshape(-1)

    def add_pass(b, out):
        pair, tok, live, sizes = pass_rows(b)
        ys = _experts(f_act, jnp.where(live, tokens[tok], 0), w1, w2, sizes, live, dot)
        return out.at[tok].add(ys.astype(jnp.float32) * wflat[pair][:, None])

    out = add_pass(0, jnp.zeros((tokens.shape[0], w2.shape[-1]), jnp.float32))
    if bound < order.shape[0]:
        out = jax.lax.fori_loop(1, passes, add_pass, out)
    return out.astype(tokens.dtype)


def _held_experts_fwd(f_act, k, bound, dot, *args):
    return _forward(f_act, k, bound, dot, *args), args


def _held_experts_bwd(f_act, k, bound, dot, res, g):
    return (*_backward(f_act, k, bound, dot, *res, g), None, None)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _backward(f_act, k, bound, dot, tokens, w1, w2, weights, order, group_sizes, g):
    # as `jax.checkpoint` does for what it computes again: the way back reads
    # its own copies of the arguments, so none made for the way forward (the
    # weights as the loop holds them) has to live until here
    tokens, w1, w2 = jax.lax.optimization_barrier((tokens, w1, w2))
    pass_rows, passes = _passes(k, bound, order, group_sizes)
    wflat = weights.reshape(-1)

    def pass_grads(b, g_tokens, g_wflat):
        pair, tok, live, sizes = pass_rows(b)
        ys, back = jax.vjp(lambda xs, w1, w2: _experts(f_act, xs, w1, w2, sizes, live, dot),
                           jnp.where(live, tokens[tok], 0), w1, w2)
        g_rows = g[tok].astype(jnp.float32)
        g_xs, g_w1, g_w2 = back((g_rows * wflat[pair][:, None]).astype(ys.dtype))
        return (g_tokens.at[tok].add(jnp.where(live, g_xs, 0).astype(jnp.float32)),
                g_wflat.at[pair].add(jnp.sum(ys.astype(jnp.float32) * g_rows, axis=-1)),
                g_w1, g_w2)

    grads = pass_grads(0, jnp.zeros(tokens.shape, jnp.float32), jnp.zeros(wflat.shape, jnp.float32))
    if bound < order.shape[0]:
        def further(b, acc):
            g_tokens, g_wflat, g_w1, g_w2 = pass_grads(b, acc[0], acc[1])
            return g_tokens, g_wflat, acc[2] + g_w1, acc[3] + g_w2

        grads = jax.lax.fori_loop(1, passes, further, grads)
    g_tokens, g_wflat, g_w1, g_w2 = grads
    return (g_tokens.astype(tokens.dtype), g_w1, g_w2,
            g_wflat.reshape(weights.shape).astype(weights.dtype))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _route(tokens, router, router_bias, k, score_fn, scaling):
    """-> (chosen expert ids [N, k] int32, their weights [N, k] float32).
    Scores in float32 over all the router's outputs.  Jitted, as the passes
    are: the layers of one shape share one trace of it."""
    logits = jnp.matmul(tokens, router.astype(tokens.dtype),
                        preferred_element_type=jnp.float32)
    if score_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(scores + router_bias.astype(jnp.float32), k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * scaling
    return chosen.astype(jnp.int32), weights


@register_layer("moe_topk", init=moe_topk_init, auto_activation=False)
def moe_topk_apply(conf, params, inputs, ctx: ApplyContext):
    from paddle_tpu.ops.activations import get_activation

    x = inputs[0]
    d = x.data.shape[-1]
    k = conf.attrs["top_k"]
    lo, hi = conf.attrs["experts_held"]
    held = hi - lo
    f_act = get_activation(conf.attr("active_type", "relu2"))
    tokens = x.data.reshape(-1, d)  # [N, D]
    n = tokens.shape[0]
    valid = _valid_tokens(x)

    with jax.named_scope("moe_route"):
        chosen, weights = _route(tokens, params["router"], params["router_bias"], k,
                                 conf.attr("score_fn", "sigmoid"), conf.attr("scaling", 1.0))
        here = (chosen >= lo) & (chosen < hi)
        if valid is not None:  # a padded position asks nothing of any expert
            here = here & (valid[:, None] > 0)
        # pairs in (token, choice) order; those held sort first, by expert
        key = jnp.where(here, chosen - lo, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        rows = jnp.sum(group_sizes)

    bound = held_rows_bound(n, k, held, conf.attrs["num_experts"])
    dot, _ = _grouped_dot(bound, d, conf.attrs["expert_hidden"],
                          (tokens.dtype, params["w1"].dtype), ctx.mesh)
    with jax.named_scope("moe_experts"):
        out = _held_experts(f_act, k, bound, dot, tokens, params["w1"], params["w2"],
                            jnp.where(here, weights, 0.0), order, group_sizes)

    if "shared_w1" in params:
        with jax.named_scope("moe_shared"):
            out = out + acc_matmul(f_act(acc_matmul(tokens, params["shared_w1"])),
                                   params["shared_w2"])

    # counters, a [B, 1] row each as @aux_loss above: the (token, choice)
    # rows computed here, the passes of the held experts' block beyond the
    # first that they took, and the rows dropped, which this routing has none of
    b = x.data.shape[0]
    for counter, value in (("rows_held", rows),
                           ("rows_over_bound", _further_passes(rows, bound)),
                           ("rows_dropped", jnp.sum(here) - rows)):
        ctx.outputs[f"{conf.name}@{counter}"] = SeqTensor(
            jnp.broadcast_to(value, (b, 1)).astype(jnp.int32))

    if valid is not None:
        out = out * valid[:, None].astype(out.dtype)
    out = out.reshape(x.data.shape[:-1] + (conf.size,))
    return SeqTensor(out, x.lengths, x.sub_lengths)
