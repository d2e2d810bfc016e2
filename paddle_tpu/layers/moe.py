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
# Shapes are static for the worst case (every token chooses k held experts:
# N x k rows); the products are spent on the rows that exist.
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, order, inverse, k):
    """x[order // k]: row i of the result is the token of the i-th sorted
    (token, choice) pair.  `inverse` undoes `order`.  The transpose is a
    gather too (un-sort, then add a token's k pairs), not a scatter."""
    return jnp.take(x, order // k, axis=0)


def _take_rows_fwd(x, order, inverse, k):
    return _take_rows(x, order, inverse, k), (inverse, x.shape[0])


def _take_rows_bwd(k, res, g):
    inverse, n = res
    return jnp.take(g, inverse, axis=0).reshape(n, k, -1).sum(axis=1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _unsort(y, order, inverse):
    """y[inverse]: from sorted rows back to (token, choice) order."""
    return jnp.take(y, inverse, axis=0)


def _unsort_fwd(y, order, inverse):
    return _unsort(y, order, inverse), order


def _unsort_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def _route(tokens, params, k, score_fn, scaling):
    """-> (chosen expert ids [N, k] int32, their weights [N, k] float32).
    Scores in float32 over all the router's outputs."""
    logits = jnp.matmul(tokens, params["router"].astype(tokens.dtype),
                        preferred_element_type=jnp.float32)
    if score_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(scores + params["router_bias"].astype(jnp.float32), k)
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
        chosen, weights = _route(tokens, params, k, conf.attr("score_fn", "sigmoid"),
                                 conf.attr("scaling", 1.0))
        here = (chosen >= lo) & (chosen < hi)
        if valid is not None:  # a padded position asks nothing of any expert
            here = here & (valid[:, None] > 0)
        # pairs in (token, choice) order; those held sort first, by expert
        key = jnp.where(here, chosen - lo, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        rows = jnp.sum(group_sizes)
        live = (jnp.arange(n * k) < rows)[:, None]

    @jax.checkpoint
    def held_experts(tokens, w1, w2, weights):
        # Recomputed on the way back rather than kept: the sorted rows and the
        # experts' hidden rows are sized for the worst case, N x k rows, of
        # which a chip holding held / num_experts of the experts fills that
        # share; keeping them for four layers cost 2 GB of the chip here.
        # Rows past the last group are neither computed nor defined: they are
        # cut out on both sides of each product, so nothing flows back
        # through them either.
        xs = jnp.where(live, _take_rows(tokens, order, inverse, k), 0)
        hmid = jax.lax.ragged_dot(xs, w1, group_sizes, preferred_element_type=xs.dtype)
        hmid = f_act(jnp.where(live, hmid, 0))
        ys = jax.lax.ragged_dot(hmid, w2, group_sizes, preferred_element_type=xs.dtype)
        pairs = _unsort(jnp.where(live, ys, 0), order, inverse).reshape(n, k, -1)
        return jnp.einsum("nk,nkd->nd", weights.astype(pairs.dtype), pairs,
                          preferred_element_type=jnp.float32).astype(tokens.dtype)

    with jax.named_scope("moe_experts"):
        out = held_experts(tokens, params["w1"], params["w2"], jnp.where(here, weights, 0.0))

    if "shared_w1" in params:
        with jax.named_scope("moe_shared"):
            out = out + acc_matmul(f_act(acc_matmul(tokens, params["shared_w1"])),
                                   params["shared_w2"])

    # counters, a [B, 1] row each as @aux_loss above: the (token, choice)
    # rows computed here, and the rows dropped, which this routing has none of
    b = x.data.shape[0]
    ctx.outputs[conf.name + "@rows_held"] = SeqTensor(jnp.broadcast_to(rows, (b, 1)))
    ctx.outputs[conf.name + "@rows_dropped"] = SeqTensor(
        jnp.broadcast_to(jnp.sum(here) - rows, (b, 1)).astype(jnp.int32))

    if valid is not None:
        out = out * valid[:, None].astype(out.dtype)
    out = out.reshape(x.data.shape[:-1] + (conf.size,))
    return SeqTensor(out, x.lengths, x.sub_lengths)
