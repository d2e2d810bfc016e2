"""Cost layers — reference: paddle/gserver/layers/CostLayer.cpp (cross-entropy
family, SumOfSquaresCostLayer, HuberCost, RankingCost, SmoothL1Cost, SumCost).

Every cost layer emits a per-sample cost column [B, 1]; the train step takes
the batch mean (the reference sums per-sample costs then divides by batch,
trainer/TrainerInternal.cpp:131 Argument::sum).  Sequence costs mask padding
and sum over valid timesteps.  jax.grad over the mean replaces each cost
layer's hand-written backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core import initializers as init
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.layers.base import register_layer
from paddle_tpu.ops import acc_matmul
from paddle_tpu.utils.timers import global_stats

_EPS = 1e-10
# two-sided probability clip for the BCE family: must be representable in
# float32 — 1.0 - 1e-10 rounds to exactly 1.0 (f32 has ~7 digits), which
# made log(1-p) = -inf for saturated probabilities
_BCE_EPS = 1e-6


def _fused_ce_from_logits(x: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """-log softmax(x)[ids] WITHOUT materializing the [N, V] log-prob
    matrix: cost = logsumexp(x) - x[ids].

    jax.nn.log_softmax writes a full f32 [N, V] block (524 MB for 4096x32k)
    just so take_along_axis can read ONE element per row — at big vocab the
    HBM traffic of that round trip dominates the whole cost layer (~5 ms of
    a 24 ms transformer-base step).  The two-reduction form reads the bf16
    logits once, accumulates in f32 (promoted per-element inside the fused
    reduction — XLA never materializes the cast), and writes [N] scalars.
    The backward autodiffs to softmax(x)·g − one_hot·g, recomputed inside
    one bwd fusion at the logits dtype."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)  # fuses into each reduction below; never stored whole
    m = jax.lax.stop_gradient(jnp.max(xf, axis=-1, keepdims=True))
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(xf - m), axis=-1))
    picked = jnp.take_along_axis(x, ids[..., None], axis=-1)[..., 0]
    return lse - picked.astype(acc)


def _per_sample(cost: jnp.ndarray, tensor: SeqTensor) -> SeqTensor:
    """Reduce a per-timestep cost [B, T] to per-*token-summed* [B, 1] with
    masking, or pass through [B] -> [B, 1]."""
    if tensor.is_seq and cost.ndim == 2:
        cost = jnp.sum(cost * tensor.mask(cost.dtype), axis=1)
    return SeqTensor(cost[:, None])


def _label_ids(label: SeqTensor) -> jnp.ndarray:
    ids = label.data.astype(jnp.int32)
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    return ids


# ---------------------------------------------------------------------------


@register_layer("cross_entropy", auto_activation=False, full_precision=True)
def cross_entropy_apply(conf, params, inputs, ctx):
    """-log p[label]; input is a probability distribution (softmax output),
    reference MultiClassCrossEntropy (CostLayer.cpp).  When the producing
    layer's activation was softmax, the compiler exposes its pre-activation
    as `<name>@logits` and we fuse into log-softmax CE instead (stable, one
    less kernel).  Where the producer is a recurrent_group with a hoisted
    output layer it exposes the same logits as the [T*B, V] rows they were
    computed as (`<name>@logits_rows`, recurrent_group.HoistedRows): the
    reduction over V then runs on the rows where they lie, the ids folded
    into their order and the per-row cost unfolded to [B, T], and nothing
    reads (or copies) the [B, T, V] view.  Those rows may be the ones that
    hold a token alone, R a shard, where the feeder bounded the scanned
    slot's tokens (core.batch.RowBound): a fill row past a shard's count
    is dropped by the unfold, so its cost, and its gradient, reach no sum.
    Which of the two a layer traced took is counted:
    ce_hoisted_rows_layers / ce_batch_major_layers."""
    prob, label = inputs[0], inputs[1]
    ids = _label_ids(label)
    hoisted = ctx.outputs.get(conf.inputs[0] + "@logits_rows")
    if hoisted is not None:
        global_stats.incr("ce_hoisted_rows_layers")
        cost = _fused_ce_from_logits(hoisted.rows, hoisted.fold(ids))
        return _per_sample(hoisted.unfold(cost), prob)
    global_stats.incr("ce_batch_major_layers")
    logits = ctx.outputs.get(conf.inputs[0] + "@logits")
    if logits is not None:
        return _per_sample(_fused_ce_from_logits(logits.data, ids), prob)
    p = jnp.take_along_axis(prob.data, ids[..., None], axis=-1)[..., 0]
    cost = -jnp.log(jnp.maximum(p, _EPS))
    return _per_sample(cost, prob)


@register_layer("softmax_with_cost", auto_activation=False, full_precision=True)
def softmax_with_cost_apply(conf, params, inputs, ctx):
    """Fused log-softmax cross-entropy from *logits* — numerically stable
    TPU-native fast path the DSL uses for classification_cost when the input
    activation is softmax (fuses the reference's softmax + cross_entropy
    pair into one lax reduction)."""
    logits, label = inputs[0], inputs[1]
    ids = _label_ids(label)
    return _per_sample(_fused_ce_from_logits(logits.data, ids), logits)


@register_layer("soft_binary_class_cross_entropy", auto_activation=False, full_precision=True)
def soft_bce_apply(conf, params, inputs, ctx):
    """Per-dim BCE with soft targets (SoftBinaryClassCrossEntropy)."""
    prob, label = inputs[0], inputs[1]
    p = jnp.clip(prob.data, _BCE_EPS, 1.0 - _BCE_EPS)
    t = label.data
    cost = -jnp.sum(t * jnp.log(p) + (1.0 - t) * jnp.log(1.0 - p), axis=-1)
    return _per_sample(cost, prob)


@register_layer("multi_binary_label_cross_entropy", auto_activation=False, full_precision=True)
def multi_binary_label_ce_apply(conf, params, inputs, ctx):
    """BCE where the label is a multi-hot vector (MultiBinaryLabelCrossEntropy).
    The label slot arrives densified to multi-hot [B, D] by the feeder; an
    integer ID label one-hots (the reference's sparse id-matrix form)."""
    prob, label = inputs[0], inputs[1]
    p = jnp.clip(prob.data, _BCE_EPS, 1.0 - _BCE_EPS)
    t = _label_as_dense(label, prob.data.shape[-1])
    cost = -jnp.sum(t * jnp.log(p) + (1.0 - t) * jnp.log(1.0 - p), axis=-1)
    return _per_sample(cost, prob)


def _label_as_dense(label: SeqTensor, width: int) -> jnp.ndarray:
    """A cost's label operand as a dense [.., width] block: already-dense
    labels pass through; integer ID labels one-hot against the prediction
    width — the reference's sparse-label support in these costs
    (SumOfSquaresCostLayer / MultiBinaryLabelCrossEntropy accept a sparse
    id matrix, CostLayer.cpp)."""
    t = label.data
    if jnp.issubdtype(t.dtype, jnp.integer):
        from paddle_tpu.layers.base import is_sparse_ids

        if is_sparse_ids(label, width):
            # padded multi-id rows (the feeder's big-vocab sparse_ids form,
            # [.., nnz] with sentinel == width): multi-hot by summing the
            # one-hots — sentinels one-hot to all-zero rows, duplicates
            # clamp to 1 (NO_VALUE sparse labels are binary).  Dispatch is
            # on the EXACT sparse_ids flag (base.is_sparse_ids contract) —
            # a plain [B, T] id-sequence label must keep per-frame one-hots
            return jnp.minimum(
                jnp.sum(
                    jax.nn.one_hot(t, width, dtype=jnp.float32), axis=-2
                ),
                1.0,
            )
        return jax.nn.one_hot(_label_ids(label), width, dtype=jnp.float32)
    return t


@register_layer("square_error", auto_activation=False, full_precision=True)
def square_error_apply(conf, params, inputs, ctx):
    """0.5 * sum((x - y)^2) per sample (SumOfSquaresCostLayer; an integer
    label acts as the one-hot row, the reference's sparse-label form)."""
    x, y = inputs[0], inputs[1]
    d = x.data - _label_as_dense(y, x.data.shape[-1])
    cost = 0.5 * jnp.sum(jnp.square(d), axis=-1)
    return _per_sample(cost, x)


@register_layer("smooth_l1", auto_activation=False, full_precision=True)
def smooth_l1_apply(conf, params, inputs, ctx):
    """SmoothL1Cost: 0.5 d^2 if |d|<1 else |d|-0.5, summed per sample."""
    x, y = inputs[0], inputs[1]
    d = x.data - y.data
    a = jnp.abs(d)
    cost = jnp.sum(jnp.where(a < 1.0, 0.5 * d * d, a - 0.5), axis=-1)
    return _per_sample(cost, x)


@register_layer("huber_regression", auto_activation=False, full_precision=True)
def huber_regression_apply(conf, params, inputs, ctx):
    delta = conf.attr("delta", 1.0)
    x, y = inputs[0], inputs[1]
    a = jnp.abs(x.data - y.data)
    cost = jnp.sum(
        jnp.where(a <= delta, 0.5 * a * a, delta * (a - 0.5 * delta)), axis=-1
    )
    return _per_sample(cost, x)


@register_layer("huber_classification", auto_activation=False, full_precision=True)
def huber_classification_apply(conf, params, inputs, ctx):
    """HuberTwoClassification: labels {0,1} -> y in {-1,+1},
    cost = 0 if y*f>1, (1-y*f)^2 if -1<=y*f<=1, -4*y*f if y*f<-1."""
    x, label = inputs[0], inputs[1]
    f = x.data[..., 0] if x.data.ndim >= 2 else x.data
    y = 2.0 * _label_ids(label).astype(f.dtype) - 1.0
    z = y * f
    cost = jnp.where(z > 1.0, 0.0, jnp.where(z < -1.0, -4.0 * z, jnp.square(1.0 - z)))
    return _per_sample(cost, x)


@register_layer("rank_cost", auto_activation=False, full_precision=True)
def rank_cost_apply(conf, params, inputs, ctx):
    """RankingCost: pairwise logistic loss on score difference
    (CostLayer.cpp RankingCost::forwardImp)."""
    left, right, label = inputs[0], inputs[1], inputs[2]
    o = left.data[..., 0] - right.data[..., 0]
    t = label.data
    t = t[..., 0] if t.ndim >= 2 else t
    t = t.astype(o.dtype)
    cost = jax.nn.softplus(o) - t * o
    return _per_sample(cost, left)


@register_layer("sum_cost", auto_activation=False, full_precision=True)
def sum_cost_apply(conf, params, inputs, ctx):
    """SumCostLayer: cost = sum of input row."""
    x = inputs[0]
    cost = jnp.sum(x.data, axis=-1)
    if x.is_seq:
        cost = jnp.sum(cost * x.mask(cost.dtype), axis=-1) if cost.ndim == 2 else cost
    return _per_sample(cost, x)


@register_layer("cross_entropy_with_selfnorm", auto_activation=False, full_precision=True)
def ce_selfnorm_apply(conf, params, inputs, ctx):
    """MultiClassCrossEntropyWithSelfNorm: CE + alpha * log(Z)^2 where Z is
    the row sum of the (softmax) output."""
    prob, label = inputs[0], inputs[1]
    alpha = conf.attr("softmax_selfnorm_alpha", 0.1)
    ids = _label_ids(label)
    z = jnp.sum(prob.data, axis=-1)
    p = jnp.take_along_axis(prob.data, ids[..., None], axis=-1)[..., 0] / jnp.maximum(
        z, _EPS
    )
    cost = -jnp.log(jnp.maximum(p, _EPS)) + alpha * jnp.square(jnp.log(jnp.maximum(z, _EPS)))
    return _per_sample(cost, prob)


@register_layer("multi_nn_cost", auto_activation=False, full_precision=True)
def multi_nn_cost_apply(conf, params, inputs, ctx):
    """Joint training objective of a model_type('multi_nn') ensemble: the
    sum of every sub-network's mean cost — the reference trainer sums all
    output Arguments of MultiNetwork::forward (Argument::sum over outArgs,
    TrainerInternal.cpp), which concatenates the sub-networks' outputs
    (MultiNetwork.cpp:67-95).  Gradients flow into every sub-network from
    this single scalar."""
    total = 0.0
    for t in inputs:
        total = total + jnp.mean(t.data)
    return SeqTensor(jnp.broadcast_to(total, (1,)))


# ---------------------------------------------------------------------------
# looped_exit_cost — the expected loss of a looped stack under its exit gate
# ---------------------------------------------------------------------------


def exit_distribution(z: jnp.ndarray) -> jnp.ndarray:
    """log p over the passes from the exit gate's logits z [R, ...]:
    p^1 = g^1, p^t = g^t prod_{j<t}(1 - g^j), and the LAST pass takes what is
    left, p^R = prod_{j<R}(1 - g^j), so p sums to 1 whatever the gate says of
    the last pass.  log(1 - g) is log_sigmoid(-z), never log(1 - sigmoid(z))."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)  # log prod_{j<=t}(1 - g^j)
    before = jnp.concatenate([jnp.zeros_like(z[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([jax.nn.log_sigmoid(z[:-1]) + before[:-1], before[-1:]], axis=0)


def looped_exit_cost_init(conf, in_confs, rng):
    """The head's and the gate's own shapes: the storage is theirs (the
    layers named by `param_names` are declared first and own it)."""
    d = in_confs[0].size
    return {"head_w": init.zeros((d, in_confs[1].size)), "gate_w": init.zeros((d, 1)),
            "gate_b": init.zeros((1,))}


@register_layer("looped_exit_cost", init=looped_exit_cost_init, auto_activation=False,
                full_precision=True)
def looped_exit_cost_apply(conf, params, inputs, ctx):
    """inputs: (the layer_loop, its head fc, its exit-gate fc, the labels).
    With x^t the R outputs of the loop's passes (`<loop>@passes`):

        ce^t_i = -log softmax(x^t_i W_out)[label_i];  g^t_i = sigmoid(w_g . x^t_i + b_g)
        p = exit_distribution(g);  H(p) = -sum_t p^t log p^t
        cost of a row = sum over its tokens of [ sum_t p^t ce^t - beta H(p) ]

    The head, the log-softmax and the gather run one pass at a time inside a
    recomputed unit (`lax.map` over `jax.checkpoint`), so one pass's [B, T, V]
    logits are alive at a time, in the forward and in the backward, and what
    is kept is ce^t and the gate's logit, [R, B, T] each.  The head's product
    runs in the compute type as an `fc` would, under the head's own scope
    inside this layer's; the log-softmax's sums, the gate, p and H in
    float32.  The head's and the gate's outputs on the last pass (inputs 1
    and 2) are not read: the last pass takes what the others leave.

    Aux outputs, a row's means over its tokens: `<name>@pass_ce` [B, R] and
    `<name>@exit_p` [B, R]."""
    loop, _, _, label = inputs
    passes = ctx.outputs[conf.inputs[0] + "@passes"].data  # [R, B, T, D]
    ids = _label_ids(label)
    head_w = params["head_w"].astype(ctx.dtype)
    gate_w, gate_b = params["gate_w"].astype(jnp.float32), params["gate_b"].astype(jnp.float32)

    def one_pass(x):
        with jax.named_scope(f"fc:{conf.inputs[1]}"):
            logits = acc_matmul(x.astype(ctx.dtype), head_w)
        ce = _fused_ce_from_logits(logits, ids)
        with jax.named_scope(f"fc:{conf.inputs[2]}"):
            z = jnp.matmul(x.astype(jnp.float32), gate_w,
                           precision=jax.lax.Precision.HIGHEST)[..., 0] + gate_b[0]
        return ce, z

    ce, z = jax.lax.map(jax.checkpoint(one_pass), passes)  # [R, B, T] each, float32
    log_p = exit_distribution(z)
    p = jnp.exp(log_p)
    beta = conf.attr("beta", 0.0)
    token_cost = jnp.sum(p * ce, axis=0) + beta * jnp.sum(p * log_p, axis=0)
    mask = loop.mask(jnp.float32) if loop.is_seq else jnp.ones(token_cost.shape, jnp.float32)
    tokens = jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    for key, value in (("pass_ce", ce), ("exit_p", p)):
        ctx.outputs[f"{conf.name}@{key}"] = SeqTensor(
            (jnp.sum(value * mask, axis=2) / tokens).T)
    return SeqTensor(jnp.sum(token_cost * mask, axis=1)[:, None])
