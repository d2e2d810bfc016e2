"""Registry-sweep gradient checks — every registered layer type is either
finite-diff-checked here or named on the asserted skip list.

The reference's test_LayerGrad.cpp (~2.3k LoC) runs testLayerGrad over
essentially every layer type; the targeted files (test_layer_grad.py and
friends) mirror its depth, while THIS file mirrors its breadth discipline:
``test_every_registered_type_is_swept`` fails the moment someone registers a
new layer type without adding a builder (grad check) or a skip entry
(non-differentiable/structural types only, with the reason stated).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.batch import SeqTensor
from paddle_tpu.core.topology import LayerConf, LayerOutput, reset_auto_names
from paddle_tpu.layers.base import registered_layer_types

from layer_grad_util import check_layer_grad, rand_batch_for

L = paddle.layer
A = paddle.activation
dt = paddle.data_type


@pytest.fixture(autouse=True)
def _reset_names():
    reset_auto_names()
    yield


def dense(dim=8, name="in0"):
    return L.data(name, dt.dense_vector(dim))


def dense_seq(dim=8, name="seq0"):
    return L.data(name, dt.dense_vector_sequence(dim))


def ids(vocab=10, name="ids0"):
    return L.data(name, dt.integer_value(vocab))


def ids_seq(vocab=12, name="idseq0"):
    return L.data(name, dt.integer_value_sequence(vocab))


def img(c=2, s=6, name="img0"):
    return L.data(name, dt.dense_vector(c * s * s), height=s, width=s)


# ---------------------------------------------------------------------------
# types with no gradient to check: integer/decode outputs, constant outputs,
# and structural wiring that never computes anything of its own
# ---------------------------------------------------------------------------

SKIP = {
    "data": "input placeholder, no computation",
    "memory": "scan carry placeholder inside recurrent_group",
    "step_input": "scan slice placeholder inside recurrent_group",
    "agent": "subnet wiring alias, no computation",
    "gather_agent": "generation-time id gather, integer plumbing",
    "scatter_agent": "generation-time id scatter, integer plumbing",
    "print": "identity pass-through with a host-side print",
    "get_output": "aux-output selector, no computation of its own",
    "maxid": "emits integer argmax ids",
    "sampling_id": "emits sampled integer ids",
    "eos_id": "emits end-of-sequence flags (integers)",
    "beam_search": "decode-time search emitting token ids",
    "crf_decoding": "viterbi argmax decode emitting label ids",
    "detection_output": "NMS decode emitting selected boxes",
    "priorbox": "constant prior-box geometry from static shapes",
    "kmax_seq_score": "top-k index selection; output ids feed beam pruning",
}


# ---------------------------------------------------------------------------
# builders: one micro-net per differentiable type.  Value = a callable
# returning either a LayerOutput or (LayerOutput, check_kwargs).
# ---------------------------------------------------------------------------


def _slice_time_out():
    # internal wiring type (memory boot / attention) with no DSL face:
    # build its conf directly
    x = dense_seq(4)
    conf = LayerConf(
        name="st", type="slice_time", size=4, inputs=(x.name,),
        act="identity", bias=False, attrs={"offset": 1},
    )
    return LayerOutput(conf, [x])


def _recurrent_group_out():
    x = dense_seq(5)

    def step(x_t):
        mem = L.memory("h", 5)
        hm = L.fc(mem, 5, act=A.Identity(), bias_attr=False, name="hproj")
        return L.addto([x_t, hm], act=A.Tanh(), bias_attr=True, name="h")

    return L.recurrent_group(step, x, name="grp")


def _gru_step_out():
    x = dense_seq(12)

    def step(x_t):
        mem = L.memory("g", 4)
        return L.gru_step(input=x_t, output_mem=mem, size=4, name="g")

    return L.recurrent_group(step, x, name="ggrp")


def _lstm_step_out():
    x = dense_seq(16)

    def step(x_t):
        om = L.memory("o", 4)
        cm = L.memory("o@cell", 4)
        return L.lstm_step(
            input=x_t, output_mem=om, state_mem=cm, size=4, name="o"
        )

    return L.recurrent_group(step, x, name="lgrp")


def _soft_bce_out():
    x = dense(6)
    t = L.data("t", dt.dense_vector(6))
    pred = L.fc(x, size=6, act=A.Sigmoid())
    topo_probe = paddle.Topology(
        [L.soft_binary_class_cross_entropy_cost(pred, t)]
    )
    batch = rand_batch_for(topo_probe)
    batch["t"] = SeqTensor(jax.nn.sigmoid(batch["t"].data))
    reset_auto_names()
    out = L.soft_binary_class_cross_entropy_cost(
        L.fc(dense(6), size=6, act=A.Sigmoid()), L.data("t", dt.dense_vector(6))
    )
    return out, {"batch": batch}


def _multi_binary_out():
    # sigmoid predictions vs {0,1} multi-label targets
    x = dense(6)
    t = L.data("t", dt.dense_vector(5))
    pred = L.fc(x, size=5, act=A.Sigmoid())
    out = L.multi_binary_label_cross_entropy_cost(pred, t)
    topo = paddle.Topology([out])
    batch = rand_batch_for(topo)
    batch["t"] = SeqTensor((batch["t"].data > 0).astype(jnp.float32))
    return out, {"batch": batch}


def _multibox_out():
    from tests.test_detection import _gt_batch, _ssd_net

    img_l, gt, cost, _ = _ssd_net()
    rng = np.random.RandomState(0)
    b = _gt_batch([[(1, 0.1, 0.1, 0.5, 0.6, 0)], [(2, 0.3, 0.2, 0.9, 0.8, 0)]])
    batch = {
        "image": SeqTensor(
            jnp.asarray(rng.randn(2, 3 * 8 * 8), jnp.float32)
        ),
        "gt": b,
    }
    return cost, {"batch": batch, "check_inputs": False,
                  "atol": 8e-2, "rtol": 8e-2}


def _ctc_out():
    # valid CTC batch (labels avoid the blank, input len >= label length):
    # random labels from rand_batch_for can include the blank id, which has
    # no gradient-consistent alignment — reuse the structured-test helper
    from tests.test_structured import _ctc_batch

    B, T, C, Lmax = 3, 8, 5, 3
    logits, in_len, labels, lab_len = _ctc_batch(B, T, C, Lmax)
    probs = L.data("probs", dt.dense_vector_sequence(C))
    lab = ids_seq(vocab=C, name="lab")
    out = L.warp_ctc(probs, lab, size=C, blank=0)
    batch = {
        "probs": SeqTensor(jnp.asarray(logits), jnp.asarray(in_len)),
        "lab": SeqTensor(jnp.asarray(labels), jnp.asarray(lab_len)),
    }
    return out, {"batch": batch, "atol": 8e-2, "rtol": 8e-2}


def _softmax_with_cost_out():
    # the fused logits->CE kernel has no direct DSL face (classification_cost
    # emits cross_entropy and the compiler fuses through the @logits aux):
    # build its conf directly to exercise the registered impl
    logits = L.fc(dense(), size=5, act=A.Identity())
    lbl = ids(5, "lbl")
    conf = LayerConf(
        name="swc", type="softmax_with_cost", size=1,
        inputs=(logits.name, lbl.name), bias=False,
    )
    return LayerOutput(conf, [logits, lbl])


def _multi_nn_out():
    # the multi_nn ensemble joint cost (built by v1_compat's multi_nn
    # assembly): sum of the sub-networks' mean costs
    a = L.classification_cost(
        L.fc(dense(6, "xa"), size=3, act=A.Softmax()), ids(3, "la")
    )
    b = L.square_error_cost(
        L.fc(dense(4, "xb"), size=2, act=A.Identity()), dense(2, "lb")
    )
    conf = LayerConf(
        name="__multi_nn_cost__", type="multi_nn_cost", size=1,
        inputs=(a.name, b.name), bias=False,
    )
    return LayerOutput(conf, [a, b])


def _layer_loop_out():
    """Three passes of one fc + residual over a sequence, one set of weights."""
    def step(x):
        return L.addto([x, L.fc(x, size=6, act=A.Tanh(), name="mix")], act=A.Identity(), name="res")

    return L.layer_loop(step, dense_seq(6), 3, name="loop")


def _looped_exit_cost_out():
    named = paddle.attr.ParamAttr
    x = L.layer_loop(lambda h: L.fc(h, size=6, act=A.Tanh(), name="mix"),
                     L.embedding(ids_seq(12, "word"), size=6), 2, name="loop")
    head = L.fc(x, size=12, act=A.Softmax(), bias_attr=False, param_attr=named(name="head.w"), name="head")
    gate = L.fc(x, size=1, act=A.Sigmoid(), param_attr=named(name="gate.w"), bias_attr=named(name="gate.b"),
                name="gate")
    return L.looped_exit_cost(x, head=head, gate=gate, label=ids_seq(12, "next"), beta=0.05)


BUILDERS = {
    "fc": lambda: L.fc(dense(), size=6, act=A.Tanh()),
    "embedding": lambda: L.embedding(ids_seq(), size=6),
    "addto": lambda: L.addto(
        [dense(8, "a"), dense(8, "b")], act=A.Tanh(), bias_attr=True
    ),
    "concat": lambda: L.concat([dense(8, "a"), dense(4, "b")]),
    "scaling": lambda: L.scaling(dense(1, "w"), dense(8, "x")),
    "slope_intercept": lambda: L.slope_intercept(
        dense(), slope=2.0, intercept=0.5
    ),
    "interpolation": lambda: L.interpolation(
        dense(1, "w"), dense(8, "a"), dense(8, "b")
    ),
    "sum_to_one_norm": lambda: L.sum_to_one_norm(dense()),
    "row_l2_norm": lambda: L.row_l2_norm(dense()),
    "cos": lambda: L.cos_sim(dense(8, "a"), dense(8, "b"), scale=5.0),
    "cos_vm": lambda: L.cos_sim_vec_mat(dense(3, "v"), dense(12, "m"), size=4),
    "out_prod": lambda: L.out_prod(dense(4, "a"), dense(3, "b")),
    "tensor": lambda: L.tensor(dense(4, "a"), dense(3, "b"), size=5,
                               act=A.Tanh()),
    "trans": lambda: L.trans(dense(12), height=3),
    "resize": lambda: L.resize(dense(12), size=6),
    "rotate": lambda: L.rotate(dense(12, "r"), height=3, width=4),
    "multiplex": lambda: L.multiplex(
        [L.data("sel", dt.integer_value(2)), dense(6, "a"), dense(6, "b")]
    ),
    "clip": lambda: L.clip(dense(), min=-0.4, max=0.4),
    "power": lambda: L.power(dense(1, "w"), dense(8, "x")),
    "dotmul": lambda: L.dotmul_operator(dense(8, "a"), dense(8, "b")),
    "mixed": lambda: L.mixed(
        size=5, input=[
            L.full_matrix_projection(dense(8, "a")),
            L.full_matrix_projection(dense(4, "b")),
        ],
    ),
    "conv_op": lambda: L.conv_operator(
        img(2, 6, "x"),
        L.fc(dense(4, "z"), size=2 * 3 * 3 * 2, act=A.Identity()),
        filter_size=3, num_filters=2, num_channels=2,
    ),
    "context_projection": lambda: L.mixed(
        size=12, input=L.context_projection(
            dense_seq(4), context_len=3, context_start=-1
        ),
    ),
    "linear_comb": lambda: L.linear_comb(dense(3, "w"), dense(12, "x"),
                                         size=4),
    "conv_shift": lambda: L.conv_shift(dense(8, "a"), dense(3, "b")),
    "scale_shift": lambda: L.scale_shift(dense()),
    "prelu": lambda: L.prelu(dense()),
    "layer_norm": lambda: L.layer_norm(dense()),
    "pos_encoding": lambda: L.pos_encoding(dense_seq(6)),
    "data_norm": lambda: L.data_norm(dense()),
    "featmap_expand": lambda: L.featmap_expand(dense(6), num_filters=3),
    "repeat": lambda: L.repeat(dense(6), num_repeats=2),
    "expand": lambda: L.expand(dense(4, "v"), dense_seq(3, "s")),
    "conv": lambda: L.img_conv(img(), filter_size=3, num_filters=3,
                               padding=1, act=A.Relu()),
    "convt": lambda: L.img_conv(img(), filter_size=3, num_filters=3,
                                padding=1, act=A.Relu(), trans=True),
    "pool": lambda: L.img_pool(img(), pool_size=2, stride=2),
    "batch_norm": lambda: (
        L.batch_norm(L.fc(dense(), size=6, act=A.Identity()), act=A.Relu()),
        {"atol": 8e-2, "rtol": 8e-2},
    ),
    "maxout": lambda: L.maxout(img(4, 4), groups=2, num_channels=4),
    "pad": lambda: L.img_pad(img(2, 4), pad_c=[0, 0], pad_h=[1, 1],
                             pad_w=[1, 1]),
    "bilinear_interp": lambda: L.bilinear_interp(img(2, 4), out_size_x=8,
                                                 out_size_y=8),
    "spp": lambda: L.spp(img(2, 6), pyramid_height=2, num_channels=2),
    "norm": lambda: L.img_cmrnorm(img(3, 4), size=3),
    "crop": lambda: L.crop(img(2, 6), axis=2, shape=[4, 4]),
    "block_expand": lambda: L.block_expand(
        img(2, 6), num_channels=2, block_x=2, block_y=2, stride_x=2,
        stride_y=2,
    ),
    "row_conv": lambda: L.row_conv(dense_seq(4), context_len=3),
    "seqpool": lambda: L.pooling(dense_seq(), pooling_type=None),
    "seqlastins": lambda: L.last_seq(dense_seq()),
    "seqconcat": lambda: L.seq_concat(dense_seq(4, "a"), dense_seq(4, "b")),
    "seqreshape": lambda: L.seq_reshape(dense_seq(4), reshape_size=8),
    "sub_seq": lambda: (
        L.sub_seq(
            dense_seq(3, "s"),
            L.data("off", dt.integer_value(2)),
            L.data("sz", dt.integer_value(2)),
        ),
        {"check_inputs": False},
    ),
    "slice_time": _slice_time_out,
    "lstmemory": lambda: L.lstmemory(
        L.fc(dense_seq(4), size=16, act=A.Identity())
    ),
    "gru": lambda: L.grumemory(
        L.fc(dense_seq(4), size=12, act=A.Identity())
    ),
    "recurrent": lambda: L.recurrent(dense_seq(6), act=A.Tanh()),
    # input pre-projected to 5*size gate channels (i, f_row, f_col, o, g)
    "mdlstmemory": lambda: (
        L.mdlstmemory(img(15, 4), size=3),
        {"batch_size": 2, "atol": 8e-2, "rtol": 8e-2},
    ),
    "recurrent_group": _recurrent_group_out,
    "layer_loop": _layer_loop_out,
    "looped_exit_cost": _looped_exit_cost_out,
    "gru_step": _gru_step_out,
    "lstm_step": _lstm_step_out,
    # tiny eps keeps the finite difference inside one top-k routing cell —
    # at the default 1e-3 a perturbation can flip an expert assignment and
    # the fd estimate jumps across the (piecewise) routing boundary
    "moe": lambda: (
        L.moe_layer(dense_seq(6), expert_hidden=4, num_experts=2),
        {"atol": 8e-2, "rtol": 8e-2, "eps": 2e-4},
    ),
    "multi_head_attention": lambda: L.multi_head_attention(
        dense_seq(8), n_heads=2
    ),
    "rms_norm": lambda: L.rms_norm(dense()),
    "mamba2": lambda: L.mamba2(
        dense_seq(6), n_heads=2, head_dim=4, n_groups=1, state_size=3, chunk_size=2
    ),
    # routing is piecewise like moe's: a small step keeps the finite
    # difference on one side of a choice
    "moe_topk": lambda: (
        L.moe_topk(dense_seq(6), expert_hidden=4, num_experts=4, top_k=2,
                   experts_held=(1, 3), shared_hidden=5),
        {"atol": 8e-2, "rtol": 8e-2, "eps": 2e-4},
    ),
    "selective_fc": lambda: (
        L.selective_fc(dense(8, "x"), ids(9, "sel"), size=9),
        {"check_inputs": False},
    ),
    "nce": lambda: (
        L.nce(dense(), ids(), num_neg_samples=4),
        {"check_inputs": False},
    ),
    "hsigmoid": lambda: (
        L.hsigmoid(dense(), ids(vocab=7)),
        {"check_inputs": False},
    ),
    "crf": lambda: (
        L.crf(
            L.fc(dense_seq(6), size=4, act=A.Identity()),
            ids_seq(vocab=4, name="lab"), size=4,
        ),
        {"check_inputs": False, "atol": 8e-2, "rtol": 8e-2},
    ),
    "ctc": _ctc_out,
    # -- costs ---------------------------------------------------------
    "square_error": lambda: L.square_error_cost(
        L.fc(dense(), size=3, act=A.Identity()), dense(3, "lbl")
    ),
    "smooth_l1": lambda: L.smooth_l1_cost(
        L.fc(dense(), size=3, act=A.Identity()), dense(3, "lbl")
    ),
    "huber_regression": lambda: L.huber_regression_cost(
        L.fc(dense(), size=3, act=A.Identity()), dense(3, "lbl")
    ),
    "huber_classification": lambda: L.huber_classification_cost(
        L.fc(dense(), size=1, act=A.Identity()), ids(2, "lbl")
    ),
    "rank_cost": lambda: L.rank_cost(
        L.fc(dense(4, "a"), size=1, act=A.Identity()),
        L.fc(dense(4, "b"), size=1, act=A.Identity()),
        ids(2, "lbl"),
    ),
    "lambda_cost": lambda: (
        L.lambda_cost(
            L.fc(dense_seq(4), size=1, act=A.Identity()),
            L.data("y", dt.dense_vector_sequence(1)),
        ),
        {"check_inputs": False, "atol": 8e-2, "rtol": 8e-2},
    ),
    "sum_cost": lambda: L.sum_cost(L.fc(dense(), size=4, act=A.Tanh())),
    "cross_entropy": lambda: L.cross_entropy_cost(
        L.fc(dense(), size=5, act=A.Softmax()), ids(5, "lbl")
    ),
    "cross_entropy_with_selfnorm": lambda: L.cross_entropy_with_selfnorm_cost(
        L.fc(dense(), size=5, act=A.Softmax()), ids(5, "lbl")
    ),
    "softmax_with_cost": _softmax_with_cost_out,
    "soft_binary_class_cross_entropy": _soft_bce_out,
    "multi_binary_label_cross_entropy": _multi_binary_out,
    "multi_nn_cost": _multi_nn_out,
    "multibox_loss": _multibox_out,
}


def test_every_registered_type_is_swept():
    """THE registry gate: a new layer type must land with a grad-check
    builder here or an explicit skip reason."""
    types = set(registered_layer_types())
    handled = set(SKIP) | set(BUILDERS)
    missing = sorted(types - handled)
    assert not missing, (
        f"registered layer types with neither a grad-check builder nor a "
        f"skip entry in test_layer_grad_sweep.py: {missing}"
    )
    stale = sorted(handled - types)
    assert not stale, f"sweep entries for unregistered types: {stale}"
    overlap = sorted(set(SKIP) & set(BUILDERS))
    assert not overlap, f"types both skipped and built: {overlap}"


@pytest.mark.parametrize("ltype", sorted(BUILDERS))
def test_registry_grad(ltype):
    built = BUILDERS[ltype]()
    out, kwargs = built if isinstance(built, tuple) else (built, {})
    # the builder must actually CONTAIN the type it claims to exercise —
    # without this a stale builder silently turns a type's check into a
    # check of something else
    topo = paddle.Topology([out])
    types_in = {c.type for c in topo.layers.values()}
    for c in topo.layers.values():
        sub = c.attrs.get("_sub_topology")
        if sub is not None:
            types_in |= {s.type for s in sub.layers.values()}
    assert ltype in types_in, (
        f"builder for {ltype!r} built a net without any {ltype!r} layer "
        f"(types present: {sorted(types_in)})"
    )
    reset_auto_names()
    built = BUILDERS[ltype]()
    out, kwargs = built if isinstance(built, tuple) else (built, {})
    check_layer_grad(out, **kwargs)
