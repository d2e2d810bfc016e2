"""Every reference DSL-suite config EXECUTES — one jitted forward with
random batches, finite outputs.

The reference's own suite (trainer_config_helpers/tests/configs/
file_list.sh, driven by test_config_parser.py) only checks the configs
PARSE to stable protostrs; the golden-serialize test here mirrors that.
This sweep goes further: each config builds a CompiledNetwork, gets a
random batch shaped by per-config slot-type hints (the DSL fixtures carry
no data declarations, so sequence-ness is knowledge about the net), and
runs forward under train=True.  A config that stops executing — a layer
lowering regression, a shape contract break — fails here even if its
serialized form is unchanged.
"""

import os

import jax
import numpy as np
import pytest

import paddle_tpu.core.data_types as dt
from paddle_tpu.core.compiler import CompiledNetwork
from paddle_tpu.v1_compat import parse_config

from layer_grad_util import rand_batch_for

DSL = "/root/reference/python/paddle/trainer_config_helpers/tests/configs"

# the reference's own list (file_list.sh `configs=`)
FILE_LIST = [
    "test_repeat_layer", "test_fc", "layer_activations", "projections",
    "test_print_layer", "test_sequence_pooling", "test_lstmemory_layer",
    "test_grumemory_layer", "last_first_seq", "test_expand_layer",
    "test_ntm_layers", "test_hsigmoid", "img_layers", "img_trans_layers",
    "util_layers", "simple_rnn_layers", "unused_layers", "test_cost_layers",
    "test_rnn_group", "shared_fc", "shared_lstm", "shared_gru",
    "test_cost_layers_with_weight", "test_spp_layer", "test_bilinear_interp",
    "test_maxout", "test_bi_grumemory", "math_ops",
    "test_seq_concat_reshape", "test_pad", "test_smooth_l1",
    "test_multiplex_layer", "test_prelu_layer", "test_row_conv",
    "test_detection_output_layer", "test_multibox_loss_layer",
    "test_recursive_topology", "test_gated_unit_layer", "test_clip_layer",
    "test_row_l2_norm_layer",
]

# configs that cannot run as plain forward passes, with the reason stated
SKIP = {
    "test_detection_output_layer":
        "needs structured ground-truth boxes; executed end-to-end by "
        "tests/test_detection.py",
    "test_multibox_loss_layer":
        "needs structured ground-truth boxes; executed end-to-end by "
        "tests/test_detection.py",
    "test_sequence_pooling":
        "one slot feeds BOTH stride pooling (defined on plain sequences) "
        "and TO_SEQUENCE pooling (needs nested input) — unrunnable on any "
        "single input type even in the reference (its suite only parses "
        "these); both modes execute in tests/test_layer_grad.py and "
        "tests/test_nested_seq.py",
    "test_expand_layer":
        "one slot feeds FROM_NO_SEQUENCE (non-seq input) and FROM_SEQUENCE "
        "(seq input over a nested pattern) expansion simultaneously — same "
        "parse-only conflict; both modes execute in tests/test_nested_seq.py",
    "last_first_seq":
        "one slot feeds stride selection (plain sequences only) and "
        "TO_SEQUENCE aggregation (nested input) simultaneously — parse-only "
        "conflict; both execute in tests/test_layer_grad.py and "
        "tests/test_nested_seq.py",
    "projections":
        "m2 += table_projection(input=m1) indexes an embedding table with a "
        "DENSE intermediate — undefined at runtime in the reference too "
        "(TableProjection requires an ids argument); every projection kind "
        "executes in tests/test_mixed.py",
    "test_rnn_group":
        "feeds a whole subsequence plus a flat memory into one fc inside a "
        "non-nested group — frame-count mismatch in the reference's fc too "
        "(gserver FC CHECKs equal row counts); the shipped nested-group "
        "form executes in tests/test_nested_seq.py and "
        "tests/test_generation_golden.py",
}

# slot-type hints: the DSL fixtures declare bare data_layer sizes; which
# slots are sequences (or labels) is net knowledge the reference encodes in
# its C++ test drivers
H = {
    "last_first_seq": {"data": dt.dense_vector_sub_sequence(30)},
    "projections": {"test": dt.integer_value_sequence(100)},
    "simple_rnn_layers": {"data": dt.dense_vector_sequence(200)},
    "test_bi_grumemory": {"data": dt.dense_vector_sequence(120)},
    "test_grumemory_layer": {"data": dt.dense_vector_sequence(120)},
    "test_lstmemory_layer": {"data": dt.dense_vector_sequence(128)},
    "test_row_conv": {"data": dt.dense_vector_sequence(2560)},
    "test_seq_concat_reshape": {
        "data1": dt.dense_vector_sequence(30),
        "data2": dt.dense_vector_sequence(30),
    },
    "shared_gru": {
        "data_a": dt.dense_vector_sequence(100),
        "data_b": dt.dense_vector_sequence(100),
        "label": dt.integer_value(10),
    },
    "shared_lstm": {
        "data_a": dt.dense_vector_sequence(100),
        "data_b": dt.dense_vector_sequence(100),
        "label": dt.integer_value(10),
    },
    "shared_fc": {"label": dt.integer_value(10)},
    "test_rnn_group": {
        "seq_input": dt.dense_vector_sequence(100),
        "sub_seq_input": dt.dense_vector_sub_sequence(100),
    },
    "test_cost_layers": {
        "input": dt.dense_vector_sequence(200),
        "labels": dt.integer_value_sequence(200),
        "crf_label": dt.integer_value_sequence(4),
        "probs": dt.dense_vector(10),
        "xe-label": dt.integer_value(10),
        "left": dt.dense_vector(1),
        "right": dt.dense_vector(1),
        "label": dt.integer_value(2),
        "list_feature": dt.dense_vector_sequence(100),
        "list_scores": dt.dense_vector_sequence(1),
        "huber_probs": dt.dense_vector(1),
        "huber_label": dt.integer_value(2),
    },
    "test_cost_layers_with_weight": {
        "label": dt.integer_value(10),
        "weight": dt.dense_vector(1),
        "multi_class_label": dt.integer_value(500),
    },
    "test_hsigmoid": {"label": dt.integer_value(10)},
}

# per-config batch adjustments where plain random values are mathematically
# out of domain (the reference layer would produce the same NaNs)
def _ntm_fix(batch):
    # power_layer computes a ** w: a negative base with a fractional
    # exponent is NaN in the reference's PowerLayer too — feed positives
    import jax.numpy as jnp

    from paddle_tpu.core.batch import SeqTensor

    out = dict(batch)
    out["a"] = SeqTensor(jnp.abs(batch["a"].data) + 0.1)
    out["w"] = SeqTensor(jnp.abs(batch["w"].data))
    return out


BATCH_FIX = {"test_ntm_layers": _ntm_fix}


def _hinted(parsed, name):
    hints = H.get(name, {})
    for lname, itype in hints.items():
        conf = parsed.topology.layers.get(lname)
        if conf is None:
            raise AssertionError(
                f"{name}: hint for unknown data layer {lname!r}; layers: "
                f"{list(parsed.topology.data_layers())}"
            )
        object.__setattr__(conf, "input_type", itype)
        conf.attrs.pop("_v1_size_only", None)
    return parsed


@pytest.mark.skipif(
    not os.path.isdir(DSL),
    reason=f"needs the reference's own DSL configs under {DSL}",
)
@pytest.mark.parametrize("name", FILE_LIST)
def test_dsl_config_executes(name):
    if name in SKIP:
        pytest.skip(SKIP[name])
    parsed = _hinted(parse_config(os.path.join(DSL, name + ".py")), name)
    net = CompiledNetwork(parsed.topology)
    params, state = net.init(jax.random.PRNGKey(0))
    batch = rand_batch_for(parsed.topology, batch_size=2, max_len=4)
    if name in BATCH_FIX:
        batch = BATCH_FIX[name](batch)
    if net.has_dynamic_widths:  # e.g. test_fc's trans -> fc
        params, _ = net.resolve_dynamic_widths(params, batch)
    outs, _ = net.apply(
        params, batch, state=state, train=True, rng=jax.random.PRNGKey(1)
    )
    for oname in parsed.topology.output_names:
        v = outs[oname]
        arr = v.data if hasattr(v, "data") else v
        assert np.all(np.isfinite(np.asarray(arr, np.float32))), (
            f"{name}: output {oname} not finite"
        )
