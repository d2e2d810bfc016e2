"""Test configuration: force an 8-device virtual CPU mesh so all sharding
paths (data/model parallel) are exercised without TPU hardware — the loopback
"fake cluster" strategy of the reference's distributed tests (reference:
paddle/trainer/tests/test_CompareSparse.cpp spawning localhost pservers).

The environment defaults (``JAX_PLATFORMS=cpu``, the 8-device ``XLA_FLAGS``,
a compile-cache directory outside the checkout) are set at import, before
anything imports jax; the subprocesses tests spawn inherit them."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.testing import ensure_cpu_mesh  # noqa: E402

ensure_cpu_mesh()
# Entry points place jax's persistent compile cache in <checkout>/.jax_cache
# unless this variable is set (utils/compile_cache.py).  Tier-1 must not grow
# the checkout — the chip tool copies the tree as it stands — so tests keep
# their cache outside it.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/paddle_tpu_tier1_jax_cache")


def pytest_configure(config):
    # test tiers (reference CI splits fast unit tests from the long
    # trainer/integration binaries, paddle/scripts/travis/): `make test`
    # runs `-m "not slow"` in under 5 minutes; `make verify` runs everything
    config.addinivalue_line(
        "markers", "slow: long-running E2E/training test (excluded from `make test`)"
    )
    import jax

    jax.config.update("jax_enable_x64", False)


# Long-running tests (>= ~4s wall on the virtual CPU mesh, measured via
# `pytest --durations=0`): excluded from the `make test` fast tier and run
# by `make verify`.  Regenerate after large suite changes with
#   pytest --durations=0 | awk '$1+0>=4' ...
_SLOW_TESTS = {
    # demo run-sweep heavyweights
    "test_quick_start_configs_execute[db-lstm]",
    "test_quick_start_configs_execute[lstm]",
    "test_quick_start_configs_execute[bidi-lstm]",
    "test_quick_start_configs_execute[resnet-lstm]",
    "test_sequence_tagging_configs_execute[rnn_crf]",
    "test_sequence_tagging_configs_execute[linear_crf]",
    # DSL run-sweep heavyweights (conv-stack configs compile ~30s each)
    "test_dsl_config_executes[img_trans_layers]",
    "test_dsl_config_executes[img_layers]",
    "test_dsl_config_executes[test_cost_layers]",
    "test_dsl_config_executes[test_cost_layers_with_weight]",
    "test_dsl_config_executes[simple_rnn_layers]",
    # registry-sweep grad checks >= ~2s each (the sweep's completeness GATE,
    # test_every_registered_type_is_swept, always runs in the fast tier)
    "test_registry_grad[multibox_loss]",
    "test_registry_grad[lstmemory]",
    "test_registry_grad[gru]",
    "test_registry_grad[moe]",
    "test_registry_grad[mdlstmemory]",
    "test_registry_grad[multi_head_attention]",
    "test_registry_grad[crf]",
    "test_registry_grad[ctc]",
    "test_registry_grad[recurrent]",
    "test_registry_grad[nce]",
    "test_registry_grad[recurrent_group]",
    "test_registry_grad[lstm_step]",
    "test_registry_grad[multi_nn_cost]",
    "test_registry_grad[lambda_cost]",
    "test_registry_grad[hsigmoid]",
    "test_registry_grad[gru_step]",
    "test_registry_grad[seqconcat]",
    "test_registry_grad[selective_fc]",
    "test_registry_grad[cross_entropy]",
    "test_registry_grad[norm]",
    "test_beam_hooks_through_dsl_layer",
    "test_beam_search_generation",
    "test_beam_search_layer_through_infer",
    "test_column_parallel_fc_matches",
    "test_conv_operator",
    "test_cos_sim_vec_mat",
    "test_cost_decreases",
    "test_crf_grad",
    "test_ctc_grad",
    "test_ctc_matches_torch",
    "test_detection_output_decodes_known_boxes",
    "test_flash_gradients_match_dense_interpret",
    "test_gan_learns_gaussian",
    "test_gan_losses_are_finite_and_adversarial",
    "test_greedy_generation_copies",
    "test_gru_grad",
    "test_hierarchical_rnn_trains",
    "test_hsigmoid_grad",
    "test_hsigmoid_probabilities_sum_to_one",
    "test_infer_field_id_and_multiple_outputs",
    "test_infer_mnist_lenet",
    "test_lambda_cost_grad",
    "test_lstmemory_grad",
    "test_lstmemory_reverse_grad",
    "test_masters_stay_f32_grads_f32",
    "test_mdlstm_shape_and_grad",
    "test_mha_self_attention_grad",
    "test_mixed_seq_input_grad",
    "test_moe_capacity_drops_tokens_and_masks_padding",
    "test_moe_expert_parallel_matches_unsharded",
    "test_moe_init_std_uses_fan_in",
    "test_moe_matches_dense_reference_when_capacity_ample",
    "test_moe_trains_on_mesh",
    "test_multibox_loss_runs_and_matches",
    "test_nce_grad",
    "test_nce_with_dist_runs",
    "test_ner_crf_trains_locally",
    "test_ner_crf_trains_sparse_sharded_on_mesh",
    "test_ner_tagging_accuracy_via_decoding",
    "test_nested_group_grad",
    "test_nmt_cost_decreases",
    "test_param_init_stable_across_processes",
    "test_pipeline_gradients_match_sequential",
    "test_profiler_trace_writes",
    "test_pipeline_matches_sequential",
    "test_prelu_grad",
    "test_rank_cost_grad",
    "test_raw_face_chunking_crf_forward",
    "test_recurrent_grad",
    "test_recurrent_group_bf16_carry",
    "test_reference_nested_rnn_equals_flat_rnn",
    "test_ring_gradients_match_dense",
    "test_ring_matches_dense",
    "test_ring_respects_key_padding",
    "test_selective_fc_grad",
    "test_sequence_memory_grad",
    "test_shared_fc_and_groups_share_storage",
    "test_soft_bce_grad",
    "test_sparse_sharded_matches_dense_numerics",
    "test_trainer_one_pass_mnist_opt_a",
    "test_training_survives_failover",
    "test_transformer_trains_on_copy_task",
    "test_transformer_with_sequence_parallel_matches_dense",
    "test_vae_config_builds_and_trains",
    "test_vae_reconstructs_and_samples",
}


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    for item in items:
        # match the base name (marks every param case) or one exact
        # parametrized id like "test_registry_grad[moe]"
        if item.name.split("[")[0] in _SLOW_TESTS or item.name in _SLOW_TESTS:
            item.add_marker(_pytest.mark.slow)
