"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's in-process multi-node simulation strategy
(pserver/test/test_ParameterServer2.cpp spins servers+clients in one process):
we give XLA 8 virtual CPU devices so every mesh/collective path is exercised
without TPU hardware.

NOTE: jax may already be imported when this file loads, so JAX_PLATFORMS set
here would be too late — we switch platform via jax.config instead, and set
XLA_FLAGS before the first backend initialization.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# ---------------------------------------------------------------------------
# fast/slow split: `-m "not slow"` is the <8-minute iteration gate; the
# plain full run (CI) is unchanged and runs everything. Centralized here by
# test id (parametrized ids included) so sweep cases can be marked without
# touching their case tables; names measured via --durations on this host.
# ---------------------------------------------------------------------------

_SLOW_TESTS = {
    "test_pipeline_over_transformer_blocks",
    "test_googlenet_geometry_and_step",
    "test_srl_trains_and_shares_params",
    "test_srl_conll05_dataset_compatible",
    "test_compare_sparse_training_parity",
    "test_transformer_generate_matches_iterative_forward",
    "test_mdlstm_forward_shape_and_grad",
    "test_transformer_trains_on_mesh8_zero",
    "test_ring_attention_grads",
    "test_transformer_bf16_dense_activations",
    "test_detection_suite",
    "test_transformer_lm_trains",
    "test_vgg_16_network_builds_and_runs",
    "test_fused_head_trains_on_mesh8_zero",
    "test_remat_training_parity",
    "test_seq2seq_trains_and_generates",
    "test_two_process_by_four_device_hybrid_mesh",
    "test_two_process_mesh_and_train_step",
    "test_seq2seq_transformer_learns_copy_task",
    "test_pipeline_grads_match_sequential",
    "test_moe_transformer_trains",
    "test_sequence_tagging_crf_trains_and_decodes",
    "test_layer[multibox_loss]",
    "test_layer[StaticInput+lstm_step+lstm_step_output+lstm_step_state]",
    "test_layer[gru_step+memory+recurrent_group]",
    "test_layer[detection_output]",
    "test_layer[lstmemory]",
    "test_layer[moe_ffn]",
    "test_layer[mdlstmemory]",
    "test_layer[grumemory]",
    "test_remat_moe_trains",
    "test_lenet_conv_one_batch",
    "test_sharded_matches_oracle_multiple_experts_per_shard",
    "test_transformer_causality",
    "test_model_parallel_weights_are_distributed",
    "test_fused_head_training_parity",
    "test_beam_finds_higher_likelihood_than_greedy",
    "test_beam_generate_control_hooks",
    "test_beam1_matches_greedy",
    "test_smallnet_trains",
    "test_quick_start_arch_trains[db_lstm]",
    "test_quick_start_arch_trains[resnet_lstm]",
    "test_quick_start_arch_trains[bidi_lstm]",
    "test_moe_trains_toward_balanced_experts",
    "test_grad_recurrent_layers",
    "test_elastic_multipass_and_periodic_checkpoint_parity",
    "test_kill_trainer_resume_parity",
    "test_mha_layer_trains",
    "test_hierarchical_group_trains_end_to_end",
    "test_simple_lstm_vs_explicit_fc_lstmemory",
    "test_gradient_check_passes_and_catches_corruption",
    "test_flash_vs_plain_attention_kernels",
    "test_lstmemory_vs_recurrent_group_lstm_step",
    "test_lm_head_cost_vs_unfused_pair",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)


def assert_serving_drained(eng):
    """Shared post-drain pool invariant for the serving suites: zero
    live refs — every usable page is either free or parked reclaimable
    (refcount 0) in the prefix cache — and the REF-LEAK/PAGE-LEAK
    conservation checks pass.  Lives here so the three serving test
    files assert ONE definition of "nothing leaked"."""
    assert eng.pool.total_refs == 0
    assert eng.pool.num_free + eng.pool.num_reclaimable == \
        eng.pool.num_usable
    eng.check_page_conservation()


def stored_pool(*pages):
    """One layer's published pages ``[P, page, KVH, D]`` (and int8 scales
    ``[P, page, KVH]``; ``None`` passes through) as a stored pool of ONE
    layer, ``[1, P, page, KVH * D]`` (scales ``[1, P, page, KVH]``): what
    ``ragged_paged_attention(..., layer=0)`` takes.  The inverse, for
    the reference, is ``kv_cache.layer_pages``."""
    import jax.numpy as jnp

    def one(a):
        if a is None:
            return None
        a = jnp.asarray(a)
        return a.reshape(1, *a.shape[:2], -1) if a.ndim == 4 else a[None]

    return tuple(one(a) for a in pages)
