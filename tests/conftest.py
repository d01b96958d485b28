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


def walk_batch(rng, decode, chunks, *, k1=1, kvh=2, group=1, page=8, d=16,
               bucket=0, pool="float32", width=None, layers=2):
    """A tick's rows as ``ServingEngine._attend`` hands them to the ragged
    kernel, over a random stored pool: every slot's ``k1`` decode rows
    padded to whole ``BLOCK_ROWS`` blocks (``decode``: the decoding slots'
    lengths, 0 an idle slot; a prefilling slot's decode rows are padding),
    then the chunks ``(rows, start)`` of the slots behind them, each padded
    to ``BLOCK_ROWS``, then padding up to ``bucket`` rows.  ``width``: the
    page table's (a ring's, where a window layer reads it; the longest
    sequence's pages by default).  Returns the arguments of
    ``ragged_paged_attention`` as a dict (``decode_rows`` among them)."""
    import jax.numpy as jnp
    from paddle_tpu.serving import BLOCK_ROWS, quantize_kv

    rbk = -(-k1 // BLOCK_ROWS) * BLOCK_ROWS
    slots = len(decode) + len(chunks)
    lens = list(decode) + [start + n for n, start in chunks]
    rows = []
    for s, n in enumerate(decode):
        pos = list(range(max(n - k1, 0), n))
        rows += [(s, p) for p in pos] + [(s, -1)] * (rbk - len(pos))
    rows += [(len(decode) + j, -1) for j in range(len(chunks))
             for _ in range(rbk)]
    td = len(rows)
    for j, (n, start) in enumerate(chunks):
        rows += [(len(decode) + j, start + i) for i in range(n)] + \
            [(len(decode) + j, -1)] * (-n % BLOCK_ROWS)
    rows += [(0, -1)] * max(0, bucket - (len(rows) - td))
    width = width or max(lens) // page + 1
    shape = (layers, 1 + slots * width, page, kvh, d)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    scales = {}
    if pool == "int8":
        k, scales["k_scale"] = quantize_kv(k)
        v, scales["v_scale"] = quantize_kv(v)
    return dict(
        q=jnp.asarray(rng.standard_normal((len(rows), kvh * group, d)),
                      jnp.float32),
        k_pool=k.reshape(*shape[:3], kvh * d),
        v_pool=v.reshape(*shape[:3], kvh * d),
        page_table=jnp.asarray(1 + np.arange(slots * width).reshape(
            slots, width), jnp.int32),
        kv_lens=jnp.asarray(lens, jnp.int32),
        row_seq=jnp.asarray([r[0] for r in rows], jnp.int32),
        qpos=jnp.asarray([r[1] for r in rows], jnp.int32),
        layer=layers - 1, decode_rows=td, **scales)


# chunk mixes that stress the regrouping of a bucket's rows into tall
# blocks: (decoding slots' lengths, chunks (rows, start), bucket rows,
# rows of a tall block; None: what the shapes give)
WALK_MIXES = {
    # one long chunk at a context of many pages: every tall block walks
    # them once, up to its own last row
    "long_chunk": ([13, 30], [(512, 256)], 512, 128),
    "three_chunks": ([13], [(8, 40), (72, 16), (136, 0)], 216, 16),
    # 20 rows pad to 24: the chunk ends inside the second tall block, and
    # another sequence's rows share it
    "ends_inside_a_block": ([9], [(20, 12), (8, 0)], 32, 16),
    "empty_bucket": ([13, 30, 7], [], 16, 16),
    "a_sequence_of_length_0": ([0, 21, 0], [(16, 8)], 16, 16),
}

# every mix at every group (1, 6, 8), the pool's type and the rows a slot
# (1: decode; 4: a block model's, or speculation's) turning with them so
# that each pair of values meets
WALK_CASES = [(mix, group, ("float32", "int8")[(i + j) % 2],
               (1, 4)[(i + j // 2) % 2])
              for i, mix in enumerate(sorted(WALK_MIXES))
              for j, group in enumerate((1, 6, 8))]
