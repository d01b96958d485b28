"""paddle_tpu.serving — paged-KV continuous-batching inference engine.

The serving-side counterpart of the training stack: block-paged KV
storage (``kv_cache``), a ragged-page-table decode-attention kernel
(``decode_attention``), a continuous-batching scheduler with admission
control and preemption (``scheduler``), and the user-facing
:class:`ServingEngine` (``engine``) with scrapeable ``metrics``.
"""

from paddle_tpu.serving.decode_attention import (
    BLOCK_ROWS, attention_path, ragged_paged_attention,
    ragged_paged_attention_reference, ragged_paged_attention_tp)
from paddle_tpu.serving.control import (DEFAULT_CLASSES, AdmissionLedger,
                                        Autoscaler, AutoscalePolicy,
                                        TenantClass, TenantRegistry,
                                        TenantSpec, WeightedFairQueue,
                                        check_control_conservation)
from paddle_tpu.serving.engine import (DecodeModel, DecoderLM, ServingEngine,
                                       greedy_decode_reference, validate_tp)
from paddle_tpu.serving.block_moe_lm import BlockMoeLM
from paddle_tpu.serving.window_moe_lm import WindowMoeLM
from paddle_tpu.serving.hybrid_ssm_lm import HybridSsmLM
from paddle_tpu.serving.looped_lm import LoopedLM
from paddle_tpu.serving.speculate import (DraftProposer, NGramProposer,
                                          SamplingParams, accept_tokens,
                                          next_token, warp_probs)
from paddle_tpu.serving.faults import (FaultPlan, FleetFaultPlan,
                                       InjectedDeviceError, ManualClock,
                                       PageLeakError)
from paddle_tpu.serving.fleet import FleetRouter, Replica, ReplicaState
from paddle_tpu.serving.kv_cache import (NULL_PAGE, KVPages, PagedKVConfig,
                                         PagePool, PrefixCache, RecurrentState,
                                         append_token,
                                         dequantize_kv, fork_page, gather_kv,
                                         init_kv_pages, layer_pages,
                                         pages_for_budget,
                                         prefix_chain_hashes, quantize_kv,
                                         recurrent_state, resolve_kv_dtype)
from paddle_tpu.serving.metrics import FleetMetrics, ServingMetrics
from paddle_tpu.serving.migrate import (MigrationBlob,
                                        check_migration_conservation,
                                        export_chain, export_prefix,
                                        import_chain, import_prefix)
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          Request, RequestStatus,
                                          SchedulerConfig, bucket_for,
                                          pack_prefill_chunks)

__all__ = [
    "ServingEngine", "DecodeModel", "DecoderLM", "BlockMoeLM",
    "WindowMoeLM", "HybridSsmLM", "LoopedLM",
    "greedy_decode_reference",
    "ragged_paged_attention", "ragged_paged_attention_reference",
    "ragged_paged_attention_tp", "attention_path", "BLOCK_ROWS",
    "validate_tp",
    "PagedKVConfig", "KVPages", "PagePool", "PrefixCache", "NULL_PAGE",
    "init_kv_pages", "layer_pages", "append_token",
    "gather_kv", "fork_page", "prefix_chain_hashes", "quantize_kv",
    "dequantize_kv",
    "pages_for_budget", "resolve_kv_dtype", "RecurrentState",
    "recurrent_state",
    "ContinuousBatchingScheduler", "Request", "RequestStatus",
    "SchedulerConfig", "bucket_for", "pack_prefill_chunks",
    "ServingMetrics", "FleetMetrics",
    "FaultPlan", "FleetFaultPlan", "ManualClock", "InjectedDeviceError",
    "PageLeakError",
    "FleetRouter", "Replica", "ReplicaState",
    "MigrationBlob", "export_chain", "import_chain", "export_prefix",
    "import_prefix", "check_migration_conservation",
    "TenantClass", "TenantSpec", "TenantRegistry", "DEFAULT_CLASSES",
    "AdmissionLedger", "WeightedFairQueue", "AutoscalePolicy", "Autoscaler",
    "check_control_conservation",
    "SamplingParams", "NGramProposer", "DraftProposer", "accept_tokens",
    "next_token", "warp_probs",
]
