"""Global flag registry — the gflags analog.

Reference: paddle/utils/Flags.cpp:18-81 centralizes process flags (use_gpu,
trainer_count, ports, log_period, ...) and python/paddle/v2/__init__.py:65-86
surfaces them via ``paddle.init(**kwargs)`` + ``PADDLE_INIT_*`` env vars.

Here flags are a typed registry populated from defaults < environment
(``PADDLE_TPU_<NAME>``) < ``init(**kwargs)``. TPU-era flags replace the GPU/
pserver ones: mesh axis sizes instead of trainer_count/num_gradient_servers.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict

from paddle_tpu.platform.enforce import EnforceError

_ENV_PREFIX = "PADDLE_TPU_"


@dataclass
class _FlagSpec:
    name: str
    default: Any
    parser: Callable[[str], Any]
    help: str


def _parse_bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


class _Flags:
    """Typed global flags with attribute access (``FLAGS.log_period``)."""

    def __init__(self):
        object.__setattr__(self, "_specs", {})
        object.__setattr__(self, "_values", {})

    def define(self, name: str, default: Any, help: str = "", parser=None) -> None:
        if parser is None:
            if isinstance(default, bool):
                parser = _parse_bool
            elif isinstance(default, int):
                parser = int
            elif isinstance(default, float):
                parser = float
            else:
                parser = str
        self._specs[name] = _FlagSpec(name, default, parser, help)
        env = os.environ.get(_ENV_PREFIX + name.upper())
        self._values[name] = parser(env) if env is not None else default

    def set(self, name: str, value: Any) -> None:
        if name not in self._specs:
            raise EnforceError(f"unknown flag {name!r}", context="flags")
        self._values[name] = value

    def update(self, **kwargs) -> None:
        for k, v in kwargs.items():
            self.set(k, v)

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._values)

    def __getattr__(self, name: str) -> Any:
        values = object.__getattribute__(self, "_values")
        if name in values:
            return values[name]
        raise AttributeError(name)

    def __setattr__(self, name: str, value: Any) -> None:
        self.set(name, value)


FLAGS = _Flags()

# Core process flags (reference: paddle/utils/Flags.cpp:18-81, re-scoped for TPU).
FLAGS.define("seed", 0, "global RNG seed (0 = nondeterministic per-process)")
FLAGS.define("log_period", 100, "print batch stats every N batches")
FLAGS.define("test_period", 0, "run the tester every N batches (0 = per pass)")
FLAGS.define("show_layer_stat", False, "print per-layer output stats each log period")
FLAGS.define("show_parameter_stats_period", 0, "print per-parameter grad stats every N batches")
FLAGS.define("check_nan", False, "enable jax debug_nans (FE_INVALID tripwire analog)")
FLAGS.define("platform", "", "force a jax platform ('cpu'/'tpu'); empty = auto")
FLAGS.define("mesh_shape", "", "comma dims for the device mesh, e.g. '8' or '2,4'")
FLAGS.define("mesh_axes", "data", "comma axis names matching mesh_shape")
FLAGS.define("use_bf16", True, "compute matmuls/convs in bfloat16 on TPU")
FLAGS.define("use_pallas", True,
             "use the hand-written pallas fused LSTM/GRU cells of "
             "ops/rnn.py; off = their plain JAX/XLA scans with identical "
             "semantics (attention has no such switch)")
FLAGS.define("bf16_activations", True,
             "store inter-layer image activations in bfloat16 (halves HBM "
             "traffic between fused conv blocks; stats/losses stay f32). "
             "Only active when use_bf16 is also on.")
FLAGS.define("bf16_dense_activations", False,
             "store fc/embedding/attention outputs (the transformer "
             "residual stream) in bfloat16. Norm statistics and losses "
             "still reduce in f32. Off by default: flip for bandwidth-"
             "bound dense models. Only active when use_bf16 is also on.")
FLAGS.define("attn_pv_f32", False,
             "keep the flash-attention PV-matmul operands (softmax probs "
             "and V, plus the backward dS/P operands) in f32 instead of "
             "the tiles' native dtype. Removes the bf16 softmax-prob "
             "rounding for accuracy-sensitive runs at the cost of the "
             "slower f32 MXU path for those matmuls.")
FLAGS.define("zero_stage", 0,
             "cross-replica sharded weight update (arXiv 2004.13336): "
             "0 = replicated optimizer state (default), 1 = ZeRO-1 — "
             "reduce-scatter grads, update a 1/N optimizer-state shard "
             "per replica over the 'data' mesh axis, all-gather updated "
             "weights. Per-trainer override: SGD(zero=...).")
FLAGS.define("pipeline_stages", 0,
             "pipeline-parallel stage count S for SGD(pipeline=...). 0 = "
             "derive: the PipelineConfig's num_stages, else the mesh's "
             "'stage' axis size, else every visible device. The model's "
             "layer count must divide by S (each stage holds L/S "
             "consecutive blocks).", parser=int)
FLAGS.define("pipeline_microbatches", 8,
             "GPipe microbatch count M per pipeline-parallel train step "
             "(PipelineConfig(microbatches=0) reads this). The batch "
             "must divide by M; bubble fraction is (S-1)/(M+S-1), so "
             "larger M amortizes the fill/drain bubble at the cost of "
             "smaller per-microbatch matmuls.", parser=int)
FLAGS.define("serving_page_size", 128,
             "paged-KV cache page size in tokens (serving engine). 128 "
             "matches the TPU lane width so a page's K/V tile feeds the "
             "MXU without padding; tests and small models may pass a "
             "smaller explicit page_size to ServingEngine.")
FLAGS.define("serving_max_pages", 512,
             "total pages in the serving KV pool (page 0 is reserved as "
             "the null page that masked/inactive writes land on). "
             "HBM cost = 2 * layers * pages * page_size * heads * "
             "head_dim * dtype bytes.")
FLAGS.define("serving_max_slots", 8,
             "maximum concurrently-decoding sequences per serving engine "
             "tick (the static batch dimension of the fused decode step)")
FLAGS.define("serving_prefill_buckets", "32,64,128,256,512",
             "comma ladder of padded prefill lengths: each admitted "
             "prompt — or, under chunked prefill, each chunk of at most "
             "serving_prefill_chunk tokens — is padded to the smallest "
             "bucket that holds it so the prefill jit specializes once "
             "per bucket, not once per distinct length")
FLAGS.define("serving_prefix_cache", True,
             "automatic prefix caching: full KV pages are indexed by "
             "chained token-block hashes and refcount-shared, so a "
             "prompt whose prefix is cached skips re-forwarding it "
             "(admission charges only the NEW pages; a full-cover hit "
             "copy-on-write-forks the last shared page and recomputes "
             "only the final token). Cached pages at refcount 0 stay "
             "reclaimable and are LRU-evicted under pool pressure. "
             "Hits are token-verified, so hash collisions degrade to "
             "misses, never to corruption.")
FLAGS.define("serving_prefill_chunk", 256,
             "chunked prefill: a prompt (or cache-miss tail) longer "
             "than this many tokens is prefilled in chunks of at most "
             "this size, ONE chunk per engine tick, interleaved with "
             "the fused decode step so a long prefill stops stalling "
             "running slots' inter-token latency. Each chunk is padded "
             "to the serving_prefill_buckets ladder, so the chunk size "
             "should be a ladder value (a chunk of C pads to the "
             "smallest bucket >= C; a chunk above the top bucket rounds "
             "up and wastes the excess). 0 disables chunking "
             "(whole-prompt single-shot prefill).", parser=int)
FLAGS.define("serving_kv_dtype", "float32",
             "storage dtype of the paged KV pool: float32 | bfloat16 | "
             "int8. bfloat16 halves and int8 roughly quarters the bytes "
             "per page (int8 adds per-token, per-kv-head f32 scale "
             "arrays — amax/127 symmetric quantization applied on every "
             "write, dequantized in-register by the ragged attention "
             "kernel and by the gather fallback, so the oracle and the "
             "kernel read identical stored values). At a fixed pool "
             "byte budget (ServingEngine(pool_bytes=...)) the smaller "
             "dtypes admit proportionally more pages, which multiplies "
             "prefix-cache capacity and admissible concurrency. "
             "Per-engine override: ServingEngine(kv_dtype=...).")
FLAGS.define("serving_host_tier_bytes", 0,
             "hierarchical KV cache: byte budget of the host-RAM spill "
             "tier under the device page pool. When > 0 (and the prefix "
             "cache is on), LRU-evicted reclaimable pages demote to host "
             "memory — checksummed over stored bytes + scales — instead "
             "of being destroyed, and a prefix lookup that runs off the "
             "device index swaps the verified continuation back in. "
             "When the budget is exceeded the tier LRU-drops (the third "
             "rung of the degradation ladder: device evict -> host "
             "spill -> host drop -> shed/preempt). 0 disables (prior "
             "behavior: eviction destroys). Per-engine override: "
             "ServingEngine(host_tier_bytes=...).", parser=int)
FLAGS.define("serving_swap_in_budget", 8,
             "host-tier swap-in charge per engine tick, in pages: at "
             "most this many verified host pages are promoted back to "
             "the device pool per tick for the head-of-queue request — "
             "the chunk-prefill charging model, so a long host-resident "
             "chain warms over several ticks and never blocks decode. "
             "0 disables swap-in (spill-only tier). Per-engine "
             "override: ServingEngine(swap_in_budget=...).", parser=int)
FLAGS.define("serving_host_kv_dtype", "stored",
             "host-tier storage format: 'stored' keeps the device "
             "pool's stored bytes verbatim (swap-in is bit-identical); "
             "'int8' transcodes float payloads to int8 + per-token "
             "f32 scales on spill (amax/127, the pool's own "
             "quantization rule), so the same serving_host_tier_bytes "
             "holds ~4x the f32 pages at quantization fidelity — "
             "dequantized on swap-in. An int8 device pool spills "
             "verbatim either way. Per-engine override: "
             "ServingEngine(host_kv_dtype=...).")
FLAGS.define("serving_spec_mode", "off",
             "speculative decoding: off | ngram | draft. 'ngram' drafts "
             "by prompt-lookup (match the last serving_spec_ngram "
             "tokens of a slot's own prompt+output history against "
             "earlier occurrences and propose what followed — zero "
             "extra model cost); 'draft' runs a small draft DecodeModel "
             "(ServingEngine(draft_model=, draft_params=)) with its own "
             "paged KV pool. Either way ONE fused target-model step "
             "verifies all k+1 positions per slot per tick (speculative "
             "slots contribute k+1 rows instead of 1), the longest "
             "agreeing prefix is accepted (greedy: exact match; "
             "sampled: rejection sampling against the target "
             "distribution) and rejected tokens roll back via COW page "
             "forks, so greedy output stays token-identical to "
             "non-speculative decoding. Per-engine override: "
             "ServingEngine(spec_mode=...).")
FLAGS.define("serving_spec_k", 4,
             "speculation depth: drafted tokens per slot per tick. The "
             "verify step compiles once per (prefill_bucket, k+1) pair "
             "— k is a jit dimension, so keep it fixed per engine. "
             "Lookahead KV pages are charged opportunistically (never "
             "by preemption) and speculation is suspended per-slot "
             "under page pressure. Per-engine override: "
             "ServingEngine(spec_k=...).", parser=int)
FLAGS.define("serving_spec_ngram", 3,
             "n-gram size of the prompt-lookup proposer: the longest "
             "history suffix matched against earlier history (falls "
             "back to shorter suffixes down to 1). Per-engine override: "
             "ServingEngine(spec_ngram=...).", parser=int)
FLAGS.define("serving_queue_deadline_s", 0.0,
             "default per-request admission deadline: a request still "
             "queued this many seconds after submit is shed as TIMED_OUT "
             "(slot/pages were never held). 0 disables; per-request "
             "override: ServingEngine.submit(queue_deadline_s=...).",
             parser=float)
FLAGS.define("serving_preempt_budget", 3,
             "max re-prefill recomputes per request. A request preempted "
             "this many times escalates: it requeues ahead of every "
             "non-escalated request and is never chosen as a preemption "
             "victim again, so youngest-first eviction cannot livelock a "
             "long prompt. 0 = unlimited.", parser=int)
FLAGS.define("serving_watchdog_ticks", 16,
             "decode-progress watchdog: a RUNNING request that emits no "
             "token for this many engine ticks (persistent device "
             "errors, stuck slot) is FAILED and its pages freed, keeping "
             "the rest of the fused batch alive. 0 disables.", parser=int)
FLAGS.define("serving_fleet_replicas", 4,
             "default replica count for FleetRouter: N ServingEngine "
             "replicas behind one prefix-affinity front-door. Traffic "
             "routes by chained prompt-block hash (the PrefixCache key "
             "chain) with healthz-driven load balancing as tiebreak and "
             "overflow; a dead replica's in-flight requests resubmit to "
             "survivors.", parser=int)
FLAGS.define("serving_fleet_heartbeat_s", 1.0,
             "fleet replica lease scale on the fleet's (possibly "
             "injected) clock: the lease TTL is 3x this. Leases renew "
             "every fleet tick (renewal is a cheap host op; only a "
             "heartbeat-partition fault blocks it), so a replica dies "
             "when its renewals stop for the TTL — then its token is "
             "dropped (a zombie can never ack after its slot is "
             "reclaimed) and its in-flight requests resubmit. On a "
             "wall clock set this above the worst-case single tick "
             "(first-compile spikes), since a tick longer than the TTL "
             "lapses every lease mid-tick.", parser=float)
FLAGS.define("serving_fleet_resubmit_budget", 2,
             "max death-driven resubmits per fleet request. A request "
             "whose replica dies is resubmitted to a survivor with its "
             "ORIGINAL absolute deadline at most this many times, then "
             "FAILED — bounded recovery, never an infinite "
             "kill->resubmit loop. 0 = fail on the first death.",
             parser=int)
FLAGS.define("serving_fleet_roles", "",
             "comma-separated replica role list for a disaggregated "
             "fleet ('prefill,prefill,decode,decode'); shorter lists "
             "pad with 'unified', empty = every replica unified (the "
             "classic fleet). Prompts route to prefill/unified "
             "replicas; a prefill-class replica hands each request off "
             "to the least-loaded decode-class replica after its first "
             "token via the page-migration plane (export_chain/"
             "import_chain), so long prefills never steal verify-row "
             "budget from chatty decoders.")
FLAGS.define("serving_migrate_budget", 16,
             "page-migration admission budget: KV pages a DESTINATION "
             "replica accepts per fleet tick across in-flight "
             "migrations (chain handoffs and cross-replica prefix "
             "seeds). Charged like chunked prefill — a blob of n pages "
             "waits ceil(n/budget) ticks in the destination's transfer "
             "queue and never blocks its decode tick. 0 disables "
             "migration (prefill-class replicas then decode their own "
             "requests to completion).", parser=int)
FLAGS.define("serving_tenant_classes", "",
             "multi-tenant SLO registry for the fleet control plane "
             "(serving/control.py): a comma list of 'name:class' pairs "
             "('alice:interactive,bulk:batch'; a bare name means "
             "standard). Classes bind latency-tier deadlines "
             "(interactive 0.5s / standard 2s / batch none), WFQ "
             "weights (4/2/1) and preemption precedence (batch slots "
             "are victimized first). Empty = no registry: submits keep "
             "their explicit deadlines, quotas and precedence are off. "
             "Unknown tenants auto-register as standard on first "
             "touch.")
FLAGS.define("serving_wfq", False,
             "weighted fair queuing at the FleetRouter: submits buffer "
             "in per-tenant virtual-time queues (prompt-token-weighted "
             "service, weights from the tenant registry) and release "
             "to dispatch each tick bounded by the READY replicas' "
             "admission slack — one tenant's 10x prompt storm backlogs "
             "only its own queue while other tenants keep their "
             "deadline SLO. Off = the classic submit->dispatch FIFO.")
FLAGS.define("serving_autoscale", False,
             "fleet autoscaler policy loop (serving/control.py "
             "Autoscaler) on the fleet's injected clock: joins a "
             "replica when any pressure signal breaches its hi "
             "threshold (queue_wait_ms_p95, live-page fraction, "
             "prefill backlog, WFQ backlog, fresh deadline misses) and "
             "drains the newest idle replica when the fleet is "
             "provably idle — never the last prefill-capable replica "
             "of a disaggregated fleet. Hysteresis via "
             "serving_autoscale_cooldown.")
FLAGS.define("serving_autoscale_cooldown", 10,
             "autoscaler hysteresis: fleet ticks with NO scaling "
             "action after any join/drain, so one pressure spike "
             "cannot flap the fleet size tick-over-tick.", parser=int)
FLAGS.define("obs_trace", False,
             "request-scoped span tracing (paddle_tpu.obs): when on, "
             "ServingEngine/FleetRouter construct a real Tracer on "
             "their injected clock and every request lifecycle edge "
             "(submit/route/admit/prefill chunk/decode tick/preempt/"
             "resubmit/terminal), fleet lease/fence/reap transition, "
             "and PagePool alloc/ref/free lands on one exportable "
             "timeline (python -m paddle_tpu.obs export -> Perfetto). "
             "Checked at CONSTRUCTION time (the audit_jit idiom): set "
             "it before building the engine/fleet being traced. Off = "
             "the shared NULL_TRACER, a true no-op — zero events, zero "
             "clock reads, zero extra compiles or host syncs on the "
             "decode tick.")
FLAGS.define("obs_keep_all", True,
             "flag-built tracers retain the FULL event list for export "
             "(the replay/debug default). A long-running service should "
             "set this off: only the bounded flight-recorder ring "
             "(obs_ring_size) is kept, so tracing memory cannot grow "
             "without bound; save()/export then cover the ring's most-"
             "recent window.")
FLAGS.define("obs_ring_size", 4096,
             "flight-recorder depth: the tracer keeps this many most-"
             "recent events in a bounded ring, dumped to a postmortem "
             "file whenever a conservation invariant (PAGE-LEAK/"
             "REF-LEAK/FLEET-LEAK) trips.", parser=int)
FLAGS.define("obs_dump_dir", "/tmp/paddle_tpu_obs",
             "directory for flight-recorder postmortem dumps; each dump "
             "prints a grep-able 'OBS-POSTMORTEM: <path>' line that "
             "tools_tier1.sh surfaces on any ladder exit >= 3.")
FLAGS.define("fluid_verify", "warn",
             "static program verification before Executor.run compiles "
             "a fluid Program: 'warn' (default) logs every diagnostic "
             "the paddle_tpu.analysis verifier finds, 'strict' raises "
             "on ERROR diagnostics (shape/dtype conflicts, "
             "def-before-use, dangling fetches, duplicate writers), "
             "'off' disables.  Runs once per compiled (program, "
             "feed-shape) specialization, so steady state pays nothing.")
FLAGS.define("jit_audit", False,
             "retrace auditing: when on, audit_jit-instrumented call "
             "sites (serving decode/prefill, trainer steps, inference, "
             "ZeRO placement, fluid executor) record abstract-signature "
             "-> compile events in paddle_tpu.analysis.retrace.auditor() "
             "and flag compiles after seal() — or recompiles of an "
             "already-compiled signature — as RETRACE diagnostics.  "
             "Checked at wrap time: set it BEFORE constructing the "
             "engine/trainer being audited.  Off = bare jax.jit, zero "
             "overhead.")
FLAGS.define("xla_audit_const_bytes", 65536,
             "const-capture threshold for the jaxpr auditor (python -m "
             "paddle_tpu.analysis xla): an array larger than this many "
             "bytes baked into an audited site's executable as a jaxpr "
             "const (instead of an argument) is an XLA-AUDIT error — "
             "consts are re-baked on every compile, duplicated per "
             "specialization, and invisible to donation. Per-site "
             "override: SiteContract(const_bytes=...).", parser=int)
FLAGS.define("xla_audit_big_arg_bytes", 1048576,
             "donation-candidate threshold for the jaxpr auditor: a "
             "non-donated argument larger than this many bytes whose "
             "avals all match unclaimed outputs is reported (WARNING) "
             "as a donation candidate — if the caller overwrites it "
             "with the result (the repo's step idiom), donating saves "
             "a full copy. Per-site override: "
             "SiteContract(big_arg_bytes=...).", parser=int)
FLAGS.define("conc_audit_max_schedules", 64,
             "per-drive schedule budget for the concurrency auditor's "
             "schedule-permutation explorer (python -m "
             "paddle_tpu.analysis concurrency): each chaos drive "
             "replays at most this many permuted intra-tick schedules "
             "(single-tick deltas first, then depth-2 combinations) "
             "against its canonical fingerprint. The default explores "
             "well past the >=50-interleavings-per-drive bar the audit "
             "documents; raise it for deeper soak runs, lower it only "
             "for quick smoke iterations.", parser=int)
FLAGS.define("shard_audit_virtual_devices", 8,
             "virtual CPU device count the sharding-audit CLI (python "
             "-m paddle_tpu.analysis sharding) forces before backend "
             "init, so its ZeRO placement drive runs on a real "
             "multi-device 'data' axis without TPU hardware (the "
             "tests/conftest.py trick). Only effective when the jax "
             "backend has not initialized yet; <=1 disables the "
             "forcing and the placement drive degrades to a loud "
             "'not audited' notice.", parser=int)
FLAGS.define("train_bad_step_policy", "off",
             "default bad-step guard for trainer.SGD (per-trainer "
             "override: SGD(guard=BadStepGuard(...))): 'off' = the "
             "classic unguarded step; 'skip' = fuse a global-norm + "
             "finiteness check over the gradients into the jitted step "
             "and skip bad steps in-graph (params, optimizer slots and "
             "model state untouched, counted lazily — no new per-step "
             "host sync); 'rollback' = skip, plus K consecutive bad "
             "steps (train_bad_step_window) dump a flight-recorder "
             "postmortem and raise BadStepRollback so the resilience "
             "supervisor restarts from the last verified checkpoint.")
FLAGS.define("train_bad_step_max_norm", 0.0,
             "bad-step guard: global gradient-norm ceiling — a FINITE "
             "step whose grad norm exceeds this is also skipped "
             "(0 = finiteness check only). Unlike "
             "gradient_clipping_threshold this does not rescale; it "
             "refuses the step.", parser=float)
FLAGS.define("train_bad_step_window", 3,
             "bad-step guard hysteresis: under policy 'rollback', this "
             "many CONSECUTIVE bad steps trigger the rollback. Also the "
             "default host-readback cadence for the on-device "
             "consecutive counter (BadStepGuard.check_every).",
             parser=int)
FLAGS.define("train_ckpt_async", False,
             "write training checkpoints on a background thread "
             "(resilience.AsyncCheckpointer): the train loop stalls "
             "only for the device->host snapshot, never the "
             "tar/pkl/md5/meta disk commit. Depth-one pipelined — a new "
             "save first waits out the previous write, and the elastic "
             "trainer acks master tasks only past that durability "
             "barrier. Per-call override: train(async_save=...).")
FLAGS.define("train_ckpt_keep", 2,
             "checkpoint prune budget for step-granular training saves: "
             "keep this many newest VERIFIED checkpoints (corrupt dirs "
             "never count toward the budget, so torn young saves cannot "
             "reap the only good artifact). 0 disables pruning. "
             "Per-call override: train(keep=...).", parser=int)
FLAGS.define("save_dir", "./output", "default checkpoint output directory")
FLAGS.define("log_level", "INFO", "logging level")
FLAGS.define("prealloc_mem", False, "let XLA preallocate the whole HBM arena")
