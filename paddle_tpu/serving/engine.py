"""ServingEngine: the user-facing paged-KV continuous-batching API.

Usage::

    model = DecoderLM(vocab_size=512, num_layers=2, num_heads=2,
                      head_dim=16)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, eos_id=1, page_size=16,
                        num_pages=96, max_pages_per_seq=8, max_slots=8)
    rid = eng.submit([7, 12, 3], max_tokens=32, deadline_s=2.0,
                     on_token=lambda tok: print(tok))
    results = eng.run()          # {rid: [generated tokens...]}
    eng.status(rid)              # RequestStatus.COMPLETED
    eng.metrics.snapshot()       # tokens/s, TTFT, SLO counters, ...
    eng.healthz()                # liveness/conservation snapshot

The engine owns exactly ONE compiled tick function family (round 12):
the **unified step**, jitted once per ``(decode_bucket,
prefill_bucket)`` pair — the decode bucket is the fixed ``max_slots``
row count, the prefill bucket the padded total of this tick's packed
prefill-chunk rows (0 on decode-only ticks).  One dispatch embeds the
tick's decode tokens AND every in-flight prefill chunk, scatters all
their K/V into pages (quantizing on write when the pool is int8 — see
``kv_dtype=``), and runs ONE ragged paged attention
(``ragged_paged_attention``: sequence-packed rows, GQA head-group
packing, in-register dequant) over the whole mixed batch — where the
v1 engine paid two dispatches and two softmax passes per tick with
in-flight prefill.

Decoding is greedy (argmax) by default — the deterministic contract
the parity tests pin.  ``submit(..., sampling=SamplingParams(...))``
turns on real sampling (temperature/top-k/top-p with seeded
per-position RNG streams, bit-reproducible across replays), and
``spec_mode="ngram"|"draft"`` (round 18) turns on speculative
decoding: a proposer drafts up to ``spec_k`` tokens per slot per
tick, the SAME unified step verifies all ``k+1`` positions per slot
(the jit ladder gains the ``k`` dimension: one compile per
``(prefill_bucket, k+1)`` pair), the longest agreeing prefix is
accepted — greedy stays token-identical to the oracle — and rejected
tokens roll back via COW-guarded page forks plus
``scheduler.rollback_pages``, so speculation composes with prefix
caching without ever dirtying a shared page.

Robustness layer (round 8): every request moves through a real
:class:`RequestStatus` lifecycle with optional queue/total deadlines and
``cancel(rid)``; timed-out and cancelled requests release their slot and
pages immediately.  The decode tick carries a finite-logits guard that
fails ONLY the poisoned slot (the rest of the fused batch keeps
running), retries transiently-failing ticks, and a progress watchdog
fails slots stuck past ``watchdog_ticks``.  Deadlocked demand is
shed: queued requests whose deadline is provably unmeetable are
early-rejected instead of burning prefill work.  All failure paths are
driven deterministically by a :class:`~paddle_tpu.serving.faults.FaultPlan`
(injectable clock, decode-step errors, NaN logits, page pressure) and a
free-list conservation check runs after every drain.

Prefix caching + chunked prefill (round 9): with
``prefix_cache`` on (the default), admission splits every
prompt into ``cached_prefix_pages + tail`` against a chained-hash
:class:`~paddle_tpu.serving.kv_cache.PrefixCache` — the prefix pages are
refcount-shared (charged zero new pages), the tail prefills with its
positions offset by the cached length, and a full-cover hit
copy-on-write-forks the last shared page and recomputes only the final
token.  Prompts longer than ``prefill_chunk`` (256) prefill one
chunk per tick — since round 12 riding the SAME unified dispatch as the
decode rows rather than a second one — so a long prompt in the queue
no longer degrades running slots' latency.

Tensor-parallel serving (round 13): ``ServingEngine(mesh=)``
places the model megatron-style over a ``model`` mesh axis — attention
heads (and GQA KV heads) + FFN columns column-parallel, the output/FFN-
down projections row-parallel with ONE psum each per layer — using the
model's ``shard_plan()`` as the single placement source of truth, and
the paged pool shards its KV heads the same way (a chip's shard of
the stored ``[L, pages, page, H_kv * D]`` is the lanes of its own
``H_kv/TP`` heads, int8 scales riding along), so every
pool byte number becomes per-chip and the same budget admits tp x the
pages.  The unified step, chunk prefill, ``fork_page``/``zero_pages``
and the decode kernel (via ``shard_map``) all run on the sharded
layout; the flipped :class:`SiteContract`s carry the closed-form psum
budget so ``python -m paddle_tpu.analysis sharding`` proves the decode
hot path stays reduce-not-gather.  ``mesh=None`` keeps the exact
replicated engine (and the exact PR 10 ``P()``/comm=0 contracts).

Block models (generation by diffusion over blocks): a model that carries
``block_length`` (``B``), ``denoise_steps`` (``S``) and ``mask_token_id``
is served block by block.  The sequence is cut into blocks of ``B`` at
absolute positions; a row sees every earlier block and its own block
WHOLE (the one ``token <= position`` mask, with each row attending as
its block's last position while its rotary position stays its own).  A
prompt's whole blocks are prefilled (chunks end on block boundaries; no
token comes of a prompt's last row); then a slot brings its OPEN block's
``B`` rows a tick, unfixed positions holding the mask token, rewritten in
its page at every pass.  A denoising pass fixes the next ``B / S`` masked
positions from the left to their best token, the mask token excluded
(``on_token`` per fixed token, in position order, never past
``max_tokens``).  A full block's clean K/V are written once more and only
then does ``cache_len`` move past it; the tick that writes them also
opens the next block (THE FOLD): the slot brings ``2 B`` rows, the full
block's and, behind them, the next block's ``B`` mask tokens, whose first
``B / S`` positions that same pass fixes.  The step writes every row's
K/V and then attends, so the next block's rows see the clean block and
the clean block's rows see nothing of the next: the mathematics of a
committing pass and a first pass in two ticks, in one.  A block so costs
``S`` ticks of its slot, not ``S + 1``.  The same rule holds behind a
prompt: the tick that prefills its last chunk carries the slot's first
open block as its decode rows, so the first tokens come of that tick.
The fold takes the next block's page where the full block ended its own
(as speculation's lookahead does: never by preemption); without one the
slot commits alone and opens the next block a tick later.  An answer's
last block is never committed (nothing follows it).  The step's ``k1`` is
``2 B`` rows a slot; the rows a slot does not bring are invalid rows,
which write no K/V, attend to nothing and take no expert.  Which pass a
slot stands at follows from its request
alone (``cache_len`` and the tokens it has), so a preempted or cancelled
request needs no unwinding, and the step's shapes do not depend on the
pass: it computes logits for ``[slots, B / S]`` selected rows and
chooses each row's best unmasked token itself, so a slot's few tokens
and a finite flag cross to the host, and the logits only for a request
that samples.
Speculation, tensor parallelism, the host tier and chain migration
refuse a block model at construction; the prefix cache serves it (whole
pages of prefilled blocks).

Window layers: a model that says ``layer_window(l)`` has layers of more
than one KIND (``kv_cache.layer_kinds``).  The full-attention layers keep
what every model's layers kept: pages from the free list, a page table a
slot, admission, growth and preemption by pages; the pool's arrays then
hold those layers only.  Each window kind keeps a ring of pages a slot
(``kv_cache.WindowRing``: the window, the rows a slot brings in a step,
page rounding), in arrays of its own, bound to the slot: what falls out
of the window is overwritten by the same sequence's later positions while
it lives, and the ring goes back with the slot.  One ``pool_bytes`` is
divided between the kinds (the rings take what their bound needs, the
free list the rest); ``free_bytes`` and ``check_page_conservation`` count
both.  The compiled step writes a row's K/V to the page of its layer's
kind and attends with the layer's window (``ragged_paged_attention(...,
window=)``: pages below it are neither fetched nor visited); a model
without windows has the one kind and builds the pool, the tick buffer and
the step it always built.  The prefix cache is not built for such a model
(a hit would stitch pages whose window layers' K/V are gone), and
speculation, tensor parallelism, int8 pages, the host tier, chain
migration and a block model refuse it at construction.

Recurrent state: a model that says ``layer_state(l)`` keeps, in those
layers, a constant-size state a slot BESIDE the layer's pages or ring
(``kv_cache.RecurrentState``: a state-space branch's state and its
convolution's last inputs; arrays ``[slots, ...]``, one a layer and leaf,
under the same ``pool_bytes``, counted by ``free_bytes`` and
``check_page_conservation``).  The compiled step hands ``model.mix`` the
layer's arrays and the tick's row layout and takes them back; they enter
and leave the step behind the rings' arrays, donated, and never cross to
the host.  The rules (``mix`` keeps them, the tests pin them): a slot
decodes OR prefills in a step and its state is written once; a chunk that
starts at position 0 reads zeros whatever the slot held (admission,
re-prefill after preemption: nothing is cleared on the host); a chunk at
a later position reads what the sequence's last row wrote; padding rows,
invalid decode rows and idle slots leave the state bit for bit.  What
cannot hold over such a state refuses the model at construction, each by
its mechanism (``_refuse_for_recurrent_model``): the prefix cache,
speculation, the host tier, chain migration, tensor parallelism, a block
model.  Preemption works: the pages go back, the state is overwritten
from zero at the re-prefill.

Looped models: a model that says ``loops`` (``T > 1``) runs its stack of
``num_layers`` weight layers ``T`` times a tick over the SAME parameters
(a looped, or universal, transformer: ``2.7 B`` parameters doing the work
of a four times deeper forward).  A layer's weights are then no longer
its cache: the keys and values that pass ``t`` makes in weight layer
``l`` are read by pass ``t`` alone, at every later position, so THE RULE
is that weight layer ``l`` at pass ``t`` writes and attends over CACHE
layer ``t * num_layers + l``.  The KV manager's layer axis counts cache
layers (``kv_cfg.num_layers = loops x`` the model's layers): a token, and
so a page, costs ``loops`` times the K/V, and ``pool_bytes -> pages``,
admission, growth, preemption and the accounting after a drain all read
that one number.  The compiled step unrolls the passes (each under a
scope ``pass<t>`` around the layers' ``l<l>``), carries every row of the
tick (decode rows and a prefill chunk's alike) through all of them, and
between two passes calls the model's ``close_pass`` where it has one (a
final norm whose output is the next pass's input; it also hands back the
pass's exit probability a row, from which the step counts ``loop_passes``,
``exit_rows`` and ``exit_step_milli`` on its decode rows:
:func:`exit_distribution`).  Every token runs every pass (no row leaves
early), the logits are the last pass's.  Chunked prefill, the prefix
cache (a page holds every cache layer of its tokens), preemption by
re-prefill and sampling work as for any model; window layers, a recurrent
state, a block model, speculation, tensor parallelism, the host tier and
chain migration refuse a looped model at construction
(``_refuse_for_looped_model``).  A model without ``loops`` (or with 1)
builds the pool and the step it always built.

What lands when (both step kinds): a step chooses its tokens itself.
A one-token tick takes, inside the compiled step, the first maximum of
each decode row's and of each chunk-final row's logits and whether the
row is all finite; a block step each fixed position's best unmasked
token.  What leaves the step for the host is ONE int32 vector of words
(the choices, the finite flags, the model's counts), replicated over the
engine's mesh; the logits stay on the device.  The read of those words
LAGS the dispatch by a call: ``step()`` dispatches tick ``k + 1`` and
then reads what tick ``k`` came to, so the host's part of a tick
(schedule, assemble, upload, walk) runs beside the device's and not
between two of its steps.  What a step will come to is known at its
dispatch but for the tokens' values (a decode row yields one token, a
prompt's final chunk its first, a pass fixes so many, a full block is
committed and in a folded tick the next one opened, a chunk ends there),
so the request moves on then
(``cache_len``, ``Request.pending``: :meth:`ServingEngine._advance`) and
the next step takes the pending tokens from the last step's words, on
the device; the finite guards, the prefix cache's inserts and
``on_token`` run when the words arrive (:meth:`ServingEngine._land`),
one ``step()`` call later, and ``has_work`` stays true while a step is
in the air (``_flying``).  A request that ended meanwhile is passed
over; an EOS that lands while a further row of its slot is in the air
drops that row (a block model's folded pass among them: the block it
committed and the one it opened are the ended request's).  A step lands
in its OWN call where the host needs its tokens or its logits before the
next step is assembled, which the engine sees from its own state: a
fault plan is bound; a proposer is
bound (the verify walk reads the rows' logits and drafts from what it
accepts); the engine's ``role`` hands requests over between calls; a
request that rode the step samples (its slot's rows are fetched for it
alone).  The step in the air lands before the scheduler where growth
may preempt (a preempted request re-prefills from its tokens) and where
nothing rides the next step; ``land()`` reads it for a caller that is
about to export a running request's tokens (``migratable_rids``,
``migrate.export_chain``).  ``ServingMetrics`` counts the steps read
after the next dispatch (``steps_lagged``) beside ``step_dispatches``.

The model plugs in through the small :class:`DecodeModel` contract
rather than a ``Topology``: serving needs per-layer access to Q/K/V
*before* attention runs (the cache sits between them), which the opaque
layer graph doesn't expose.  :class:`DecoderLM` is the built-in
reference implementation (and the bench model); any object with the same
methods works, so a topology-built transformer can be adapted by
exposing its projection weights.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import math
import time
from collections import deque
from dataclasses import dataclass
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.analysis.retrace import SiteContract, audit_jit, auditor
from paddle_tpu.obs.registry import MetricsRegistry
from paddle_tpu.obs.trace import NULL_TRACER, tracer_for
from paddle_tpu.ops.attention import mha_reference
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving.decode_attention import (
    BLOCK_ROWS, _ragged_reference_blocked, attention_path,
    expand_decode_rows, heads_per_cell, ragged_paged_attention,
    ragged_paged_attention_tp, ragged_walk, tall_rows_for, visit_counts)
from paddle_tpu.serving.faults import (FaultPlan, InjectedDeviceError,
                                       PageLeakError)
from paddle_tpu.serving.kv_cache import (NULL_PAGE, NUM_PAGES, PAGE_SIZE,
                                         _CHAIN_SEED, HostPageTier,
                                         KVPages, PagedKVConfig, PagePool,
                                         PrefixCache, RecurrentState,
                                         WindowRing, append_token,
                                         dequantize_kv, fork_page,
                                         init_kv_pages, kv_pool_specs,
                                         layer_kinds, layer_pages,
                                         make_window_ring, pages_for_budget,
                                         pages_spanned,
                                         read_pages, recurrent_state,
                                         resolve_kv_dtype,
                                         split_pool_bytes,
                                         write_pages, zero_pages)
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.serving.speculate import (DraftProposer, NGramProposer,
                                          SamplingParams, accept_tokens,
                                          next_token)
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          Request, RequestStatus,
                                          SchedulerConfig, bucket_for,
                                          pack_prefill_chunks)

__all__ = ["DecodeModel", "DecoderLM", "SamplingParams", "ServingEngine",
           "exit_distribution", "greedy_decode_reference", "validate_tp"]

_SPEC_MODES = ("off", "ngram", "draft")
# the mesh axis a tensor-parallel engine places heads and FFN columns
# over: what ``kv_pool_specs``, ``shard_plan`` and ``bind_tp`` default to
TP_AXIS = "model"
# what else a tick of a block model's slot wrote beside its open block
# (``_Flight.passes``, ``ServingEngine._passes``): nothing; the full block
# before it, committed in the tick that opens the next; the last chunk of
# its prompt, with the first open block behind it
_FOLD_NONE, _FOLD_BLOCK, _FOLD_CHUNK = 0, 1, 2
# the default ladder of padded prefill row counts (``buckets=``); its top
# and the chunk bound the packer's row budget a tick (``_prefill_budget``)
PREFILL_BUCKETS = (32, 64, 128, 256, 512)


class DecodeModel:
    """Structural contract the engine drives (duck-typed; subclassing is
    optional).  All methods must be jax-traceable and shape-polymorphic
    over leading batch/sequence dims:

    - ``num_layers``, ``num_heads``, ``head_dim``, ``vocab_size``
    - ``num_kv_heads`` (optional, defaults to ``num_heads``): GQA — K/V
      carry this many heads (``<= num_heads``, dividing it); query head
      ``h`` reads KV head ``h // (num_heads // num_kv_heads)``.  The
      paged pool stores KV heads only and the ragged kernel loads each
      K/V page once per head GROUP instead of once per query head.
    - ``embed(params, tokens, positions) -> [..., E]``
    - ``qkv(params, layer, x) -> (q, k, v)`` — q ``[..., H, D]``, k/v
      ``[..., H_kv, D]``
    - ``attn_out(params, layer, ctx, x) -> [..., E]`` — attention output
      ``ctx`` [..., H, D] combined with the residual stream ``x``
      (projection, residual, FFN — whatever the architecture does after
      attention)
    - ``logits(params, x) -> [..., vocab_size]``
    - ``rotate(params, layer, q, k, positions) -> (q, k)`` (optional):
      called on what ``qkv`` returned with the rows' absolute positions
      ``[T]``, before K is written to its page — rotary positions.
    - ``step_counters`` (optional, a tuple of names) with
      ``attn_out_counted(params, layer, ctx, x, valid) -> (x, int32
      [len(step_counters)])``: called in ``attn_out``'s place with the
      rows' validity ``[T]`` (padding rows of the step are False); the
      counts are summed over the step's layers, read back beside the
      logits and added to ``ServingMetrics`` under those names.
    - ``block_length``, ``denoise_steps``, ``mask_token_id`` (optional,
      together): a BLOCK model, which generates by diffusion over blocks
      of ``block_length`` positions (``ServingEngine``: "block models").
    - ``layer_window(layer) -> Optional[int]`` (optional): the number of
      most recent tokens the layer attends over, None for a layer that
      attends over everything (``ServingEngine``: "window layers").  The
      query heads a layer brings are its ``q``'s (``q.shape[-2]``; any
      multiple of ``num_kv_heads``); ``num_heads`` is the most any layer
      has, and ``layer_heads`` (optional; a count a layer) says each
      layer's where they differ: the host's count of the kernel's
      visits lays the tall blocks out by it.
    - ``layer_state(layer) -> Optional[{leaf: (shape, dtype)}]`` with
      ``mix(params, layer, x, state, rows) -> (mixed, state)`` (optional,
      together): a layer that keeps a constant-size RECURRENT state a
      slot beside its K/V (``ServingEngine``: "recurrent state").
      ``mix`` is called in the layer's scope beside ``qkv``, on the same
      block input ``x [T, E]``, with the layer's arrays ``{leaf: [slots,
      ...]}`` and the tick's row layout ``rows``: ``row_seq [T]`` (a row's
      slot), ``pos [T]``, ``live [T]`` (False: padding or an invalid row)
      and ``decode_rows`` (static: the first so many rows are the slots'
      decode rows, row ``s`` slot ``s``'s; the rest the bucket's chunks, a
      chunk's rows contiguous).  ``mixed`` is the model's own (the
      branch's rows, its counts) and reaches ``attn_out`` /
      ``attn_out_counted`` as a further last argument.

    - ``loops`` (optional, an int; absent: 1) with ``close_pass(params,
      t, x) -> (x, lam)`` (optional): a LOOPED model, whose ``num_layers``
      weight layers run ``loops`` times a tick over the same parameters
      (``ServingEngine``: "looped models").  Weight layer ``l`` at pass
      ``t`` keeps cache layer ``t * num_layers + l``.  ``close_pass`` is
      called behind the last layer of pass ``t`` (0-based) on the tick's
      rows ``x [T, E]``; what it returns is the next pass's input (the
      last pass's goes to ``logits``), and ``lam [T]`` (or None) the
      probability with which a row would leave after this pass, which
      the engine only counts (no row leaves early).

    Tensor-parallel serving (``ServingEngine(mesh=...)``) additionally
    needs:

    - ``shard_plan(axis="model", tp=None) -> {param name: per-dim
      PartitionSpec tuple}`` — the megatron placement (attention heads +
      FFN columns over ``axis``, row-parallel down projections); and
    - ``bind_tp(mesh, axis) -> model`` (optional) — return a TP-bound
      VIEW of the model whose forward asserts the plan's activation
      shardings (sharding constraints after each projection) so the
      row-parallel blocks lower to exactly one psum each.  Must NOT
      mutate ``self``: the same model object may back a replicated
      engine in the same process (the A/B benches do exactly that).
    """

    num_layers: int
    num_heads: int
    head_dim: int
    vocab_size: int
    num_kv_heads: int  # optional on duck-typed models (= num_heads)


def validate_tp(model: "DecodeModel", tp: int, axis: str = "model") -> None:
    """Fail fast — with a fix in the message — on a model whose
    geometry cannot split ``tp`` ways over ``axis``: attention sharding
    moves whole query/KV heads per chip and FFN sharding whole columns,
    so every one of those counts must divide.  Checked at BOTH
    ``ServingEngine(mesh=...)`` construction and ``shard_plan()``, so a
    bad plan can't reach placement from either direction."""
    from paddle_tpu.platform.enforce import enforce_that

    tp = int(tp)
    enforce_that(tp >= 1, f"tensor-parallel degree must be >= 1, got {tp}",
                 context="serving-tp")
    if tp == 1:
        return
    h = int(model.num_heads)
    kvh = int(getattr(model, "num_kv_heads", 0) or h)
    enforce_that(
        h % tp == 0,
        f"num_heads ({h}) is not divisible by the {axis!r} mesh axis "
        f"size ({tp}): tensor parallelism places whole attention heads "
        f"per chip — pick a tp that divides {h}, or resize the model",
        context="serving-tp")
    enforce_that(
        tp <= kvh,
        f"GQA corner: tp={tp} exceeds num_kv_heads ({kvh}) — a KV head "
        "cannot split below one per chip and this engine does not "
        f"replicate KV heads across the {axis!r} axis; lower tp to at "
        f"most {kvh}, or serve a model with more KV heads",
        context="serving-tp")
    enforce_that(
        kvh % tp == 0,
        f"num_kv_heads ({kvh}) is not divisible by the {axis!r} mesh "
        f"axis size ({tp}): the paged KV pool shards whole KV heads per "
        f"chip — pick a tp that divides {kvh}", context="serving-tp")
    ffn = int(getattr(model, "ffn_dim", 0) or 0)
    if ffn:
        enforce_that(
            ffn % tp == 0,
            f"FFN width ({ffn}) is not divisible by the {axis!r} mesh "
            f"axis size ({tp}): the column-parallel up projection places "
            f"whole FFN columns per chip — pick a tp that divides {ffn}",
            context="serving-tp")


def exit_distribution(lam):
    """The step a row of a looped model leaves at, as a distribution over
    its passes: ``lam [T, ...]`` is the exit gate's probability behind
    each pass; ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for ``t < T`` and
    the last pass takes what is left, ``p_T = prod_{j<T} (1 - lam_j)``,
    so the ``p_t`` sum to one whatever the last gate says."""
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay])
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]])


def _rms(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps)


class DecoderLM(DecodeModel):
    """A compact pre-norm decoder-only transformer LM implementing the
    :class:`DecodeModel` contract — the built-in serving/bench model.
    Parameter-free RMSNorm keeps the param dict to embeddings +
    projections."""

    def __init__(self, vocab_size: int, num_layers: int = 2,
                 num_heads: int = 2, head_dim: int = 16,
                 ffn_mult: int = 4, max_positions: int = 1024,
                 num_kv_heads: Optional[int] = None):
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = int(num_kv_heads or num_heads)
        if num_heads % self.num_kv_heads != 0:
            raise ValueError(f"num_kv_heads ({self.num_kv_heads}) must "
                             f"divide num_heads ({num_heads})")
        self.head_dim = head_dim
        self.embed_dim = num_heads * head_dim
        self.kv_dim = self.num_kv_heads * head_dim
        self.ffn_dim = ffn_mult * self.embed_dim
        self.max_positions = max_positions
        # tensor-parallel binding (None = unbound; see bind_tp)
        self._tp_mesh = None
        self._tp_axis = None

    # ---- tensor-parallel placement (the megatron plan) -------------------

    def shard_plan(self, axis: str = "model",
                   tp: Optional[int] = None) -> Dict[str, Tuple]:
        """Megatron-style tensor-parallel placement over ``axis``:
        Q/K/V and FFN-up projections are COLUMN-parallel (output
        features — i.e. heads / FFN columns — sharded, no collective on
        the forward matmul); the attention-output and FFN-down
        projections are ROW-parallel (input features sharded, the
        contraction emits ONE psum per block); embeddings, positions
        and the vocab head stay replicated.  Returns ``{param name:
        per-dim PartitionSpec tuple}`` — the single source of truth the
        engine turns into ``NamedSharding``s, the ZeRO composition
        turns into explicit ``ParamAttr.sharding``s, and the serving
        :class:`~paddle_tpu.analysis.retrace.SiteContract` declares to
        the sharding auditor.  ``tp`` (when given) validates
        divisibility up front with actionable errors."""
        if tp is not None:
            validate_tp(self, tp, axis)
        plan: Dict[str, Tuple] = {"emb": (), "pos": (), "out": ()}
        for l in range(self.num_layers):
            plan[f"l{l}.wq"] = (None, axis)
            plan[f"l{l}.wk"] = (None, axis)
            plan[f"l{l}.wv"] = (None, axis)
            plan[f"l{l}.wo"] = (axis, None)
            plan[f"l{l}.w1"] = (None, axis)
            plan[f"l{l}.w2"] = (axis, None)
        return plan

    def bind_tp(self, mesh, axis: str = "model") -> "DecoderLM":
        """Return a TP-bound VIEW of this model: same config, but the
        forward asserts the plan's activation placements with sharding
        constraints — heads sharded after Q/K/V, FFN columns sharded
        after the up projection, and an explicit replicated constraint
        after each ROW-parallel matmul, which is the megatron ``g``:
        GSPMD lowers it to exactly one psum per block instead of
        deferring partial sums into the nonlinearities.  ``self`` is
        NOT mutated — the unbound original can keep backing a
        replicated engine in the same process."""
        import copy

        m = copy.copy(self)
        m._tp_mesh, m._tp_axis = mesh, axis
        return m

    def _tp_sharded(self, x, dim_from_last: int):
        """Constrain ``x`` sharded over the TP axis on the dim
        ``dim_from_last`` positions from the end (no-op unbound)."""
        if self._tp_mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        dims = [None] * x.ndim
        dims[x.ndim - 1 - dim_from_last] = self._tp_axis
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self._tp_mesh, P(*dims)))

    def _tp_psum(self, x):
        """The megatron ``g`` after a row-parallel matmul: constrain
        the partial-sum output replicated, forcing the one psum per
        block (no-op unbound)."""
        if self._tp_mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self._tp_mesh, P()))

    def init_params(self, key) -> Dict[str, jax.Array]:
        e, f, v = self.embed_dim, self.ffn_dim, self.vocab_size
        kv = self.kv_dim
        keys = jax.random.split(key, 2 + 6 * self.num_layers + 1)
        ki = iter(keys)

        def mat(shape, scale):
            return (jax.random.normal(next(ki), shape, jnp.float32) * scale)

        p = {"emb": mat((v, e), 0.02), "pos": mat((self.max_positions, e),
                                                  0.02)}
        for l in range(self.num_layers):
            p[f"l{l}.wq"] = mat((e, e), e ** -0.5)
            p[f"l{l}.wk"] = mat((e, kv), e ** -0.5)
            p[f"l{l}.wv"] = mat((e, kv), e ** -0.5)
            p[f"l{l}.wo"] = mat((e, e), e ** -0.5)
            p[f"l{l}.w1"] = mat((e, f), e ** -0.5)
            p[f"l{l}.w2"] = mat((f, e), f ** -0.5)
        p["out"] = mat((e, v), e ** -0.5)
        return p

    def embed(self, params, tokens, positions):
        return params["emb"][tokens] + params["pos"][positions]

    def qkv(self, params, layer, x):
        h, kvh, d = self.num_heads, self.num_kv_heads, self.head_dim
        xn = _rms(x)
        q = (xn @ params[f"l{layer}.wq"]).reshape(x.shape[:-1] + (h, d))
        k = (xn @ params[f"l{layer}.wk"]).reshape(x.shape[:-1] + (kvh, d))
        v = (xn @ params[f"l{layer}.wv"]).reshape(x.shape[:-1] + (kvh, d))
        # TP: heads live sharded over the model axis (no-ops unbound)
        return (self._tp_sharded(q, 1), self._tp_sharded(k, 1),
                self._tp_sharded(v, 1))

    def attn_out(self, params, layer, ctx, x):
        flat = ctx.reshape(x.shape[:-1] + (self.embed_dim,))
        # row-parallel output projection: contraction over the sharded
        # feature dim -> partial sums -> ONE psum (the _tp_psum
        # constraint), then the replicated residual add
        with jax.named_scope("attn"):
            a = x + self._tp_psum(flat @ params[f"l{layer}.wo"])
        with jax.named_scope("ffn"):
            up = self._tp_sharded(_rms(a) @ params[f"l{layer}.w1"], 0)
            # row-parallel FFN-down projection: the block's second psum
            return a + self._tp_psum(
                jax.nn.gelu(up) @ params[f"l{layer}.w2"])

    def logits(self, params, x):
        with jax.named_scope("head"):
            return _rms(x) @ params["out"]


def greedy_decode_reference(model: DecodeModel, params, prompt: List[int],
                            max_tokens: int, eos_id: int) -> List[int]:
    """The NON-paged oracle: a host loop that re-runs the full causal
    forward over the whole history each step (``mha_reference``, no KV
    cache at all) and greedily extends.  Slow by construction — it
    exists as the parity target for the engine's paged path."""
    tokens = list(prompt)
    out: List[int] = []
    for _ in range(max_tokens):
        # per-step host syncs are the POINT of this oracle: it trades
        # throughput for an unarguable reference trajectory
        t = jnp.asarray(tokens, jnp.int32)[None]   # lint: allow(host-sync)
        pos = jnp.arange(len(tokens), dtype=jnp.int32)[None]
        x = model.embed(params, t, pos)
        for l in range(model.num_layers):
            q, k, v = model.qkv(params, l, x)
            ctx = mha_reference(q, k, v, causal=True)
            x = model.attn_out(params, l, ctx, x)
        nxt = int(jnp.argmax(model.logits(params, x[0, -1])))  # lint: allow(host-sync)
        out.append(nxt)
        tokens.append(nxt)
        if nxt == eos_id:
            break
    return out


@dataclass
class _Flight:
    """A dispatched step whose words the host has not read yet
    (``ServingEngine``: "what lands when")."""

    # every slot that rode the step: (request, slot) and then what its
    # walk needs that the request will no longer say when the words
    # arrive.  A block model's: the start of the block the pass works in,
    # the tokens that block had, the tokens the pass fixes (0 is a lone
    # committing pass) and what clean K/V the same tick wrote before the
    # block (``_FOLD_*``: none, the full block before it, a prompt's
    # last chunk).  Else: the drafts the slot's rows verify, and their
    # proposal probabilities
    passes: List[tuple]
    chunks: list                   # as ``pack_prefill_chunks`` gave them
    words: Any                     # the step's words, on the device
    # its logits, there too: [slots, B / S, V] of a block model, else
    # [slots * k1 + slots, V] (the decode / verify rows, then each
    # slot's chunk-final row)
    logits: Any
    rows: Tuple[int, int, int]     # decode, prefill and padding rows
    h2d_bytes: int
    attn_cells: Tuple[int, int, int, int]
    # what the host counted of the kinds' state (``_kind_counts``)
    kind_counts: Tuple[int, ...] = ()


def _samples(req: Request) -> bool:
    """Whether the request draws its tokens on the host, from logits
    (else they are the step's own choices)."""
    return req.sampling is not None and not req.sampling.greedy


def _settle_heap() -> None:
    """Called when a step program has just been compiled and run.  What
    the process built for it (jaxprs, HLO, the executable's metadata:
    some hundred thousand objects that live as long as the engine) is
    moved out of the garbage collector's generations, so that the full
    collections Python starts now and then walk the young heap only.  A
    tick makes almost nothing for them to free (its objects die by
    reference count), but each walked the whole heap and stalled the
    tick it fell in: 0.13 s, three to five times in a 45 s window on
    the chip's host (PERF.md s6, PR 37)."""
    gc.collect()
    gc.freeze()


class ServingEngine:
    """Paged-KV continuous-batching inference engine (see module doc)."""

    def __init__(self, model: DecodeModel, params, *, eos_id: int,
                 page_size: int = PAGE_SIZE,
                 num_pages: Optional[int] = None,
                 max_pages_per_seq: Optional[int] = None,
                 max_slots: int = 8,
                 buckets: Sequence[int] = PREFILL_BUCKETS,
                 max_queue: Optional[int] = None,
                 kv_dtype="float32",
                 pool_bytes: Optional[int] = None,
                 use_kernel: Optional[bool] = None,
                 queue_deadline_s: float = 0.0,
                 preempt_budget: int = 3,
                 watchdog_ticks: int = 16,
                 decode_retries: int = 2,
                 transient_errors: Tuple[type, ...] = (InjectedDeviceError,),
                 max_retained: int = 10000,
                 prefix_cache: Optional[bool] = None,
                 prefill_chunk: int = 256,
                 faults: Optional[FaultPlan] = None,
                 time_fn: Optional[Callable[[], float]] = None,
                 tracer=None, registry: Optional[MetricsRegistry] = None,
                 mesh=None,
                 spec_mode: str = "off",
                 spec_k: int = 4,
                 draft_model=None, draft_params=None,
                 draft_pool_pages: Optional[int] = None,
                 role: str = "unified",
                 host_tier_bytes: int = 0,
                 swap_in_budget: int = 8,
                 host_kv_dtype: str = "stored"):
        """What an engine is built with (the module doc has the
        mechanisms):

        - ``num_pages`` None: ``pool_bytes`` worth of pages where that is
          given, else ``kv_cache.NUM_PAGES`` (page 0 is the null page);
          ``max_pages_per_seq`` None: half the usable pool.
        - ``buckets``: padded prefill row counts; a tick's packed chunks
          pad to the smallest that holds them, so the step compiles once
          a bucket.  ``prefill_chunk`` should be one of them (a chunk
          above the top bucket rounds up and wastes the excess); 0
          prefills whole prompts.
        - ``kv_dtype``: "float32" | "bfloat16" | "int8" or a dtype; int8
          adds per-token, per-KV-head f32 scales (amax/127 on every
          write), so a ``pool_bytes`` budget holds about 3-4x the pages.
        - ``prefix_cache`` None: built, unless the model has window
          layers or a recurrent state (which refuse an explicit True).
        - ``queue_deadline_s``: a request still queued this long is shed
          as TIMED_OUT (0: never).  ``preempt_budget``: re-prefills
          before a request escalates (requeues ahead of all others and
          is no victim again; 0: unlimited).  ``watchdog_ticks``: a
          RUNNING request silent this many ticks is FAILED (0: off).
        - ``spec_k`` drafts a slot and tick is a jit dimension (one
          compile a ``(bucket, k + 1)`` pair).
        - ``host_tier_bytes`` 0: no host tier (eviction destroys);
          ``swap_in_budget`` host pages promoted a tick (0: spill
          only); ``host_kv_dtype`` "stored" | "int8" (float pages
          transcoded on spill, about 4x the pages in the same bytes).
        """
        from paddle_tpu.platform.enforce import enforce_that

        self.eos_id = int(eos_id)
        # fleet class (round 16): "prefill" replicas hand requests off to
        # "decode" replicas after the first token via the page-migration
        # plane (serving/migrate.py); "unified" runs both phases.  The
        # engine itself treats every role identically — the role is an
        # advertised routing attribute the FleetRouter reads.
        self.role = str(role)
        enforce_that(self.role in ("prefill", "decode", "unified"),
                     f"role must be prefill/decode/unified, got {role!r}",
                     context="serving")
        page_size = int(page_size)
        max_slots = int(max_slots)
        # a block model (see the module doc): up to 2 B rows a slot and
        # tick, B / S tokens fixed a denoising pass, a block in S ticks
        # (its commit rides with the next block's first pass).  None: one
        # token a tick.
        self._block: Optional[int] = None
        self._ticks_per_token = 1.0
        if hasattr(model, "block_length"):
            self._block = int(model.block_length)
            self._denoise_steps = int(model.denoise_steps)
            self._mask_id = int(model.mask_token_id)
            self._fix_rows = self._block // self._denoise_steps
            self._ticks_per_token = self._denoise_steps / self._block
            self._refuse_for_block_model(
                page_size, int(prefill_chunk), mesh, spec_mode,
                host_tier_bytes)
        # int8 turns on quantized pages
        kv_dtype = resolve_kv_dtype(kv_dtype)
        num_kv_heads = int(getattr(model, "num_kv_heads", 0)
                           or model.num_heads)
        # the model's layers by the state they keep (the module doc:
        # "window layers"): full-attention pages first, then a ring a
        # slot for each window.  One kind for a model without windows
        kinds = layer_kinds(model)
        if len(kinds) > 1:
            self._refuse_for_window_model(
                kinds, mesh, kv_dtype, prefix_cache, spec_mode,
                host_tier_bytes)
            prefix_cache = False
        # and what a layer keeps BESIDE them: a constant-size state a slot
        # (the module doc: "recurrent state"); None for a model without
        self._recurrent: Optional[RecurrentState] = recurrent_state(
            model, max_slots)
        if self._recurrent is not None:
            self._refuse_for_recurrent_model(
                mesh, prefix_cache, spec_mode, host_tier_bytes)
            prefix_cache = False
        # a looped model (the module doc: "looped models") runs its weight
        # layers this many times a tick, each pass on cache layers of its
        # own.  1 for every other model
        self._loops = int(getattr(model, "loops", 1))
        if self._loops != 1:
            self._refuse_for_looped_model(
                kinds, mesh, spec_mode, host_tier_bytes)
        rows = int(prefill_chunk) if int(prefill_chunk) > 0 else 1 << 30
        self._rings: Tuple[WindowRing, ...] = tuple(
            make_window_ring(
                kind, slots=max_slots,
                rows=-(-rows // BLOCK_ROWS) * BLOCK_ROWS,
                num_heads=model.num_heads, num_kv_heads=num_kv_heads,
                head_dim=model.head_dim, page_size=page_size,
                max_pages_per_seq=int(max_pages_per_seq or 1 << 30),
                dtype=kv_dtype)
            for kind in kinds[1:])
        # layer -> (its kind: 0 the pages, i the i-th ring; its index
        # among the kind's layers, which is its layer in the kind's arrays)
        self._layer_state = {l: (i, j) for i, kind in enumerate(kinds)
                             for j, l in enumerate(kind.layers)}
        # the pool's layer axis is CACHE layers: a pass's own a weight layer
        full_layers = len(kinds[0].layers) * self._loops
        # tensor-parallel placement (ROADMAP item 1): with a mesh, the
        # megatron shard_plan places attention heads + FFN columns over
        # the `model` axis, the paged pool shards its KV-head dim the
        # same way, and every byte/contract below becomes per-chip.
        self.mesh = mesh
        self.tp = 1
        self._shard_plan: Optional[Dict[str, Tuple]] = None
        self.param_sharding = None
        # where a tick's input buffer is placed: replicated over the
        # mesh, as the compiled step reads it (None: the default device,
        # where the one-chip engine's pool lies)
        self._tick_sharding = None
        if mesh is not None:
            enforce_that(
                TP_AXIS in mesh.axis_names,
                f"mesh has no {TP_AXIS!r} axis (axes: "
                f"{tuple(mesh.axis_names)}) — build one with "
                "make_mesh((tp,), ('model',))", context="serving-tp")
            self.tp = int(mesh.shape[TP_AXIS])
            validate_tp(model, self.tp, TP_AXIS)
            enforce_that(
                hasattr(model, "shard_plan"),
                "ServingEngine(mesh=...) needs the model to expose "
                "shard_plan(axis, tp) (see the DecodeModel contract); "
                f"{type(model).__name__} does not", context="serving-tp")
            enforce_that(
                isinstance(params, dict),
                "tensor-parallel placement needs a flat {name: array} "
                "param dict (the shard_plan key space)",
                context="serving-tp")
            self._shard_plan = {k: tuple(v) for k, v in
                                model.shard_plan(axis=TP_AXIS,
                                                 tp=self.tp).items()}
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.param_sharding = {
                name: NamedSharding(mesh,
                                    P(*self._shard_plan.get(name, ())))
                for name in params}
            params = {name: jax.device_put(v, self.param_sharding[name])
                      for name, v in params.items()}
            self._tick_sharding = NamedSharding(mesh, P())
            if hasattr(model, "bind_tp"):
                # a TP-bound VIEW (bind_tp must not mutate): the bound
                # forward asserts the activation shardings, so each
                # row-parallel block lowers to exactly one psum
                model = model.bind_tp(mesh, TP_AXIS)
        self.model = model
        self.params = params
        if num_pages is None and pool_bytes is not None:
            # size the pool by BYTES — PER CHIP: smaller KV dtypes admit
            # proportionally more pages, and tensor parallelism tp x
            # more again (each chip stores 1/tp of every page's KV
            # heads).  The scheduler charges admission in pages, so both
            # multipliers flow straight into admissible concurrency.
            num_pages = pages_for_budget(
                split_pool_bytes(pool_bytes, self._rings, self._recurrent),
                full_layers,
                model.num_heads, model.head_dim, page_size, kv_dtype,
                num_kv_heads=num_kv_heads, tp=self.tp)
        num_pages = int(num_pages or NUM_PAGES)
        if max_pages_per_seq is None:
            # default: one sequence may claim up to half the usable pool
            max_pages_per_seq = max(1, (num_pages - 1) // 2)
        self.queue_deadline_s = queue_deadline_s or None   # 0 = disabled
        self.watchdog_ticks = int(watchdog_ticks)          # 0 = disabled
        self.decode_retries = max(0, int(decode_retries))
        # which exceptions the decode tick treats as transient and
        # retries.  Default: only the fault-plan's injected error.  The
        # retry is sound ONLY for errors raised before the decode
        # executes (the fault plan's injection point): once the jitted
        # step has run, the donated KV pool may already be consumed, so
        # retrying a real mid-execution XLA failure needs KV
        # snapshot/rebuild this engine does not do — don't widen the set
        # to device errors without adding that.
        self.transient_errors = tuple(transient_errors)
        self.max_retained = max(1, int(max_retained))
        self.faults = faults
        # clock precedence: fault-plan clock > explicit time_fn > monotonic
        if faults is not None and faults.clock is not None:
            self._time = faults.clock
        else:
            self._time = time_fn or time.monotonic
        self.kv_cfg = PagedKVConfig(
            num_layers=full_layers, num_heads=model.num_heads,
            head_dim=model.head_dim, page_size=page_size,
            num_pages=num_pages, max_pages_per_seq=int(max_pages_per_seq),
            dtype=kv_dtype, num_kv_heads=num_kv_heads, tp=self.tp)
        self._kv: KVPages = init_kv_pages(self.kv_cfg, mesh=self.mesh,
                                          axis=TP_AXIS)
        self._ring_kv: Tuple[KVPages, ...] = tuple(
            init_kv_pages(ring.cfg) for ring in self._rings)
        # the recurrent kind's arrays ({leaf: [slots, ...]} a layer of the
        # kind) and, by model layer, which of them is the layer's
        self._rec_kv: Tuple[Dict[str, jax.Array], ...] = \
            self._recurrent.init() if self._recurrent is not None else ()
        self._rec_layer = {l: j for j, l in enumerate(
            self._recurrent.layers if self._recurrent is not None else ())}
        self.pool = PagePool(num_pages)
        if prefix_cache is None:
            prefix_cache = True
        self._prefill_chunk = max(0, int(prefill_chunk))
        self.cache: Optional[PrefixCache] = None
        if prefix_cache:
            hash_fn = faults.cache_hash_fn() if faults is not None else None
            self.cache = PrefixCache(self.pool, page_size, hash_fn=hash_fn)
        # hierarchical host tier (round 21): evicted reclaimable pages
        # demote to host RAM (checksummed) instead of being destroyed;
        # lookups that run off the device index swap the continuation
        # back in, verified, charged like chunk prefill.  Off unless a
        # byte budget is set.
        self.host_tier: Optional[HostPageTier] = None
        self._swap_in_budget = int(swap_in_budget)
        self._host_hits = 0   # swap-in events that promoted >= 1 page
        host_bytes = int(host_tier_bytes)
        if self.cache is not None and host_bytes > 0:
            self.host_tier = HostPageTier(
                host_bytes, dtype=str(host_kv_dtype), faults=faults)
            self.cache.host_tier = self.host_tier
            # read at call time: self._kv is rebound every step
            self.cache.page_reader = \
                lambda pages: read_pages(self._kv, pages)
        self.scheduler = ContinuousBatchingScheduler(
            self.pool, SchedulerConfig(
                max_slots=max_slots, page_size=page_size,
                max_pages_per_seq=int(max_pages_per_seq),
                max_queue=max_queue,
                preempt_budget=preempt_budget if preempt_budget > 0
                else None, block_length=self._block),
            cache=self.cache, time_fn=self._time)
        # counters the model's layers return from inside the step
        self._counted: Tuple[str, ...] = tuple(
            getattr(model, "step_counters", ()))
        # what the step itself counts of a looped model's passes and of
        # the exit gate on its decode rows, behind them in the words
        self._loop_counted: Tuple[str, ...] = (
            "loop_passes", "exit_rows", "exit_step_milli") \
            if self._loops != 1 else ()
        # and what the host counts of the kinds' state a step
        # (``_kind_counts``), under these names beside them
        self._kind_counted: Tuple[str, ...] = (
            "full_kv_tokens_held", "window_kv_tokens_held",
            "window_kv_tokens_live", "window_pages_released",
            "window_kernel_calls",
            "window_grid_cells", "window_live_cells") if self._rings else ()
        if self._recurrent is not None:
            self._kind_counted = (self._kind_counted
                                  or ("full_kv_tokens_held",)) + (
                "state_slots_live", "state_bytes_live")
        self.metrics = ServingMetrics(
            pool_pages=self.pool.num_usable,
            model_counters=self._counted + self._loop_counted
            + self._kind_counted)
        # the step in the air, and the words a step takes where none is
        # (no token is pending then)
        self._flying: Optional[_Flight] = None
        self._no_words = None
        # obs: tracer (FLAGS.obs_trace-gated at construction — a fleet
        # rebinds its shared, replica-scoped tracer via set_tracer) and
        # the unified metrics registry the per-stage latency histograms
        # and healthz publish into
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._reg_labels: Dict[str, str] = {}
        self._tracer = NULL_TRACER
        self._postmortems_dumped: set = set()
        self.set_tracer(tracer if tracer is not None
                        else tracer_for(self._time, registry=self.registry))
        # dispatch path, decided ONCE through the single chooser (the
        # per-call decision of v1 is gone): kernel iff the shapes are
        # native-compile-clean on this backend, or forced by the caller
        if use_kernel is None:
            self._ragged_kernel = attention_path(
                self.kv_cfg.head_dim, self.kv_cfg.page_size,
                num_heads=self.kv_cfg.num_heads,
                num_kv_heads=self.kv_cfg.kv_heads,
                quantized=self.kv_cfg.quantized) == "kernel"
        else:
            self._ragged_kernel = bool(use_kernel)
        # KV-head groups of the kernel's grid on one chip (its middle
        # axis), for the grid counters of ``_attn_cells``
        kvh = self.kv_cfg.kv_heads // self.tp
        self._attn_cell_heads = heads_per_cell(
            kvh, self.kv_cfg.page_size, self.kv_cfg.head_dim,
            jnp.dtype(self.kv_cfg.dtype).itemsize, self.kv_cfg.quantized)
        self._attn_head_groups = kvh // self._attn_cell_heads
        # and, for each kind of layer state, its layers by their GQA
        # group (query heads a KV head: a tall block's height follows
        # it): {group: layers}.  ``layer_heads`` is the model's where
        # its layers differ (the DecodeModel contract)
        heads = getattr(model, "layer_heads", None) or \
            (model.num_heads,) * int(model.num_layers)
        self._kind_groups = tuple(
            collections.Counter(int(heads[l]) // self.kv_cfg.kv_heads
                                for l in kind.layers * self._loops)
            for kind in kinds)
        self._buckets = tuple(sorted(int(b) for b in buckets))
        self._max_slots = max_slots
        # prefill-row packing: the kernel needs each sequence's rows
        # padded to whole BLOCK_ROWS blocks; the per-tick row budget
        # bounds the (decode_bucket, prefill_bucket) jit-pair ladder
        self._row_align = BLOCK_ROWS if self._ragged_kernel else 1
        top = max(self._buckets) if self._buckets else \
            self.kv_cfg.max_seq_len
        chunk_rows = self._prefill_chunk if self._prefill_chunk > 0 \
            else self.kv_cfg.max_seq_len
        chunk_rows = -(-chunk_rows // self._row_align) * self._row_align
        self._prefill_budget = max(top, chunk_rows)
        # speculative decoding (round 18): a proposer drafts up to
        # spec_k tokens per running slot per tick and ONE widened step
        # verifies all k+1 positions (each speculative slot contributes
        # k+1 rows instead of 1), accepting the longest agreeing prefix
        # and rolling rejected tokens back via COW-guarded page forks.
        # k+1 is a jit dimension: the step ladder is keyed
        # (prefill_bucket, k1), one compile per pair.
        self.spec_mode = str(spec_mode)
        enforce_that(self.spec_mode in _SPEC_MODES,
                     f"spec_mode must be one of {_SPEC_MODES}, got "
                     f"{self.spec_mode!r}", context="serving-spec")
        self.spec_k = int(spec_k)
        enforce_that(self.spec_mode == "off" or self.spec_k >= 1,
                     "spec_k must be >= 1 when speculation is on",
                     context="serving-spec")
        self._proposer = None
        if self.spec_mode == "ngram":
            self._proposer = NGramProposer()
        elif self.spec_mode == "draft":
            enforce_that(
                draft_model is not None and draft_params is not None,
                "spec_mode='draft' needs ServingEngine(draft_model=, "
                "draft_params=) — a small DecodeModel sharing the "
                "target's vocabulary", context="serving-spec")
            enforce_that(
                int(draft_model.vocab_size) == int(model.vocab_size),
                f"draft vocab ({draft_model.vocab_size}) must equal the "
                f"target vocab ({model.vocab_size})",
                context="serving-spec")
            self._proposer = DraftProposer(
                draft_model, draft_params, page_size=page_size,
                num_pages=int(draft_pool_pages or num_pages),
                max_pages_per_seq=int(max_pages_per_seq),
                max_slots=max_slots)
        # rows per decode slot: 1 (plain decode) + spec_k drafts to
        # verify, or a block model's two blocks (the full one it commits
        # and the next one it opens in the same tick)
        self._k1 = 2 * self._block if self._block is not None else \
            1 + (self.spec_k if self._proposer is not None else 0)
        # donate the incoming KV pool: every call overwrites self._kv
        # with the returned pool, so XLA may update pages in place —
        # without this the decode tick copies the whole pool and peak
        # HBM doubles the documented cost.  Declared UNCONDITIONALLY:
        # audit_jit strips donation before the underlying jax.jit on
        # CPU (which can't donate and would only warn), so a CPU tier-1
        # run still declares — and the jaxpr auditor still verifies —
        # the TPU donation contract.  The old per-backend gate here left
        # the contract invisible (and untested) on CPU.
        # (behind the rings' arrays: the recurrent kind's, one argument)
        self._donate_kv = (1,) + tuple(range(
            4, 4 + len(self._rings) + (self._recurrent is not None)))
        # compiled-path contracts, declared next to the jit sites they
        # bind (checked by `python -m paddle_tpu.analysis xla`): the KV
        # pool must be donated and alias back out, per-tick sites must
        # not host-sync or pay collectives, narrow KV dtypes may
        # intentionally dequantize into f32 attention math, and the
        # per-signature footprint stays under an order-of-magnitude
        # budget — generous slack constants make the budgets guardrails
        # against asymptotic surprises (a duplicated pool, an O(B*S^2)
        # broadcast), not cycle predictions.
        param_bytes = param_count = 0
        for leaf in jax.tree.leaves(params):
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                n = int(np.prod(leaf.shape)) if leaf.shape else 1
                param_count += n
                param_bytes += n * jnp.dtype(leaf.dtype).itemsize
        # the widened step's worst-case row stack: k1 verify rows per
        # slot plus the packed prefill budget
        rows = max_slots * self._k1 + self._prefill_budget
        e = model.num_heads * model.head_dim
        # peak budgets reason about LOGICAL (global) avals — the xla
        # auditor's live-set estimator sums full aval bytes and cannot
        # see GSPMD's per-chip split — so scale the per-chip pool bytes
        # back up by tp (healthz keeps reporting the per-chip number)
        kv_bytes = self.kv_cfg.kv_bytes() * self.tp + \
            sum(ring.kv_bytes() for ring in self._rings) + \
            (self._recurrent.kv_bytes() if self._recurrent is not None
             else 0)
        act_bytes = 4 * rows * (8 * e * model.num_layers * self._loops
                                + model.vocab_size)
        kv_name = jnp.dtype(self.kv_cfg.dtype).name
        allow_upcast = (kv_name,) if kv_name != "float32" else ()
        if FLAGS.attn_pv_f32:
            allow_upcast += ("bfloat16",)
        # sharding contract (checked by `python -m paddle_tpu.analysis
        # sharding`).  Replicated engine (mesh=None): every argument and
        # output pins P() with a zero collective-byte budget per tick —
        # a replicated plan moves 0 bytes over links, so any inferred
        # collective busts the budget.  Tensor-parallel engine: params
        # carry the shard_plan per leaf, the KV pool (args AND outputs)
        # shards its head dim over the model axis, and the budget is the
        # CLOSED-FORM megatron cost — two row-parallel psums per layer,
        # 2*b*(N-1)/N each over the [rows, E] f32 activation — so the
        # gate proves the decode hot path stays reduce-not-gather: one
        # implicit all-gather anywhere and the audited estimate leaves
        # the closed form.
        if self.mesh is None:
            step_in: Tuple = ((),)
            step_out: Tuple = ((),)
            kv_in: Tuple = ((),)
            kv_out: Tuple = ((),)
            mesh_axes: Tuple = ()
            expect = ()
        else:
            kvspec = kv_pool_specs(TP_AXIS)
            # per-leaf param specs (keyed by name: the auditor resolves
            # dict entries against the pytree path) + the pool spec for
            # both the donated input and the aliased output
            # (parameters, pool, the tick's buffer, the last step's
            # words) -> (words, logits, pool)
            step_in = (dict(self._shard_plan), kvspec, (), ())
            step_out = ((), ()) + (kvspec,) * 4
            kv_in = (kvspec, (), ())
            kv_out = (kvspec,) * 4
            mesh_axes = ((TP_AXIS, self.tp),)
            expect = (0, 1)      # params and pool must arrive sharded
        self._step_contract = SiteContract(
            per_tick=True, donate=self._donate_kv,
            allow_upcast=allow_upcast,
            peak_bytes=2 * kv_bytes + 8 * param_bytes + 16 * act_bytes
            + (1 << 26),
            flops=64.0 * rows * self._loops * (
                param_count + self.kv_cfg.max_seq_len * e) + 1e9,
            in_specs=step_in, out_specs=step_out, mesh_axes=mesh_axes,
            comm_bytes=self.tp_step_comm_bytes(rows),
            expect_sharded=expect)
        kv_contract = SiteContract(
            per_tick=True, donate=(0,),
            peak_bytes=2 * kv_bytes + (1 << 24),
            in_specs=kv_in, out_specs=kv_out, mesh_axes=mesh_axes,
            comm_bytes=0.0)
        # audit_jit == jax.jit unless FLAGS.jit_audit is on, in which
        # case each named site's compiles are counted by the retrace
        # auditor (paddle_tpu.analysis.retrace): the unified step must
        # compile exactly once per (prefill_bucket, k1) pair — the
        # decode row count is the fixed max_slots * k1 (k1 = 1 +
        # spec_k, 1 with speculation off), so the pair ladder is one
        # entry per prefill bucket per speculation depth, and
        # speculation adds the k dimension and nothing else
        self._step_fns: Dict[Tuple[int, int], Callable] = {}
        # COW fork + failure scrub: kv is argument 0 in both (same
        # donation contract as above)
        self._fork_fn = audit_jit(
            fork_page, site="serving.fork_page", donate_argnums=(0,),
            xla_contract=kv_contract)
        self._zero_fn = audit_jit(
            zero_pages, site="serving.zero_pages", donate_argnums=(0,),
            xla_contract=kv_contract)
        # page-migration splice (round 16): whole imported pages land in
        # the pool via one donated scatter.  The page-count dimension is
        # padded to a pow2 ladder by _apply_import so migrations of any
        # size share O(log pages) compiles; padding rows target
        # NULL_PAGE with a zero payload (page 0 is reserved scratch).
        n_payload = 4 if self.kv_cfg.quantized else 2
        if self.mesh is None:
            imp_in: Tuple = ((),)
            imp_out: Tuple = ((),)
        else:
            imp_in = (kvspec,) + ((),) * (1 + n_payload)
            imp_out = (kvspec,) * 4
        import_contract = SiteContract(
            per_tick=True, donate=(0,),
            peak_bytes=3 * kv_bytes + (1 << 24),
            in_specs=imp_in, out_specs=imp_out, mesh_axes=mesh_axes,
            comm_bytes=0.0)
        if self.kv_cfg.quantized:
            def _import_pages(kv, ids, k, v, ks, vs):
                return write_pages(kv, ids, k, v, ks, vs)
        else:
            def _import_pages(kv, ids, k, v):
                return write_pages(kv, ids, k, v)
        self._import_fn = audit_jit(
            _import_pages, site="serving.import_pages", donate_argnums=(0,),
            xla_contract=import_contract)
        self._results: Dict[int, List[int]] = {}
        self._requests: Dict[int, Request] = {}
        # terminal rids in retirement order; oldest evicted past
        # max_retained so a long-running engine's memory stays bounded
        self._retired: Deque[int] = deque()
        self._tick = 0
        self._last_tick_at: Optional[float] = None
        self._prev_tick_busy = False
        self._tick_dur_ema = 0.0      # drives the unmeetable-deadline shed
        self._draining = False        # drain(): REJECT new submits

    def _refuse_for_block_model(self, page_size: int, prefill_chunk: int,
                                mesh, spec_mode: str,
                                host_tier_bytes: int) -> None:
        """What a block model cannot be built with, said at construction
        (nothing takes a silent second path)."""
        from paddle_tpu.platform.enforce import enforce_that

        b, s = self._block, self._denoise_steps
        ctx = "serving-block"
        enforce_that(b >= 1 and s >= 1 and b % s == 0,
                     f"denoise_steps ({s}) must divide block_length ({b}): "
                     "every denoising pass fixes block_length / "
                     "denoise_steps positions", context=ctx)
        enforce_that(page_size % b == 0,
                     f"page_size ({page_size}) must be a multiple of the "
                     f"model's block_length ({b}): a block is rewritten in "
                     "place in ONE page until it is committed", context=ctx)
        enforce_that(prefill_chunk % b == 0,
                     f"prefill_chunk ({prefill_chunk}) must be a multiple "
                     f"of the model's block_length ({b}): a row sees its "
                     "whole block, so a chunk ends on a block boundary",
                     context=ctx)
        enforce_that(str(spec_mode) == "off",
                     "speculative decoding verifies one token a row; a "
                     "block model (block_length on the model) fixes "
                     "several positions a pass and is not speculated on: "
                     "build it with spec_mode='off'", context=ctx)
        enforce_that(mesh is None,
                     "tensor-parallel serving (mesh=) of a block model is "
                     "not built: its expert layer has no shard plan here; "
                     "serve it with mesh=None", context=ctx)
        enforce_that(int(host_tier_bytes) <= 0,
                     "the host tier has not been driven with a block "
                     "model: build it with host_tier_bytes=0", context=ctx)
        enforce_that(self.role == "unified",
                     f"role={self.role!r} hands requests over by chain "
                     "migration, which cannot carry a block that is "
                     "between passes: a block model serves as 'unified'",
                     context=ctx)

    def _refuse_for_window_model(self, kinds, mesh, kv_dtype, prefix_cache,
                                 spec_mode: str,
                                 host_tier_bytes: int) -> None:
        """What a model with window layers cannot be built with, said at
        construction, each by its mechanism (nothing takes a silent
        second path).  The prefix cache left to its default is simply
        not built."""
        from paddle_tpu.platform.enforce import enforce_that

        ctx = "serving-window"
        enforce_that(len(kinds[0].layers) >= 1,
                     "every layer of the model has a window: the page "
                     "table, admission and preemption are the "
                     "full-attention layers', and a model without one is "
                     "not built", context=ctx)
        enforce_that(self._block is None,
                     "a block model's rows attend as their block's last "
                     "position; that under a window is not built: serve a "
                     "block model without layer_window", context=ctx)
        enforce_that(not prefix_cache,
                     "the prefix cache stitches full-attention pages; the "
                     "window layers' K/V of a cached prefix are gone once "
                     "its sequence moved on, so a hit would read what is "
                     "no longer there: build the engine with "
                     "prefix_cache=False (the default builds none for a "
                     "model with window layers)", context=ctx)
        enforce_that(str(spec_mode) == "off",
                     "speculative decoding rolls rejected rows back by "
                     "page; a window layer's ring has no pages to give "
                     "back and a rejected row has overwritten what fell "
                     "out of the window: build it with spec_mode='off'",
                     context=ctx)
        enforce_that(mesh is None,
                     "tensor-parallel serving (mesh=) of a model with "
                     "window layers is not built: the rings have no "
                     "placement over a mesh; serve it with mesh=None",
                     context=ctx)
        enforce_that(jnp.dtype(kv_dtype) != jnp.int8,
                     "int8 pages have not been driven through a window "
                     "layer's ring: serve it with a float kv_dtype",
                     context=ctx)
        enforce_that(int(host_tier_bytes) <= 0,
                     "the host tier spills cached prefix pages, which a "
                     "model with window layers has none of: build it with "
                     "host_tier_bytes=0", context=ctx)
        enforce_that(self.role == "unified",
                     f"role={self.role!r} hands requests over by chain "
                     "migration, which exports full-attention pages only "
                     "and would leave the window layers' rings behind: a "
                     "model with window layers serves as 'unified'",
                     context=ctx)

    def _refuse_for_recurrent_model(self, mesh, prefix_cache,
                                    spec_mode: str,
                                    host_tier_bytes: int) -> None:
        """What a model with a recurrent state cannot be built with, said
        at construction, each by its mechanism (nothing takes a silent
        second path).  The prefix cache left to its default is simply
        not built."""
        from paddle_tpu.platform.enforce import enforce_that

        ctx = "serving-recurrent"
        enforce_that(self._block is None,
                     "a block model rewrites its current block at every "
                     "pass; a recurrent state has advanced past a row once "
                     "it has seen it: serve a block model without "
                     "layer_state", context=ctx)
        enforce_that(not prefix_cache,
                     "the prefix cache stitches pages; a hit would also "
                     "need the recurrent state at the prefix's end, and a "
                     "snapshot of it at page boundaries is not built: "
                     "build the engine with prefix_cache=False (the "
                     "default builds none for a model with a recurrent "
                     "state)", context=ctx)
        enforce_that(str(spec_mode) == "off",
                     "speculative decoding rolls rejected rows back by "
                     "page; a rejected row has already advanced the "
                     "recurrent state, which keeps no earlier copy: build "
                     "it with spec_mode='off'", context=ctx)
        enforce_that(mesh is None,
                     "tensor-parallel serving (mesh=) of a model with a "
                     "recurrent state is not built: the state has no "
                     "placement over a mesh; serve it with mesh=None",
                     context=ctx)
        enforce_that(int(host_tier_bytes) <= 0,
                     "the host tier spills cached prefix pages, which a "
                     "model with a recurrent state has none of: build it "
                     "with host_tier_bytes=0", context=ctx)
        enforce_that(self.role == "unified",
                     f"role={self.role!r} hands requests over by chain "
                     "migration, which exports pages only and would leave "
                     "the recurrent state behind: a model with a recurrent "
                     "state serves as 'unified'", context=ctx)

    def _refuse_for_looped_model(self, kinds, mesh, spec_mode: str,
                                 host_tier_bytes: int) -> None:
        """What a looped model (``loops > 1``) cannot be built with, said
        at construction, each by its mechanism (nothing takes a silent
        second path).  The prefix cache, chunked prefill and preemption
        need nothing of it: a page holds every cache layer of its
        tokens."""
        from paddle_tpu.platform.enforce import enforce_that

        ctx = "serving-looped"
        enforce_that(self._loops >= 1,
                     f"loops must be at least 1, got {self._loops}",
                     context=ctx)
        enforce_that(self._block is None,
                     "a block model rewrites its current block at every "
                     "denoising pass; that inside a stack which itself "
                     "runs several times a tick is not built: serve a "
                     "block model without loops", context=ctx)
        enforce_that(len(kinds) == 1,
                     "a window layer's ring holds ONE layer's last tokens "
                     "a slot; a ring for every pass of a looped stack is "
                     "not built: serve a looped model without "
                     "layer_window", context=ctx)
        enforce_that(self._recurrent is None,
                     "a recurrent state is advanced once a row; a looped "
                     "stack would advance it once a pass, and a state for "
                     "every pass is not built: serve a looped model "
                     "without layer_state", context=ctx)
        enforce_that(str(spec_mode) == "off",
                     "speculative decoding has not been driven through a "
                     "looped stack (a verify row costs every pass, and "
                     "the draft proposer's own step runs one): build it "
                     "with spec_mode='off'", context=ctx)
        enforce_that(mesh is None,
                     "tensor-parallel serving (mesh=) of a looped model is "
                     "not built: the step's closed-form collective budget "
                     "counts one pass; serve it with mesh=None",
                     context=ctx)
        enforce_that(int(host_tier_bytes) <= 0,
                     "the host tier has not been driven with a looped "
                     "model's pages (every pass's cache layers a page): "
                     "build it with host_tier_bytes=0", context=ctx)
        enforce_that(self.role == "unified",
                     f"role={self.role!r} hands requests over by chain "
                     "migration, which has not been driven with a looped "
                     "model's pages: a looped model serves as 'unified'",
                     context=ctx)

    # ---- observability wiring -------------------------------------------

    def set_tracer(self, tracer) -> None:
        """(Re)bind the engine's span tracer — the fleet calls this with
        its shared tracer scoped to the replica index.  The pool,
        scheduler and prefix cache get the raw hook (None when tracing
        is off, so their hot paths pay one is-None check); when the
        retrace auditor is active the tracer also receives its
        ``jit_compile`` events."""
        self._tracer = tracer if tracer is not None else NULL_TRACER
        hook = self._tracer if self._tracer.enabled else None
        self.pool.tracer = hook
        self.scheduler.tracer = hook
        if self.cache is not None:
            self.cache.tracer = hook
        if self.host_tier is not None:
            self.host_tier.tracer = hook
        if hook is not None and getattr(FLAGS, "jit_audit", False):
            auditor().attach_tracer(self._tracer.base)

    def set_registry(self, registry: MetricsRegistry, **labels) -> None:
        """(Re)bind the unified metrics registry (fleet: one registry,
        per-replica labels).  All later stage observations and healthz
        publishes land there."""
        self.registry = registry
        self._reg_labels = {k: str(v) for k, v in labels.items()}

    def _observe_stage(self, stage: str, seconds: float) -> None:
        """Per-stage latency attribution (queue / prefill / decode) on
        the engine's injected clock — the registry half of the span
        timeline, cheap enough to stay on unconditionally."""
        self.registry.histogram(
            "serving_stage_seconds",
            "request time per lifecycle stage").labels(
            stage=stage, **self._reg_labels).observe(max(0.0, seconds))

    def _dump_postmortem(self, reason: str) -> None:
        """Flight-recorder dump on a tripped conservation invariant —
        once per reason per engine, so a prober that calls healthz in a
        leaky steady state doesn't spray one file per probe."""
        if reason not in self._postmortems_dumped:
            self._postmortems_dumped.add(reason)
            self._tracer.dump_postmortem(reason)

    # ---- compiled device functions --------------------------------------

    def tp_step_comm_bytes(self, rows: int) -> float:
        """Closed-form per-call collective budget for ``serving.step``
        under ``tp``-way tensor parallelism: each of the model's layers
        pays exactly TWO row-parallel psums (attention-output and
        FFN-down projections — the megatron pattern), each moving
        ``2 * b * (N-1)/N`` bytes over the ``model`` links for the
        ``[rows, E]`` f32 activation of ``b = 4 * rows * E`` bytes.
        Attention itself is head-local and the paged pool ops are
        batching-dim scatters, so NOTHING else may touch the links —
        the sharding gate checks the audited estimate against exactly
        this number, which is how "the decode step stays
        reduce-not-gather" becomes a CI property."""
        if self.tp <= 1:
            return 0.0
        # the residual-stream width: duck-typed models may carry an
        # embed_dim decoupled from num_heads * head_dim
        e = int(getattr(self.model, "embed_dim", 0)
                or self.model.num_heads * self.model.head_dim)
        psum = 2.0 * (4.0 * rows * e) * (self.tp - 1) / self.tp
        return float(self.model.num_layers * 2 * psum)

    def _tp_kv(self, kv: KVPages) -> KVPages:
        """Pin the returned pool to its canonical per-chip layout
        (``[L, pages, page, (H_kv/TP) * D]``, THE ``kv_pool_sharding``
        layout — same source of truth as placement and the contract) so
        the donated-in/aliased-out pair stays shard-identical across
        ticks (no-op replicated)."""
        if self.mesh is None:
            return kv
        from paddle_tpu.serving.kv_cache import kv_pool_sharding

        sh = kv_pool_sharding(self.mesh, TP_AXIS)
        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(a, sh), kv)

    def _tp_ctx(self, ctx):
        """Re-assert the head sharding on an attention output (no-op on
        replicated engines).  The reference fallback's row-blocked
        ``lax.map`` is a scan whose body GSPMD — and the static
        propagation walk — cannot see through; without this constraint
        the downstream row-parallel projection would consume an
        unconstrained operand and the partitioner would be free to
        all-gather instead of psum."""
        if self.mesh is None:
            return ctx
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            ctx, NamedSharding(self.mesh, P(None, TP_AXIS, None)))

    def _attend(self, kv: KVPages, layer: int, q, table, att_lens,
                row_seq, qpos, k1: int = 1, window: Optional[int] = None,
                walks: Optional[dict] = None):
        """One ragged paged attention over the tick's mixed row stack.
        The reference path consumes the compact ``[B * k1 + pb]`` rows
        as-is; the kernel path expands each slot's ``k1`` decode/verify
        rows to whole BLOCK_ROWS blocks (every aligned BLOCK_ROWS rows
        belong to one sequence: the kernel's packing contract) — prefill
        rows are already block-aligned by the packer — tells the kernel
        where the decode region ends (its rows stand in short resident
        blocks, the bucket's in tall ones), and slices the context back
        out.  The expansion touches [B*k1, H, D]-sized data, noise next
        to the attention itself.  Under TP the kernel rides a
        ``shard_map`` over the model axis (heads are attention-local, so
        each chip runs the unchanged kernel on its head shard) and both
        paths re-assert the head sharding on the context.  ``kv``,
        ``layer`` and ``table`` are the layer's KIND's (a window layer's
        ring and its ``window`` with them).  ``walks``: the step's cache of the kernel's walks:
        the layers of one kind (and head count) attend over the same
        rows, so the first of them makes the schedule of visits
        (``decode_attention.ragged_walk``) and the others take it."""
        if not self._ragged_kernel:
            # row-blocked fallback: identical math to the oracle, with
            # the per-row K/V gather bounded to one block of rows
            k, v, ks, vs = layer_pages(kv, layer)
            return self._tp_ctx(_ragged_reference_blocked(
                q, k, v, table, att_lens, row_seq, qpos, k_scale=ks,
                v_scale=vs, window=window))
        b, rb = self._max_slots, BLOCK_ROWS
        bd = b * k1                      # compact decode/verify rows
        rbk = -(-k1 // rb) * rb          # padded rows per slot
        td = b * rbk                     # expanded decode/verify rows
        h, d = q.shape[1], q.shape[2]
        # decode rows expand through THE shared packing helper (one copy
        # of the packing contract); prefill rows are
        # already block-aligned by the packer and concatenate behind
        qd, rsd, qpd = expand_decode_rows(q[:bd], qpos[:bd],
                                          rows_per_seq=k1)
        qe = jnp.concatenate([qd, q[bd:]])
        rs = jnp.concatenate([rsd, row_seq[bd:]])
        qp = jnp.concatenate([qpd, qpos[bd:]])
        # the kernel takes the pool's own leaves and the layer's index:
        # no slice of the layer, no re-tiling, no copy of either
        pool = dict(layer=layer, k_scale=kv.k_scale, v_scale=kv.v_scale,
                    use_kernel=True, decode_rows=td)
        if window is not None:
            pool["window"] = window
        if walks is not None:
            if (window, h) not in walks:
                walks[window, h] = ragged_walk(
                    table, att_lens, rs, qp, num_heads=h // self.tp,
                    num_kv_heads=kv.k.shape[3] // d // self.tp, head_dim=d,
                    page_size=kv.k.shape[2],
                    kv_itemsize=kv.k.dtype.itemsize,
                    quantized=kv.k_scale is not None, decode_rows=td,
                    window=window)
            pool["walk"] = walks[window, h]
        if self.mesh is not None and self.tp > 1:
            ctx = ragged_paged_attention_tp(
                self.mesh, TP_AXIS, qe, kv.k, kv.v, table, att_lens,
                rs, qp, **pool)
        else:
            ctx = ragged_paged_attention(qe, kv.k, kv.v, table, att_lens,
                                         rs, qp, **pool)
        cd = ctx[:td].reshape(b, rbk, h, d)[:, :k1].reshape(bd, h, d)
        return self._tp_ctx(jnp.concatenate([cd, ctx[td:]]))

    def _cache_layer(self, t: int, layer: int) -> int:
        """Where weight layer ``layer`` (its index in its kind's arrays)
        keeps its K/V at pass ``t``: THE cache-layer rule of the module
        doc ("looped models").  Pass 0 is the layer itself, so a model of
        one pass reads what it always read."""
        return t * int(self.model.num_layers) + layer

    def _loop_counts(self, lams, valid):
        """What a looped model's step counts itself, int32 under
        ``_loop_counted``'s names: the passes it ran, and from the exit
        gate on its decode rows (``valid [B * k1]``; ``lams``: each
        pass's ``lam [T]`` as ``close_pass`` handed it back, None for a
        model without a gate) how many rows were read and the step they
        would leave at were the threshold lower, the expectation ``sum_t
        t p_t`` (:func:`exit_distribution`) in thousandths, summed."""
        seen = step = 0
        if lams and lams[0] is not None:
            p = exit_distribution(jnp.stack(lams)[:, :valid.shape[0]])
            at = jnp.sum(p * jnp.arange(1, len(lams) + 1)[:, None], axis=0)
            seen = jnp.sum(valid)
            step = jnp.sum(jnp.where(valid, jnp.round(1e3 * at), 0.0))
        return jnp.stack([jnp.asarray(n, jnp.int32)
                          for n in (self._loops, seen, step)])

    def _step_fn(self, pb: int, k1: int = 1):
        """The unified per-tick step for prefill bucket ``pb`` (0 =
        decode-only) at ``k1`` decode/verify rows per slot (1 = plain
        decode; ``1 + spec_k`` when speculating — the widened verify
        step; ``2 B`` for a block model, the block a slot commits and the
        one it opens): ONE dispatch embeds every slot's verify rows and the
        packed prefill-chunk rows, scatters every row's K/V into its
        page (quantize-on-write on int8 pools; masked rows write ZEROS
        to the shared null page so computed junk can never leak into
        gathered fallback reads), runs one ragged paged attention over
        the whole mixed batch per layer, and computes logits for ALL
        ``B * k1`` decode/verify rows plus each slot's chunk-final row
        — prior context, in-chunk causality AND in-verify causality
        (draft ``i`` sees drafts ``< i``) all come from the ONE
        ``token <= position`` mask, with no separate paths to keep in
        sync.  It returns ``(words, logits, pool)``: the int32 words the
        host reads (each row's choice and finite flag), the logits left
        on the device, the pool; and takes the words of the step before
        it as its fourth argument (``_last_words``)."""
        fn = self._step_fns.get((pb, k1))
        if fn is not None:
            return fn
        model, cfg = self.model, self.kv_cfg
        b, page = self._max_slots, cfg.page_size
        bd = b * k1
        blk = self._block
        rotate = getattr(model, "rotate", None)

        rings = self._rings
        ring_tables = [ring.table() for ring in rings]
        windows = (None,) + tuple(ring.window for ring in rings)
        # (a model of one kind names no kind: its step is what it was)
        kind_scope = (lambda i: jax.named_scope(
            "attn.full" if windows[i] is None else "attn.window")) \
            if rings else (lambda i: contextlib.nullcontext())

        rec_layer = self._rec_layer
        mix = getattr(model, "mix", None)
        # a looped model's passes (the module doc: "looped models"): the
        # stack runs ``loops`` times, pass ``t`` on cache layers ``t *
        # num_layers + l``; a model of one pass names none
        loops, n_layers = self._loops, int(model.num_layers)
        close_pass = getattr(model, "close_pass", None)
        pass_scope = (lambda t: jax.named_scope(f"pass{t}")) \
            if loops != 1 else (lambda t: contextlib.nullcontext())

        def raw(params, kv: KVPages, packed, *last):
            # (behind the last step's words: each ring's arrays, then the
            # recurrent kind's)
            last, ring_kv, rec_kv = (last[:1], last[1:1 + len(rings)],
                                     last[1 + len(rings):])
            rec = list(rec_kv[0]) if rec_kv else []
            # packed: the tick's one int32 input buffer, replicated;
            # taken apart by static slices (a chip reads its own copy)
            (d_tokens, d_pos, d_valid, p_tokens, p_qpos, p_seq, p_last,
             table, att_lens, *d_sel) = self._tick_parts(packed, k1)
            # last: the words of the step before.  A token that step
            # chose has not reached the host when this one is assembled:
            # d_tokens names it and it is taken from there
            if blk is not None:
                # (-1 - j: the j-th pick of the slot)
                f = self._fix_rows
                picked = last[0][:b * (f + 1)].reshape(b, f + 1)[:, :f]
                d_tokens = jnp.where(d_tokens < 0, jnp.take_along_axis(
                    picked, jnp.clip(-1 - d_tokens, 0, f - 1), axis=1),
                    d_tokens)
            else:
                # (-1: the pick of the slot's decode row; -2: that of its
                # chunk-final row, a prompt's first token)
                slot = jnp.arange(b)[:, None]
                d_tokens = jnp.where(d_tokens < 0, last[0][jnp.where(
                    d_tokens == -2, bd + slot, slot * k1)], d_tokens)
            # d_tokens/d_pos/d_valid: [B, k1] — row 0 of a slot is the
            # plain decode token, rows 1..k its drafted lookahead
            # (invalid rows write the null page and produce garbage
            # logits the host ignores).  p_tokens/p_qpos/p_seq: [pb] —
            # packed prefill rows, qpos -1 = padding (p_seq stays the
            # owning slot so kernel blocks remain sequence-uniform).
            # p_last: [B] — row index of each slot's chunk-final row in
            # the packed stack (0 for slots not prefilling).  table:
            # [B, Pm]; att_lens: [B] — valid KV per slot AFTER this
            # step's writes.  d_sel (block models only): [B, B / S] —
            # the rows of each slot's open block (among its 2 B) that the
            # step computes logits for.
            d_seq = jnp.repeat(jnp.arange(b), k1)
            dt = d_tokens.reshape(bd)
            dp = d_pos.reshape(bd)
            dv = d_valid.reshape(bd) != 0
            p_act = p_qpos >= 0
            pq = jnp.maximum(p_qpos, 0)
            tokens = jnp.concatenate([dt, p_tokens])
            pos = jnp.concatenate([dp, pq])
            x = model.embed(params, tokens, pos)       # [B*k1 + pb, E]
            d_pages = jnp.where(dv, table[d_seq, dp // page], NULL_PAGE)
            p_pages = jnp.where(p_act, table[p_seq, pq // page], NULL_PAGE)
            pages = jnp.concatenate([d_pages, p_pages])
            offs = jnp.concatenate([dp % page, pq % page])
            live = jnp.concatenate([dv, p_act])
            wmask = live[:, None, None]
            row_seq = jnp.concatenate([d_seq, p_seq])
            qpos = jnp.concatenate([jnp.where(dv, dp, -1), p_qpos])
            if blk is not None and blk > 1:
                # a row sees its whole block: it attends as the block's
                # last position (its rotary position stays its own)
                qpos = jnp.where(qpos >= 0, (qpos // blk + 1) * blk - 1, -1)
            counts = 0
            # every kind's arrays, page table and the page each row's K/V
            # goes to: the pool's as above, a ring's by its rule (page
            # ``a`` of a slot's sequence at entry ``a mod R`` of its ring)
            state = [kv, *ring_kv]
            tables = [table, *(jnp.asarray(t) for t in ring_tables)]
            row_pages = [pages] + [
                jnp.where(live, t[row_seq, (pos // page) % t.shape[1]],
                          NULL_PAGE) for t in tables[1:]]
            walks = {}      # the kernel's walks, by kind and head count
            # the tick's row layout, for a layer that keeps a state a slot
            rows = dict(row_seq=row_seq, pos=pos, live=live, decode_rows=bd)
            lams = []       # a pass's exit probability a row, where said
            for t, l in itertools.product(range(loops), range(n_layers)):
                # named_scope: blocks and their parts show up by name in
                # xplane/profiler traces (as topology.forward's layers)
                i, li = self._layer_state[l]
                li = self._cache_layer(t, li)
                with pass_scope(t), jax.named_scope(f"l{l}"):
                    with jax.named_scope("attn"), kind_scope(i):
                        q, k, v = model.qkv(params, l, x)
                        if rotate is not None:
                            q, k = rotate(params, l, q, k, pos)
                        state[i] = append_token(
                            state[i], li, jnp.where(wmask, k, 0.0),
                            jnp.where(wmask, v, 0.0), row_pages[i], offs)
                        ctx = self._attend(state[i], li, q, tables[i],
                                           att_lens, row_seq, qpos, k1=k1,
                                           window=windows[i], walks=walks)
                    more = ()
                    if l in rec_layer:
                        # the layer's recurrent branch, on the same input
                        mixed, rec[rec_layer[l]] = mix(
                            params, l, x, rec[rec_layer[l]], rows)
                        more = (mixed,)
                    if self._counted:
                        x, n = model.attn_out_counted(params, l, ctx, x,
                                                      live, *more)
                        counts = counts + n
                    else:
                        x = model.attn_out(params, l, ctx, x, *more)
                if close_pass is not None and l == n_layers - 1:
                    with pass_scope(t), jax.named_scope("close"):
                        x, lam = close_pass(params, t, x)
                    lams.append(lam)
            kv, ring_kv = state[0], tuple(state[1:])
            if rec_kv:
                ring_kv += (tuple(rec),)
            more = (counts,) if self._counted else ()
            if self._loop_counted:
                more += (self._loop_counts(lams, dv),)
            if blk is not None:
                # logits for the rows a denoising pass fixes only.  What
                # crosses to the host is ONE small int32 vector (a read
                # back costs its latency, not its bytes; taken apart by
                # ``_walk_passes``): each row's best token with the mask
                # token left out and, last in a slot's row, whether all
                # its logits are finite (``picks`` [B, B / S + 1]); of a
                # prompt's last row only whether it is finite (the chunk
                # guard's reading; no token comes of it) [B]; the
                # model's counts.  The logits themselves stay on the
                # device, for a request that samples.
                sel = (jnp.arange(b)[:, None] * k1 + d_sel[0]).reshape(-1)
                logits = model.logits(params, x[sel])
                token = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
                best = jnp.argmax(jnp.where(token == self._mask_id,
                                            -jnp.inf, logits), axis=-1)
                logits = logits.reshape(b, self._fix_rows, -1)
                finite = jnp.all(jnp.isfinite(logits), axis=(1, 2))
                guard = jnp.isfinite(jnp.sum(x[p_last], axis=-1))
                words = jnp.concatenate(
                    [jnp.concatenate([best.reshape(b, -1), finite[:, None]],
                                     axis=1).reshape(-1), guard, *more],
                    dtype=jnp.int32)
                return words, logits, self._tp_kv(kv)
            # logits only where a token may come of them: the B*k1
            # decode/verify rows + each slot's chunk-final row.  Of each
            # the best token (the first maximum, as ``np.argmax``) and
            # whether the row is all finite (the guards' reading) leave
            # as int32 words ``[best | finite | counts]``
            # (``_walk_rows``), replicated as the next step takes them;
            # the logits stay on the device, for a request that samples
            # and for the verify walk.  The head is replicated under
            # tensor parallelism, so the choice adds no collective.
            sel = jnp.concatenate([jnp.arange(bd), p_last])
            logits = model.logits(params, x[sel])
            words = jnp.concatenate(
                [jnp.argmax(logits, axis=-1),
                 jnp.all(jnp.isfinite(logits), axis=-1), *more],
                dtype=jnp.int32)
            if self._tick_sharding is not None:
                words = jax.lax.with_sharding_constraint(
                    words, self._tick_sharding)
            return (words, logits, self._tp_kv(kv)) + ring_kv

        fn = audit_jit(raw, site="serving.step",
                       donate_argnums=self._donate_kv,
                       xla_contract=self._step_contract)
        self._step_fns[(pb, k1)] = fn
        return fn

    # ---- user surface ----------------------------------------------------

    def submit(self, prompt: Sequence[int], max_tokens: int,
               on_token: Optional[Callable[[int], None]] = None,
               now: Optional[float] = None,
               queue_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None,
               sampling: Optional[SamplingParams] = None,
               tenant: str = "default") -> int:
        """Queue a request and return its rid — ALWAYS, even when the
        request is refused (infeasible size or queue backpressure): a
        refused rid carries status ``REJECTED``, so callers distinguish
        "rejected at submit" from "in flight" from "unknown rid" via
        ``status``/``result`` instead of a bare ``None`` sentinel.

        ``queue_deadline_s`` bounds time waiting for admission (engine
        default: its ``queue_deadline_s=``); ``deadline_s``
        bounds submit-to-last-token.  Either lapsing marks the request
        ``TIMED_OUT`` and frees everything it held.

        ``sampling`` (a :class:`SamplingParams`) turns on real sampling
        — temperature/top-k/top-p with seeded per-position RNG streams,
        bit-reproducible across replays on the injected clock; None (or
        temperature 0) keeps greedy argmax, token-identical to the
        oracle."""
        req = Request(prompt=list(int(t) for t in prompt),
                      max_tokens=int(max_tokens), on_token=on_token,
                      sampling=sampling, tenant=str(tenant))
        t = self._time() if now is None else now
        if queue_deadline_s is None:
            # engine-wide default; self.queue_deadline_s is None when
            # the engine was built with 0 (0 means off THERE, not on the
            # per-request parameters)
            queue_deadline_s = self.queue_deadline_s
        if queue_deadline_s is not None:
            req.queue_deadline_at = t + float(queue_deadline_s)
        if deadline_s is not None:
            req.deadline_at = t + float(deadline_s)
        # for BOTH per-request overrides, None = no deadline and an
        # explicit 0.0 is an already-spent budget (times out next tick)
        if self._draining:
            # drain mode: admission is closed.  The request is REJECTED
            # up front — queued and running work keeps going, but no new
            # demand enters (the fleet router reads this as "route
            # elsewhere").
            req.submitted_at = t
            req.status = RequestStatus.REJECTED
            ok = False
        else:
            ok = self.scheduler.submit(req, now=t)
        self.metrics.on_submit(t, ok)
        self._requests[req.rid] = req
        self._tracer.instant("submit", rid=req.rid, tokens=len(req.prompt),
                             max_tokens=req.max_tokens, accepted=ok)
        if not ok:
            self._retire(req)
        return req.rid

    def _finish(self, req: Request, status: RequestStatus, now: float,
                shed: bool = False) -> None:
        """THE terminal-transition path (every non-completed exit and
        completion itself funnel through here): return the slot and
        pages — or leave the queue — stamp, count, retire.  One copy of
        the invariant, so no path can forget eviction or a counter."""
        if status is RequestStatus.FAILED and req.pages:
            # a FAILED request may have written non-finite K/V; scrub
            # the suspect pages so re-granted ones can't leak inf into
            # the next owner's masked attention reads.  Suspect = the
            # request's UNCACHED pages: cached pages were finite-vouched
            # at insertion (a failing chunk's were just forgotten) and
            # may be shared right now — decode appends and failing
            # chunks only ever write uncached ones.
            suspect = [p for p in req.pages if not self.pool.is_cached(p)]
            if suspect:
                self._kv = self._zero_fn(self._kv,
                                         jnp.asarray(suspect, jnp.int32))
            if req.slot is not None:
                # and its slot's ring of every window kind: the next
                # sequence there reads the ring's pages under its mask
                self._ring_kv = tuple(
                    self._zero_fn(kv, jnp.asarray(ring.table()[req.slot]))
                    for ring, kv in zip(self._rings, self._ring_kv))
        if self._proposer is not None:
            # drop any draft-model cache state (its pages return to the
            # draft pool); a no-op for the n-gram proposer
            self._proposer.release(req.rid)
        if req.slot is not None:
            self.scheduler.release(req, status)
        else:
            self.scheduler.drop_queued(req, status)
        req.finished_at = now
        req.pending = 0
        hook = self.metrics.on_shed if shed else {
            RequestStatus.COMPLETED: self.metrics.on_complete,
            RequestStatus.TIMED_OUT: self.metrics.on_timeout,
            RequestStatus.CANCELLED: self.metrics.on_cancel,
            RequestStatus.FAILED: self.metrics.on_fail,
        }[status]
        hook()
        if shed or status is RequestStatus.TIMED_OUT:
            # deadline miss billed to the tenant (round 17): both the
            # hard expiry and the unmeetable-estimate shed count — same
            # numerator as deadline_miss_rate, split per tenant
            self.metrics.on_tenant_miss(req.tenant)
        if req.first_token_at is not None:
            self._observe_stage("decode", now - req.first_token_at)
        self._tracer.instant("terminal", rid=req.rid, status=str(status),
                             shed=shed, tokens=len(req.generated))
        self._retire(req)

    def _retire(self, req: Request) -> None:
        """Record a terminal transition; evict the oldest terminal
        requests (and their results) past ``max_retained`` so request
        history doesn't grow without bound on a long-running engine.
        ``status``/``result`` raise KeyError for evicted rids, same as
        never-issued ones."""
        self._retired.append(req.rid)
        while len(self._retired) > self.max_retained:
            old = self._retired.popleft()
            self._requests.pop(old, None)
            self._results.pop(old, None)

    def cancel(self, rid: int, now: Optional[float] = None) -> bool:
        """Cancel a request.  Queued/preempted requests leave the queue;
        a running one releases its slot and pages immediately (its page
        writes are garbage the next owner overwrites).  Returns False if
        the request already reached a terminal status; raises KeyError
        for an unknown rid."""
        req = self._requests[rid]
        if req.finished:
            return False
        now = self._time() if now is None else now
        self._finish(req, RequestStatus.CANCELLED, now)
        return True

    def status(self, rid: int) -> RequestStatus:
        """Lifecycle status of ``rid``; raises KeyError for a rid this
        engine never issued."""
        return self._requests[rid].status

    def drain(self, on: bool = True) -> None:
        """Toggle drain mode: while draining, every new ``submit`` is
        REJECTED immediately, but requests already queued or running
        finish normally (admission from the existing queue continues —
        the drain stops new DEMAND, not accepted work).  ``drain(False)``
        reopens admission (a replica rejoining a fleet)."""
        self._draining = bool(on)

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work or self._flying is not None

    def step(self, now: Optional[float] = None) -> bool:
        """One engine tick: shed expired/unmeetable work, grow/preempt,
        admit + prefill, one fused decode over all running sequences
        (with transient-error retry, finite-logits isolation, and the
        progress watchdog).  Returns True if any work remains.

        The tick names its phases for the profiler (``pt:tick`` and,
        inside it, ``pt:tick.schedule`` here, ``.assemble``, ``.upload``
        (and inside that ``.dispatch``, the compiled step's call alone),
        ``.wait`` and ``.sample`` in :meth:`_land`, ``.sample`` again
        for the bookkeeping that closes the tick).  ``.wait`` and
        ``.sample`` belong to the step that the call BEFORE this one
        dispatched, where that was left in the air (``_flying``)."""
        tick, m = self._tick, self.metrics
        phase = self._tracer.phase
        with phase("tick", tick=tick):
            if self._flying is not None and self._growth_may_preempt():
                # a preempted request is re-prefilled from its tokens,
                # which have to be on the host: the flight lands first
                self.land()
            with phase("tick.schedule", tick=tick):
                running, chunks, total_rows, drafts, busy = \
                    self._schedule_tick(tick, now)
            if not (running or chunks):
                self.land()       # (nothing rides a next step to read behind)
            if running or chunks:
                for req, start, n, _ in chunks:
                    self._tracer.instant("prefill_chunk", rid=req.rid,
                                         slot=req.slot, start=start, n=n,
                                         tick=tick)
                # span keeps its historical name: it IS the fused tick
                with self._tracer.span("decode_tick", tick=tick,
                                       n=len(running),
                                       prefill_rows=total_rows):
                    self._step_with_retry(running, chunks, total_rows,
                                          tick, drafts)
            with phase("tick.sample", tick=tick):
                self._prev_tick_busy = busy
                self._watchdog_sweep(tick)
                m.on_tick(self.scheduler.queue_depth, self.pool.num_live,
                          self.pool.num_cached,
                          self.cache.evictions if self.cache is not None
                          else 0)
                if self.host_tier is not None:
                    m.on_host_tier(self.host_tier.snapshot(),
                                   self._host_hits)
            self._tick = tick + 1
        return self.has_work

    def _schedule_tick(self, tick: int, now: Optional[float]):
        """The host's decisions before a tick's dispatch: what is shed,
        grown, preempted, admitted, and which rows ride the step.
        Returns ``(running, chunks, total_rows, drafts, busy)``."""
        sched, m = self.scheduler, self.metrics
        if self.faults is not None:
            self.faults.tick_begin(tick)
            self.faults.apply_page_pressure(tick, self.pool)
            self.faults.apply_cache_storm(tick, self.cache)
        now = self._time() if now is None else now
        # the shed estimator learns tick duration only from ticks that
        # followed a BUSY tick: in a continuous serving loop those run
        # back-to-back so the gap is compute time, while idle gaps (a
        # server polling step() with nothing in flight) would inflate
        # the EMA and shed whole bursts spuriously
        if (self._last_tick_at is not None and now > self._last_tick_at
                and self._prev_tick_busy):
            dur = now - self._last_tick_at
            self._tick_dur_ema = dur if self._tick_dur_ema == 0.0 else \
                0.5 * self._tick_dur_ema + 0.5 * dur
        self._last_tick_at = now
        self._enforce_deadlines(now)
        # growth/preemption BEFORE admission: a tick must not pay for a
        # new request's prefill and then immediately preempt it (the
        # youngest) to grow older sequences.  admit() reserves the first
        # decode append's page, so fresh admissions never need same-tick
        # growth either.
        preempted = sched.ensure_decode_pages()
        npreempt = len(preempted)
        m.on_preempt(npreempt)
        if self._proposer is not None:
            for req in preempted:
                # a preempted request re-prefills from scratch later;
                # keeping its draft-model cache pinned meanwhile would
                # starve the draft pool (and the state is stale anyway
                # — catch-up rebuilds it at the next propose)
                self._proposer.release(req.rid)
        # host-tier advance BEFORE admission: commit the staged spill
        # (depth-one writer) and swap in up to swap_in_budget verified
        # host pages for the head-of-queue request, so the admission
        # lookup right below sees them as ordinary device hits
        self._pump_host_tier(tick)
        admitted = sched.admit()
        for req in admitted:
            if req.admitted_at is None:
                # queue wait is a first-admission stat: re-admissions
                # after preemption would fold running time into it
                wait = now - (req.submitted_at
                              if req.submitted_at is not None else now)
                m.on_admit(wait)
                m.on_tenant_admit(req.tenant, wait)
                self._observe_stage("queue", wait)
                req.admitted_at = now
            req.last_progress_tick = tick
            self._tracer.instant("admit", rid=req.rid, slot=req.slot,
                                 cached=req.cached_len, tick=tick)
            self._begin_prefill(req)
        # the unified step: this tick's decode/verify rows AND every
        # selected prefill chunk ride ONE dispatch (one ragged
        # attention over shared pages), so a long prefill no longer
        # stalls running slots' inter-token latency NOR costs a second
        # dispatch.  Chunk candidates go oldest-progress-first so a
        # request crowded out by the row budget is first in line next
        # tick.
        prefilling = sorted(
            (r for r in sched.running_requests()
             if r.status is RequestStatus.RUNNING and r.prefilling),
            key=lambda r: (r.last_progress_tick, r.slot))
        chunks, total_rows = pack_prefill_chunks(
            prefilling, self._prefill_chunk, self._row_align,
            self._prefill_budget, block=self._block or 1)
        # (a block model's first token comes of a block pass, not of the
        # prompt's last row: its slots run with nothing generated yet;
        # else the first token may be the one the flight in the air is
        # choosing)
        running = [r for r in sched.running_requests()
                   if r.status is RequestStatus.RUNNING
                   and not r.prefilling
                   and (r.generated or r.pending
                        or self._block is not None)
                   # (every token of it fixed by the flight in the air:
                   # it ends when that lands)
                   and len(r.generated) + r.pending < r.max_tokens]
        # speculation: draft lookahead tokens per slot BEFORE the retry
        # loop (drafting mutates proposer state — it must run once per
        # tick, and the position-keyed RNG keeps it deterministic)
        drafts = self._propose_drafts(running, under_pressure=npreempt > 0)
        busy = bool(running) or bool(admitted) or bool(prefilling)
        return running, chunks, total_rows, drafts, busy

    def run(self, max_ticks: Optional[int] = None) -> Dict[int, List[int]]:
        """Tick until drained (or ``max_ticks``); returns
        {rid: generated tokens} for everything completed so far.  A full
        drain releases any fault-plan page pressure and asserts free-list
        conservation (:class:`PageLeakError` on violation)."""
        ticks = 0
        while self.has_work:
            self.step()
            ticks += 1
            if max_ticks is not None and ticks >= max_ticks:
                break
        if not self.has_work:
            if self.faults is not None:
                self.faults.release_pressure(self.pool)
            if self.host_tier is not None:
                # drain barrier: the staged spill commits (no torn
                # pending across a quiesce) before conservation runs
                self.host_tier.flush()
            self.check_page_conservation()
        return dict(self._results)

    def result(self, rid: int) -> Optional[List[int]]:
        """Generated tokens for a COMPLETED rid; None while the request
        is in flight or if it ended in a non-completed terminal status
        (disambiguate via ``status``); KeyError for a rid the engine
        never issued or already evicted past ``max_retained``."""
        if rid not in self._requests:
            raise KeyError(rid)
        return self._results.get(rid)

    # ---- invariants / health --------------------------------------------

    def check_page_conservation(self) -> None:
        """Two-part conservation (raises :class:`PageLeakError`, whose
        message carries a grep-able token either way):

        - ``PAGE-LEAK`` — every usable page is either on the free list
          or tracked in use (live or cached-reclaimable);
        - ``REF-LEAK`` — the pool's total refcount equals the references
          actually held: one per page-table entry of every running or
          queued request, one per fault-plan pressure page.  Cached
          pages parked at refcount 0 hold none, so sharing, COW forks,
          preemption-unref and eviction all have to balance exactly."""
        pool = self.pool
        if pool.num_free + pool.num_in_use != pool.num_usable:
            # flight recorder: the leak report ships WITH the event
            # history that produced it (no-op when tracing is off)
            self._dump_postmortem("PAGE-LEAK")
            raise PageLeakError(
                f"PAGE-LEAK: free={pool.num_free} in_use={pool.num_in_use} "
                f"usable={pool.num_usable}")
        live = (list(self.scheduler.running.values()) +
                list(self.scheduler.queue))
        held = sum(len(r.pages) for r in live)
        # an admission-time COW pin (fork source awaiting the copy) is a
        # held reference too, until the engine's fork consumes it
        held += sum(1 for r in live if r.cow_src is not None)
        if self.faults is not None:
            held += len(self.faults.held_pages)
        if held != pool.total_refs:
            self._dump_postmortem("REF-LEAK")
            raise PageLeakError(
                f"REF-LEAK: held={held} refs={pool.total_refs} "
                f"cached={pool.num_cached} free={pool.num_free} "
                f"usable={pool.num_usable}")
        # a window kind's rings go with the slots: every slot is either
        # free or a running request's, so no ring is lost with one
        slots = len(self.scheduler.running) + \
            len(self.scheduler._free_slots)
        if (self._rings or self._recurrent is not None) \
                and slots != self._max_slots:
            token = "RING-LEAK" if self._rings else "STATE-LEAK"
            self._dump_postmortem(token)
            raise PageLeakError(
                f"{token}: {len(self.scheduler.running)} running and "
                f"{len(self.scheduler._free_slots)} free slots of "
                f"{self._max_slots}: a slot's window rings or recurrent "
                "state are held by no one")
        if self._proposer is not None:
            # the draft-model pool obeys the same conservation law:
            # pages held by live draft states == draft-pool refcounts
            self._proposer.check_conservation()
        if self.host_tier is not None:
            # third state (round 21): pages now conserve across device,
            # host, and dropped — the tier's own ledger must balance
            # (HOSTTIER-LEAK) at any tick, not just at drain
            try:
                self.host_tier.check()
            except PageLeakError:
                self._dump_postmortem("HOSTTIER-LEAK")
                raise

    def free_bytes(self) -> int:
        """What of ``pool_bytes`` no live sequence holds, over every kind
        of layer state: the free list's pages and the rings and recurrent
        states of the free slots (per chip)."""
        per_slot = sum(ring.bytes_per_slot() for ring in self._rings) + (
            self._recurrent.bytes_per_slot() if self._recurrent is not None
            else 0)
        return self.pool.num_free * self.kv_cfg.bytes_per_page() + \
            len(self.scheduler._free_slots) * per_slot

    # ---- page-migration plane (round 16) --------------------------------

    def migratable_rids(self) -> List[int]:
        """Requests eligible for a chain handoff to a decode-class
        replica: still RUNNING, prefill fully materialized, and at
        least the first token emitted (so the destination starts with a
        decodable state — ``generated[-1]`` is the next step's input)."""
        if self._block is not None or self._rings \
                or self._recurrent is not None:
            return []     # a block between passes is not handed over,
            #               nor a window layer's ring, nor a slot's state
        self.land()       # (the answer is about tokens the host has)
        return [r.rid for r in self.scheduler.running_requests()
                if r.status is RequestStatus.RUNNING and not r.prefilling
                and r.generated]

    def apply_imported_pages(self, page_ids: Sequence[int], k, v,
                             k_scale=None, v_scale=None) -> None:
        """Splice host page payloads (STORED values from
        ``kv_cache.read_pages`` on the source engine) into this
        engine's device pool at ``page_ids``.  The page-count dimension
        is padded up to the next power of two so migrations of any size
        share O(log pages) compiles of the donated
        ``serving.import_pages`` scatter; padding rows write a zero
        payload into NULL_PAGE (reserved scratch, never read)."""
        n = len(page_ids)
        if n == 0:
            return
        padded = 1 << max(0, (n - 1).bit_length())
        pad = padded - n
        ids = list(page_ids) + [NULL_PAGE] * pad

        def _pad(a):
            if a is None or pad == 0:
                return a
            z = np.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)
            return np.concatenate([a, z], axis=1)

        ids_dev = jnp.asarray(ids, jnp.int32)
        if self.kv_cfg.quantized:
            self._kv = self._import_fn(self._kv, ids_dev, _pad(k), _pad(v),
                                       _pad(k_scale), _pad(v_scale))
        else:
            self._kv = self._import_fn(self._kv, ids_dev, _pad(k), _pad(v))

    # ---- hierarchical host tier (round 21) -------------------------------

    def _pump_host_tier(self, tick: int) -> None:
        """One tick of host-tier work, BEFORE admission and never
        blocking decode: advance the depth-one spill writer, then — for
        the head-of-queue request only — walk the host index past the
        device index's longest hit and promote up to ``swap_in_budget``
        verified pages back into the pool (the chunk-prefill charging
        model: bounded pages per tick; a longer host chain continues
        next tick).  Promoted pages are inserted into the device index
        and parked RECLAIMABLE, so the admission lookup right after
        treats them exactly like any other cached prefix — the COW /
        pinning machinery is reused unchanged.  A checksum mismatch
        pops the record, counts HOSTTIER-CORRUPT, and truncates the
        swap-in there: corruption degrades to a shorter hit (a miss for
        that block), never to wrong KV."""
        tier, cache, sched = self.host_tier, self.cache, self.scheduler
        if tier is None or cache is None:
            return
        tier.pump(tick)
        if self._swap_in_budget <= 0 or not sched.queue:
            return
        req = sched.queue[0]
        toks = req.cache_tokens
        page = self.kv_cfg.page_size
        nblocks = len(toks) // page
        if nblocks == 0 or len(tier) == 0:
            return
        _, hit_len = cache.lookup(toks)       # pure probe, no LRU churn
        j = hit_len // page
        if j >= nblocks:
            return
        keys = cache.chain_keys(toks)
        h = _CHAIN_SEED if j == 0 else keys[j - 1]
        probe: List[Tuple[int, int, Tuple[int, ...]]] = []
        jj, hh = j, h
        while jj < nblocks and len(probe) < self._swap_in_budget:
            block = tuple(toks[jj * page:(jj + 1) * page])
            if tier.peek(keys[jj], hh, block) is None:
                break
            probe.append((keys[jj], hh, block))
            hh = keys[jj]
            jj += 1
        if not probe:
            return
        # device pages first (the ladder may evict-and-spill to make
        # room); under pressure the records simply stay host-resident
        # and the walk retries next tick
        new = sched.alloc_pages(len(probe))
        if new is None:
            return
        got = []
        for key, prev, block in probe:
            rec = tier.take_verified(key, prev, block)
            if rec is None:
                break                  # HOSTTIER-CORRUPT: chain ends here
            got.append(rec)
        used, unused = new[:len(got)], new[len(got):]
        if got:
            k = np.concatenate([r.k for r in got], axis=1)
            v = np.concatenate([r.v for r in got], axis=1)
            ks = vs = None
            if got[0].k_scale is not None:
                ks = np.concatenate([r.k_scale for r in got], axis=1)
                vs = np.concatenate([r.v_scale for r in got], axis=1)
            if not self.kv_cfg.quantized and ks is not None:
                # int8-on-host under a float device pool: dequantize on
                # promotion with the one shared rule
                k = np.asarray(dequantize_kv(jnp.asarray(k),
                                             jnp.asarray(ks)))
                v = np.asarray(dequantize_kv(jnp.asarray(v),
                                             jnp.asarray(vs)))
                ks = vs = None
            self.apply_imported_pages(used, k, v, ks, vs)
            # pages[] is indexed by block: blocks < j are already
            # device-resident (insert never touches them — NULL_PAGE
            # padding keeps the indices aligned)
            cache.insert(toks, [NULL_PAGE] * j + used,
                         upto=(j + len(got)) * page, from_block=j,
                         prev_hash=h, tenant=req.tenant)
            self._host_hits += 1
            self._tracer.instant("host_swap_in", rid=req.rid,
                                 n=len(got), tick=tick)
        if used:
            # park the promoted pages reclaimable (insert registered
            # them cached; dropping our alloc ref leaves refcount 0)
            self.pool.free(used)
        if unused:
            self.pool.free(unused)

    def load(self) -> Dict[str, object]:
        """Cheap load probe: the same queue_depth / running /
        free_pages numbers ``healthz`` reports, WITHOUT the
        conservation scan healthz pays for its ``ok`` bit.  The fleet
        router reads this once per candidate replica per submit, so it
        must stay O(1); ``healthz`` remains the full diagnostic for
        external probers."""
        return {"queue_depth": self.scheduler.queue_depth,
                "running": len(self.scheduler.running),
                "free_pages": self.pool.num_free,
                # class-aware routing probe (round 16): prompt tokens
                # still owed a prefill, and this engine's fleet class —
                # both O(1) (the scheduler maintains the backlog
                # incrementally on every cache_len edge)
                "prefill_backlog_tokens":
                    self.scheduler.prefill_backlog_tokens,
                "role": self.role,
                "draining": self._draining,
                # host-tier depth (round 21): pages warm in host RAM —
                # a router's restart/balance decision reads this O(1)
                "pages_host": (len(self.host_tier)
                               if self.host_tier is not None else 0),
                # per-tenant split (round 17): the control plane's WFQ /
                # autoscaler read this; O(live requests), still cheap at
                # the bounded slot/queue sizes this probe already scans
                "tenants": self.tenant_counts()}

    def tenant_counts(self) -> Dict[str, Dict[str, int]]:
        """Per-tenant live/terminal split: running, queued and
        pages_in_use from the bounded live scans, deadline_misses from
        the metrics counter.  Keys appear once a tenant has ever been
        seen live, been admitted, or missed a deadline — "default"
        covers legacy callers that never pass ``tenant=``."""
        out: Dict[str, Dict[str, int]] = {}

        def _slot(t: str) -> Dict[str, int]:
            return out.setdefault(t, {"running": 0, "queued": 0,
                                      "pages_in_use": 0,
                                      "pages_host": 0,
                                      "deadline_misses": 0})

        for req in self.scheduler.running.values():
            s = _slot(req.tenant)
            s["running"] += 1
            s["pages_in_use"] += len(req.pages)
        for req in self.scheduler.queued_requests():
            _slot(req.tenant)["queued"] += 1
        for t, n in self.metrics.tenant_deadline_misses.items():
            _slot(t)["deadline_misses"] = n
        # host-tier residency billed to whoever prefilled the page
        # (round 21): the ledger view splits warm capacity by tenant
        if self.host_tier is not None:
            for t, n in self.host_tier.resident_by_tenant.items():
                _slot(t)["pages_host"] = n
        # tenants whose work all completed cleanly must still report a
        # zero-miss row: the admission window remembers everyone admitted
        for t in self.metrics.tenant_queue_wait_s:
            _slot(t)
        return out

    def healthz(self) -> Dict[str, object]:
        """One-call liveness snapshot for an external prober.  O(live
        requests), not O(history): terminal counts come from the metrics
        counters, live states from the bounded queue/slot scans."""
        m = self.metrics
        counts: Dict[str, int] = {}
        for key, val in (("completed", m.completed),
                         ("timed_out", m.timed_out),
                         ("cancelled", m.cancelled),
                         ("failed", m.failed),
                         ("rejected", m.rejected + m.shed)):
            if val:
                counts[key] = val
        for req in (list(self.scheduler.queue) +
                    list(self.scheduler.running.values())):
            counts[req.status.value] = counts.get(req.status.value, 0) + 1
        try:
            self.check_page_conservation()
            leak = False
        except PageLeakError:
            leak = True
        # the unified-registry surface: publish this engine's counters,
        # then hand back the registry's flat snapshot so one healthz
        # probe reads the same numbers a scraper would.  Host-tier
        # gauges are stamped first so a probe between ticks (or before
        # the first) reads current tier state, not last tick's.
        if self.host_tier is not None:
            m.on_host_tier(self.host_tier.snapshot(), self._host_hits)
        self.metrics.publish(self.registry, **self._reg_labels)
        # retrace-auditor compile counts ride the same scrape surface
        # (jit_compiles_total{site=...}): before this they existed only
        # as jit_compile trace instants, invisible to a scraper.  Gated
        # on the auditor actually having sites, so audit-off engines
        # pay nothing and publish nothing.  Published WITHOUT the
        # per-engine labels: the auditor is process-global (every
        # replica's compiles land on ONE SiteRecord per site name), so
        # stamping replica labels on the shared sums would make each
        # replica appear to own the whole fleet's compiles — in a
        # shared-registry fleet the publishes are idempotent instead.
        if auditor().sites:
            auditor().publish(self.registry)
        return {
            "ok": not leak,
            "metrics": self.registry.snapshot(),
            "tick": self._tick,
            "queue_depth": self.scheduler.queue_depth,
            "running": len(self.scheduler.running),
            "draining": self._draining,
            # first-class load signals for a fleet router's balancing /
            # overflow decision (queue_depth above + free_pages here):
            # admission headroom without reaching into pool internals.
            # pages_free stays as the historical alias.
            "free_pages": self.pool.num_free,
            "pages_free": self.pool.num_free,
            # in_use = live sequence holders; cached/reclaimable pages
            # are reported separately so a prober can assert the cache
            # drains to steady state (live 0, cached >= 0 all evictable)
            "pages_in_use": self.pool.num_live,
            "pages_cached": self.pool.num_cached,
            "pages_reclaimable": self.pool.num_reclaimable,
            # effective cache capacity: what the pool's byte budget buys
            # at this KV dtype (int8 admits ~4x the f32 pages — see
            # ServingEngine(pool_bytes=...))
            "pages_total": self.pool.num_usable,
            "kv_dtype": str(jnp.dtype(self.kv_cfg.dtype).name),
            # per-CHIP pool bytes: under TP each chip holds 1/tp of
            # every page's KV heads (scales sharded with them)
            "kv_bytes": self.kv_cfg.kv_bytes(),
            "tp": self.tp,
            # `is not None`, not truthiness: PrefixCache defines __len__,
            # so an empty-but-active cache is falsy
            "cache_hits": self.cache.hits if self.cache is not None else 0,
            "cache_misses": (self.cache.misses
                             if self.cache is not None else 0),
            # host-tier gauges (round 21) — same is-not-None rule
            # (HostPageTier defines __len__ too); zeros with the tier off
            # so probers read one stable schema
            "pages_host": (len(self.host_tier)
                           if self.host_tier is not None else 0),
            "host_swap_ins": (self.host_tier.swap_ins
                              if self.host_tier is not None else 0),
            "host_swap_outs": (self.host_tier.spills
                               if self.host_tier is not None else 0),
            "host_hits": self._host_hits,
            "host_corrupt": (self.host_tier.corrupt
                             if self.host_tier is not None else 0),
            "spill_stall_ticks": (self.host_tier.spill_stall_ticks
                                  if self.host_tier is not None else 0),
            "page_leak": leak,
            "status_counts": counts,
            "deadline_miss_rate": round(self.metrics.deadline_miss_rate(),
                                        4),
            # disaggregated-fleet probe (round 16): same pair load()
            # exposes, on the full diagnostic surface
            "prefill_backlog_tokens":
                self.scheduler.prefill_backlog_tokens,
            "role": self.role,
            # per-tenant counters (round 17) on the full diagnostic
            # surface, same shape as load()["tenants"]
            "tenants": self.tenant_counts(),
        }

    # ---- internals -------------------------------------------------------

    def _enforce_deadlines(self, now: float) -> None:
        sched = self.scheduler
        # running requests past their total deadline: free immediately
        for req in list(sched.running.values()):
            if req.deadline_at is not None and now >= req.deadline_at:
                self._finish(req, RequestStatus.TIMED_OUT, now)
        for req in sched.queued_requests():
            # the queue deadline is an ADMISSION SLO: once a request has
            # been admitted it is satisfied forever — a preempted request
            # back in the queue is judged only by its total deadline
            expired = (req.deadline_at is not None and
                       now >= req.deadline_at) or \
                      (req.admitted_at is None and
                       req.queue_deadline_at is not None and
                       now >= req.queue_deadline_at)
            if expired:
                self._finish(req, RequestStatus.TIMED_OUT, now)
                continue
            # load shedding, on the WORST-CASE length assumption: at one
            # token per tick (the engine's best rate; a block model's is
            # B tokens in S ticks), a request that
            # runs to its full max_tokens cannot finish by its deadline.
            # An early EOS could beat the estimate — callers who rely on
            # early stopping should size max_tokens to what they
            # actually expect, since it is the only length signal the
            # engine has before decoding.
            if (req.deadline_at is not None and self._tick_dur_ema > 0.0
                    and now + req.tokens_remaining * self._ticks_per_token
                    * self._tick_dur_ema > req.deadline_at):
                self._finish(req, RequestStatus.REJECTED, now, shed=True)

    def _propose_drafts(self, running: List[Request],
                        under_pressure: bool) -> Dict[int, Tuple]:
        """Per-tick speculation: ask the proposer for up to ``spec_k``
        drafts per running slot, charge lookahead pages (opportunistic
        — never by preemption), and privatize any shared page the
        verify would write (:meth:`_cow_guard`).  Under page pressure
        (a preemption ran this tick, or the pool is dry) speculation is
        suspended outright: the tick degrades to plain 1-row decode,
        which the base page charge already guaranteed.  Returns
        ``{rid: (draft tokens, warped proposal probs or None)}``."""
        if self._proposer is None or not running:
            return {}
        m = self.metrics
        if under_pressure or self.pool.num_free == 0:
            m.on_spec_suspend(len(running))
            return {}
        caps = {req.rid: max(0, min(self.spec_k,
                                    req.tokens_remaining - 1,
                                    self.kv_cfg.max_seq_len
                                    - req.cache_len - 1))
                for req in running}
        eligible = [r for r in running if caps[r.rid] > 0]
        proposals = self._proposer.propose(eligible,
                                           lambda r: caps[r.rid]) \
            if eligible else {}
        drafts: Dict[int, Tuple] = {}
        for req in running:
            got = proposals.get(req.rid, ((), None))
            toks, probs = list(got[0])[:caps[req.rid]], got[1]
            if toks:
                granted = self.scheduler.grant_lookahead(req, len(toks))
                if granted < len(toks):
                    m.on_spec_suspend()       # page-pressure shrink
                    toks = toks[:granted]
            # the guard also covers the base decode row (toks may be
            # empty): a speculating engine never writes ANY verify row
            # into a cached or refcount-shared page un-forked
            toks = self._cow_guard(req, toks)
            if toks:
                drafts[req.rid] = (
                    toks, None if probs is None else probs[:len(toks)])
        if isinstance(self._proposer, DraftProposer):
            m.on_draft(self._proposer.steps, self._proposer.step_time_s)
        return drafts

    def _cow_guard(self, req: Request, toks: List[int]) -> List[int]:
        """Copy-on-write guard for the verify's multi-token write: every
        page the ``len(toks) + 1`` rows would touch that is cached or
        refcount-shared is forked into a private replica first (table
        entry swapped, our reference moved), so a rejected speculative
        branch can never dirty K/V another holder — a prefix-cache
        sharer, or the cache itself — reads.  If the fork cannot get a
        page, the lookahead truncates to stop short of the shared page
        instead."""
        page = self.kv_cfg.page_size
        for idx in pages_spanned(req.cache_len, len(toks) + 1, page):
            src = req.pages[idx]
            if not self.pool.is_cached(src) and \
                    self.pool.refcount(src) <= 1:
                continue
            got = self.scheduler.alloc_pages(1)
            if got is None:
                # cannot privatize: write nothing into this page.  The
                # base decode row (position cache_len) always ships —
                # its page is never shared under the engine's own
                # insert policy (only FULL prefix pages are ever
                # cached/stitched), this guard exists for duck-typed
                # callers that cache more aggressively.
                self.metrics.on_spec_suspend()
                return toks[:max(0, idx * page - 1 - req.cache_len)]
            # scalar page-id UPLOADS for the rare fork dispatch, not
            # readbacks — same shape _begin_prefill's COW fork uses
            self._kv = self._fork_fn(
                self._kv,
                jnp.asarray(src, jnp.int32),       # lint: allow(host-sync)
                jnp.asarray(got[0], jnp.int32))    # lint: allow(host-sync)
            self.pool.free([src])     # drop OUR ref; sharers keep theirs
            req.pages[idx] = got[0]
            self.metrics.on_spec_cow()
            self._tracer.instant("spec_cow", rid=req.rid, src=src,
                                 dst=got[0])
        return toks

    def _step_with_retry(self, running: List[Request], chunks, total_rows,
                         tick: int, drafts: Dict[int, Tuple]) -> None:
        if self.faults is None:
            # nothing injects a failure, and a read back that lags its
            # dispatch is not run again: what it raises reaches the caller
            self._do_step(running, chunks, total_rows, drafts)
            return
        attempt = 0
        while True:
            try:
                if self.faults is not None and \
                        self.faults.decode_should_fail(tick, attempt):
                    raise InjectedDeviceError(f"injected @ tick {tick} "
                                              f"attempt {attempt}")
                self._do_step(running, chunks, total_rows, drafts)
                return
            except self.transient_errors:
                attempt += 1
                if attempt > self.decode_retries:
                    return   # tick lost; the watchdog counts the stall
                self.metrics.on_retry()

    def _watchdog_sweep(self, tick: int) -> None:
        if self.watchdog_ticks <= 0:
            return
        sched = self.scheduler
        for req in list(sched.running.values()):
            if tick - req.last_progress_tick >= self.watchdog_ticks:
                self._finish(req, RequestStatus.FAILED, self._time())

    def _begin_prefill(self, req: Request) -> None:
        """Stitch-time work for a newly (re-)admitted request: record
        the prefix-cache outcome, run the COW fork, and arm the chunked
        prefill (its first chunk runs this same tick)."""
        toks = req.cache_tokens
        # (a block model may have nothing to prefill: a prompt inside one
        # block, or whole blocks that the cache covers)
        req.prefilling = req.cache_len < self._prefill_target(req)
        req.chain_hash, req.chain_blocks = None, 0   # fresh insert cursor
        self.metrics.on_prefix(len(toks), req.cached_len)
        if req.cow_src is not None:
            # full-cover hit: the tail's only token rewrites a position
            # INSIDE the last shared page, so fork it into the request's
            # first private page before anything is written
            dst = req.pages[req.cache_len // self.kv_cfg.page_size]
            self._kv = self._fork_fn(self._kv,
                                     jnp.asarray(req.cow_src, jnp.int32),
                                     jnp.asarray(dst, jnp.int32))
            # the fork consumed the source: drop the admission-time pin
            # that kept it from being evicted before the copy ran
            self.pool.free([req.cow_src])
            req.cow_src = None
            self.metrics.on_cow()

    def _do_step(self, running: List[Request], chunks,
                 total_rows: int, drafts: Dict[int, Tuple]) -> None:
        """Assemble and dispatch ONE unified step, move the requests on
        by what it will come to (:meth:`_advance`), and read the words
        of the step BEFORE it (:meth:`_land`): the step just dispatched
        stays in the air (``_flying``) until the next call, unless the
        host needs its tokens or its logits now (:meth:`_lands_now`;
        the module doc says when).

        With speculation, slot ``s`` ships ``1 + len(drafts[s])`` rows
        (the plain decode token plus the lookahead); the accept walk
        (``speculate.accept_tokens``) emits the longest agreeing prefix
        plus one bonus/corrected token, and a partial acceptance rolls
        the lookahead pages back (``scheduler.rollback_pages``) — the
        rejected rows' K/V beyond the new length is masked junk the
        next real tokens overwrite."""
        k1, tick, phase = self._k1, self._tick, self._tracer.phase
        with phase("tick.assemble", tick=tick):
            if self._block is not None:
                passes = self._passes(running, chunks)
                n_rows = sum(self._pass_rows(p[5]) for p in passes)
            else:
                passes = [(r, r.slot) + drafts.get(r.rid, ((), None))
                          for r in running]
                n_rows = sum(1 + len(p[2]) for p in passes)
            packed = self._assemble(running, chunks, total_rows, drafts,
                                    passes)
        parts = self._tick_parts(packed, k1)
        p_seq, att_lens = parts[5], parts[8]
        pb = p_seq.shape[0]                     # the prefill bucket
        compiles = (pb, k1) not in self._step_fns
        with phase("tick.upload", tick=tick):
            # ONE placement, already in the layout the step wants
            # (replicated over the engine's mesh), so the call re-lays
            # nothing: its other arguments are committed there too
            step = self._step_fn(pb, k1)
            placed = jax.device_put(packed, self._tick_sharding)
            with phase("tick.dispatch", tick=tick):
                words, logits, self._kv, *kinds = step(
                    self.params, self._kv, placed, self._last_words(),
                    *self._kind_kv())
                self._ring_kv = tuple(kinds[:len(self._rings)])
                if self._recurrent is not None:
                    self._rec_kv = kinds[-1]
        flight = _Flight(
            passes, chunks, words, logits,
            (n_rows, total_rows, pb - sum(c[2] for c in chunks)),
            packed.nbytes, self._attn_cells(parts),
            self._kind_counts(parts, chunks))
        self._advance(flight)
        if compiles:
            _settle_heap()            # (beside the device's first run)
        before, self._flying = self._flying, flight
        if before is not None:
            self._land(before, lagged=True)
        if self._lands_now(flight):
            self._land(flight)

    def _kind_kv(self) -> Tuple:
        """What a step takes behind the last step's words, and returns
        behind the pool: each ring's arrays, then (one argument) the
        recurrent kind's."""
        return self._ring_kv + ((self._rec_kv,) if self._recurrent
                                is not None else ())

    # ---- the lagged read back --------------------------------------------

    def _last_words(self):
        """What a step takes after the tick's buffer: the words of the
        step before it, still on the device (zeros where none is in the
        air: no token is pending then)."""
        if self._flying is not None:
            return self._flying.words
        if self._no_words is None:
            b = self._max_slots
            n = b * (self._fix_rows + 2) if self._block is not None \
                else 2 * (b * self._k1 + b)
            self._no_words = jax.device_put(
                np.zeros(n + len(self._counted) + len(self._loop_counted),
                         np.int32),
                self._tick_sharding)
        return self._no_words

    def _lands_now(self, flight: _Flight) -> bool:
        """Whether the host needs a step's tokens or its logits before
        the next step is assembled, by what the engine can see: a fault
        plan is bound (it injects at the walk, tick by tick); a proposer
        drafts from the tokens the verify walk accepts; a replica that
        hands its requests over (``role``) exports their tokens between
        two calls; a request that rode the step draws its token on the
        host."""
        return (self.faults is not None or self._proposer is not None
                or self.role != "unified"
                or any(_samples(p[0])
                       for p in flight.passes + flight.chunks))

    def land(self) -> None:
        """Read the step in the air now, if one is: where the host needs
        its tokens before the next dispatch, and for a caller that is
        about to read running requests' tokens between two calls of
        ``step`` (``migrate.export_chain``)."""
        if self._flying is not None:
            self._land(self._flying)

    def _growth_may_preempt(self) -> bool:
        """Whether the pages that ``ensure_decode_pages`` is about to take
        may not be there without a preemption."""
        page = self.kv_cfg.page_size
        need = sum(1 for r in self.scheduler.running.values()
                   if r.cache_len >= len(r.pages) * page)
        return need > self.pool.num_free + self.pool.num_reclaimable

    def _pass_rows(self, fold: int) -> int:
        """The rows a slot really brings for a pass: its open block, and
        at a fold the full block before it."""
        return self._block * (2 if fold == _FOLD_BLOCK else 1)

    def _passes(self, running: List[Request], chunks=()):
        """Which pass each slot with block rows in this tick stands at:
        ``_Flight.passes``, from the requests alone (``cache_len``, the
        tokens a request has, ``pending``).  A running slot works in the
        block at ``cache_len``: a denoising pass while it has masked
        positions.  Once it is full (the pending tokens counted) the tick
        that writes its clean K/V also OPENS THE NEXT BLOCK: the slot
        brings both blocks' rows, the next one's all mask tokens, and the
        pass fixes the next block's first ``B / S`` tokens
        (``_FOLD_BLOCK``).  The next block's page is taken here where the
        full block ended its page, the way speculation's lookahead takes
        one (``grant_lookahead``: the cache may give a page up, nothing is
        preempted); without one the slot commits alone (``n`` 0) and the
        next tick's growth finds the page.  The same holds behind a
        prompt: the tick that prefills a prompt's last chunk carries the
        slot's first open block as its decode rows (``_FOLD_CHUNK``)."""
        blk, out = self._block, []
        for req in running:
            at, got = req.cache_len, len(req.generated) + req.pending
            have = len(req.prompt) + got - at
            n = min(self._fix_rows, req.max_tokens - got)
            if have < blk:
                out.append((req, req.slot, at, have, min(n, blk - have),
                            _FOLD_NONE))
            elif self.scheduler.grant_lookahead(req, 2 * blk - 1) \
                    == 2 * blk - 1:
                out.append((req, req.slot, at + blk, 0, n, _FOLD_BLOCK))
            else:
                out.append((req, req.slot, at, have, 0, _FOLD_NONE))
        for req, start, n, _rows in chunks:
            at, got = start + n, len(req.generated)
            if at >= self._prefill_target(req):
                have = len(req.prompt) + got - at
                out.append((req, req.slot, at, have, min(
                    self._fix_rows, blk - have, req.max_tokens - got),
                    _FOLD_CHUNK))
        return out

    def _advance(self, flight: _Flight) -> None:
        """At its dispatch, what a step will come to but for its tokens'
        values: chunks are prefilled (a prompt's last row yields its
        first token, which is pending; not a block model's), a decode
        row's token is pending and in the cache (what a verify walk
        accepts beyond it is added when it lands), a full block is
        committed (alone, or in the tick that opens the next block:
        ``cache_len`` moves past it either way), a denoising pass's
        tokens are pending."""
        blk = self._block
        for req, start, n, _rows in flight.chunks:
            self._advance_chunk(req, start, n)
            if blk is None and not req.prefilling:
                req.pending += 1
        if blk is None:
            for req, *_ in flight.passes:
                req.cache_len += 1
                req.pending += 1
            return
        for req, _slot, at, _have, n, fold in flight.passes:
            req.pending += n
            if fold == _FOLD_BLOCK or not n:
                old = req.cache_len
                req.cache_len = at if n else at + blk
                # a block that a prompt's tail shares was still owed
                self.scheduler.note_prefill_progress(req, old)

    def _land(self, flight: _Flight, lagged: bool = False) -> None:
        """Read a dispatched step's words and walk them: chunk
        bookkeeping first (finite guard, cache inserts, a final chunk's
        first token: the v1 tick order), the slots' tokens second.
        ``lagged``: the next step was dispatched first, so the device
        does not wait for this.  A request that ended meanwhile
        (cancelled, timed out, failed or completed by an earlier
        flight) is passed over: its slot and pages went back then, and
        what the step wrote there lies before any later step's writes."""
        tick, phase = self._tick, self._tracer.phase
        if self._flying is flight:
            self._flying = None
        with phase("tick.wait", tick=tick):
            # (no copy queued behind the call: a tick later it gains nothing)
            words = np.asarray(flight.words)      # waits for the device
        with phase("tick.sample", tick=tick):
            rows, prefill_rows, pad_rows = flight.rows
            self.metrics.on_step(
                rows, prefill_rows, pad_rows, n_slots=len(flight.passes),
                h2d_bytes=flight.h2d_bytes, d2h_bytes=words.nbytes,
                attn_cells=flight.attn_cells,
                # (the model's counts are the words' tail)
                model_counts=tuple(
                    words[words.size - len(self._counted)
                          - len(self._loop_counted):])
                + flight.kind_counts, lagged=lagged)
            # stamp AFTER the sync so TTFT includes the step compute
            now = self._time()
            poisoned = self.faults.nan_rids if self.faults is not None \
                else ()
            walk = self._walk_rows if self._block is None \
                else self._walk_passes
            walk(flight, words, poisoned, now)

    def _walk_passes(self, flight: _Flight, words: np.ndarray, poisoned,
                     now: float) -> None:
        """What the host does with a block step's words: ``picks`` ``[B,
        B / S + 1]`` (:meth:`_finish_block_pass`), then whether each
        slot's last prompt row was finite (the chunk guard's reading)."""
        b, f = self._max_slots, self._fix_rows + 1
        picks = words[:b * f].reshape(b, f)
        guard = words[b * f:b * f + b] != 0
        for req, start, n, _rows in flight.chunks:
            if req.status is RequestStatus.RUNNING:
                self._finish_chunk(req, start, n, guard[req.slot], now)
        for req, slot, *stood in flight.passes:
            if req.status is not RequestStatus.RUNNING:
                continue
            mine = picks[slot]
            if req.rid in poisoned:
                mine = mine.copy()
                mine[-1] = 0                  # "not finite"
            self._finish_block_pass(req, tuple(stood), mine,
                                    flight.logits, now)

    def _walk_rows(self, flight: _Flight, words: np.ndarray, poisoned,
                   now: float) -> None:
        """What the host does with a one-token (or verify) step's words:
        each row's best token, then whether each row's logits are all
        finite, over the ``B * k1`` decode / verify rows and then each
        slot's chunk-final row.  Greedy tokens are the step's own
        choices; a request that samples draws from its rows of the
        logits, fetched for it alone, and a proposer's verify walk reads
        all of them, once."""
        k1 = self._k1
        bd = self._max_slots * k1
        best, finite = words[:bd + self._max_slots], \
            words[bd + self._max_slots:2 * (bd + self._max_slots)] != 0
        host = None
        if self._proposer is not None:
            host = np.asarray(flight.logits)
            self.metrics.on_fetch(host.nbytes)

        def fetch(lo: int, n: int) -> np.ndarray:
            if host is not None:
                return host[lo:lo + n]
            got = np.asarray(flight.logits[lo:lo + n])
            self.metrics.on_fetch(got.nbytes)
            return got

        for req, start, n, _rows in flight.chunks:
            if req.status is not RequestStatus.RUNNING:
                continue    # cancelled from an earlier chunk's on_token
            at = bd + req.slot
            if not self._finish_chunk(req, start, n, finite[at], now):
                continue
            # first token: greedy argmax unless the request samples
            # (seeded per-position draw — position 0 of its stream)
            req.pending -= 1
            self._emit(req, next_token(
                fetch(at, 1)[0], req.sampling, len(req.generated))
                if _samples(req) else int(best[at]), now)
        for req, slot, dr, dprobs in flight.passes:
            if req.status is not RequestStatus.RUNNING:
                continue    # cancelled from another slot's on_token
            lo, nrows = slot * k1, 1 + len(dr)
            req.pending -= 1
            if req.rid in poisoned or not finite[lo:lo + nrows].all():
                # poisoned slot (possibly mid-verify): fail ONLY this
                # request — its pages go back (uncached ones scrubbed
                # by _finish), the fused batchmates keep decoding
                # untouched and the proposer state is released
                self._finish(req, RequestStatus.FAILED, now)
                continue
            if dr or _samples(req):
                emitted, accepted = accept_tokens(
                    fetch(lo, nrows), dr, dprobs, req.sampling,
                    len(req.generated), self.eos_id)
            else:
                emitted, accepted = [int(best[lo])], 0
            req.cache_len += accepted       # (the slot's own row: at
            #                                 the dispatch)
            if dr:
                req.spec_proposed += len(dr)
                req.spec_accepted += accepted
                self.metrics.on_spec(len(dr), accepted)
                self._tracer.instant("spec_accept", rid=req.rid,
                                     proposed=len(dr), accepted=accepted)
                if accepted < len(dr):
                    # rejected branch: return the lookahead pages past
                    # the accepted length (the rolled-back rows' K/V is
                    # masked junk; a shared page was already COW-forked
                    # before the write)
                    self.scheduler.rollback_pages(req)
                    self._tracer.instant("spec_rollback", rid=req.rid,
                                         rejected=len(dr) - accepted)
            for tok in emitted:
                self._emit(req, tok, now)
                if req.finished:
                    break
            if not req.finished and self._proposer is not None:
                # accepted history is now truth: the draft proposer
                # rolls its own cache back to it (no-op for n-gram)
                self._proposer.commit(req)

    def _walk_counts(self, parts, kind: int) -> Tuple[int, int, int, int]:
        """(kernel calls, grid steps, those of them that compute, pages
        needed) of one kind's layers in one step on one chip, counted as
        ``_ragged_call`` lays its walk out (``decode_attention.
        visit_counts`` runs the schedule's own rule over the tick's rows
        as ``_attend`` hands them over): a step is a visit of a resident
        row block to one page of one of its sequences, a slot's decode
        rows one short block, the bucket's rows tall blocks of
        ``tall_block_rows``; a block nobody's row sees a page of has one
        step that computes nothing.  ``pages needed``: the distinct
        (sequence, page) pairs among the visits, what a walk that read
        each page once would fetch (the visits over it: the re-read
        factor).  All times layers and KV-head groups."""
        d_pos, d_valid, p_qpos, p_seq, att_lens = (
            parts[1], parts[2], parts[4], parts[5], parts[8])
        b, k1, cfg = self._max_slots, self._k1, self.kv_cfg
        rbk = -(-k1 // BLOCK_ROWS) * BLOCK_ROWS
        qpos = np.full((b, rbk), -1, np.int32)
        qpos[:, :k1] = np.where(d_valid != 0, d_pos, -1)
        qpos = np.concatenate([qpos.ravel(), p_qpos])
        if self._block is not None:
            # (a row attends as its block's last position)
            qpos = np.where(qpos >= 0, (qpos // self._block + 1)
                            * self._block - 1, -1)
        row_seq = np.concatenate([np.repeat(np.arange(b, dtype=np.int32),
                                            rbk), p_seq])
        window, width = (None, cfg.max_pages_per_seq) if kind == 0 else (
            self._rings[kind - 1].window, self._rings[kind - 1].ring_pages)
        calls = visits = computing = pages = 0
        for group, layers in self._kind_groups[kind].items():
            tall = tall_rows_for(len(p_qpos), group, self._attn_cell_heads,
                                 cfg.head_dim)
            v, c, p = visit_counts(
                row_seq, qpos, att_lens, decode_rows=b * rbk, tall_rows=tall,
                page=cfg.page_size, width=width, window=window)
            n = layers * self._attn_head_groups
            calls, visits = calls + layers, visits + n * v
            computing, pages = computing + n * c, pages + n * p
        return calls, visits, computing, pages

    def _attn_cells(self, parts) -> Tuple[int, int, int, int]:
        """What the full-attention layers' kernel calls of one step walk
        (:meth:`_walk_counts`).  Zeros on the reference path."""
        if not self._ragged_kernel:
            return (0, 0, 0, 0)
        return self._walk_counts(parts, 0)

    def _kind_counts(self, parts, chunks) -> Tuple[int, ...]:
        """What one step holds and visits of the kinds' state, counted on
        the host from the tick's arrays under ``_kind_counted``'s names:
        the tokens the full-attention layers hold of the step's sequences
        (a layer), those a window layer still holds and those of them its
        rows' windows reach (both summed over the window kinds), the
        pages that fell out of a window with this step's rows, and the
        window layers' kernel calls, grid steps and live grid steps
        (:meth:`_window_cells`); for a model with a recurrent state the
        slots that hold a sequence and their states' bytes over its
        layers.  Empty for a model with neither."""
        if not self._kind_counted:
            return ()
        d_valid, att_lens = parts[2], parts[8]
        state = ()
        if self._recurrent is not None:
            # the slots that hold a sequence (which its state belongs to,
            # with or without a row in this step), and their states' bytes
            live = len(self.scheduler.running)
            state = (live, live * self._recurrent.bytes_per_slot())
        if not self._rings:
            return (int(att_lens.sum()),) + state
        rows = d_valid.sum(axis=1)         # a slot's rows of this step
        for req, _start, n, _rows in chunks:
            rows[req.slot] = n
        held = seen = released = 0
        for ring in self._rings:
            held += int(ring.tokens_held(att_lens).sum())
            seen += int(np.minimum(att_lens, ring.window + rows - 1).sum())
            released += int((ring.released(att_lens)
                             - ring.released(att_lens - rows)).sum())
        return (int(att_lens.sum()), held, seen, released) + \
            self._window_cells(parts) + state

    def _window_cells(self, parts) -> Tuple[int, int, int]:
        """(kernel calls, grid steps, those that compute) of the window
        layers in one step (:meth:`_walk_counts` over the rings: a run's
        visits go from the first page its rows' windows reach to the
        page of its last row).  Zeros on the reference path, as
        :meth:`_attn_cells`."""
        if not self._ragged_kernel:
            return (0, 0, 0)
        counts = [self._walk_counts(parts, 1 + i)[:3]
                  for i in range(len(self._rings))]
        return tuple(int(n) for n in np.sum(counts, axis=0))

    def _tick_shapes(self, pb: int, k1: int) -> Tuple[Tuple[int, ...], ...]:
        """A tick's nine input arrays (ten for a block model) in the order
        they lie in its one
        packed int32 buffer: ``d_tokens``, ``d_pos``, ``d_valid`` (0/1)
        ``[B, k1]``; ``p_tokens``, ``p_qpos``, ``p_seq`` ``[pb]``;
        ``p_last`` ``[B]``; ``table`` ``[B, Pm]``; ``att_lens`` ``[B]``."""
        b, pm = self._max_slots, self.kv_cfg.max_pages_per_seq
        shapes = ((b, k1),) * 3 + ((pb,),) * 3 + ((b,), (b, pm), (b,))
        if self._block is not None:
            # a tenth, ``d_sel`` ``[B, B / S]``: the rows, of those a
            # slot brings, whose logits the step returns
            shapes += ((b, self._fix_rows),)
        return shapes

    def _tick_parts(self, packed, k1: int) -> List:
        """Those arrays as views of ``packed`` at static offsets
        (``pb`` is what the buffer's length leaves): the host fills
        these views of a NumPy buffer (``_assemble``), the compiled
        step slices the traced one (``_step_fn``)."""
        fixed = sum(math.prod(s) for s in self._tick_shapes(0, k1))
        parts, off = [], 0
        for shape in self._tick_shapes((packed.shape[0] - fixed) // 3, k1):
            n = math.prod(shape)
            parts.append(packed[off:off + n].reshape(shape))
            off += n
        return parts

    def _empty_tick(self, pb: int, k1: int) -> np.ndarray:
        """The packed buffer of a step that carries no row yet: every
        table entry the null page, every prefill row padding."""
        packed = np.zeros(sum(math.prod(s)
                              for s in self._tick_shapes(pb, k1)), np.int32)
        parts = self._tick_parts(packed, k1)
        parts[4][:] = -1                        # p_qpos
        parts[7][:] = NULL_PAGE                 # table
        return packed

    def _assemble(self, running: List[Request], chunks, total_rows: int,
                  drafts: Dict[int, Tuple], passes=()) -> np.ndarray:
        """The host side of one unified step's inputs: ONE int32 buffer
        (``_tick_shapes`` says what lies where), which ``_step_fn``
        takes after the parameters and the pool.  A block model's decode
        rows are laid out from ``passes`` (:meth:`_passes`): a slot's
        open block and, at a fold, the full block before it; the rows of
        its ``2 B`` that a slot does not bring stay invalid (they write
        no K/V, attend to nothing and take no expert)."""
        b, k1 = self._max_slots, self._k1
        cfg = self.kv_cfg
        pb = 0
        if chunks:
            pb = bucket_for(total_rows, self._buckets,
                            max(cfg.max_seq_len, total_rows))
            if self._ragged_kernel:  # whole blocks only (kernel packing)
                pb = -(-pb // BLOCK_ROWS) * BLOCK_ROWS
        packed = self._empty_tick(pb, k1)
        (d_tokens, d_pos, d_valid, p_tokens, p_qpos, p_seq, p_last, table,
         att_lens, *d_sel) = self._tick_parts(packed, k1)
        # the chunks first: a block model's slot may bring its first open
        # block behind its prompt's last chunk, and then attends that far
        off = 0
        for req, start, n, rows in chunks:
            s = req.slot
            toks = req.cache_tokens
            p_tokens[off:off + n] = toks[start:start + n]
            p_qpos[off:off + n] = np.arange(start, start + n)
            # padding rows keep the owning slot so each kernel block
            # stays sequence-uniform (their qpos -1 masks them out)
            p_seq[off:off + rows] = s
            # absolute row in the step's stack (behind the B*k1
            # decode/verify rows)
            p_last[s] = b * k1 + off + n - 1
            att_lens[s] = start + n
            table[s, :len(req.pages)] = req.pages
            off += rows
        if self._block is not None:
            blk, fix_rows = self._block, np.arange(self._fix_rows)
            for req, s, at, have, _n, fold in passes:
                table[s, :len(req.pages)] = req.pages
                # the slot's rows: its open block, whole (the tokens it
                # has, the mask token where none is fixed yet), behind the
                # full block a fold commits
                rows, n_p = self._pass_rows(fold), len(req.prompt)
                lo = at + blk - rows
                known = req.prompt[lo:lo + rows] + req.generated[
                    max(0, lo - n_p):max(0, lo + rows - n_p)]
                d_tokens[s, :rows] = self._mask_id
                d_tokens[s, :len(known)] = known
                # and those the flight in the air is fixing: its j-th pick
                d_tokens[s, len(known):len(known) + req.pending] = \
                    -1 - np.arange(req.pending)
                d_pos[s, :rows] = lo + np.arange(rows)
                d_valid[s, :rows] = 1
                att_lens[s] = lo + rows
                # the open block's rows this pass fixes (a lone committing
                # pass has none left: what it selects is read by no one)
                d_sel[0][s] = np.minimum(at - lo + have + fix_rows, rows - 1)
            return packed
        # whose pending token is a prompt's first: the pick of the
        # chunk-final row of the step in the air (-2), not of a
        # decode row (-1)
        first = {c[0].rid for c in self._flying.chunks} \
            if self._flying is not None else ()
        for req in running:
            s = req.slot
            table[s, :len(req.pages)] = req.pages
            dr = drafts.get(req.rid, ((), None))[0]
            n = 1 + len(dr)
            d_tokens[s, 0] = req.generated[-1] if not req.pending \
                else -2 if req.rid in first else -1
            d_tokens[s, 1:n] = dr
            d_pos[s, :n] = req.cache_len + np.arange(n)
            d_valid[s, :n] = 1
            att_lens[s] = req.cache_len + n
        return packed

    def _finish_block_pass(self, req: Request,
                           stood: Tuple[int, int, int, int],
                           picks: np.ndarray, logits, now: float) -> None:
        """What one pass over a slot's block came to.  ``stood``: where
        the block starts, how many tokens it had, how many the pass was
        to fix and what else the tick wrote for the slot (``_FOLD_*``), as
        the dispatch found them (``_Flight.passes``; the request has moved
        on since).  ``picks``: the slot's row of the step's words, the
        best unmasked token of each position the pass was to fix and,
        last, whether their logits were all finite; ``logits``: the
        step's ``[B, B / S, V]`` logits, on the device.  With nothing to
        fix it was a lone committing pass: the block's clean K/V lie in
        its page (``cache_len`` moved past it at the dispatch).  Else the
        next masked positions, from the left, take their best token with
        the mask token left out (a request that samples draws from the
        logits of its slot, fetched for it alone), each emitted in
        position order; the answer may end (``max_tokens``, EOS) inside
        the block.  A folded pass did both in one tick: the block before
        ``stood``'s was committed beside this block's first pass."""
        blk, m = self._block, self.metrics
        _at, _have, n, fold = stood
        if not n:
            req.last_progress_tick = self._tick
            m.on_block_pass(blk)
            return
        req.pending -= n
        if not picks[-1]:
            # as a poisoned decode row: fail this request alone
            self._finish(req, RequestStatus.FAILED, now)
            return
        if _samples(req):
            rows = np.array(logits[req.slot, :n])
            m.on_fetch(rows.nbytes)
            rows[:, self._mask_id] = -np.inf
            at = len(req.generated)
            toks = [next_token(row, req.sampling, at + i)
                    for i, row in enumerate(rows)]
        else:
            toks = [int(t) for t in picks[:n]]
        for fixed, tok in enumerate(toks, 1):
            self._emit(req, tok, now)
            if req.finished:
                break
        m.on_block_pass(self._pass_rows(fold), fixed,
                        committed=fold == _FOLD_BLOCK,
                        behind_prompt=fold == _FOLD_CHUNK)

    def _prefill_target(self, req: Request) -> int:
        """How many of ``cache_tokens`` are prefilled before decoding:
        all of them, or a block model's whole blocks (the rest share
        their block with the first tokens to come)."""
        n = len(req.prompt) + len(req.generated)
        return n if self._block is None else n // self._block * self._block

    def _advance_chunk(self, req: Request, start: int, n: int) -> None:
        """A prefill chunk's rows are in the cache: the materialized
        length moves past them, and with the last chunk the request
        leaves its prefill."""
        req.cache_len = start + n
        self.scheduler.note_prefill_progress(req, start)
        req.prefilling = req.cache_len < self._prefill_target(req)

    def _finish_chunk(self, req: Request, start: int, n: int,
                      finite: bool, now: float) -> bool:
        """What one prefill chunk that rode a step came to, read when
        its words arrive (the materialized length moved at the
        dispatch): guard, then index the newly-completed full pages.
        ``finite``: whether the chunk-final row's logits were.  They
        attend over every K/V written so far, so finiteness transitively
        vouches for the whole chain, and every chunk's goes through the
        guard BEFORE its full pages are indexed: without the per-chunk
        check, suspect K/V from an overflowing prompt would be hittable
        for the whole multi-tick prefill window, and a sharer admitted
        in that window would stitch it before the final-chunk rollback
        ran.  True where the chunk was the prompt's last and its final
        row yields the first token (never a block model's)."""
        toks = req.cache_tokens
        self.metrics.on_prefill(n)
        req.last_progress_tick = self._tick   # chunks are progress too
        if not finite:
            if self.cache is not None:
                # roll back entries ONLY for pages the FAILING chunk
                # wrote (from the pre-chunk position onward): earlier
                # chunks passed their own finite guard and their cached
                # pages may already be stitched by a concurrent sharer —
                # forgetting them would route them into the FAILED scrub
                # below and zero-wipe K/V the sharer is reading
                self.cache.forget(
                    req.pages[start // self.kv_cfg.page_size:])
            req.prefilling = False
            self._finish(req, RequestStatus.FAILED, now)
            return False
        if self.cache is not None:
            # newly-completed FULL pages — now finite-vouched — become
            # hittable immediately, so even a preempted or mid-prefill
            # prompt re-prefills cheaply.  The chain cursor makes each
            # chunk's insert O(chunk), not O(prefix-so-far).
            req.chain_hash, req.chain_blocks = self.cache.insert(
                toks, req.pages, start + n,
                from_block=req.chain_blocks, prev_hash=req.chain_hash,
                tenant=req.tenant)
        # (a later chunk of this prompt may be in the air already:
        # ``req.prefilling`` is the dispatch's, so ask the lengths)
        return self._block is None and \
            start + n >= self._prefill_target(req)

    def _emit(self, req: Request, tok: int, now: float) -> None:
        req.generated.append(tok)
        req.last_progress_tick = self._tick
        ttft = None
        if req.first_token_at is None:
            req.first_token_at = now
            ttft = max(0.0, now - (req.submitted_at
                                   if req.submitted_at is not None else now))
            self._observe_stage("prefill", now - (
                req.admitted_at if req.admitted_at is not None else now))
            self._tracer.instant("first_token", rid=req.rid, slot=req.slot)
        self.metrics.on_token(now, ttft)
        if req.on_token is not None:
            req.on_token(tok)
            if req.finished:
                return   # the callback cancelled this request: keep it
        if tok == self.eos_id or len(req.generated) >= req.max_tokens:
            self._results[req.rid] = list(req.generated)
            self._finish(req, RequestStatus.COMPLETED, now)
