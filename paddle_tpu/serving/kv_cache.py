"""Block-paged KV cache for the serving engine.

The TPU-native analog of vLLM-style paged KV storage (Ragged Paged
Attention, arXiv 2604.15464): instead of one contiguous per-sequence
[max_len, H, D] buffer, K/V live in a preallocated pool of fixed-size
pages ``[num_pages, page_size, H, D]`` (one pool slice per layer).  Each
sequence owns an ordered list of page ids — its *page table* — and grows
one page at a time, so HBM is shared at page granularity across
concurrently-decoding requests with zero fragmentation beyond the last
partial page.

**Stored layout.**  On the device a page's heads are MERGED into its
lanes: ``[L, num_pages, page_size, H * D]``, KV head ``h`` at lanes
``[h * D, (h + 1) * D)`` (:class:`KVPages`).  That is the tile the
ragged kernel DMAs, so the serving step hands the kernel the pool's own
leaves and a layer index — no per-layer slice, no re-tiling copy.  The
published ``[..., H, D]`` shape lives on at the boundaries: rows come in
as ``[B, H, D]`` (:func:`append_token`), whole pages leave and enter as
``[L, n, page, H, D]`` (:func:`read_pages` / :func:`write_pages`), and
the non-kernel read paths take one layer's ``[pages, page, H, D]`` view
from :func:`layer_pages`.

Split of responsibilities:

- **Device side** (pure functions, jit-safe): ``append_token`` scatters
  new K/V into pages, ``gather_kv`` linearizes a page table back into a
  contiguous view (the oracle/fallback path).
  These take page ids and offsets as *arrays*, so one jit specialization
  serves every allocation pattern.
- **Host side**: :class:`PagePool` is the free list.  Allocation is a
  scheduling decision (admission control, growth, preemption), so it
  stays in python — the device never sees the free list, only page
  tables.

Page 0 is **reserved as the null page**: masked writes (prompt padding,
inactive decode slots) are steered to it instead of being predicated
out, which keeps every scatter dense and shape-stable under jit.  No
live sequence is ever granted page 0.

**Kinds of layer state.**  What a layer keeps of a live sequence is
its KIND's business; there are three, read off the model
(:func:`layer_kinds`, :func:`recurrent_state`), under ONE byte budget
(:func:`split_pool_bytes`: the kinds bound to a slot take what their
bound needs, the free list gets the rest):

1. PAGES (every model): every token of the sequence, in pages from the
   one free list, granted at admission and growth, freed (or shared,
   forked, cached, rolled back, migrated) by page, for as long as the
   sequence lives.  A model whose layers all attend over everything and
   keep nothing else has this kind alone and builds exactly the pool it
   always built (the pool then holds every layer).  The pool's layer
   axis counts CACHE layers: a looped model (``model.loops = T > 1``: its
   weight layers run ``T`` times a tick over the same parameters) keeps
   pass ``t``'s K/V of weight layer ``l`` in cache layer ``t * layers +
   l``, read by pass ``t`` alone, so its pool has ``T x layers`` of them,
   a token and a page cost ``T`` times the K/V, and ``pool_bytes ->
   pages``, admission, growth, preemption and the accounting after a
   drain all read that one number (:attr:`PagedKVConfig.num_layers`).
2. A RING of pages a slot (``model.layer_window(l)``; exclusive with 1 in
   a layer): a layer with a WINDOW attends over the last ``window`` tokens
   only, so its kind keeps no more than those, the chunk in flight and
   page rounding: a :class:`WindowRing` gives every slot a ring of ``R =
   ceil((window + rows - 1) / page) + 1`` pages of its own (``rows`` the
   most rows a slot brings in one step: :func:`window_pages`).  THE RULE:
   page ``a`` of a sequence (positions ``[a * page, (a + 1) * page)``)
   lives at entry ``a mod R`` of its slot's ring; when the sequence writes
   position ``p`` it overwrites what page ``p // page - R`` left there,
   which by then lies below every window that can still be asked for.  So
   a page that falls out of the window is released WHILE the sequence
   lives and used again by the same slot's later positions; nothing is
   allocated at admission, growth cannot fail, and preemption,
   cancellation and completion return the ring with the slot.  Each
   window kind has device arrays of its own (``[layers of the kind, 1 +
   slots * R, page, H_kv * D]``: its layers only).
3. A RECURRENT STATE a slot (``model.layer_state(l)``; BESIDE 1 or 2 in
   the same layer): a constant-size state whatever the sequence's length
   (a state-space branch's ``[heads, lanes, state]`` and its
   convolution's last inputs), one array a layer and leaf, ``[slots,
   ...]`` (:class:`RecurrentState`).  Its lifetime is the slot's: the
   compiled step starts a sequence's first row (position 0: admission, or
   re-prefill after preemption) from zeros whatever the slot held, every
   later row reads what the sequence's last row wrote, and a slot
   without a row in a step keeps its state bit for bit.  Nothing is
   allocated, freed, forked or rolled back, and nothing is cleared on the
   host.

Automatic prefix caching (round 9): pages are **refcounted** — a page
shared by N sequences is freed only when the last holder unrefs it —
and a host-side :class:`PrefixCache` indexes *full* pages by chained
token-block hashes, so a new prompt can be split into
``cached_prefix_pages + tail`` and skip re-forwarding the prefix
entirely (arXiv 2603.09555: the cache, not the kernel, is where serving
latency is won).  Cached pages at refcount 0 stay out of the free list
as a reclaimable pool; LRU eviction returns them under pressure.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import (Callable, Dict, List, Optional, Sequence,
                    Set, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.platform.enforce import enforce_that

NULL_PAGE = 0


_QMAX = 127.0        # symmetric int8 range; -128 is never produced
_KV_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
              "int8": jnp.int8}


def resolve_kv_dtype(name):
    """Map a ``kv_dtype=`` ("float32" | "bfloat16" | "int8", or an
    explicit dtype) to a jnp dtype."""
    if isinstance(name, str):
        enforce_that(name in _KV_DTYPES,
                     f"kv_dtype must be one of {sorted(_KV_DTYPES)},"
                     f" got {name!r}", context="serving")
        return _KV_DTYPES[name]
    return jnp.dtype(name)


# A page's tokens where the engine is told none: 128 is the TPU's lane
# width, so a page's K/V tile feeds the MXU without padding (tests and
# small models pass a smaller ``page_size``).  And the pool's pages where
# it is given neither ``num_pages`` nor ``pool_bytes``; its HBM cost is
# 2 * layers * pages * page_size * kv_heads * head_dim * dtype bytes.
PAGE_SIZE = 128
NUM_PAGES = 512


@dataclass(frozen=True)
class PagedKVConfig:
    """Static geometry of one kind's paged pool (shared by all the
    kind's layers: page id ``p`` addresses the kind's ``l``-th layer's
    slice ``k[l, p]`` for every l; a model with no window layer has the
    one kind, and ``num_layers`` is the model's; a looped model's is its
    CACHE layers, ``loops x`` its weight layers: the module doc).
    ``num_heads`` is what
    the GQA check below reads: the fewest query heads any layer brings
    (a layer's own count is its ``q``'s).

    ``num_kv_heads`` (None = ``num_heads``) is the GQA knob: the pool
    stores K/V for the KV heads only, and the ragged attention kernel
    packs each group of ``num_heads // num_kv_heads`` query heads
    against one K/V load.  ``dtype=jnp.int8`` turns on quantized pages:
    every write stores amax/127-scaled int8 values plus a per-token,
    per-kv-head f32 scale (see :func:`quantize_kv`), read back by
    dequantizing in-register — roughly quartering bytes per page.

    ``tp`` (default 1) is the tensor-parallel degree: the pool's KV-head
    dim shards over the ``model`` mesh axis, so each chip physically
    holds ``kv_heads / tp`` heads of every page — and every byte
    accounting here (:meth:`bytes_per_page`, :meth:`kv_bytes`, hence
    :func:`pages_for_budget`) is PER CHIP.  The int8 scale arrays shard
    with their KV heads, so they divide by ``tp`` too."""

    num_layers: int
    num_heads: int
    head_dim: int
    page_size: int
    num_pages: int           # includes the reserved null page 0
    max_pages_per_seq: int   # page-table width (static decode grid bound)
    dtype: jnp.dtype = jnp.float32
    num_kv_heads: Optional[int] = None   # None = MHA (== num_heads)
    tp: int = 1              # model-axis shards of the KV-head dim

    def __post_init__(self):
        enforce_that(self.num_pages >= 2,
                     "need at least one usable page beyond the null page",
                     context="serving")
        enforce_that(self.page_size >= 1 and self.max_pages_per_seq >= 1,
                     "page_size and max_pages_per_seq must be positive",
                     context="serving")
        enforce_that(self.num_heads % self.kv_heads == 0,
                     f"num_kv_heads ({self.kv_heads}) must divide "
                     f"num_heads ({self.num_heads})", context="serving")
        enforce_that(self.tp >= 1, "tp must be >= 1", context="serving")
        enforce_that(self.kv_heads % self.tp == 0,
                     f"tensor parallelism tp={self.tp} must divide "
                     f"num_kv_heads ({self.kv_heads}): the paged pool "
                     "shards whole KV heads over the model axis, so each "
                     "chip must own an integer number of them — pick a "
                     f"tp that divides {self.kv_heads}, or a model with "
                     "more KV heads", context="serving")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads if self.num_kv_heads else self.num_heads

    @property
    def q_heads_per_group(self) -> int:
        return self.num_heads // self.kv_heads

    @property
    def quantized(self) -> bool:
        return jnp.dtype(self.dtype) == jnp.int8

    @property
    def max_seq_len(self) -> int:
        return self.page_size * self.max_pages_per_seq

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1  # page 0 is the null page

    def bytes_per_page(self) -> int:
        """K + V bytes ONE page costs PER CHIP across all layers, scale
        arrays included — the unit the pool-byte budget is charged in.
        Under tensor parallelism (``tp > 1``) each chip holds only its
        ``kv_heads / tp`` shard of every page (scales ride with their
        heads), so the same per-chip budget buys ``tp`` x the pages —
        the per-chip capacity arithmetic the TP serving plan banks on."""
        heads_per_chip = self.kv_heads // self.tp
        per = (self.num_layers * self.page_size * heads_per_chip *
               self.head_dim * jnp.dtype(self.dtype).itemsize)
        if self.quantized:
            # per-token, per-kv-head f32 scales ride with the page
            per += self.num_layers * self.page_size * heads_per_chip * 4
        return 2 * per

    def kv_bytes(self) -> int:
        """Whole-pool bytes PER CHIP (the number HBM budgets care
        about; multiply by ``tp`` for the global pool)."""
        return self.num_pages * self.bytes_per_page()


def pages_for_budget(pool_bytes: int, num_layers: int, num_heads: int,
                     head_dim: int, page_size: int, dtype,
                     num_kv_heads: Optional[int] = None,
                     tp: int = 1) -> int:
    """Total ``num_pages`` (null page included) that fit in a PER-CHIP
    pool byte budget — the knob that makes int8 pages *mean* something:
    the same ``pool_bytes`` admits ~2x the pages of bf16 and ~4x of f32
    (minus the scale-array overhead), and under ``tp``-way tensor
    parallelism ``tp`` x the pages again (each chip stores 1/tp of every
    page's KV heads, scale arrays sharded with them).  The scheduler
    charges admission in pages, so capacity gains flow straight into
    admissible concurrency and prefix-cache headroom."""
    probe = PagedKVConfig(num_layers=num_layers, num_heads=num_heads,
                          head_dim=head_dim, page_size=page_size,
                          num_pages=2, max_pages_per_seq=1,
                          dtype=resolve_kv_dtype(dtype),
                          num_kv_heads=num_kv_heads, tp=int(tp))
    return max(2, int(pool_bytes) // probe.bytes_per_page())


@dataclass(frozen=True)
class LayerKind:
    """The layers of a model that keep the same state of a live sequence:
    ``window`` None for full attention (pages from the free list, the
    first kind), else the number of most recent tokens a layer of the
    kind attends over (a :class:`WindowRing`).  ``layers`` are the
    model's layer indices, ascending: the kind's ``i``-th layer is layer
    ``i`` of its device arrays."""

    window: Optional[int]
    layers: Tuple[int, ...]


def layer_kinds(model) -> Tuple[LayerKind, ...]:
    """The model's layers by kind, the full-attention kind first (with
    every layer, for a model that has no ``layer_window``), then one kind
    a distinct window, ascending."""
    n = int(model.num_layers)
    window_of = getattr(model, "layer_window", None)
    windows = [None if window_of is None else window_of(l) for l in range(n)]
    for w in windows:
        enforce_that(w is None or int(w) >= 1,
                     f"a layer's window must be positive, got {w!r}",
                     context="serving")
    kinds = [LayerKind(None, tuple(l for l in range(n)
                                   if windows[l] is None))]
    for w in sorted({int(w) for w in windows if w is not None}):
        kinds.append(LayerKind(w, tuple(l for l in range(n)
                                        if windows[l] == w)))
    return tuple(kinds)


def window_pages(window: int, rows: int, page: int, width: int) -> int:
    """How many pages ``rows`` consecutive positions can see between them
    under ``window``: those that positions ``[p - window + 1, p + rows)``
    can touch, for any ``p`` (never more than the table's ``width``).
    With a step's most rows of one sequence it is the length of a
    :class:`WindowRing`, with a resident row block's the most visits one
    sequence can have in it in the windowed kernel's walk."""
    return min(int(width), (int(window) + max(1, int(rows)) - 2) // page + 2)


@dataclass(frozen=True)
class WindowRing:
    """The state of one window kind (the module doc has the rule): every
    slot owns ``ring_pages`` pages for good, ``table[s]`` lists them, and
    page ``a`` of the slot's sequence lives at ``table[s, a mod
    ring_pages]``.  ``cfg`` is the geometry of the kind's device arrays
    (its layers only; ``max_pages_per_seq`` is the ring's length)."""

    window: int
    slots: int
    cfg: PagedKVConfig

    @property
    def ring_pages(self) -> int:
        return self.cfg.max_pages_per_seq

    def table(self) -> np.ndarray:
        """``[slots, ring_pages]`` int32, fixed for the engine's life
        (page 0 stays the null page)."""
        r = self.ring_pages
        return (1 + np.arange(self.slots * r, dtype=np.int32)
                ).reshape(self.slots, r)

    def released(self, n_tokens) -> "np.ndarray":
        """How many of its first pages a sequence of ``n_tokens`` cached
        tokens no longer needs in this kind: those wholly below the window
        of the next position (``n_tokens``), and of every later one."""
        return np.maximum(0, np.asarray(n_tokens) - self.window + 1) \
            // self.cfg.page_size

    def tokens_held(self, n_tokens) -> "np.ndarray":
        """Tokens of a sequence that the kind still keeps, a layer."""
        n = np.asarray(n_tokens)
        return n - self.released(n) * self.cfg.page_size

    def bytes_per_slot(self) -> int:
        return self.ring_pages * self.cfg.bytes_per_page()

    def kv_bytes(self) -> int:
        """The kind's device arrays, the null page included."""
        return self.cfg.kv_bytes()



def make_window_ring(kind: LayerKind, *, slots: int, rows: int,
                     num_heads: int, num_kv_heads: int, head_dim: int,
                     page_size: int, max_pages_per_seq: int, dtype
                     ) -> WindowRing:
    r = window_pages(kind.window, rows, page_size, max_pages_per_seq)
    return WindowRing(int(kind.window), int(slots), PagedKVConfig(
        num_layers=len(kind.layers), num_heads=num_heads, head_dim=head_dim,
        page_size=page_size, num_pages=1 + slots * r, max_pages_per_seq=r,
        dtype=resolve_kv_dtype(dtype), num_kv_heads=num_kv_heads))


@dataclass(frozen=True)
class RecurrentState:
    """The recurrent kind (the module doc): ``layers`` are the model's
    layers that keep one, ``leaves`` ``((name, shape, dtype name), ...)``
    what ONE slot keeps in ONE of them, the same in each."""

    layers: Tuple[int, ...]
    slots: int
    leaves: Tuple[Tuple[str, Tuple[int, ...], str], ...]

    def bytes_per_slot(self) -> int:
        """A slot's state over all the kind's layers."""
        return len(self.layers) * sum(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize
            for _, shape, dtype in self.leaves)

    def kv_bytes(self) -> int:
        """The kind's device arrays."""
        return self.slots * self.bytes_per_slot()

    def init(self) -> Tuple[Dict[str, jax.Array], ...]:
        """Zeros: for each of the kind's layers ``{leaf: [slots, ...]}``.
        One array a layer and leaf, not one stacked over the layers: the
        step reads and writes a layer's WHOLE array, which as a slab of a
        stacked one would be copied out and back."""
        return tuple({name: jnp.zeros((self.slots,) + shape, dtype)
                      for name, shape, dtype in self.leaves}
                     for _ in self.layers)


def recurrent_state(model, slots: int) -> Optional[RecurrentState]:
    """The model's recurrent kind: the layers whose ``layer_state(l)`` is
    not None, ascending.  None for a model without the member (or without
    such a layer)."""
    state_of = getattr(model, "layer_state", None)
    if state_of is None:
        return None
    found = {l: state_of(l) for l in range(int(model.num_layers))}
    layers = tuple(l for l, leaves in found.items() if leaves is not None)
    if not layers:
        return None
    norm = lambda leaves: tuple(  # noqa: E731
        (str(name), tuple(int(n) for n in shape), jnp.dtype(dtype).name)
        for name, (shape, dtype) in sorted(leaves.items()))
    leaves = norm(found[layers[0]])
    for l in layers[1:]:
        enforce_that(norm(found[l]) == leaves,
                     f"layer {l} keeps another recurrent state than layer "
                     f"{layers[0]}: one kind holds one shape of state",
                     context="serving")
    return RecurrentState(layers, int(slots), leaves)


def split_pool_bytes(pool_bytes: int, rings: Sequence[WindowRing],
                     recurrent: Optional[RecurrentState] = None) -> int:
    """ONE byte budget over the kinds: the recurrent states and the rings
    take what their bound needs (they cannot run with less), the
    full-attention free list gets what is left, which is returned."""
    state = recurrent.kv_bytes() if recurrent is not None else 0
    left = int(pool_bytes) - state - sum(r.kv_bytes() for r in rings)
    held = (f"the {recurrent.slots} slots' recurrent states ({state} "
            "bytes) and " if recurrent is not None else "")
    enforce_that(left > 0,
                 f"pool_bytes ({pool_bytes}) does not hold {held}the window "
                 f"layers' rings ({[r.kv_bytes() for r in rings]} bytes): "
                 "give the pool more, or fewer slots", context="serving")
    return left


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "k_scale", "v_scale"),
                   meta_fields=("head_dim",))
@dataclass(frozen=True)
class KVPages:
    """The device-resident pool, STORED in the tile layout the ragged
    kernel reads: ``k``/``v`` are
    [num_layers, num_pages, page_size, num_kv_heads * head_dim], KV head
    ``h`` at lanes ``[h * head_dim, (h + 1) * head_dim)`` of a token's
    row.  On a TPU the last two dims are the tiled ones, so a
    ``(page, hb * head_dim)`` block of this array is what the kernel
    DMAs as it stands, and merging the two LEADING dims (``[L * pages,
    ...]``, how the kernel addresses a layer) is free — where a
    ``[..., H_kv, D] -> [..., H_kv * D]`` reshape of the old 5-d pool
    was a re-tiling copy of every layer on every tick.  With int8
    pages, ``k_scale``/``v_scale`` are the matching per-token,
    per-kv-head f32 scales [num_layers, num_pages, page_size,
    num_kv_heads]; None for float pools (the two layouts share every
    code path through ``is-None`` checks that resolve at trace time).

    ``head_dim`` is static metadata (not a leaf): it is what splits the
    merged dim back into ``[H_kv, D]`` wherever the published shape is
    wanted (:func:`layer_pages`, :func:`read_pages`)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    head_dim: int = field(kw_only=True)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def kv_heads(self) -> int:
        return self.k.shape[-1] // self.head_dim


def layer_pages(kv: KVPages, layer):
    """One layer of the pool in the published shape: ``(k, v, k_scale,
    v_scale)`` with k/v ``[num_pages, page_size, H_kv, D]`` (scales
    ``[num_pages, page_size, H_kv]`` or None).  THE one place the
    non-kernel read paths (the reference attention, :func:`gather_kv`,
    the draft model's step) get a layer from: on the CPU the view is
    free; on a TPU it is the slice and re-tiling copy the kernel path
    exists to avoid, so nothing a chip serves with goes through here."""
    shape = kv.k.shape[1:3] + (kv.kv_heads, kv.head_dim)
    if kv.quantized:
        return (kv.k[layer].reshape(shape), kv.v[layer].reshape(shape),
                kv.k_scale[layer], kv.v_scale[layer])
    return kv.k[layer].reshape(shape), kv.v[layer].reshape(shape), None, None


def init_kv_pages(cfg: PagedKVConfig, mesh=None, axis: str = "model"
                  ) -> KVPages:
    """Allocate the pool in its stored layout ``[L, pages, page,
    H_kv * D]``.  With a ``mesh``, every leaf is placed with its merged
    head dim sharded over ``axis`` (see :func:`kv_pool_specs`): a chip's
    shard is the lanes of its ``H_kv / TP`` heads, ``[L, pages, page,
    (H_kv / TP) * D]``, from tick zero — the scatters of the serving
    step keep it there (they index dims 0-2 only)."""
    shape = (cfg.num_layers, cfg.num_pages, cfg.page_size,
             cfg.kv_heads * cfg.head_dim)
    # allocate every leaf ALREADY sharded: a pool sized per chip is tp x
    # that in total, and staging it whole on device 0 first could not fit
    sh = None if mesh is None else kv_pool_sharding(mesh, axis)
    if cfg.quantized:
        scales = shape[:-1] + (cfg.kv_heads,)
        return KVPages(jnp.zeros(shape, jnp.int8, device=sh),
                       jnp.zeros(shape, jnp.int8, device=sh),
                       jnp.zeros(scales, jnp.float32, device=sh),
                       jnp.zeros(scales, jnp.float32, device=sh),
                       head_dim=cfg.head_dim)
    return KVPages(jnp.zeros(shape, cfg.dtype, device=sh),
                   jnp.zeros(shape, cfg.dtype, device=sh),
                   head_dim=cfg.head_dim)


def kv_pool_specs(axis: str = "model") -> Tuple[Optional[str], ...]:
    """THE canonical pool layout, as one PartitionSpec covering every
    :class:`KVPages` leaf: all are 4-d with the heads at position 3 —
    ``k``/``v`` as the merged ``H_kv * D`` lanes, head-major, so an
    even split over ``axis`` gives each chip the lanes of its own
    ``H_kv / TP`` heads and no other's; the scale arrays as ``H_kv``
    itself — so ``(None, None, None, axis)`` shards exactly the heads
    of each.  Single source of
    truth — the TP :class:`~paddle_tpu.analysis.retrace.SiteContract`s
    declare it for the pool argument/outputs, :func:`init_kv_pages`
    places with it, and the engine's per-tick output constraint
    re-asserts it — so the donated-in/aliased-out layout cannot drift
    between the three."""
    return (None, None, None, axis)


def kv_pool_sharding(mesh, axis: str = "model"):
    """:func:`kv_pool_specs` as a ``NamedSharding`` (one object serves
    every pool leaf: unspecified trailing dims are replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(*kv_pool_specs(axis)))


def quantize_kv(x: jax.Array):
    """Symmetric per-token, per-head int8 quantization of K/V rows.

    x: [..., D] float.  Returns ``(q, scale)`` with ``q`` int8 [..., D]
    and ``scale`` f32 [...] such that ``q * scale`` reconstructs x to
    within one quantization step of amax/127.  All-zero rows quantize
    to (0, tiny) — dequant is exactly 0 either way, and the scale never
    divides by zero."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-20) / _QMAX
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Exact inverse read of :func:`quantize_kv`'s stored form — the ONE
    dequant rule the kernel, the gather fallback, and the parity oracle
    all share, so they can never disagree on what an int8 page means."""
    return q.astype(jnp.float32) * scale[..., None]


def append_token(kv: KVPages, layer: int, k_new: jax.Array, v_new: jax.Array,
                 page_ids: jax.Array, offsets: jax.Array) -> KVPages:
    """Scatter one K/V row per ragged batch row into its page.

    k_new/v_new: [B, H_kv, D]; page_ids/offsets: [B] int32 (inactive
    rows pass page_ids == NULL_PAGE — duplicates on the null page are
    fine, nothing reads it).  A row lands as the ``H_kv * D`` lanes of
    the stored layout at ``[layer, page, offset]`` (merging a row's
    heads is a reshape of ``B`` rows, not of the pool).  Quantized
    pools quantize on write, per head, BEFORE the merge (the scale
    lands at the same [layer, page, offset, head] address).
    Pure; returns the updated pool.

    This is also the MULTI-TOKEN scatter of the speculative verify
    step: a slot speculating ``k`` tokens contributes ``k+1``
    consecutive rows (positions ``cache_len .. cache_len+k``, possibly
    spanning a page boundary — see :func:`pages_spanned`), all written
    in the one dispatch.  Rollback after a partial acceptance is
    host-side: the rejected positions' K/V stays as finite junk beyond
    the new length, masked away by the ``token <= position`` attention
    inequality until the real tokens overwrite it, while the lookahead
    PAGES past the length return to the pool
    (``scheduler.rollback_pages`` — rollback-to-length)."""
    rows = (k_new.shape[0], kv.k.shape[-1])
    scales = {}
    if kv.quantized:
        k_new, ks = quantize_kv(k_new)
        v_new, vs = quantize_kv(v_new)
        scales = dict(
            k_scale=kv.k_scale.at[layer, page_ids, offsets].set(ks),
            v_scale=kv.v_scale.at[layer, page_ids, offsets].set(vs))
    return dataclasses.replace(
        kv,
        k=kv.k.at[layer, page_ids, offsets].set(
            k_new.astype(kv.k.dtype).reshape(rows)),
        v=kv.v.at[layer, page_ids, offsets].set(
            v_new.astype(kv.v.dtype).reshape(rows)),
        **scales)


def pages_spanned(start: int, count: int, page_size: int) -> range:
    """Page-table INDICES a write of ``count`` consecutive token
    positions starting at ``start`` touches (empty for ``count <= 0``).
    The one arithmetic the engine's verify-time COW guard and its tests
    share: every spanned page that is cached or refcount-shared must be
    forked before a speculative branch may write into it, so a rejected
    branch can never dirty pages another holder reads."""
    if count <= 0:
        return range(0)
    return range(start // page_size, (start + count - 1) // page_size + 1)


def zero_pages(kv: KVPages, page_ids: jax.Array) -> KVPages:
    """Zero whole pages across every layer (failed-request scrub).

    page_ids: [n] int32.  A prompt that overflows to non-finite values
    leaves inf/NaN K/V in the pages it wrote; freed and re-granted,
    those stale values would poison the NEXT owner through masked
    attention reads (softmax weight 0 times inf is NaN).  Scrubbing on
    the failure path keeps the pool finite-by-construction.  (int8
    pools can't store non-finite VALUES, but their scale arrays can —
    both are scrubbed.)"""
    return jax.tree.map(
        lambda a: a.at[:, page_ids].set(jnp.zeros((), a.dtype)), kv)


def fork_page(kv: KVPages, src: jax.Array, dst: jax.Array) -> KVPages:
    """Copy one page's K/V (and scales) across every layer — the
    copy-on-write fork.

    src/dst: scalar int32 page ids.  The forked page becomes a private
    replica of a shared cached page, so a sequence whose tail must write
    into the last shared page of its prefix does so without corrupting
    the other holders.  Pure; returns the updated pool."""
    return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), kv)


def read_pages(kv: KVPages, page_ids: Sequence[int]):
    """Pull whole pages to the host as STORED values — the export half
    of the page-migration plane (``serving/migrate.py``).

    page_ids: n page ids.  Returns ``(k, v, k_scale, v_scale)`` numpy
    arrays, k/v in the PUBLISHED shape [L, n, page, H_kv, D] (the
    stored lanes split back into heads on the host, where a reshape is
    free) in the pool dtype and the scales [L, n, page, H_kv] f32 (None
    for float pools).  int8 pages are NOT dequantized: migration moves
    the quantized bytes plus their scales verbatim, so the destination
    reads bit-identical K/V and the transfer costs ~1/4 the f32
    bytes."""
    ids = jnp.asarray(list(page_ids), jnp.int32)
    heads = (kv.kv_heads, kv.head_dim)
    k = np.asarray(kv.k[:, ids])
    v = np.asarray(kv.v[:, ids])
    k = k.reshape(k.shape[:-1] + heads)
    v = v.reshape(v.shape[:-1] + heads)
    if kv.quantized:
        return (k, v, np.asarray(kv.k_scale[:, ids]),
                np.asarray(kv.v_scale[:, ids]))
    return k, v, None, None


def write_pages(kv: KVPages, page_ids: jax.Array, k: jax.Array,
                v: jax.Array, k_scale: Optional[jax.Array] = None,
                v_scale: Optional[jax.Array] = None) -> KVPages:
    """Splice whole pages into the pool — the import half of the
    migration plane, shape-compatible with :func:`read_pages` output
    (k/v [L, n, page, H_kv, D]; merged into the stored lanes here, a
    reshape of the n imported pages only).

    page_ids: [n] int32 destination ids (pad rows with NULL_PAGE and
    zero payload: nothing reads the null page, so padded writes keep
    the jitted import ladder shape-stable).  Stored values go in
    verbatim — no re-quantization — so an exported int8 page arrives
    bit-identical, scales included.  Pure; returns the updated pool."""
    lanes = k.shape[:3] + (kv.k.shape[-1],)
    scales = {}
    if kv.quantized:
        scales = dict(
            k_scale=kv.k_scale.at[:, page_ids].set(
                k_scale.astype(jnp.float32)),
            v_scale=kv.v_scale.at[:, page_ids].set(
                v_scale.astype(jnp.float32)))
    return dataclasses.replace(
        kv,
        k=kv.k.at[:, page_ids].set(k.astype(kv.k.dtype).reshape(lanes)),
        v=kv.v.at[:, page_ids].set(v.astype(kv.v.dtype).reshape(lanes)),
        **scales)


def gather_kv(kv: KVPages, layer: int, page_table: jax.Array):
    """Linearize page tables into contiguous K/V.

    page_table: [B, max_pages_per_seq] int32.  Returns (k, v) each
    [B, max_pages_per_seq * page_size, H_kv, D] — positions beyond a
    sequence's length hold whatever the referenced pages contain
    (callers mask by length; this is the oracle/fallback read path).
    Quantized pools are dequantized here with the shared
    :func:`dequantize_kv` rule, so the fallback reads the SAME stored
    values the kernel does and parity stays pinned."""
    kl, vl, ksl, vsl = layer_pages(kv, layer)
    b, pm = page_table.shape
    _, page, h, d = kl.shape
    k = kl[page_table]
    v = vl[page_table]
    if kv.quantized:
        k = dequantize_kv(k, ksl[page_table])
        v = dequantize_kv(v, vsl[page_table])
    return (k.reshape(b, pm * page, h, d), v.reshape(b, pm * page, h, d))


@dataclass
class PagePool:
    """Host-side refcounted allocator over page ids 1..num_pages-1 (0 is
    the null page).  Allocation is all-or-nothing so admission control
    can't partially strand a request.

    Every non-free page carries a refcount: ``alloc`` grants pages at
    refcount 1, ``ref`` adds a holder (prefix sharing), ``free`` drops
    one — the page returns to the free list only at refcount 0, and not
    even then if a :class:`PrefixCache` has registered it (``mark_cached``):
    cached pages at refcount 0 are *reclaimable*, parked for future
    prefix hits until ``release_cached`` (LRU eviction) returns them.

    The free list is LIFO over ascending ids (recently-freed pages are
    re-granted first, keeping the working set compact) and mirrored by a
    set, so the double-free guard is O(1) instead of an O(pages) list
    scan on every free."""

    num_pages: int
    # obs hook: the engine binds its (enabled) tracer here so page
    # custody changes land on the request timeline; None = tracing off,
    # one is-None check per pool call (never per page)
    tracer: Optional[object] = field(default=None, repr=False, compare=False)
    _free: List[int] = field(default_factory=list)
    _free_set: Set[int] = field(default_factory=set)
    _refs: Dict[int, int] = field(default_factory=dict)
    _cached: Set[int] = field(default_factory=set)

    def __post_init__(self):
        enforce_that(self.num_pages >= 2, "pool needs >= 2 pages",
                     context="serving")
        self._free = list(range(self.num_pages - 1, NULL_PAGE, -1))
        self._free_set = set(self._free)
        self._refs = {}
        self._cached = set()

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_usable(self) -> int:
        return self.num_pages - 1

    @property
    def num_in_use(self) -> int:
        """Pages not on the free list: live (refcount > 0) plus cached
        pages parked at refcount 0."""
        return len(self._refs)

    @property
    def num_live(self) -> int:
        """Pages held by at least one sequence (or the fault plan)."""
        return sum(1 for c in self._refs.values() if c > 0)

    @property
    def num_cached(self) -> int:
        """Pages registered by a PrefixCache (any refcount)."""
        return len(self._cached)

    @property
    def num_reclaimable(self) -> int:
        """Cached pages at refcount 0 — evictable under pressure."""
        return sum(1 for p in self._cached if self._refs[p] == 0)

    @property
    def total_refs(self) -> int:
        """Sum of all refcounts — must equal the holders' page-list
        lengths summed (the REF-LEAK conservation invariant)."""
        return sum(self._refs.values())

    def occupancy(self) -> float:
        return self.num_in_use / max(1, self.num_usable)

    def refcount(self, p: int) -> int:
        return self._refs.get(p, 0)

    def is_cached(self, p: int) -> bool:
        return p in self._cached

    def alloc(self, n: int) -> Optional[List[int]]:
        """Grant ``n`` pages at refcount 1 each, or None (and no change)
        if fewer are free.  Reclaimable cached pages are NOT granted
        here — evict them first (``PrefixCache.evict``)."""
        if n < 0 or n > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n)]
        for p in got:
            self._free_set.discard(p)
            self._refs[p] = 1
        if self.tracer is not None and got:
            self.tracer.instant("page_alloc", cat="pages", n=len(got),
                                pages=tuple(got))
        return got

    def ref(self, pages: Sequence[int]) -> None:
        """Add one holder to each page (a prefix-cache hit sharing them
        with a new sequence).  Pages must be in use or cached."""
        for p in pages:
            enforce_that(p in self._refs, f"ref of free page {p}",
                         context="serving")
            self._refs[p] += 1
        if self.tracer is not None and pages:
            self.tracer.instant("page_ref", cat="pages", n=len(pages),
                                pages=tuple(pages))

    def free(self, pages: Sequence[int]) -> None:
        """Drop one holder per page (unref).  A page reaches the free
        list only at refcount 0, and stays parked (reclaimable) instead
        if a PrefixCache holds it."""
        for p in pages:
            enforce_that(p != NULL_PAGE, "cannot free the null page",
                         context="serving")
            enforce_that(p not in self._free_set,
                         f"double free of page {p}", context="serving")
            enforce_that(self._refs.get(p, 0) > 0,
                         f"free of unreferenced page {p}", context="serving")
            self._refs[p] -= 1
            if self._refs[p] == 0 and p not in self._cached:
                del self._refs[p]
                self._free.append(p)
                self._free_set.add(p)
        if self.tracer is not None and pages:
            self.tracer.instant("page_free", cat="pages", n=len(pages),
                                pages=tuple(pages))

    def mark_cached(self, p: int) -> None:
        """Register a (non-free) page as prefix-cache-held: at refcount
        0 it parks as reclaimable instead of returning to the free
        list."""
        enforce_that(p in self._refs, f"cannot cache free page {p}",
                     context="serving")
        self._cached.add(p)

    def unmark_cached(self, p: int) -> None:
        """Withdraw a page's cache registration (failed-prefill
        rollback).  A page already parked at refcount 0 is freed on the
        spot — nothing holds it and nothing can hit it anymore."""
        if p not in self._cached:
            return
        self._cached.discard(p)
        if self._refs.get(p, 0) == 0:
            del self._refs[p]
            self._free.append(p)
            self._free_set.add(p)

    def release_cached(self, p: int) -> None:
        """Eviction: return a refcount-0 cached page to the free list."""
        enforce_that(p in self._cached, f"page {p} is not cached",
                     context="serving")
        enforce_that(self._refs.get(p, 0) == 0,
                     f"evicting page {p} with live holders",
                     context="serving")
        self._cached.discard(p)
        del self._refs[p]
        self._free.append(p)
        self._free_set.add(p)
        if self.tracer is not None:
            self.tracer.instant("page_evict", cat="pages", page=p)


# ---------------------------------------------------------------------------
# Automatic prefix caching: host-side index over full pages
# ---------------------------------------------------------------------------

_CHAIN_SEED = 0x9E3779B9   # any fixed non-zero start for the hash chain


def _chain_hash(prev: int, block: Tuple[int, ...]) -> int:
    """Default chained block hash: each full page's key commits to every
    token before it via the previous link.  Python's tuple hash over
    ints is deterministic within and across processes (int hashing is
    not seed-randomized), which is all the index needs — collisions are
    verified away by token comparison, never trusted."""
    return hash((prev, block))


def prefix_chain_hashes(tokens: Sequence[int], page_size: int,
                        hash_fn: Optional[Callable[[int, Tuple[int, ...]],
                                                   int]] = None) -> List[int]:
    """The :class:`PrefixCache` key chain of ``tokens``: one chained
    hash per FULL page block, ``h_j = hash(h_{j-1}, block_j)`` from
    :data:`_CHAIN_SEED` — exactly the keys ``lookup``/``insert`` walk.
    Exposed so the fleet router (``serving/fleet.py``) routes by the
    SAME function the cache indexes with: two prompts that would share
    cached pages produce a common chain prefix by construction, so
    affinity routing and cache hits can never disagree on what "same
    prefix" means."""
    hf = hash_fn or _chain_hash
    page = int(page_size)
    h = _CHAIN_SEED
    out: List[int] = []
    for j in range(len(tokens) // page):
        h = hf(h, tuple(tokens[j * page:(j + 1) * page]))
        out.append(h)
    return out


@dataclass
class _CacheEntry:
    page: int                 # the page holding this block's K/V
    tokens: Tuple[int, ...]   # the block itself (collision verification)
    prev: int                 # parent link hash (chain verification)
    tenant: Optional[str] = None   # who prefilled it (host-tier billing)


class PrefixCache:
    """Hash-chained index over *full* KV pages for automatic prefix
    caching.

    Key design points:

    - only FULL pages are indexed: a partial page is still being
      appended to by its owner, so it can never be safely shared;
    - keys are chained (``h_j = hash(h_{j-1}, block_j)``), so a hit on
      page j implies the whole prefix up to j matched — the index acts
      as a radix tree flattened into a hash map;
    - every hit is VERIFIED by comparing the stored block tokens and
      parent link, so a hash collision (including fault-injected
      degenerate hashes) degrades to a miss, never to corruption;
    - entries are LRU-ordered; :meth:`evict` frees refcount-0 pages
      oldest-first under pool pressure.  Evicting a mid-chain entry
      orphans its descendants (unreachable, evicted later by the same
      LRU sweep) — safe, just conservative.

    The cache does NOT hold refcounts of its own: a cached page with no
    sequence holders parks at refcount 0 inside the :class:`PagePool`
    (reclaimable) rather than returning to the free list."""

    def __init__(self, pool: PagePool, page_size: int,
                 hash_fn: Optional[Callable[[int, Tuple[int, ...]], int]]
                 = None):
        enforce_that(page_size >= 1, "page_size must be positive",
                     context="serving")
        self.pool = pool
        self.page_size = int(page_size)
        self._hash = hash_fn or _chain_hash
        self.tracer = None     # obs hook, bound by the engine (see pool)
        self._index: "OrderedDict[int, _CacheEntry]" = OrderedDict()
        self.hits = 0          # lookups that matched >= 1 page (healthz)
        self.misses = 0        # lookups that matched none (healthz)
        self.evictions = 0     # pages evicted (LRU or storm)
        # hierarchical spill (round 21): when the engine binds a
        # HostPageTier plus a page reader (device pages -> stored host
        # bytes), eviction DEMOTES instead of destroying — the victim's
        # K/V is staged into the host tier before the device page
        # returns to the free list
        self.host_tier: Optional["HostPageTier"] = None
        self.page_reader: Optional[Callable[[Sequence[int]], tuple]] = None

    def __len__(self) -> int:
        return len(self._index)

    def chain_keys(self, tokens: Sequence[int]) -> List[int]:
        """Every full block's chained key under THIS cache's hash
        (fault-injected overrides included) — what the host-tier
        swap-in walks to continue a lookup past the device index."""
        return prefix_chain_hashes(tokens, self.page_size, self._hash)

    def lookup(self, tokens: Sequence[int],
               touch: bool = False) -> Tuple[List[int], int]:
        """Longest verified cached prefix of ``tokens`` in full pages.

        Returns ``(pages, hit_len)`` with ``hit_len = len(pages) *
        page_size``.  Does NOT take references — the caller refs the
        pages it actually stitches (all-or-nothing with its allocation),
        so a failed admission leaves no state behind.

        ``touch=False`` (the default) is a PURE read: no LRU reorder, no
        hit/miss counting.  The scheduler probes every admission attempt
        — a head-of-line request blocked on pages re-probes every tick,
        and counting those would inflate the stats and churn eviction
        order for zero actual stitches.  It re-calls with ``touch=True``
        exactly once, when the admission commits."""
        page = self.page_size
        pages: List[int] = []
        h = _CHAIN_SEED
        for j in range(len(tokens) // page):
            block = tuple(tokens[j * page:(j + 1) * page])
            key = self._hash(h, block)
            e = self._index.get(key)
            if e is None or e.tokens != block or e.prev != h:
                break          # miss or verified-away collision
            if touch:
                self._index.move_to_end(key)
            pages.append(e.page)
            h = key
        if touch:
            if pages:
                self.hits += 1
            else:
                self.misses += 1
        return pages, len(pages) * page

    def insert(self, tokens: Sequence[int], pages: Sequence[int],
               upto: int, from_block: int = 0,
               prev_hash: Optional[int] = None,
               tenant: Optional[str] = None) -> Tuple[int, int]:
        """Index the full pages covering ``tokens[:upto]`` (page j of
        the sequence lives in ``pages[j]``).  Idempotent — re-inserting
        a chunk already indexed is a no-op, and an existing entry always
        wins (a concurrent identical prefill keeps its private copy).

        ``from_block``/``prev_hash`` resume the hash chain at a block
        boundary, so a chunked prefill indexes each chunk in O(chunk)
        instead of re-hashing the whole prefix per chunk (quadratic in
        prompt length on the tick hot path).  Returns ``(chain_hash,
        blocks_done)`` for the caller to pass back on its next chunk."""
        page = self.page_size
        h = _CHAIN_SEED if prev_hash is None else prev_hash
        nblocks = min(upto, len(tokens)) // page
        for j in range(from_block, nblocks):
            block = tuple(tokens[j * page:(j + 1) * page])
            key = self._hash(h, block)
            e = self._index.get(key)
            if e is None:
                self._index[key] = _CacheEntry(page=int(pages[j]),
                                               tokens=block, prev=h,
                                               tenant=tenant)
                self.pool.mark_cached(int(pages[j]))
            h = key
        return h, max(from_block, nblocks)

    def forget(self, pages: Sequence[int]) -> int:
        """Drop every index entry whose page is in ``pages`` (they stay
        with their holder; once unref'd they go straight to the free
        list instead of parking).  A prefill that fails the finite-
        logits guard calls this so its (possibly NaN-laden) K/V can
        never be stitched into a later request — without it, one
        overflowing prompt would poison every future request sharing
        the prefix."""
        ps = {int(p) for p in pages}
        dropped = 0
        for key in [k for k, e in self._index.items() if e.page in ps]:
            e = self._index.pop(key)
            self.pool.unmark_cached(e.page)
            dropped += 1
        return dropped

    def evict(self, n: int) -> int:
        """Evict up to ``n`` refcount-0 cached pages, LRU first; returns
        how many were actually freed.  Pages with live holders are
        skipped (their entries stay — they are still hittable)."""
        if n <= 0:
            return 0
        freed = 0
        spill = (self.host_tier is not None and
                 self.page_reader is not None)
        for key in list(self._index):
            if freed >= n:
                break
            e = self._index[key]
            if self.pool.refcount(e.page) == 0:
                if spill:
                    # demotion, not destruction: stage the victim's
                    # stored bytes into the host tier before the device
                    # page is reclaimed (depth-one writer — this commits
                    # the PREVIOUS pending spill, stages this one)
                    payload = self.page_reader([e.page])
                    self.host_tier.spill(key, e.prev, e.tokens, payload,
                                         tenant=e.tenant)
                del self._index[key]
                self.pool.release_cached(e.page)
                self.evictions += 1
                freed += 1
        if self.tracer is not None and freed:
            self.tracer.instant("cache_evict", cat="pages", n=freed)
        return freed

    def flush(self) -> int:
        """Evict every reclaimable page (the fault plan's eviction
        storm; also useful for tests).  Entries with live holders
        survive."""
        return self.evict(len(self._index))


# ---------------------------------------------------------------------------
# Hierarchical host tier (round 21): spilled pages live in host RAM,
# checksummed, until a prefix hit swaps them back in
# ---------------------------------------------------------------------------


def page_checksum(k, v, k_scale=None, v_scale=None) -> int:
    """CRC32 chained over a page's STORED bytes plus its scale arrays —
    the one integrity rule the spill writer, the swap-in verifier, and
    the warm-restart adopter share.  Computed over the bytes the writer
    INTENDED to store, so a torn commit or a flipped bit can never
    verify."""
    c = zlib.crc32(np.ascontiguousarray(k).tobytes())
    c = zlib.crc32(np.ascontiguousarray(v).tobytes(), c)
    if k_scale is not None:
        c = zlib.crc32(np.ascontiguousarray(k_scale).tobytes(), c)
        c = zlib.crc32(np.ascontiguousarray(v_scale).tobytes(), c)
    return c


@dataclass
class _HostPage:
    """One spilled page: the prefix-cache chain identity (key / prev /
    tokens — so the host index IS the same radix chain, resumable after
    the device entry is gone) plus the stored payload and its checksum."""

    key: int
    prev: int
    tokens: Tuple[int, ...]
    k: "np.ndarray"                    # [L, 1, page, H_kv, D] stored dtype
    v: "np.ndarray"
    k_scale: Optional["np.ndarray"]    # [L, 1, page, H_kv] f32, or None
    v_scale: Optional["np.ndarray"]
    checksum: int
    nbytes: int
    seq: int                           # spill sequence (fault addressing)
    tenant: Optional[str] = None


class HostPageTier:
    """The host-RAM spill tier under the device :class:`PagePool`.

    Evicted RECLAIMABLE pages demote here instead of being destroyed
    (``PrefixCache.evict`` stages them), keyed by the SAME chained block
    hash the device index uses — so a later lookup that runs off the end
    of its device hits can continue the walk in host memory and swap the
    continuation back in, verified, instead of re-prefilling it.

    Write path — the depth-one pipelined writer from
    ``resilience/checkpointer.py``, tick-deterministic (no threads, no
    wall clock): ``spill`` first commits the previously staged page
    (wait-out-previous), then stages the new one; the engine's per-tick
    ``pump`` commits the staged page unless a fault plan declares a
    slow-host-I/O window for that tick (counted as
    ``spill_stall_ticks``); ``flush`` commits unconditionally (drain,
    handoff).  Fault hooks mutate the payload AT COMMIT — after the
    checksum was taken over the intended bytes — so a torn write or a
    seeded bit flip is exactly what the verifier later catches.

    Capacity is a byte budget.  With ``dtype='int8'`` float payloads are
    transcoded to int8 + per-token scales on spill (the "engine owns the
    memory format" lever: the host tier holds ~4x the pages of the f32
    device pool for the same bytes, at quantization fidelity); with the
    default ``'stored'`` the device bytes are kept verbatim, so swap-in
    is bit-identical.  When the budget is exceeded the tier LRU-drops —
    the third rung of the degradation ladder, after device eviction and
    before shed/preempt.

    Conservation (``HOSTTIER-LEAK``): every page that ever entered the
    tier ends in exactly one state —

        spills + adopted == resident + swap_ins + dropped + corrupt
                            + handed_off + pending

    checked by :meth:`check`, which the engine folds into
    ``check_page_conservation`` (pages conserve across device, host,
    and dropped)."""

    def __init__(self, capacity_bytes: int, dtype: str = "stored",
                 faults=None, tracer=None):
        enforce_that(dtype in ("stored", "int8"),
                     "host_kv_dtype must be 'stored' or 'int8', "
                     f"got {dtype!r}", context="serving")
        self.capacity_bytes = int(capacity_bytes)
        self.dtype = dtype
        self.faults = faults
        self.tracer = tracer
        # single-threaded by design: the engine tick loop is the only
        # writer (spill/pump/flush/swap-in), and warm-restart adopt()
        # runs before the successor engine starts ticking — the tier
        # needs no lock, just confinement to its owning engine
        # guarded_by(serialized: engine tick loop owns the tier)
        self._index: "OrderedDict[int, _HostPage]" = OrderedDict()
        # guarded_by(serialized: engine tick loop owns the tier)
        self._pending: Optional[_HostPage] = None
        # guarded_by(serialized: engine tick loop owns the tier)
        self._seq = 0
        # guarded_by(serialized: engine tick loop owns the tier)
        self.resident_bytes = 0
        # guarded_by(serialized: engine tick loop owns the tier)
        self.resident_by_tenant: Dict[str, int] = {}
        # ledger counters (see class docstring for the invariant)
        self.spills = 0            # pages ever staged (swap_outs gauge)
        self.swap_ins = 0          # verified pages promoted back to device
        self.dropped = 0           # LRU-dropped / forgotten / displaced
        self.corrupt = 0           # checksum failures (NEVER served)
        self.handed_off = 0        # adopted away by a successor tier
        self.adopted = 0           # records taken FROM predecessors
        self.restored = 0          # of those, verified + resident here
        self.spill_stall_ticks = 0  # pump ticks lost to slow host I/O

    def __len__(self) -> int:
        return len(self._index)

    # ---- write path (depth-one pipelined) --------------------------------

    def spill(self, key: int, prev: int, tokens: Sequence[int], payload,
              tenant: Optional[str] = None) -> None:
        """Stage one evicted page (``payload`` is ``read_pages`` output
        for a single page).  Commits any previously staged page first —
        at most one spill is ever in flight, and the tick path never
        waits on more than that one commit."""
        if self._pending is not None:
            self._commit(self._pending)
            self._pending = None
        k, v, ks, vs = payload
        k = np.array(k)
        v = np.array(v)
        ks = None if ks is None else np.array(ks, np.float32)
        vs = None if vs is None else np.array(vs, np.float32)
        if self.dtype == "int8" and ks is None:
            # transcode-on-spill: host holds int8 + f32 scales (~4x the
            # f32 pages per byte); swap-in dequantizes back
            kq, ks = quantize_kv(jnp.asarray(k, jnp.float32))
            vq, vs = quantize_kv(jnp.asarray(v, jnp.float32))
            k, v = np.array(kq), np.array(vq)
            ks, vs = np.array(ks, np.float32), np.array(vs, np.float32)
        nbytes = k.nbytes + v.nbytes
        if ks is not None:
            nbytes += ks.nbytes + vs.nbytes
        seq = self._seq       # 0-based, like the migration drop schedule:
        self._seq += 1        # the fault plan's Nth spill is seq N
        self.spills += 1
        self._pending = _HostPage(
            key=int(key), prev=int(prev), tokens=tuple(tokens),
            k=k, v=v, k_scale=ks, v_scale=vs,
            checksum=page_checksum(k, v, ks, vs),
            nbytes=int(nbytes), seq=seq, tenant=tenant)
        if self.tracer is not None:
            self.tracer.instant("host_spill", cat="pages", seq=seq)

    def pump(self, tick: int) -> int:
        """Per-tick writer advance: commit the staged page, unless the
        fault plan has host I/O stalled this tick (the spill then rides
        along until the window ends — decode never waits on it)."""
        if self._pending is None:
            return 0
        if self.faults is not None and self.faults.host_io_stalled(tick):
            self.spill_stall_ticks += 1
            return 0
        self._commit(self._pending)
        self._pending = None
        return 1

    def flush(self) -> None:
        """Commit unconditionally (drain / handoff barrier)."""
        if self._pending is not None:
            self._commit(self._pending)
            self._pending = None

    def _commit(self, rec: _HostPage) -> None:
        f = self.faults
        if f is not None:
            if f.spill_is_torn(rec.seq):
                # torn commit: the tail half of V never lands.  The
                # checksum was taken over the intended bytes at stage
                # time, so verification catches this as corruption.
                flat = rec.v.reshape(-1).view(np.uint8)
                flat[flat.size // 2:] = 0
            off = f.spill_bitflip_offset(rec.seq, rec.k.nbytes)
            if off is not None:
                flat = rec.k.reshape(-1).view(np.uint8)
                flat[off % flat.size] ^= 0x40
        self._insert(rec)

    def _insert(self, rec: _HostPage) -> bool:
        if rec.key in self._index:
            # existing entry wins (same idempotence rule as the device
            # index) — the duplicate is accounted as dropped
            self.dropped += 1
            return False
        if rec.nbytes > self.capacity_bytes:
            self.dropped += 1
            return False
        while self.resident_bytes + rec.nbytes > self.capacity_bytes:
            # ladder rung 3: host tier full -> LRU-drop host pages
            self._pop_lru()
        self._index[rec.key] = rec
        self.resident_bytes += rec.nbytes
        if rec.tenant is not None:
            self.resident_by_tenant[rec.tenant] = \
                self.resident_by_tenant.get(rec.tenant, 0) + 1
        return True

    def _pop(self, key: int) -> _HostPage:
        rec = self._index.pop(key)
        self.resident_bytes -= rec.nbytes
        if rec.tenant is not None:
            n = self.resident_by_tenant.get(rec.tenant, 0) - 1
            if n > 0:
                self.resident_by_tenant[rec.tenant] = n
            else:
                self.resident_by_tenant.pop(rec.tenant, None)
        return rec

    def _pop_lru(self) -> None:
        key = next(iter(self._index))
        self._pop(key)
        self.dropped += 1
        if self.tracer is not None:
            self.tracer.instant("host_drop", cat="pages", key=key)

    # ---- read path (verified swap-in) ------------------------------------

    def peek(self, key: int, prev: int,
             block: Sequence[int]) -> Optional[_HostPage]:
        """Pure probe: the record for ``key`` if present AND its chain
        identity matches (same token/parent verification as the device
        index — a collision is a miss).  No checksum work, no removal;
        the scheduler uses this to size a swap-in before charging it."""
        rec = self._index.get(key)
        if rec is None or rec.prev != int(prev) or \
                rec.tokens != tuple(block):
            return None
        return rec

    def take_verified(self, key: int, prev: int,
                      block: Sequence[int]) -> Optional[_HostPage]:
        """Remove-and-return the record for ``key`` iff its chain
        identity matches AND its checksum verifies.  A mismatch pops the
        record, counts ``corrupt`` (the HOSTTIER-CORRUPT counter), and
        returns None — corruption degrades to a miss, never to a
        wrong-KV hit."""
        if self.peek(key, prev, block) is None:
            return None
        rec = self._pop(int(key))
        if page_checksum(rec.k, rec.v, rec.k_scale,
                         rec.v_scale) != rec.checksum:
            self.corrupt += 1
            if self.tracer is not None:
                self.tracer.instant("HOSTTIER-CORRUPT", cat="pages",
                                    key=int(key))
            return None
        self.swap_ins += 1
        return rec

    def forget(self, keys: Sequence[int]) -> int:
        """Drop records (and any matching staged spill) by chain key —
        the no-double-adopt rule: when a chain migrates to another
        replica, the source's host copies are forgotten so the pages
        can never be re-adopted from two places."""
        n = 0
        for key in list(keys):
            key = int(key)
            if self._pending is not None and self._pending.key == key:
                self._pending = None
                self.dropped += 1
                n += 1
            if key in self._index:
                self._pop(key)
                self.dropped += 1
                n += 1
        return n

    # ---- crash-warm restart ----------------------------------------------

    def adopt(self, other: "HostPageTier") -> int:
        """Take every record from a predecessor tier (warm restart: the
        host tier outlives the engine).  Each record is re-verified
        against its checksum before becoming hittable here — a record
        corrupted while orphaned counts ``corrupt`` on THIS tier and is
        never served.  The source ledger stays balanced via
        ``handed_off``.  Returns how many records were restored."""
        other.flush()
        restored = 0
        # reaching into the predecessor's confined state is the POINT
        # of adopt(): the old engine is already stopped at handoff, so
        # its tier has no concurrent owner left
        # lint: allow(guarded-by)
        for key in list(other._index):
            rec = other._pop(key)
            other.handed_off += 1
            self.adopted += 1
            if page_checksum(rec.k, rec.v, rec.k_scale,
                             rec.v_scale) != rec.checksum:
                self.corrupt += 1
                if self.tracer is not None:
                    self.tracer.instant("HOSTTIER-CORRUPT", cat="pages",
                                        key=int(key))
                continue
            if self._insert(rec):
                self.restored += 1
                restored += 1
        return restored

    # ---- conservation + scrape -------------------------------------------

    def check(self) -> None:
        """The HOSTTIER-LEAK invariant (valid at any tick, not just at
        drain): every page that entered the tier is in exactly one of
        resident / swapped-in / dropped / corrupt / handed-off /
        pending, and resident bytes match the index under the budget."""
        from paddle_tpu.serving.faults import PageLeakError

        pend = 1 if self._pending is not None else 0
        lhs = self.spills + self.adopted
        rhs = (len(self._index) + self.swap_ins + self.dropped +
               self.corrupt + self.handed_off + pend)
        if lhs != rhs:
            raise PageLeakError(
                f"HOSTTIER-LEAK: spills({self.spills}) + "
                f"adopted({self.adopted}) != resident({len(self._index)})"
                f" + swap_ins({self.swap_ins}) + dropped({self.dropped})"
                f" + corrupt({self.corrupt}) + "
                f"handed_off({self.handed_off}) + pending({pend})")
        nb = sum(r.nbytes for r in self._index.values())
        if nb != self.resident_bytes or nb > self.capacity_bytes:
            raise PageLeakError(
                f"HOSTTIER-LEAK: resident bytes ledger {self.resident_bytes}"
                f" vs actual {nb} (capacity {self.capacity_bytes})")

    def snapshot(self) -> Dict[str, int]:
        """Host-tier gauges, merged into the engine's scrape surface."""
        return {
            "pages_host": len(self._index),
            "host_swap_ins": self.swap_ins,
            "host_swap_outs": self.spills,
            "host_corrupt": self.corrupt,
            "host_dropped": self.dropped,
            "host_restored": self.restored,
            "host_resident_bytes": self.resident_bytes,
            "spill_stall_ticks": self.spill_stall_ticks,
        }


# ---------------------------------------------------------------------------
# standalone gate: `python -m paddle_tpu.serving.kv_cache check`
# ---------------------------------------------------------------------------


def _selfcheck() -> int:
    """Replay a seeded hierarchical-tier trace — the tier-1 ladder's
    HOSTTIER gate (tools_tier1.sh exit 13).  Two phases:

    1. single engine: a clean spill/swap-in round-trip must be
       token-identical to a cold re-prefill, and an injected torn spill
       plus a seeded bit-flip must BOTH be caught by the checksum at
       swap-in (degrading to a miss) — a corrupt page served would show
       up as a parity break;
    2. small fleet: kill a replica whose host tier holds spilled pages,
       ``restart_replica`` it, and the warm successor must re-adopt
       >= 1 verified page and serve the same prompt token-identically
       with zero duplicate completions.

    Returns 0 (clean) or 1 (findings); a crash propagates as 2."""
    import jax
    import numpy as np

    from paddle_tpu.serving.engine import DecoderLM, ServingEngine
    from paddle_tpu.serving.faults import (FaultPlan, FleetFaultPlan,
                                           ManualClock)

    model = DecoderLM(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=64)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompt = rng.randint(2, 64, size=16).tolist()   # 4 full pages
    problems = []

    def mk_engine(**faults_kw):
        plan = FaultPlan(seed=0, clock=ManualClock(tick_s=0.01),
                         **faults_kw)
        return ServingEngine(model, params, eos_id=1, page_size=4,
                             num_pages=16, max_pages_per_seq=8,
                             max_slots=2, buckets=(8, 16), faults=plan,
                             host_tier_bytes=1 << 20, swap_in_budget=4)

    def roundtrip(eng):
        """cold serve -> flush (spill) -> warm serve; returns (cold,
        warm) token lists from the SAME engine (rids are globally
        numbered, so cross-engine comparison must go by order)."""
        r1 = eng.submit(list(prompt), max_tokens=6)
        eng.run()
        cold = eng.result(r1)
        eng.cache.flush()
        r2 = eng.submit(list(prompt), max_tokens=6)
        eng.run()
        return cold, eng.result(r2)

    # phase 1a: clean round trip — the tier must actually serve
    eng = mk_engine()
    cold, warm = roundtrip(eng)
    snap = eng.host_tier.snapshot()
    if warm != cold:
        problems.append(f"clean swap-in parity break: {warm} != {cold}")
    if snap["host_swap_ins"] < 1 or eng._host_hits < 1:
        problems.append(f"clean round trip never hit the host tier: {snap}")
    clean_swapins = snap["host_swap_ins"]
    eng.check_page_conservation()

    # phase 1b/1c: torn spill, then seeded bit-flip — each must be
    # caught at swap-in (miss + HOSTTIER-CORRUPT), never served
    for kw, name in (({"torn_spill_at": {0}}, "torn"),
                     ({"bitflip_spill_at": {0}}, "bitflip")):
        eng = mk_engine(**kw)
        cold, warm = roundtrip(eng)
        snap = eng.host_tier.snapshot()
        if warm != cold:
            problems.append(f"{name}: corrupt page SERVED "
                            f"(parity break {warm} != {cold})")
        if snap["host_corrupt"] < 1:
            problems.append(f"{name}: checksum missed the corruption "
                            f"({snap})")
        eng.check_page_conservation()

    # phase 2: crash-warm restart in a fleet
    plan = FleetFaultPlan(seed=0, clock=ManualClock(tick_s=0.01))

    def mk(i, time_fn):
        return ServingEngine(model, params, eos_id=1, page_size=4,
                             num_pages=32, max_pages_per_seq=8,
                             max_slots=4, buckets=(8, 16), time_fn=time_fn,
                             host_tier_bytes=1 << 20, swap_in_budget=4)

    from paddle_tpu.serving.fleet import FleetRouter

    fleet = FleetRouter(mk, 2, heartbeat_s=0.05, resubmit_budget=2,
                        faults=plan)
    f1 = fleet.submit(list(prompt), max_tokens=6)
    fleet.run(max_ticks=200)
    cold = fleet.result(f1)
    victim = next(r.idx for r in fleet.replicas
                  if r.engine.cache is not None and len(r.engine.cache))
    fleet.replicas[victim].engine.cache.flush()
    fleet.kill_replica(victim)
    new_idx = fleet.restart_replica(victim)
    fleet.drain_replica(1 - victim)
    for _ in range(5):
        fleet.step()
    f2 = fleet.submit(list(prompt), max_tokens=6)
    fleet.run(max_ticks=200)
    warm = fleet.result(f2)
    restored = fleet.metrics.pages_restored
    if warm != cold:
        problems.append(f"warm-restart parity break: {warm} != {cold}")
    if restored < 1:
        problems.append("warm restart adopted 0 pages")
    if fleet.metrics.duplicate_completions:
        problems.append(f"{fleet.metrics.duplicate_completions} duplicate "
                        "completions after warm restart")
    fleet.check_fleet_conservation()

    if problems:
        print("HOSTTIER: " + "; ".join(problems))
        return 1
    print(f"kv-cache check ok: clean swap-in x{clean_swapins} "
          "token-identical to cold prefill, torn + bit-flip spills both "
          f"caught at swap-in (0 corrupt pages served), warm restart "
          f"re-adopted {restored} page(s) with 0 duplicate completions, "
          "0 leaks")
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI dispatch, importable so callers (tools_tier1.sh) can run the
    gate via ``python -c "...kv_cache.main(['check'])"`` — ``python -m``
    would have runpy execute a SECOND copy of this module alongside the
    one ``paddle_tpu.serving`` already imported."""
    import sys

    args = list(sys.argv[1:] if argv is None else argv)
    cmd = args[0] if args else "check"
    if cmd != "check":
        print(f"unknown command {cmd!r}; usage: "
              "python -m paddle_tpu.serving.kv_cache check")
        return 2
    from paddle_tpu.serving.faults import PageLeakError

    try:
        return _selfcheck()
    except PageLeakError as e:
        print(str(e))
        return 1
    except Exception as e:   # crash != findings: distinct exit code
        print(f"kv-cache check crashed: {e!r}")
        return 2


if __name__ == "__main__":
    import sys

    sys.exit(main())
