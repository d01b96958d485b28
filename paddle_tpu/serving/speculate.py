"""Speculative decoding + real sampling for the serving engine.

Decode dominates chatty serving cost because every emitted token pays a
full fused-step dispatch.  Speculation multiplies tokens per dispatch:
a cheap *proposer* drafts ``k`` candidate tokens per running slot, the
target model scores all ``k+1`` positions in ONE widened unified step
(speculative slots contribute ``k+1`` verify rows instead of 1 — the
exact ragged shape the v2 kernel already consumes for prefill chunks),
and the engine accepts the longest agreeing prefix plus one bonus
token, rolling the rejected suffix back.  Every tick still emits at
least one token, so speculation can slow nothing down besides the
proposer's own (cheap) cost.

Two proposers, selected by ``ServingEngine(spec_mode=)``:

- :class:`NGramProposer` — prompt lookup: match the last ``n`` tokens
  of the slot's own prompt+output history against earlier occurrences
  and propose what followed.  Zero extra model cost; strong on
  repetitive/chatty traffic (quotes, code, templated replies).
- :class:`DraftProposer` — a small :class:`~engine.DecodeModel` with
  its OWN paged KV pool (same ``KVPages``/``PagePool`` machinery as
  the engine, conservation-checked the same way).  Per tick it first
  teacher-forces any history it has not yet materialized (chunked,
  bucketed rows), then drafts ``k`` tokens autoregressively; after the
  verify it rolls its state back to the accepted history.

Acceptance semantics:

- **greedy** (the default, ``sampling=None``): a draft is accepted iff
  it equals the target's argmax at its position — the emitted stream is
  token-identical to non-speculative greedy decoding by construction
  (a rejected position emits the target's own argmax; full acceptance
  emits the bonus argmax).
- **sampled** (:class:`SamplingParams` with ``temperature > 0``):
  standard speculative rejection sampling — accept draft ``d`` with
  probability ``min(1, p(d)/q(d))`` against the *warped* (temperature/
  top-k/top-p) target distribution ``p`` and proposal ``q`` (a point
  mass for the n-gram proposer), else emit a sample from the residual
  ``max(p - q, 0)`` — so the emitted distribution equals plain
  sampling from the target.  All randomness is drawn from counter-based
  per-(seed, position) RNG streams, so replays are bit-identical on
  the injected clock and resubmitted requests re-emit the same tokens
  regardless of how speculation regrouped the ticks.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu.platform.enforce import enforce_that

__all__ = ["SamplingParams", "NGramProposer", "DraftProposer",
           "next_token", "accept_tokens", "warp_probs", "position_rng"]


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------

# RNG stream ids: one MT19937 stream per (seed, token position, role),
# so every draw is a pure function of request seed + emitted-token
# index — replays, preemption re-prefills and fleet resubmits all
# re-derive identical draws without carrying RNG state.
_STREAM_ACCEPT = 0      # accept/residual/bonus draws (the emission side)
_STREAM_DRAFT = 1       # the draft model's own proposal draws


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling policy.  ``temperature <= 0`` is greedy
    (argmax — the engine default and the parity-test contract);
    ``top_k``/``top_p`` restrict the warped support (0 / 1.0 = off).
    ``seed`` keys the per-position RNG streams: two replays of the same
    request emit bit-identical tokens."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        enforce_that(self.temperature >= 0.0,
                     "temperature must be >= 0", context="serving-spec")
        enforce_that(self.top_k >= 0, "top_k must be >= 0",
                     context="serving-spec")
        enforce_that(0.0 < self.top_p <= 1.0,
                     "top_p must be in (0, 1]", context="serving-spec")

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def position_rng(seed: int, position: int, stream: int = _STREAM_ACCEPT
                 ) -> np.random.RandomState:
    """Counter-based RNG: one deterministic stream per (seed, position,
    stream).  MT19937's init_by_array seeding makes this a pure
    function of its arguments — no state is carried across tokens, so
    the draw for emitted-token ``position`` is identical whether the
    token arrived speculatively, non-speculatively, or on a replay
    after a preemption or fleet resubmit."""
    return np.random.RandomState(
        [int(seed) & 0xFFFFFFFF, int(position) & 0xFFFFFFFF,
         0x5BEC0DE ^ int(stream)])


def warp_probs(logits: np.ndarray, s: SamplingParams) -> np.ndarray:
    """The warped target/proposal distribution: temperature, then
    top-k, then nucleus (top-p) truncation, renormalized.  f64
    throughout so two replays (and the accept-vs-residual arithmetic)
    cannot diverge on rounding."""
    z = np.asarray(logits, np.float64)
    z = z / max(float(s.temperature), 1e-6)
    z = z - z.max()
    p = np.exp(z)
    p /= p.sum()
    if s.top_k and s.top_k < p.size:
        cut = np.partition(p, -s.top_k)[-s.top_k]
        p = np.where(p >= cut, p, 0.0)
    if s.top_p < 1.0:
        order = np.argsort(-p, kind="stable")
        csum = np.cumsum(p[order])
        # keep the smallest prefix reaching top_p (always >= 1 token)
        keep_n = int(np.searchsorted(csum, s.top_p, side="left")) + 1
        mask = np.zeros_like(p)
        mask[order[:keep_n]] = 1.0
        p = p * mask
    tot = p.sum()
    if tot <= 0.0:              # degenerate logits: fall back to argmax
        p = np.zeros_like(p)
        p[int(np.argmax(logits))] = 1.0
        return p
    return p / tot


def _draw(probs: np.ndarray, rng: np.random.RandomState) -> int:
    csum = np.cumsum(probs)
    u = rng.random_sample() * csum[-1]
    return int(min(np.searchsorted(csum, u, side="right"),
                   probs.size - 1))


def next_token(logits: np.ndarray, sampling: Optional[SamplingParams],
               position: int) -> int:
    """One non-speculative emission: argmax when greedy (``sampling``
    None or temperature 0 — bit-identical to the historical engine
    behavior), else a seeded draw from the warped distribution.
    ``position`` is the index of this token in the request's generated
    stream (the RNG counter)."""
    if sampling is None or sampling.greedy:
        return int(np.argmax(logits))
    rng = position_rng(sampling.seed, position)
    return _draw(warp_probs(logits, sampling), rng)


def accept_tokens(rows: np.ndarray, drafts: Sequence[int],
                  draft_probs: Optional[np.ndarray],
                  sampling: Optional[SamplingParams],
                  position: int, eos_id: int) -> Tuple[List[int], int]:
    """The verify walk: score ``drafts`` against the target logits and
    return ``(emitted tokens, accepted draft count)``.

    ``rows`` is ``[len(drafts) + 1, V]`` — row ``i`` is the target's
    logits after history + ``drafts[:i]`` (row 0 is the plain next-token
    distribution, so with no drafts this degenerates to exactly one
    non-speculative emission).  ``draft_probs`` is the proposer's warped
    distribution per draft (``[k, V]``) or None for a point-mass
    proposer (n-gram, or any greedy draft).  ``position`` indexes the
    first emitted token in the request's generated stream.

    Greedy: accept while ``argmax(rows[i]) == drafts[i]``; the first
    disagreement emits the target's own argmax instead, full agreement
    emits the bonus ``argmax(rows[k])`` — token-identical to the
    non-speculative stream by induction.  Sampled: standard rejection
    sampling (accept w.p. ``min(1, p/q)``, residual ``max(p − q, 0)``
    renormalized, bonus sampled from ``rows[k]``), which preserves the
    target distribution exactly.  An accepted/emitted EOS ends the walk
    (nothing is emitted past it)."""
    emitted: List[int] = []
    greedy = sampling is None or sampling.greedy
    for i, d in enumerate(drafts):
        d = int(d)
        if greedy:
            g = int(np.argmax(rows[i]))
            if g != d:
                emitted.append(g)          # rejection: the target's token
                return emitted, i
        else:
            p = warp_probs(rows[i], sampling)
            if draft_probs is not None:
                # a HOST numpy row (the proposer already synced it), so
                # this asarray is a dtype view, never a device readback
                q = np.asarray(draft_probs[i], np.float64)  # lint: allow(host-sync)
            else:
                q = np.zeros(p.shape, np.float64)
                q[d] = 1.0
            rng = position_rng(sampling.seed, position + i)
            ratio = 0.0 if q[d] <= 0.0 else min(1.0, p[d] / q[d])
            if rng.random_sample() >= ratio:
                resid = np.maximum(p - q, 0.0)
                tot = resid.sum()
                # numerically-empty residual (p ~= q): any p-sample is
                # distribution-correct
                emitted.append(_draw(resid / tot if tot > 0.0 else p, rng))
                return emitted, i
        emitted.append(d)
        if d == eos_id:
            return emitted, i + 1          # accepted EOS: no bonus token
    # every draft accepted: one bonus token from the last row
    k = len(drafts)
    if greedy:
        emitted.append(int(np.argmax(rows[k])))
    else:
        rng = position_rng(sampling.seed, position + k)
        emitted.append(_draw(warp_probs(rows[k], sampling), rng))
    return emitted, k


# ---------------------------------------------------------------------------
# Proposers
# ---------------------------------------------------------------------------


class Proposer:
    """Structural proposer contract the engine drives.  ``propose``
    returns ``{rid: (drafts, warped proposal probs or None)}`` for the
    eligible requests; ``commit``/``release``/``check_conservation``
    are state hooks only the draft-model proposer needs."""

    def propose(self, requests, k_for) -> Dict[int, Tuple[List[int],
                                                          Optional[np.ndarray]]]:
        raise NotImplementedError

    def commit(self, req) -> None:      # accepted history is now truth
        pass

    def release(self, rid: int) -> None:
        pass

    def check_conservation(self) -> None:
        pass


class NGramProposer(Proposer):
    """Prompt-lookup speculation: match the last ``n`` tokens of the
    slot's own prompt+output history against earlier occurrences (most
    recent match wins; falls back to shorter suffixes down to 1) and
    propose the ``k`` tokens that followed.  Zero model cost, so even a
    low acceptance rate is pure profit; repetitive traffic (the chatty
    serving shape) accepts most drafts."""

    def __init__(self, n: int = 3):
        self.n = int(n)
        enforce_that(self.n >= 1, "n-gram size must be >= 1",
                     context="serving-spec")

    def propose_one(self, history: Sequence[int], k: int) -> List[int]:
        h = list(history)
        ln = len(h)
        if k <= 0 or ln < 2:
            return []
        for size in range(min(self.n, ln - 1), 0, -1):
            tail = h[ln - size:]
            # most recent earlier occurrence WITH a full k-token
            # continuation wins (scan match ends backwards, stop at the
            # first full one); matches truncated by the history end —
            # ubiquitous inside repeated runs, where the nearest match
            # sits one period back — only win if nothing fuller exists
            best = None
            for end in range(ln - 1, size - 1, -1):
                if h[end - size:end] == tail:
                    cont = min(k, ln - end)
                    if best is None or cont > best[1]:
                        best = (end, cont)
                    if cont >= k:
                        break
            if best is not None:
                end, cont = best
                return h[end:end + cont]
            # no match at this size: try a shorter suffix
        return []

    def propose(self, requests, k_for):
        out = {}
        for req in requests:
            drafts = self.propose_one(req.cache_tokens, k_for(req))
            if drafts:
                out[req.rid] = (drafts, None)
        return out


@dataclass
class _DraftSeq:
    """Per-request draft-model cache state: ``tokens`` is the history
    whose KV is materialized in ``pages`` (positions 0..len-1)."""

    tokens: List[int]
    pages: List[int]


class DraftProposer(Proposer):
    """Draft-model speculation: a small :class:`DecodeModel` sharing
    the engine's page/pool machinery via its OWN ``KVPages`` pool.

    Per tick the engine hands it the running slots; for each it (1)
    teacher-forces any history tokens its cache has not materialized —
    batched across slots, chunked to a small row-bucket ladder so the
    jitted draft step compiles a bounded number of shapes — and (2)
    drafts ``k`` tokens autoregressively (greedy argmax, or seeded
    draws from its warped distribution when the request samples,
    returning the warped proposal rows for rejection sampling).  After
    the verify, :meth:`commit` rolls the state back to the accepted
    history (longest common prefix — accepted drafts stay materialized,
    rejected ones are overwritten next catch-up) and frees lookahead
    pages past it, so the draft pool obeys the same conservation
    arithmetic as the main pool (:meth:`check_conservation`)."""

    # catch-up row buckets per slot (rows beyond the top loop extra
    # dispatches); drafting itself always uses the 1-row shape
    CATCHUP_BUCKETS = (1, 8, 32, 128)

    def __init__(self, model, params, *, page_size: int, num_pages: int,
                 max_pages_per_seq: int, max_slots: int,
                 use_kernel: bool = False):
        from paddle_tpu.analysis.retrace import SiteContract, audit_jit
        from paddle_tpu.serving.kv_cache import PagedKVConfig, PagePool, \
            init_kv_pages

        self.model = model
        self.params = params
        self.cfg = PagedKVConfig(
            num_layers=model.num_layers, num_heads=model.num_heads,
            head_dim=model.head_dim, page_size=int(page_size),
            num_pages=int(num_pages),
            max_pages_per_seq=int(max_pages_per_seq),
            num_kv_heads=int(getattr(model, "num_kv_heads", 0)
                             or model.num_heads))
        self._kv = init_kv_pages(self.cfg)
        self.pool = PagePool(int(num_pages))
        self.max_slots = int(max_slots)
        self._use_kernel = bool(use_kernel)
        self._state: Dict[int, _DraftSeq] = {}
        self._fns: Dict[int, object] = {}
        self.steps = 0               # draft-model dispatches
        self.step_time_s = 0.0       # wall time inside draft dispatches
        # the draft pool is donated exactly like the engine's (the
        # returned pool overwrites self._kv every call); budgets are
        # generous guardrails like the engine's own
        self._contract = SiteContract(
            per_tick=True, donate=(1,),
            peak_bytes=4 * self.cfg.kv_bytes() + (1 << 26),
            flops=1e12)
        self._audit_jit = audit_jit

    # ---- compiled draft step --------------------------------------------

    def _fn(self, rows: int):
        fn = self._fns.get(rows)
        if fn is not None:
            return fn
        from paddle_tpu.serving.decode_attention import \
            ragged_paged_attention
        from paddle_tpu.serving.kv_cache import NULL_PAGE, append_token

        import jax.numpy as jnp

        model, cfg = self.model, self.cfg
        b, page, r = self.max_slots, cfg.page_size, int(rows)
        use_kernel = self._use_kernel or None

        def raw(params, kv, tokens, pos, valid, table, att_lens):
            # tokens/pos/valid: [B, R] slot-major rows; att_lens: [B]
            # valid KV per slot AFTER this step's writes.  Returns
            # logits for EVERY row ([B, R, V]) — catch-up reads only
            # each slot's last valid row, drafting reads row 0.
            t = tokens.reshape(-1)
            p = jnp.maximum(pos.reshape(-1), 0)
            v = valid.reshape(-1)
            seq = jnp.repeat(jnp.arange(b), r)
            x = model.embed(params, t, p)
            pages = jnp.where(v, table[seq, p // page], NULL_PAGE)
            offs = p % page
            qpos = jnp.where(v, p, -1)
            wmask = v[:, None, None]
            for l in range(cfg.num_layers):
                q, k, vv = model.qkv(params, l, x)
                kv = append_token(kv, l, jnp.where(wmask, k, 0.0),
                                  jnp.where(wmask, vv, 0.0), pages, offs)
                ctx = ragged_paged_attention(
                    q, kv.k, kv.v, table, att_lens, seq, qpos, layer=l,
                    k_scale=kv.k_scale, v_scale=kv.v_scale,
                    use_kernel=use_kernel)
                x = model.attn_out(params, l, ctx, x)
            logits = model.logits(params, x)
            return logits.reshape(b, r, -1), kv

        fn = self._audit_jit(raw, site="serving.draft",
                             donate_argnums=(1,),
                             xla_contract=self._contract)
        self._fns[rows] = fn
        return fn

    def _dispatch(self, rows: int, tokens, pos, valid, table, att_lens):
        import jax.numpy as jnp

        t0 = time.perf_counter()   # lint: allow(wall-clock) — honest
        #                            device timing of the draft step
        #                            (a metric, never a control input)
        logits, self._kv = self._fn(rows)(
            self.params, self._kv, jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(valid), jnp.asarray(table),
            jnp.asarray(att_lens))
        out = np.asarray(logits)
        self.steps += 1
        self.step_time_s += time.perf_counter() - t0  # lint: allow(wall-clock)
        return out

    # ---- host-side state ------------------------------------------------

    def _ensure_pages(self, st: _DraftSeq, upto_len: int) -> bool:
        """Grow ``st.pages`` to cover ``upto_len`` tokens; False if the
        draft pool is dry or the table is full (the caller skips the
        slot this tick — speculation, not correctness)."""
        page = self.cfg.page_size
        while len(st.pages) * page < upto_len:
            if len(st.pages) >= self.cfg.max_pages_per_seq:
                return False
            got = self.pool.alloc(1)
            if got is None:
                return False
            st.pages.extend(got)
        return True

    def _bucket(self, need: int) -> int:
        for bkt in self.CATCHUP_BUCKETS:
            if need <= bkt:
                return bkt
        return self.CATCHUP_BUCKETS[-1]

    def propose(self, requests, k_for):
        reqs = [r for r in requests if k_for(r) > 0]
        for req in reqs:
            if req.rid not in self._state:
                self._state[req.rid] = _DraftSeq(tokens=[], pages=[])
        # ---- phase 1: batched teacher-forced catch-up -------------------
        while True:
            needs = {}
            for req in reqs:
                st = self._state[req.rid]
                hist = req.cache_tokens
                # a diverged stored suffix (rejected drafts) is simply
                # re-forced: truncate to the common prefix first
                cp = _common_prefix(st.tokens, hist)
                del st.tokens[cp:]
                gap = len(hist) - len(st.tokens)
                if gap > 0:
                    needs[req.rid] = gap
            if not needs:
                break
            bkt = self._bucket(max(needs.values()))
            tokens = np.zeros((self.max_slots, bkt), np.int32)
            pos = np.zeros((self.max_slots, bkt), np.int32)
            valid = np.zeros((self.max_slots, bkt), bool)
            table = np.zeros((self.max_slots, self.cfg.max_pages_per_seq),
                             np.int32)
            att = np.zeros((self.max_slots,), np.int32)
            rows_of = {}
            for slot, req in enumerate(reqs):
                gap = needs.get(req.rid, 0)
                if gap <= 0:
                    continue
                st = self._state[req.rid]
                n = min(gap, bkt)
                start = len(st.tokens)
                if not self._ensure_pages(st, start + n):
                    needs.pop(req.rid, None)   # dry pool: skip this slot
                    continue
                hist = req.cache_tokens
                tokens[slot, :n] = hist[start:start + n]
                pos[slot, :n] = np.arange(start, start + n)
                valid[slot, :n] = True
                table[slot, :len(st.pages)] = st.pages
                att[slot] = start + n
                rows_of[slot] = (req, n)
            if not rows_of:
                break
            self._dispatch(bkt, tokens, pos, valid, table, att)
            for slot, (req, n) in rows_of.items():
                st = self._state[req.rid]
                hist = req.cache_tokens
                st.tokens.extend(hist[len(st.tokens):len(st.tokens) + n])
        # ---- phase 2: autoregressive drafting ---------------------------
        out: Dict[int, Tuple[List[int], Optional[np.ndarray]]] = {}
        live = []
        for req in reqs:
            st = self._state[req.rid]
            if st.tokens and st.tokens == list(req.cache_tokens):
                live.append(req)
        if not live:
            return out
        drafts = {req.rid: [] for req in live}
        probs: Dict[int, List[np.ndarray]] = {req.rid: [] for req in live}
        kmax = max(k_for(r) for r in live)
        for step in range(kmax):
            tokens = np.zeros((self.max_slots, 1), np.int32)
            pos = np.zeros((self.max_slots, 1), np.int32)
            valid = np.zeros((self.max_slots, 1), bool)
            table = np.zeros((self.max_slots, self.cfg.max_pages_per_seq),
                             np.int32)
            att = np.zeros((self.max_slots,), np.int32)
            active = []
            for slot, req in enumerate(live):
                if len(drafts[req.rid]) < step:
                    continue            # this slot stopped drafting
                if step >= k_for(req):
                    continue
                st = self._state[req.rid]
                # the row feeds the LAST known token; its logits draft
                # the next.  Position = len-1's successor slot...
                feed = (st.tokens + drafts[req.rid])[-1]
                p = len(st.tokens) + len(drafts[req.rid]) - 1
                if not self._ensure_pages(st, p + 1):
                    continue
                tokens[slot, 0] = feed
                pos[slot, 0] = p
                valid[slot, 0] = True
                table[slot, :len(st.pages)] = st.pages
                att[slot] = p + 1
                active.append((slot, req))
            if not active:
                break
            logits = self._dispatch(1, tokens, pos, valid, table, att)
            for slot, req in active:
                row = logits[slot, 0]
                s = req.sampling
                base = len(req.generated)
                if s is None or s.greedy:
                    tok = int(np.argmax(row))
                    probs[req.rid] = None   # point mass: exact-match walk
                else:
                    wp = warp_probs(row, s)
                    rng = position_rng(s.seed, base + step, _STREAM_DRAFT)
                    tok = _draw(wp, rng)
                    probs[req.rid].append(wp)
                drafts[req.rid].append(tok)
        for req in live:
            dr = drafts[req.rid]
            if not dr:
                continue
            pr = probs[req.rid]
            out[req.rid] = (list(dr), np.stack(pr) if pr else None)
            # record as materialized ONLY the drafts whose KV was
            # actually written: drafting step j FEEDS (and writes)
            # token j-1, so the LAST draft was produced but never fed —
            # claiming it would leave a zero-KV hole at its position
            # that every later draft would silently attend over
            self._state[req.rid].tokens.extend(dr[:-1])
        return out

    def commit(self, req) -> None:
        """Verify finished: roll the draft state back to the accepted
        history (a rejected suffix keeps its pages' junk — it is simply
        re-forced over next tick) and free lookahead pages past it."""
        st = self._state.get(req.rid)
        if st is None:
            return
        hist = req.cache_tokens
        cp = _common_prefix(st.tokens, hist)
        del st.tokens[cp:]
        page = self.cfg.page_size
        needed = -(-len(st.tokens) // page)
        if len(st.pages) > needed:
            extra = st.pages[needed:]
            del st.pages[needed:]
            self.pool.free(extra)

    def release(self, rid: int) -> None:
        st = self._state.pop(rid, None)
        if st is not None and st.pages:
            self.pool.free(st.pages)

    def check_conservation(self) -> None:
        """The draft pool's REF-LEAK twin: pages held by live draft
        states must equal the pool's refcounts (no sharing, no cache —
        refcounts are all 1)."""
        from paddle_tpu.serving.faults import PageLeakError

        held = sum(len(st.pages) for st in self._state.values())
        if held != self.pool.total_refs:
            raise PageLeakError(
                f"REF-LEAK: draft pool held={held} "
                f"refs={self.pool.total_refs} free={self.pool.num_free} "
                f"usable={self.pool.num_usable}")
        if self.pool.num_free + self.pool.num_in_use != \
                self.pool.num_usable:
            raise PageLeakError(
                f"PAGE-LEAK: draft pool free={self.pool.num_free} "
                f"in_use={self.pool.num_in_use} "
                f"usable={self.pool.num_usable}")


def _common_prefix(a: Sequence[int], b: Sequence[int]) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i
