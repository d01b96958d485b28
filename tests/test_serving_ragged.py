"""Ragged paged attention v2 (round 12): one pallas kernel for mixed
prefill+decode batches, GQA head-group packing, int8-quantized KV pages.

Covers the kernel/reference parity matrix (mixed batches, ragged
lengths, offset masks, GQA, int8, and every number of KV heads a grid
cell may take), the single dispatch chooser, the
bytes-per-page accounting behind ``kv_dtype=`` and
``ServingEngine(pool_bytes=...)``, the unified-step engine (fused vs
v1-shaped split ticks, token-identical), GQA greedy parity against a
head-replicated MHA oracle, int8 chaos conservation, and the
QUANT-DRIFT parity harness the tier-1 ladder greps (exit 7).
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving import (BLOCK_ROWS, DecoderLM, FaultPlan,
                                ManualClock, PagedKVConfig, Request,
                                RequestStatus, ServingEngine,
                                attention_path, greedy_decode_reference,
                                pack_prefill_chunks, pages_for_budget,
                                quantize_kv, ragged_paged_attention,
                                ragged_paged_attention_reference)
from paddle_tpu.serving import decode_attention
from paddle_tpu.serving.decode_attention import (QUANT_DRIFT_BOUND,
                                                 _ragged_pallas,
                                                 check_quant_drift,
                                                 heads_per_cell,
                                                 quant_parity_error)
from paddle_tpu.ops.attention import mha_reference

from conftest import assert_serving_drained as assert_drained  # noqa: E402
from conftest import stored_pool  # noqa: E402
from conftest import WALK_CASES, WALK_MIXES, walk_batch  # noqa: E402

ragged = pytest.mark.ragged
serving = pytest.mark.serving


@pytest.fixture(autouse=True)
def f32():
    old = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    yield
    FLAGS.use_bf16 = old


# ---------------------------------------------------------------------------
# mixed-batch construction helpers
# ---------------------------------------------------------------------------


def _ragged_pallas_layer(q, kp, vp, ks, vs, *rest):
    """The kernel on ONE layer's published ``[P, page, KVH, D]`` pages,
    taken as a stored pool of one layer."""
    return _ragged_pallas(q, *stored_pool(kp, vp, ks, vs), 0, *rest)


def _build_mixed(rng, seqs, page, pm, num_pages, kvh, d, h):
    """Build a sequence-packed mixed batch.  ``seqs`` is a list of
    (kv_len, q_rows, q_start): q_rows == 1 models a decode slot (its
    query sits at position kv_len-1), q_rows > 1 a prefill chunk whose
    rows occupy positions q_start..q_start+q_rows-1 (so kv_len ==
    q_start + q_rows).  Rows are padded per-sequence to BLOCK_ROWS (the
    kernel's packing contract).  Returns (q, k_pages, v_pages, table,
    kv_lens, row_seq, qpos, contig_k, contig_v)."""
    s = len(seqs)
    kc = rng.randn(s, pm * page, kvh, d).astype(np.float32)
    vc = rng.randn(s, pm * page, kvh, d).astype(np.float32)
    kp = rng.randn(num_pages, page, kvh, d).astype(np.float32)  # garbage
    vp = rng.randn(num_pages, page, kvh, d).astype(np.float32)
    table = np.zeros((s, pm), np.int32)
    free = list(range(1, num_pages))
    rng.shuffle(free)
    for i, (n, _, _) in enumerate(seqs):
        for j in range(-(-int(n) // page)):
            pg = free.pop()
            table[i, j] = pg
            kp[pg] = kc[i, j * page:(j + 1) * page]
            vp[pg] = vc[i, j * page:(j + 1) * page]
    rows, row_seq, qpos = [], [], []
    for i, (n, qr, qs) in enumerate(seqs):
        blocks = -(-qr // BLOCK_ROWS)
        pos = [qs + r for r in range(qr)] if qr > 1 else [n - 1]
        pos += [-1] * (blocks * BLOCK_ROWS - qr)
        qpos += pos
        row_seq += [i] * blocks * BLOCK_ROWS
        rows.append(blocks * BLOCK_ROWS)
    t = sum(rows)
    q = rng.randn(t, h, d).astype(np.float32)
    return (q, kp, vp, table, np.asarray([n for n, _, _ in seqs], np.int32),
            np.asarray(row_seq, np.int32), np.asarray(qpos, np.int32),
            kc, vc)


def _oracle(q, kc, vc, kv_lens, row_seq, qpos, h):
    """Per-row mha_reference oracle over the CONTIGUOUS ground-truth
    K/V (never touches pages), with the causal/offset mask expressed as
    a kv-length slice per row."""
    t = q.shape[0]
    out = np.zeros_like(q)
    for r in range(t):
        if qpos[r] < 0:
            continue
        s = row_seq[r]
        upto = qpos[r] + 1          # row sees tokens 0..qpos inclusive
        o = mha_reference(jnp.asarray(q[r:r + 1][:, None]),
                          jnp.asarray(kc[s][None, :upto]),
                          jnp.asarray(vc[s][None, :upto]))
        out[r] = np.asarray(o)[0, 0]
    return out


MIXED_CASES = [
    # (kv_len, q_rows, q_start) per sequence; page=8, pm=4
    [(13, 1, 0), (9, 5, 4), (20, 1, 0)],          # decode + offset chunk
    [(8, 8, 0), (1, 1, 0), (32, 1, 0)],           # page-exact chunk, len-1
    [(27, 11, 16), (5, 1, 0), (17, 17, 0)],       # multi-block chunks
]


@ragged
@serving
@pytest.mark.parametrize("kvh,h", [(2, 2), (2, 4)])   # MHA and GQA
@pytest.mark.parametrize("case", MIXED_CASES)
def test_ragged_mixed_batch_matches_oracle(rng, case, kvh, h):
    page, pm, num_pages, d = 8, 4, 32, 16
    q, kp, vp, table, kv_lens, row_seq, qpos, kc, vc = _build_mixed(
        rng, case, page, pm, num_pages, kvh, d, h)
    want = _oracle(q, kc, vc, kv_lens, row_seq, qpos, h)
    real = qpos >= 0

    ref = np.asarray(ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
        jnp.asarray(qpos)))
    np.testing.assert_allclose(ref[real], want[real], rtol=2e-5, atol=2e-5)

    ker = np.asarray(_ragged_pallas_layer(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), None, None,
        jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
        jnp.asarray(qpos), float(d) ** -0.5, True))
    np.testing.assert_allclose(ker[real], want[real], rtol=2e-5, atol=2e-5)

    # public entry, kernel forced (interpret on CPU)
    pub = np.asarray(ragged_paged_attention(
        jnp.asarray(q), *stored_pool(kp, vp),
        jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
        jnp.asarray(qpos), layer=0, use_kernel=True))
    np.testing.assert_allclose(pub[real], want[real], rtol=2e-5, atol=2e-5)


@ragged
@serving
def test_blocked_reference_matches_oracle(rng):
    """The engine's row-blocked fallback (bounded per-row K/V gather)
    is the oracle applied blockwise — identical results on a row stack
    spanning several blocks, pad rows included."""
    from paddle_tpu.serving.decode_attention import \
        _ragged_reference_blocked
    page, pm, num_pages, kvh, h, d = 8, 4, 32, 2, 4, 16
    q, kp, vp, table, kv_lens, row_seq, qpos, _, _ = _build_mixed(
        rng, MIXED_CASES[2], page, pm, num_pages, kvh, d, h)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(kv_lens),
            jnp.asarray(row_seq), jnp.asarray(qpos))
    want = np.asarray(ragged_paged_attention_reference(*args))
    got = np.asarray(_ragged_reference_blocked(*args, block=16))
    real = qpos >= 0
    np.testing.assert_allclose(got[real], want[real], rtol=1e-6, atol=1e-6)


@ragged
@serving
def test_cancel_from_chunk_callback_skips_batchmate_chunk(rng):
    """A request cancelled by a BATCHMATE's on_token (fired from the
    same unified step's chunk walk) must not have its own chunk results
    applied: no cache insert on released pages, no resurrection of the
    terminal status, and conservation holds at drain."""
    model = DecoderLM(vocab_size=50, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=128)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params)
    victim_rid = {}

    def assassin(tok):
        eng.cancel(victim_rid["b"])

    # both prompts fit one chunk, so both finish prefill — and emit
    # their first token through the chunk walk — in the SAME tick;
    # slot order makes A's callback run before B's chunk bookkeeping
    a = eng.submit(rng.randint(2, 50, size=5).tolist(), max_tokens=4,
                   on_token=assassin)
    b = eng.submit(rng.randint(2, 50, size=6).tolist(), max_tokens=4)
    victim_rid["b"] = b
    eng.step()                          # the chunks' step is dispatched
    eng.step()                          # and walked, a call later
    assert eng.status(b) is RequestStatus.CANCELLED
    assert eng.status(a) is RequestStatus.RUNNING
    eng.run(max_ticks=100)
    assert eng.status(a) is RequestStatus.COMPLETED
    assert eng.status(b) is RequestStatus.CANCELLED
    assert eng.result(b) is None        # never resurrected to COMPLETED
    assert_drained(eng)


@ragged
@serving
def test_ragged_kernel_int8_reads_what_reference_reads(rng):
    """Kernel and gather-fallback dequantize the SAME stored int8
    values — their outputs agree to float tolerance (the quantization
    error itself cancels out of this comparison)."""
    page, pm, num_pages, kvh, h, d = 8, 4, 32, 2, 4, 16
    q, kp, vp, table, kv_lens, row_seq, qpos, _, _ = _build_mixed(
        rng, MIXED_CASES[0], page, pm, num_pages, kvh, d, h)
    kq, ks = quantize_kv(jnp.asarray(kp))
    vq, vs = quantize_kv(jnp.asarray(vp))
    args = (jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
            jnp.asarray(qpos))
    ref = np.asarray(ragged_paged_attention_reference(
        jnp.asarray(q), kq, vq, *args, k_scale=ks, v_scale=vs))
    ker = np.asarray(_ragged_pallas_layer(
        jnp.asarray(q), kq, vq, ks, vs, *args, float(d) ** -0.5, True))
    real = qpos >= 0
    np.testing.assert_allclose(ker[real], ref[real], rtol=2e-5, atol=2e-5)


@ragged
@serving
def test_int8_quant_parity_harness_within_bound(rng):
    """THE QUANT-DRIFT gate: the int8 roundtrip must stay inside its
    logit-error bound on a mixed ragged batch.  If quantization ever
    regresses (wrong scale axis, missing dequant, clipped range), this
    raises with the grep-able QUANT-DRIFT tag and tools_tier1.sh exits
    7."""
    page, pm, num_pages, kvh, h, d = 8, 4, 32, 2, 4, 16
    q, kp, vp, table, kv_lens, row_seq, qpos, _, _ = _build_mixed(
        rng, MIXED_CASES[2], page, pm, num_pages, kvh, d, h)
    err = check_quant_drift(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
        jnp.asarray(qpos))
    assert 0.0 <= err <= QUANT_DRIFT_BOUND
    # and the tag actually fires when the bound is violated (an
    # impossible bound stands in for a broken quant path; pytest.raises
    # swallows the message so the tier-1 grep never sees a passing run)
    with pytest.raises(AssertionError, match="QUANT-DRIFT"):
        check_quant_drift(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(kv_lens),
            jnp.asarray(row_seq), jnp.asarray(qpos), bound=0.0)
    assert quant_parity_error(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
        jnp.asarray(qpos)) == err


# ---------------------------------------------------------------------------
# KV heads a grid cell (PR 27): one kernel, hb computed from shapes
# ---------------------------------------------------------------------------


def _parent_ragged_pallas(q, k_pages, v_pages, k_scale, v_scale, table,
                          kv_lens, row_seq, qpos, sm_scale):
    """The kernel as it stood before KV heads were folded into the cell
    (grid ``(row blocks, KV heads, pages)``, one ``(page, D)`` tile of
    one head a cell), kept word for word as the yardstick of "the same
    arithmetic in fewer steps".  Interpret mode only."""
    import functools
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops.attention import DEFAULT_MASK_VALUE

    t, h, d = q.shape
    _, page, kvh, _ = k_pages.shape
    pm, g, nb = table.shape[1], h // kvh, t // BLOCK_ROWS
    rbg, quantized = BLOCK_ROWS * g, k_scale is not None

    def kernel(blk_ref, pt_ref, len_ref, qpos_ref, q_ref, k_ref, v_ref,
               *rest):
        if quantized:
            ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
        else:
            o_ref, m_scr, l_scr, acc_scr = rest
        ib, hi, j = (pl.program_id(a) for a in range(3))

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(j * page < len_ref[blk_ref[ib]])
        def _compute():
            qb, kb, vb = q_ref[0, 0], k_ref[0], v_ref[0]
            if quantized:
                def head_scale(ref):
                    sc = ref[0]
                    lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
                    return jnp.sum(jnp.where(lane == hi, sc, 0.0),
                                   axis=1, keepdims=True)
                kb = kb.astype(jnp.float32) * head_scale(ks_ref)
                vb = vb.astype(jnp.float32) * head_scale(vs_ref)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * sm_scale
            tok = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(tok <= qpos_ref[0], s, DEFAULT_MASK_VALUE)
            m_prev = jnp.max(m_scr[...], axis=1, keepdims=True)
            l_prev = jnp.max(l_scr[...], axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(j == pm - 1)
        def _finalize():
            l = jnp.max(l_scr[...], axis=1, keepdims=True)
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)

    def live_page(ib, j, blk_ref, pt_ref, len_ref):
        seq = blk_ref[ib]
        last = jnp.maximum(len_ref[seq] - 1, 0) // page
        return pt_ref[seq, jnp.minimum(j, last)]

    q_idx = lambda ib, hi, j, *_: (hi, ib, 0, 0)                # noqa: E731
    kv_idx = lambda ib, hi, j, *r: (live_page(ib, j, *r), 0, hi)  # noqa: E731
    sc_idx = lambda ib, hi, j, *r: (live_page(ib, j, *r), 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, rbg, 1), lambda ib, hi, j, *_: (ib, 0, 0)),
                pl.BlockSpec((1, 1, rbg, d), q_idx),
                pl.BlockSpec((1, page, d), kv_idx),
                pl.BlockSpec((1, page, d), kv_idx)]
    args = [jnp.repeat(qpos.reshape(nb, BLOCK_ROWS), g, axis=1)[..., None],
            q.reshape(nb, BLOCK_ROWS, kvh, g, d).transpose(2, 0, 1, 3, 4)
            .reshape(kvh, nb, rbg, d),
            k_pages.reshape(-1, page, kvh * d),
            v_pages.reshape(-1, page, kvh * d)]
    if quantized:
        in_specs += [pl.BlockSpec((1, page, kvh), sc_idx)] * 2
        args += [k_scale, v_scale]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(nb, kvh, pm), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, rbg, d), q_idx),
            scratch_shapes=[pltpu.VMEM((rbg, 128), jnp.float32),
                            pltpu.VMEM((rbg, 128), jnp.float32),
                            pltpu.VMEM((rbg, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((kvh, nb, rbg, d), q.dtype),
        interpret=True,
    )(row_seq.reshape(nb, BLOCK_ROWS)[:, 0], table, kv_lens, *args)
    return out.reshape(kvh, nb, BLOCK_ROWS, g, d).transpose(1, 2, 0, 3, 4) \
        .reshape(t, h, d)


def _budget_for(hb, kvh, page, d, itemsize, quantized):
    """A ``_KV_VMEM_BUDGET`` under which the chooser gives exactly
    ``hb``: what that many heads' K and V tiles take, double-buffered,
    beside an int8 pool's two scale blocks."""
    return 2 * 2 * page * d * itemsize * hb + \
        (2 * 2 * page * kvh * 4 if quantized else 0)


# k1 > 1: 5 verify rows a slot at consecutive positions (one block,
# three padding rows), beside a decode slot and a prefill chunk
VERIFY_CASE = [(13, 5, 8), (30, 5, 25), (7, 1, 0), (21, 9, 12)]


@ragged
@serving
@pytest.mark.parametrize("hb", [4, 2, 1])     # all, a proper divisor, 1
@pytest.mark.parametrize("pool", ["float32", "int8"])
@pytest.mark.parametrize("kvh,h", [(4, 4), (4, 8)])   # MHA and GQA
@pytest.mark.parametrize("case", [MIXED_CASES[0], MIXED_CASES[2],
                                  VERIFY_CASE],
                         ids=["mixed", "multiblock", "verify_k5"])
def test_ragged_kernel_heads_per_cell_parity(rng, monkeypatch, case, kvh, h,
                                             pool, hb):
    """The kernel against the oracle (and, on an int8 pool, against the
    reference that reads the same stored values) at every number of KV
    heads a grid cell may take, forced through the budget constant.
    One head a cell is the grid this kernel had before: its outputs are
    the former kernel's bit for bit, and so are the folded grids'."""
    page, pm, num_pages, d = 8, 4, 32, 16
    q, kp, vp, table, kv_lens, row_seq, qpos, kc, vc = _build_mixed(
        rng, case, page, pm, num_pages, kvh, d, h)
    kp, vp, ks, vs = jnp.asarray(kp), jnp.asarray(vp), None, None
    quantized = pool == "int8"
    if quantized:
        kp, ks = quantize_kv(kp)
        vp, vs = quantize_kv(vp)
    monkeypatch.setattr(
        decode_attention, "_KV_VMEM_BUDGET",
        _budget_for(hb, kvh, page, d, kp.dtype.itemsize, quantized))
    assert heads_per_cell(kvh, page, d, kp.dtype.itemsize, quantized) == hb
    args = (jnp.asarray(q), kp, vp, ks, vs, jnp.asarray(table),
            jnp.asarray(kv_lens), jnp.asarray(row_seq), jnp.asarray(qpos),
            float(d) ** -0.5)
    ker = np.asarray(_ragged_pallas_layer(*args, True))
    real = qpos >= 0
    if quantized:
        want = np.asarray(ragged_paged_attention_reference(
            args[0], kp, vp, *args[5:9], k_scale=ks, v_scale=vs))
    else:
        want = _oracle(q, kc, vc, kv_lens, row_seq, qpos, h)
    np.testing.assert_allclose(ker[real], want[real], rtol=2e-5, atol=2e-5)
    # (a padded row sees nothing, and what it holds is read by nobody: the
    # former kernel walked its sequence's every page past it, this one
    # only the pages a live row of its block can see)
    np.testing.assert_array_equal(ker[real], np.asarray(
        _parent_ragged_pallas(*args))[real])


# ---------------------------------------------------------------------------
# the walk (PR 42): short blocks for decode rows, tall ones for a bucket's
# ---------------------------------------------------------------------------


def _kernel_and_reference(batch, **kw):
    """The kernel's and the reference path's outputs for ``walk_batch``'s
    arguments, and which rows are real."""
    batch = dict(batch)
    q = batch.pop("q")
    rest = [batch.pop(n) for n in ("k_pool", "v_pool", "page_table",
                                   "kv_lens", "row_seq", "qpos")]
    td = batch.pop("decode_rows")
    got = np.asarray(ragged_paged_attention(
        q, *rest, **batch, **kw, use_kernel=True, interpret=True,
        decode_rows=td))
    want = np.asarray(ragged_paged_attention(q, *rest, **batch, **kw,
                                             use_kernel=False))
    return got, want, np.asarray(rest[-1]) >= 0


def _tall(monkeypatch, rows, group):
    """Make a tall block ``rows`` rows high (None: what the shapes give)."""
    if rows is not None:
        monkeypatch.setattr(decode_attention, "_TALL_SCORE_ROWS",
                            rows * group)


@ragged
@serving
@pytest.mark.parametrize("mix,group,pool,k1", WALK_CASES)
def test_the_walk_over_a_mixed_tick_matches_the_reference(
        rng, monkeypatch, mix, group, pool, k1):
    """Decode rows in short blocks and the bucket's rows regrouped into
    tall ones, in one call: every real row is the reference's, whatever
    the chunks' lengths and wherever they end in a tall block; every row
    of the output is written (a block that visits nothing, a padded row),
    and finite."""
    decode, chunks, bucket, tall = WALK_MIXES[mix]
    _tall(monkeypatch, tall, group)
    batch = walk_batch(rng, decode, chunks, k1=k1, group=group, pool=pool,
                       bucket=bucket)
    got, want, real = _kernel_and_reference(batch)
    np.testing.assert_allclose(got[real], want[real], rtol=2e-5, atol=2e-5)
    assert np.isfinite(got).all()


@ragged
@serving
@pytest.mark.parametrize("mix,group", [
    (mix, (6, 1, 8)[i % 3]) for i, mix in enumerate(sorted(WALK_MIXES))])
def test_the_walk_at_the_height_the_shapes_give(rng, mix, group):
    """The same mixes with tall blocks as high as ``tall_block_rows`` makes
    them (128, 64 and 64 rows at these groups, or the whole bucket): blocks
    that hold several sequences' rows and work on one a visit.  And told
    nothing of the decode region (``decode_rows`` 0: every row in tall
    blocks), the kernel computes the same rows."""
    decode, chunks, bucket, _ = WALK_MIXES[mix]
    batch = walk_batch(rng, decode, chunks, group=group, bucket=bucket)
    got, want, real = _kernel_and_reference(batch)
    np.testing.assert_allclose(got[real], want[real], rtol=2e-5, atol=2e-5)
    batch["decode_rows"] = 0
    alone, _, _ = _kernel_and_reference(batch)
    np.testing.assert_allclose(alone[real], want[real], rtol=2e-5, atol=2e-5)
    assert np.isfinite(got).all() and np.isfinite(alone).all()


@ragged
@serving
def test_a_tall_block_keeps_its_sequences_apart(rng, monkeypatch):
    """A page of one sequence that holds a NaN reaches that sequence's rows
    and no other's, though they share a tall block."""
    _tall(monkeypatch, 16, 2)
    batch = walk_batch(rng, [9], [(8, 0), (8, 8)], group=2, bucket=16)
    table = np.asarray(batch["page_table"])
    batch["v_pool"] = batch["v_pool"].at[:, table[1, 0]].set(np.nan)
    got, want, real = _kernel_and_reference(batch)
    seq = np.asarray(batch["row_seq"])
    assert np.isnan(got[real & (seq == 1)]).all()
    np.testing.assert_allclose(got[real & (seq != 1)],
                               want[real & (seq != 1)], rtol=2e-5, atol=2e-5)


@ragged
@serving
def test_visit_schedule_against_a_count_by_hand():
    """Page 4, two decode blocks and a bucket of 32 rows in tall blocks of
    16: slot 0 decodes at length 13 (4 pages), slot 1 is idle (one step
    that computes nothing); slot 2's chunk of 20 rows at positions 4-23
    fills the first tall block (positions 4-19: pages 0-4) and half of the
    second (20-23: pages 0-5), which slot 3's chunk of 8 rows at 0-7 shares
    (pages 0-1)."""
    from paddle_tpu.serving.decode_attention import (_COMPUTE, _FIRST, _LAST,
                                                     _TALL, visit_counts,
                                                     visit_schedule)
    rows = [(0, 12)] + [(0, -1)] * 7 + [(1, -1)] * 8 + [(2, -1)] * 8 + \
        [(3, -1)] * 8
    rows += [(2, 4 + i) for i in range(20)] + [(2, -1)] * 4 + \
        [(3, i) for i in range(8)]
    row_seq = np.asarray([r[0] for r in rows], np.int32)
    qpos = np.asarray([r[1] for r in rows], np.int32)
    lens = np.asarray([13, 0, 24, 8], np.int32)
    kw = dict(decode_rows=32, tall_rows=16, page=4)
    assert visit_counts(row_seq, qpos, lens, width=6, **kw) == (
        4 + 1 + 1 + 1 + 5 + 6 + 2, 4 + 5 + 6 + 2, 4 + 6 + 2)
    walk = jax.jit(functools.partial(visit_schedule, width=6, **kw))(
        row_seq, qpos, lens)
    n = int(walk.count)
    assert n == 20 and walk.flags.shape == (4 * 6 + 4 * 6,)
    flags, at, seq = (np.asarray(a)[:n] for a in (walk.flags, walk.at,
                                                  walk.seq))
    #            slot 0's pages  idle x3    block 0       block 1: slot 2, 3
    assert list(seq) == [0] * 4 + [1, 2, 3] + [2] * 5 + [2] * 6 + [3] * 2
    assert list(at) == [0, 1, 2, 3, 0, 0, 0, 0, 1, 2, 3, 4,
                        0, 1, 2, 3, 4, 5, 0, 1]
    first = [0, 4, 5, 6, 7, 12]
    last = [3, 4, 5, 6, 11, 19]
    assert [i for i in range(n) if flags[i] & _FIRST] == first
    assert [i for i in range(n) if flags[i] & _LAST] == last
    assert [i for i in range(n) if not flags[i] & _COMPUTE] == [4, 5, 6]
    assert [i for i in range(n) if flags[i] & _TALL] == list(range(7, 20))
    # the resident blocks, the four short ones numbered before the tall
    assert list(np.asarray(walk.block)[:n]) == [0] * 4 + [1, 2, 3] + \
        [4] * 5 + [5] * 8
    # under a window of 6 a run starts at the first page its rows can see
    assert visit_counts(row_seq, qpos, lens, width=6, window=6, **kw)[:2] == (
        3 + 1 + 1 + 1 + 5 + 3 + 2, 3 + 5 + 3 + 2)


# ---------------------------------------------------------------------------
# the pool where it lies (PR 30): whole stored pool + a layer index
# ---------------------------------------------------------------------------


def _stored_pool(rng, layers, layer, kp, vp, quantized):
    """A stored pool ``[L, P, page, KVH * D]`` whose layer ``layer`` is
    ``kp``/``vp`` and whose other layers are other random values (a read
    of the wrong layer cannot pass), int8 with its ``[L, P, page, KVH]``
    scales where asked; and the layer's own published view."""
    k5 = rng.randn(layers, *kp.shape).astype(np.float32)
    v5 = rng.randn(layers, *vp.shape).astype(np.float32)
    k5[layer], v5[layer] = kp, vp
    k5, v5, ks, vs = jnp.asarray(k5), jnp.asarray(v5), None, None
    if quantized:
        k5, ks = quantize_kv(k5)
        v5, vs = quantize_kv(v5)
    lanes = k5.shape[:3] + (k5.shape[3] * k5.shape[4],)
    view = (k5[layer], v5[layer],
            None if ks is None else ks[layer],
            None if vs is None else vs[layer])
    return (k5.reshape(lanes), v5.reshape(lanes), ks, vs), view


@ragged
@serving
@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize("pool", ["float32", "int8"])
@pytest.mark.parametrize("kvh,h", [(4, 4), (4, 8)])   # MHA and GQA
@pytest.mark.parametrize("case", [MIXED_CASES[0], VERIFY_CASE],
                         ids=["mixed", "verify_k5"])
def test_ragged_kernel_on_the_whole_pool_reads_its_layer(rng, case, kvh, h,
                                                        pool, layer):
    """The kernel handed the WHOLE stored pool and a layer index reads
    that layer and no other: bit for bit what it computes on the
    layer's own view taken as a one-layer pool, and the reference path
    on that view (which reads the same stored values, scales indexed by
    the same layer) to float tolerance."""
    page, pm, num_pages, d, layers = 8, 4, 32, 16, 5
    q, kp, vp, table, kv_lens, row_seq, qpos, _, _ = _build_mixed(
        rng, case, page, pm, num_pages, kvh, d, h)
    (kl, vl, ksl, vsl), view = _stored_pool(rng, layers, layer, kp, vp,
                                            pool == "int8")
    rest = (jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
            jnp.asarray(qpos))
    q = jnp.asarray(q)
    got = np.asarray(ragged_paged_attention(
        q, kl, vl, *rest, layer=layer, k_scale=ksl, v_scale=vsl,
        use_kernel=True, interpret=True))
    alone = np.asarray(_ragged_pallas_layer(q, *view, *rest, float(d) ** -0.5,
                                            True))
    np.testing.assert_array_equal(got, alone)
    ref = np.asarray(ragged_paged_attention_reference(
        q, view[0], view[1], *rest, k_scale=view[2], v_scale=view[3]))
    real = qpos >= 0
    np.testing.assert_allclose(got[real], ref[real], rtol=2e-5, atol=2e-5)
    # the reference path of the same entry takes the same layer
    fall = np.asarray(ragged_paged_attention(
        q, kl, vl, *rest, layer=layer, k_scale=ksl, v_scale=vsl,
        use_kernel=False))
    np.testing.assert_array_equal(fall[real], ref[real])


@ragged
@serving
def test_ragged_kernel_layer_is_an_operand_not_a_constant(rng):
    """The layer rides in as a traced scalar: ONE compiled program
    serves every layer, and each call reads its own."""
    page, pm, num_pages, kvh, h, d, layers = 8, 4, 32, 2, 4, 16, 3
    q, kp, vp, table, kv_lens, row_seq, qpos, _, _ = _build_mixed(
        rng, MIXED_CASES[0], page, pm, num_pages, kvh, d, h)
    (kl, vl, _, _), _ = _stored_pool(rng, layers, 1, kp, vp, False)
    rest = (jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
            jnp.asarray(qpos))

    @jax.jit
    def attend(layer):
        return ragged_paged_attention(jnp.asarray(q), kl, vl, *rest,
                                      layer=layer, use_kernel=True,
                                      interpret=True)

    outs = [np.asarray(attend(jnp.int32(l))) for l in range(layers)]
    assert attend._cache_size() == 1
    for l in range(layers):
        k4 = kl[l].reshape(num_pages, page, kvh, d)
        v4 = vl[l].reshape(num_pages, page, kvh, d)
        np.testing.assert_array_equal(outs[l], np.asarray(
            _ragged_pallas_layer(jnp.asarray(q), k4, v4, None, None, *rest,
                                 float(d) ** -0.5, True)))
    assert not np.array_equal(outs[0], outs[1])


@ragged
@serving
@pytest.mark.parametrize("entry", ["one_chip", "tp"])
def test_ragged_entries_take_the_stored_pool_and_a_layer_only(rng, entry):
    """One operand form: the pool as stored and a layer.  A call without
    ``layer`` is a TypeError; one layer's published pages (int8, scales
    ``[P, page, KVH]``), the published ``[L, P, page, KVH, D]`` and lanes
    that are no multiple of the head dim are refused with a message
    that names the stored form.  (An f32 ``[P, page, KVH, D]`` alone has
    the shape of a one-head pool ``[L, P, page, 1 * D]``: no check on
    shapes can tell them apart, which is why ``layer`` is required.)"""
    from jax.sharding import Mesh
    from paddle_tpu.platform.enforce import EnforceError
    from paddle_tpu.serving.decode_attention import ragged_paged_attention_tp

    page, pm, num_pages, kvh, h, d = 8, 4, 32, 2, 4, 16
    q, kp, vp, table, kv_lens, row_seq, qpos, _, _ = _build_mixed(
        rng, MIXED_CASES[0], page, pm, num_pages, kvh, d, h)
    rest = (jnp.asarray(table), jnp.asarray(kv_lens), jnp.asarray(row_seq),
            jnp.asarray(qpos))
    if entry == "one_chip":
        attend = functools.partial(ragged_paged_attention, jnp.asarray(q))
    else:
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
        attend = functools.partial(ragged_paged_attention_tp, mesh, "model",
                                   jnp.asarray(q))
    k1, v1 = stored_pool(kp, vp)
    with pytest.raises(TypeError, match="layer"):
        attend(k1, v1, *rest)
    kq, ks = quantize_kv(jnp.asarray(kp))
    vq, vs = quantize_kv(jnp.asarray(vp))
    stored = r"STORED pool \[L, pages, page, KVH \* D\]"
    with pytest.raises(EnforceError, match=stored):
        attend(kq, vq, *rest, layer=0, k_scale=ks, v_scale=vs)
    with pytest.raises(EnforceError, match=stored):
        attend(jnp.asarray(kp)[None], jnp.asarray(vp)[None], *rest, layer=0)
    with pytest.raises(EnforceError, match=stored):
        attend(k1[..., :-1], v1[..., :-1], *rest, layer=0)
    # and the stored form of the same operands is taken
    got = attend(*stored_pool(kq, vq), *rest, layer=0,
                 **dict(zip(("k_scale", "v_scale"), stored_pool(ks, vs))))
    assert got.shape == q.shape


def _eqns(jaxpr, name):
    """Every equation of primitive ``name`` at any depth, with the
    jaxpr it sits in."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append((eqn, jaxpr))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


@ragged
@serving
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_engine_step_hands_the_kernel_the_pool_itself(kv_dtype):
    """The compiled serving step, kernel path on: every ``pallas_call``
    takes K and V (and an int8 pool's scales) from the pool's own
    leaves through a reshape of LEADING dims only — no slice of a
    layer, no transpose, no reshape that touches the two tiled dims —
    and the kernel is traced once for all L layers."""
    layers = 3
    model = DecoderLM(vocab_size=50, num_layers=layers, num_heads=4,
                      head_dim=8, num_kv_heads=2, max_positions=128)
    eng = _engine(model, model.init_params(jax.random.PRNGKey(0)),
                  use_kernel=True, kv_dtype=kv_dtype, page_size=8)
    pb, k1 = 8, 1
    closed = jax.make_jaxpr(eng._step_fn(pb, k1))(
        eng.params, eng._kv, eng._empty_tick(pb, k1), eng._last_words())
    calls = [e for e, _ in _eqns(closed.jaxpr, "jit")
             if e.params["name"] == "_ragged_call"]
    assert len(calls) == layers
    # one trace of the kernel, shared by the L call sites
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    # and ONE walk for them all: the layers attend over the tick's rows,
    # so the schedule of visits is made once a step, not once a layer
    assert len([e for e, _ in _eqns(closed.jaxpr, "jit")
                if e.params["name"] == "_walk"]) == 1
    pool_shapes = {"k": eng._kv.k.shape, "s": None if eng._kv.k_scale is None
                   else eng._kv.k_scale.shape}
    n_pool = 2 if kv_dtype == "float32" else 4
    for e in calls:
        # operands 1.. of _ragged_call are the pool's leaves, whole
        got = [tuple(v.aval.shape) for v in e.invars[1:1 + n_pool]]
        assert got[:2] == [pool_shapes["k"]] * 2, got
        if n_pool == 4:
            assert got[2:] == [pool_shapes["s"]] * 2, got
    pallas = {id(e): (e, j) for e, j in _eqns(closed.jaxpr, "pallas_call")}
    assert len(pallas) == 1          # inside the one shared trace
    (eqn, inner), = pallas.values()
    made_by = {v: q for q in inner.eqns for v in q.outvars}
    # scalar prefetch (the walk and the layer), each kind's row columns
    # and q, then K, V (and the scales) last
    for v in eqn.invars[-n_pool:]:
        src = made_by[v]
        assert src.primitive.name == "reshape", src
        (pool,) = src.invars
        assert pool in inner.invars
        assert tuple(pool.aval.shape)[2:] == tuple(v.aval.shape)[1:]
        assert v.aval.shape[0] == pool.aval.shape[0] * pool.aval.shape[1]
    # and nowhere in the step is a layer cut out of the pool
    for name in ("slice", "dynamic_slice", "gather"):
        for q, _ in _eqns(closed.jaxpr, name):
            assert tuple(q.invars[0].aval.shape) not in (
                pool_shapes["k"], pool_shapes["s"]), q


@ragged
@serving
def test_heads_per_cell_chooser(monkeypatch):
    """``hb`` is a function of shapes: the widths served so far fold
    whole, a slab over the budget falls to a divisor, and whatever the
    shapes it divides the head count."""
    budget = decode_attention._KV_VMEM_BUDGET
    # 8 (the 6.7B's shard) and 16 (the 1.3B) float32 heads of 128 at
    # page 128: 2 and 4 MiB of double-buffered K and V
    assert heads_per_cell(8, 128, 128, 4) == 8
    assert heads_per_cell(16, 128, 128, 4) == 16
    assert heads_per_cell(32, 128, 128, 4) == 32      # 8 MiB: the edge
    assert heads_per_cell(64, 128, 128, 4) == 32      # 16 MiB: halves
    assert heads_per_cell(64, 128, 128, 2) == 64      # bf16 pool
    assert heads_per_cell(64, 128, 128, 1, quantized=True) == 64
    assert heads_per_cell(12, 256, 128, 4) == 12      # 6 MiB
    assert heads_per_cell(24, 256, 128, 4) == 12      # 12 MiB: a divisor
    assert heads_per_cell(7, 1024, 128, 4) == 1       # prime, 2 MiB a head
    # the scale blocks of an int8 pool count against the budget
    monkeypatch.setattr(decode_attention, "_KV_VMEM_BUDGET",
                        _budget_for(4, 8, 128, 128, 1, False))
    assert heads_per_cell(8, 128, 128, 1) == 4
    assert heads_per_cell(8, 128, 128, 1, quantized=True) == 2
    monkeypatch.setattr(decode_attention, "_KV_VMEM_BUDGET", 0)
    assert heads_per_cell(8, 128, 128, 4) == 1        # never under one
    monkeypatch.setattr(decode_attention, "_KV_VMEM_BUDGET", budget)
    for kvh in range(1, 41):
        for page in (8, 128, 512):
            for item in (1, 2, 4):
                hb = heads_per_cell(kvh, page, 128, item, item == 1)
                assert 1 <= hb <= kvh and kvh % hb == 0


@ragged
@serving
def test_tall_block_rows_chooser(monkeypatch):
    """A tall block's height, from shapes alone: the score rows a KV head
    it aims at over the GQA group, never above the most rows, a power of
    two times ``BLOCK_ROWS``, and less where VMEM has no room."""
    from paddle_tpu.serving.decode_attention import tall_block_rows
    assert tall_block_rows(1, 8, 128) == 128      # the 6.7B's shard
    assert tall_block_rows(6, 8, 128) == 64       # laguna's full layers
    assert tall_block_rows(8, 8, 128) == 64       # its window layers
    assert tall_block_rows(8, 4, 128) == 64       # sdar
    assert tall_block_rows(16, 8, 256) == 32
    assert tall_block_rows(512, 1, 128) == BLOCK_ROWS   # never under 8
    # aimed at 64 rows, 8 heads of 256 a cell at group 16 would hold 50
    # MiB of q, output and carries: 32 rows fit, and 16 at 16 heads
    monkeypatch.setattr(decode_attention, "_TALL_SCORE_ROWS", 1024)
    assert tall_block_rows(16, 8, 256) == 32
    assert tall_block_rows(16, 16, 256) == 16
    assert tall_block_rows(16, 2, 128) == 64
    for group in (1, 2, 3, 4, 6, 8, 12, 16):
        for hb in (1, 2, 8, 32):
            rows = tall_block_rows(group, hb, 128)
            assert rows % BLOCK_ROWS == 0 and rows & (rows - 1) == 0
            assert BLOCK_ROWS <= rows <= 128


# ---------------------------------------------------------------------------
# the single dispatch chooser
# ---------------------------------------------------------------------------


@ragged
@serving
def test_attention_path_single_chooser():
    # forced answers win over everything
    assert attention_path(7, 3, use_kernel=True) == "kernel"
    assert attention_path(128, 128, use_kernel=False) == "reference"
    # interpret (the CPU default) rides the reference path
    assert attention_path(128, 128, interpret=True) == "reference"
    # native gate: lane-aligned head dim, sublane-aligned pages
    assert attention_path(128, 128, interpret=False) == "kernel"
    assert attention_path(96, 128, interpret=False) == "reference"
    assert attention_path(128, 12, interpret=False) == "reference"
    # int8 additionally wants lane-aligned pages for its scale vectors
    assert attention_path(128, 128, quantized=True,
                          interpret=False) == "kernel"
    assert attention_path(128, 64, quantized=True,
                          interpret=False) == "reference"
    # mismatched head grouping falls back
    assert attention_path(128, 128, num_heads=6, num_kv_heads=4,
                          interpret=False) == "reference"
    assert attention_path(128, 128, num_heads=8, num_kv_heads=4,
                          interpret=False) == "kernel"


# ---------------------------------------------------------------------------
# bytes-per-page accounting + pool byte budgets (kv_dtype=)
# ---------------------------------------------------------------------------


def _cfg(dtype, kvh=None):
    return PagedKVConfig(num_layers=2, num_heads=4, head_dim=16,
                         page_size=8, num_pages=10, max_pages_per_seq=4,
                         dtype=dtype, num_kv_heads=kvh)


@ragged
@serving
def test_bytes_per_page_accounting():
    f32, bf16, i8 = (_cfg(jnp.float32), _cfg(jnp.bfloat16), _cfg(jnp.int8))
    # exact arithmetic: 2 (K+V) * L * page * H_kv * D * itemsize
    assert f32.bytes_per_page() == 2 * 2 * 8 * 4 * 16 * 4
    assert bf16.bytes_per_page() == f32.bytes_per_page() // 2
    # int8 = 1 byte/elem + one f32 scale per (layer, token, kv head)
    assert i8.bytes_per_page() == 2 * 2 * 8 * 4 * (16 * 1 + 4)
    assert f32.kv_bytes() == 10 * f32.bytes_per_page()
    # GQA halves the pool bytes when kv heads halve
    assert _cfg(jnp.float32, kvh=2).bytes_per_page() == \
        f32.bytes_per_page() // 2
    # the acceptance arithmetic: at one byte budget, int8 admits the
    # pages the smaller footprint buys — >= 1.8x f32 even with the
    # scale overhead (exactly 3.2x at D=16)
    budget = 1 << 20
    pages = {d: pages_for_budget(budget, 2, 4, 16, 8, d)
             for d in ("float32", "bfloat16", "int8")}
    assert pages["int8"] >= int(1.8 * pages["float32"])
    assert pages["bfloat16"] == 2 * pages["float32"]
    assert pages["int8"] == int(budget // _cfg(jnp.int8).bytes_per_page())


@ragged
@serving
def test_bf16_kv_pool_via_param(rng):
    """Satellite: kv_dtype plumbs through the cache config —
    bf16 KV works end to end even without int8."""
    model = DecoderLM(vocab_size=50, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=64)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, eos_id=1, page_size=4,
                        num_pages=20, max_pages_per_seq=5, max_slots=2,
                        buckets=(4, 8), kv_dtype="bfloat16")
    assert eng.kv_cfg.dtype == jnp.bfloat16
    assert eng._kv.k.dtype == jnp.bfloat16 and eng._kv.k_scale is None
    rid = eng.submit(rng.randint(2, 50, size=6).tolist(), max_tokens=6)
    res = eng.run(max_ticks=100)
    assert eng.status(rid) is RequestStatus.COMPLETED and len(res[rid]) >= 1
    assert eng.healthz()["kv_dtype"] == "bfloat16"
    assert_drained(eng)
    eng2 = ServingEngine(model, params, eos_id=1, page_size=4,
                         num_pages=20, max_pages_per_seq=5, max_slots=2,
                         buckets=(4, 8), kv_dtype="int8")
    assert eng2.kv_cfg.quantized and eng2._kv.k_scale is not None


@ragged
@serving
def test_pool_bytes_budget_doubles_int8_admission(rng):
    """The scheduler charges admission in pages, so the int8 page
    multiplier IS an admission multiplier: at the same pool_bytes the
    int8 engine owns >= 1.8x the f32 pages."""
    model = DecoderLM(vocab_size=50, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=64)
    params = model.init_params(jax.random.PRNGKey(0))
    budget = 64 * 1024
    engines = {d: ServingEngine(model, params, eos_id=1, page_size=4,
                                num_pages=None, pool_bytes=budget,
                                max_pages_per_seq=5, max_slots=2,
                                buckets=(4, 8), kv_dtype=d)
               for d in ("float32", "int8")}
    f32p = engines["float32"].pool.num_usable
    i8p = engines["int8"].pool.num_usable
    assert i8p >= int(1.8 * f32p)
    hz = engines["int8"].healthz()
    assert hz["pages_total"] == i8p and hz["kv_dtype"] == "int8"


# ---------------------------------------------------------------------------
# packer policy
# ---------------------------------------------------------------------------


def _fake_req(n_tokens, done=0):
    r = Request(prompt=list(range(2, 2 + n_tokens)), max_tokens=4)
    r.cache_len = done
    return r


@ragged
@serving
def test_pack_prefill_chunks_budget_align_and_oversize():
    a, b, c = _fake_req(20), _fake_req(20), _fake_req(4)
    sel, total = pack_prefill_chunks([a, b, c], chunk=8, align=8, budget=16)
    # greedy in order until the budget: a and b fit, c is crowded out
    assert [(r.rid, s, n, rows) for r, s, n, rows in sel] == \
        [(a.rid, 0, 8, 8), (b.rid, 0, 8, 8)]
    assert total == 16
    # alignment pads partial chunks to whole blocks
    sel, total = pack_prefill_chunks([c], chunk=8, align=8, budget=16)
    assert sel == [(c, 0, 4, 8)] and total == 8
    # the first chunk packs even when it alone exceeds the budget
    big = _fake_req(40)
    sel, total = pack_prefill_chunks([big], chunk=0, align=1, budget=16)
    assert sel == [(big, 0, 40, 40)] and total == 40
    # resume point honors prior progress; finished requests are skipped
    sel, _ = pack_prefill_chunks([_fake_req(20, done=17),
                                  _fake_req(6, done=6)],
                                 chunk=8, align=1, budget=16)
    assert [(s, n) for _, s, n, _ in sel] == [(17, 3)]


# ---------------------------------------------------------------------------
# unified-step engine: kernel parity, fused-vs-split, GQA, int8
# ---------------------------------------------------------------------------


def _mixed_traffic(eng, rng_seed=0):
    """Mixed long-prefill/heavy-decode traffic: long prompts chunking
    while short ones decode — the shape the v1 tick interleave handled
    worst.  Deterministic; returns outputs in submit order."""
    rng = np.random.RandomState(rng_seed)
    prompts = [rng.randint(2, 50, size=n).tolist()
               for n in (3, 26, 5, 19, 2, 11)]
    rids = []
    for i, p in enumerate(prompts):
        rids.append(eng.submit(p, max_tokens=10 if len(p) < 8 else 4))
        if i % 2:
            eng.step()              # interleave arrivals with ticks
    eng.run(max_ticks=400)
    return prompts, rids, [eng.result(r) for r in rids]


def _engine(model, params, **kw):
    kw.setdefault("eos_id", 1)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 60)
    kw.setdefault("max_pages_per_seq", 10)
    kw.setdefault("max_slots", 4)
    kw.setdefault("buckets", (8, 16, 32))
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(model, params, **kw)


@ragged
@serving
def test_engine_kernel_fallback_parity_mixed(rng):
    """CPU fallback parity for the ragged kernel at ENGINE level: the
    same mixed prefill+decode traffic (ragged lengths, offset masks via
    chunked prefill) through use_kernel=True (pallas, interpret on CPU)
    and the reference path produces token-identical outputs, and both
    match the non-paged oracle."""
    model = DecoderLM(vocab_size=50, num_layers=2, num_heads=2, head_dim=8,
                      max_positions=128)
    params = model.init_params(jax.random.PRNGKey(0))
    prompts, _, out_ref = _mixed_traffic(_engine(model, params,
                                                 use_kernel=False))
    _, _, out_ker = _mixed_traffic(_engine(model, params, use_kernel=True))
    assert out_ker == out_ref
    for p, toks in zip(prompts, out_ref):
        mt = 10 if len(p) < 8 else 4
        assert toks == greedy_decode_reference(model, params, p, mt, 1)


@ragged
@serving
@pytest.mark.parametrize("hb", [4, 2])
def test_attn_cell_counters_match_a_count_by_hand(rng, monkeypatch, hb):
    """``attn_kernel_calls`` / ``attn_grid_cells`` / ``attn_live_cells`` /
    ``attn_pages_needed``: a call's grid is ``(KVH / hb, visits)``, a
    visit one page of one sequence before one resident row block: each of
    the four slots' decode blocks walks the pages its decode row can see
    (an idle or prefilling slot's block: one step that computes nothing),
    the 8-row bucket is one tall block that walks its chunk's pages up to
    the chunk's last row; the pages needed are the distinct (slot, page)
    pairs among the visits — counted here by hand, tick by tick."""
    model = DecoderLM(vocab_size=50, num_layers=2, num_heads=4, head_dim=8,
                      max_positions=128)
    params = model.init_params(jax.random.PRNGKey(0))
    # page 4, 4 slots, 10 pages a sequence, chunks and smallest bucket 8
    monkeypatch.setattr(decode_attention, "_KV_VMEM_BUDGET",
                        _budget_for(hb, 4, 4, 8, 4, False))
    eng = _engine(model, params, use_kernel=True, eos_id=50)
    layers, groups = 2, 4 // hb
    seen = []

    def step():
        m = eng.metrics
        before = (m.attn_kernel_calls, m.attn_grid_cells, m.attn_live_cells,
                  m.attn_pages_needed)
        eng.step()
        eng.land()      # (a step is counted when its words are read)
        seen.append((m.attn_kernel_calls - before[0],
                     m.attn_grid_cells - before[1],
                     m.attn_live_cells - before[2],
                     m.attn_pages_needed - before[3]))

    eng.submit(rng.randint(2, 50, size=5).tolist(), max_tokens=20)
    step()      # A prefills 5 rows in one 8-row bucket: length 5
    step()      # A decodes: length 6
    eng.submit(rng.randint(2, 50, size=11).tolist(), max_tokens=20)
    step()      # A decodes at 7; B's first chunk, 8 rows: length 8
    step()      # A at 8; B's last chunk, 3 rows: length 11
    step()      # A at 9, B at 12: decode-only again

    def pages(n):
        return -(-n // 4)

    # (visits that read a page, blocks that see nothing, pages needed)
    want = [
        (pages(5), 4, pages(5)),            # A's chunk; no decode row yet
        (pages(6), 3, pages(6)),
        (pages(7) + pages(8), 3, pages(7) + pages(8)),   # A; B's chunk
        # B's chunk block reads B's pages once, B's decode block nothing
        (pages(8) + pages(11), 3, pages(8) + pages(11)),
        (pages(9) + pages(12), 2, pages(9) + pages(12)),
    ]
    assert seen == [(layers, layers * groups * (live + idle),
                     layers * groups * live, layers * groups * needed)
                    for live, idle, needed in want]
    snap = eng.metrics.snapshot()
    assert snap["attn_kernel_calls"] == 5 * layers
    assert snap["attn_grid_cells"] == sum(c for _, c, _, _ in seen)
    assert snap["attn_live_cells"] == sum(c for _, _, c, _ in seen)
    assert snap["attn_pages_needed"] == sum(c for _, _, _, c in seen)
    # every page is read once: the kernel's re-read factor is 1 here
    assert snap["attn_live_cells"] == snap["attn_pages_needed"]
    # the reference path dispatches no kernel: the counters stay at zero
    ref = _engine(model, params, use_kernel=False, eos_id=50)
    ref.submit([3, 4, 5], max_tokens=2)
    ref.run(max_ticks=20)
    assert ref.metrics.step_dispatches > 0
    assert ref.metrics.snapshot()["attn_kernel_calls"] == 0
    assert ref.metrics.snapshot()["attn_grid_cells"] == 0


@ragged
@serving
def test_gqa_engine_parity_vs_head_replicated_mha_oracle(rng):
    """Satellite: a GQA DecoderLM (num_kv_heads < num_heads) decodes
    token-identically to (a) the non-paged greedy oracle on its own
    weights and (b) an MHA DecoderLM whose K/V projections replicate
    each KV head across its query group — the algebraic identity GQA
    packing must preserve."""
    gqa = DecoderLM(vocab_size=50, num_layers=2, num_heads=4, head_dim=8,
                    num_kv_heads=2, max_positions=128)
    gp = gqa.init_params(jax.random.PRNGKey(3))
    assert gp["l0.wk"].shape == (32, 16)          # E x (H_kv * D)
    # head-replicated MHA twin: KV head g serves query heads 2g, 2g+1
    mha = DecoderLM(vocab_size=50, num_layers=2, num_heads=4, head_dim=8,
                    max_positions=128)
    mp = dict(gp)
    group = gqa.num_heads // gqa.num_kv_heads
    for l in range(2):
        for w in ("wk", "wv"):
            m = gp[f"l{l}.{w}"].reshape(32, gqa.num_kv_heads, 8)
            mp[f"l{l}.{w}"] = jnp.repeat(m, group, axis=1).reshape(32, 32)
    prompts = [np.random.RandomState(7).randint(2, 50, size=n).tolist()
               for n in (3, 9, 14)]
    for p in prompts:
        want = greedy_decode_reference(mha, mp, p, 8, 1)
        assert greedy_decode_reference(gqa, gp, p, 8, 1) == want
    eng = _engine(gqa, gp)
    rids = [eng.submit(p, max_tokens=8) for p in prompts]
    res = eng.run(max_ticks=200)
    for p, rid in zip(prompts, rids):
        assert res[rid] == greedy_decode_reference(mha, mp, p, 8, 1)
    # the pool really stores only the KV heads: 2 heads of 8 lanes each
    # in the merged dim of the stored layout
    assert eng._kv.kv_heads == 2
    assert eng._kv.k.shape == (2, eng.kv_cfg.num_pages, eng.kv_cfg.page_size,
                               2 * 8)
    assert_drained(eng)


@ragged
@serving
def test_int8_engine_completes_with_prefix_cow_and_conservation(rng):
    """int8 pages through the full engine: chunked prefill, prefix
    cache hits, a COW fork (scales must fork with the values), and the
    REF-LEAK/PAGE-LEAK conservation checks at drain.  Determinism:
    resubmitting an identical prompt (now a full-cover cache hit that
    decodes from forked int8 pages) reproduces the first answer
    token-for-token."""
    model = DecoderLM(vocab_size=50, num_layers=2, num_heads=2, head_dim=8,
                      max_positions=128)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, kv_dtype="int8")
    sys_p = rng.randint(2, 50, size=12).tolist()
    a = eng.submit(sys_p, max_tokens=6)
    eng.run(max_ticks=100)
    b = eng.submit(sys_p, max_tokens=6)          # full-cover hit -> COW
    c = eng.submit(sys_p + [9, 8], max_tokens=6)  # partial hit
    res = eng.run(max_ticks=200)
    assert res[b] == res[a]
    assert eng.metrics.cow_forks >= 1
    assert eng.metrics.prefill_tokens_saved > 0
    assert len(res[c]) >= 1
    assert_drained(eng)


@ragged
@serving
@pytest.mark.faults
def test_int8_chaos_keeps_conservation_and_terminal_statuses(rng):
    """Acceptance: 0 PAGE-LEAK / REF-LEAK under the chaos plan with
    int8 pages enabled — pressure, transient decode errors, a NaN rid,
    preemption and eviction all running over quantized pages."""
    model = DecoderLM(vocab_size=50, num_layers=1, num_heads=2, head_dim=8,
                      max_positions=128)
    params = model.init_params(jax.random.PRNGKey(0))
    clock = ManualClock(tick_s=0.02)
    plan = FaultPlan(seed=0, clock=clock, decode_error_rate=0.1,
                     page_pressure=(3, 12, 10))
    # eos outside the vocab: every request really decodes its full
    # max_tokens, so the poisoned rid is guaranteed to meet the NaN
    # injection at a decode tick (a first token emitted straight from
    # prefill could otherwise complete it before poisoning applies)
    eng = _engine(model, params, kv_dtype="int8", num_pages=24,
                  max_pages_per_seq=8, faults=plan, watchdog_ticks=32,
                  eos_id=51)
    prompts = [rng.randint(2, 50, size=rng.randint(2, 14)).tolist()
               for _ in range(8)]
    rids = [eng.submit(p, max_tokens=8) for p in prompts]
    plan.poison_nan(rids[3])
    eng.run(max_ticks=500)
    assert eng.status(rids[3]) is RequestStatus.FAILED
    for r in rids:
        assert eng.status(r).terminal
    assert_drained(eng)
