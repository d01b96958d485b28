"""paddle_tpu.serving tests: paged decode attention vs the mha_reference
oracle (ragged lengths, page-boundary crossings), scheduler invariants
(no page leaks, admission control, preemption), end-to-end greedy parity
of the ServingEngine against the non-paged oracle AND against
``beam_search`` with ``beam_size=1``, plus the Inference.infer
tail-padding satellites.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import layer
from paddle_tpu.attr import ParamAttr
from paddle_tpu.generation import GeneratedInput, beam_search
from paddle_tpu.ops.attention import mha_reference
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.serving import (DecoderLM, PagePool, PagedKVConfig, Request,
                                SchedulerConfig, ServingEngine,
                                append_token, bucket_for,
                                ContinuousBatchingScheduler, gather_kv,
                                greedy_decode_reference, init_kv_pages,
                                ragged_paged_attention,
                                ragged_paged_attention_reference)
from paddle_tpu.serving.decode_attention import (BLOCK_ROWS,
                                                 expand_decode_rows)
from paddle_tpu.serving.kv_cache import (fork_page, layer_pages, read_pages,
                                         write_pages, zero_pages)
from paddle_tpu.topology import LayerOutput, ParamSpec

from conftest import assert_serving_drained as assert_drained  # noqa: E402
from conftest import stored_pool  # noqa: E402

serving = pytest.mark.serving


@pytest.fixture(autouse=True)
def f32():
    old = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    yield
    FLAGS.use_bf16 = old


# ---------------------------------------------------------------------------
# paged decode attention vs oracle
# ---------------------------------------------------------------------------


def _scatter_into_pages(rng, lens, page, pm, num_pages, h, d):
    """Build contiguous ground-truth K/V and scatter them into a shuffled
    page pool; returns (q, k_contig, v_contig, k_pages, v_pages, table)."""
    b = len(lens)
    kc = rng.randn(b, pm * page, h, d).astype(np.float32)
    vc = rng.randn(b, pm * page, h, d).astype(np.float32)
    k_pages = rng.randn(num_pages, page, h, d).astype(np.float32)  # garbage
    v_pages = rng.randn(num_pages, page, h, d).astype(np.float32)
    table = np.zeros((b, pm), np.int32)
    free = list(range(1, num_pages))
    rng.shuffle(free)
    for i, n in enumerate(lens):
        for j in range(-(-int(n) // page)):
            pg = free.pop()
            table[i, j] = pg
            k_pages[pg] = kc[i, j * page:(j + 1) * page]
            v_pages[pg] = vc[i, j * page:(j + 1) * page]
    q = rng.randn(b, h, d).astype(np.float32)
    return q, kc, vc, k_pages, v_pages, table


@serving
@pytest.mark.parametrize("lens", [
    (1, 8, 27),      # sub-page, exact page boundary, mid-page crossing
    (32, 3, 16),     # full table, tiny, exact two pages
])
def test_paged_decode_attention_matches_oracle(rng, lens):
    page, pm, num_pages, h, d = 8, 4, 16, 2, 16
    lens = np.asarray(lens, np.int32)
    q, kc, vc, kp, vp, table = _scatter_into_pages(
        rng, lens, page, pm, num_pages, h, d)

    # oracle: contiguous layout + mha_reference with length masking
    pos = np.arange(pm * page)[None]
    kv_seg = jnp.asarray((pos >= lens[:, None]).astype(np.int32))
    q_seg = jnp.zeros((len(lens), 1), jnp.int32)
    want = np.asarray(mha_reference(
        jnp.asarray(q)[:, None], jnp.asarray(kc), jnp.asarray(vc),
        segment_ids=q_seg, kv_segment_ids=kv_seg)[:, 0])

    # a decode tick's rows: one per sequence, at position len - 1
    kv_lens = jnp.asarray(lens)
    ref = np.asarray(ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(table), kv_lens, jnp.arange(len(lens)), kv_lens - 1))
    np.testing.assert_allclose(ref, want, rtol=1e-5, atol=1e-5)

    # the kernel (interpret mode) as the engine calls it: rows padded to
    # one block a sequence, the stored pool and a layer index
    qe, row_seq, qpos = expand_decode_rows(jnp.asarray(q), kv_lens - 1)
    ker = np.asarray(ragged_paged_attention(
        qe, *stored_pool(kp, vp), jnp.asarray(table), kv_lens, row_seq,
        qpos, layer=0, use_kernel=True, interpret=True))[::BLOCK_ROWS]
    np.testing.assert_allclose(ker, want, rtol=1e-5, atol=1e-5)


@serving
def test_append_token_and_gather_roundtrip(rng):
    cfg = PagedKVConfig(num_layers=2, num_heads=2, head_dim=4, page_size=4,
                        num_pages=6, max_pages_per_seq=3)
    kv = init_kv_pages(cfg)
    table = np.array([[1, 2, 3], [4, 5, 0]], np.int32)
    toks = rng.randn(2, 2, 10, 2, 4).astype(np.float32)  # [kv, B, T, H, D]
    for t in range(10):
        # seq 0 appends all 10 tokens; seq 1 stops at 7 (null page after)
        page_ids = np.array([table[0, t // 4],
                             table[1, t // 4] if t < 7 else 0], np.int32)
        kv = append_token(kv, 1, jnp.asarray(toks[0, :, t]),
                          jnp.asarray(toks[1, :, t]), jnp.asarray(page_ids),
                          jnp.asarray([t % 4, t % 4], np.int32))
    k, v = gather_kv(kv, 1, jnp.asarray(table))
    np.testing.assert_allclose(np.asarray(k)[0, :10], toks[0, 0], atol=0)
    np.testing.assert_allclose(np.asarray(v)[0, :10], toks[1, 0], atol=0)
    np.testing.assert_allclose(np.asarray(k)[1, :7], toks[0, 1, :7], atol=0)
    # layer 0 untouched
    assert float(jnp.abs(kv.k[0]).max()) == 0.0


def _filled_pool(rng, dtype):
    """A 3-layer pool of 2 KV heads x 4 lanes with every token slot of
    pages 1..5 written through ``append_token``; and what was written,
    ``[kv, L, page, offset, H, D]``."""
    cfg = PagedKVConfig(num_layers=3, num_heads=4, head_dim=4, page_size=4,
                        num_pages=8, max_pages_per_seq=3, num_kv_heads=2,
                        dtype=dtype)
    kv = init_kv_pages(cfg)
    rows = rng.randn(2, 3, 5, 4, 2, 4).astype(np.float32)
    ids = jnp.asarray(np.repeat(np.arange(1, 6), 4), jnp.int32)
    offs = jnp.asarray(np.tile(np.arange(4), 5), jnp.int32)
    for l in range(3):
        kv = append_token(kv, l, jnp.asarray(rows[0, l].reshape(20, 2, 4)),
                          jnp.asarray(rows[1, l].reshape(20, 2, 4)), ids,
                          offs)
    return cfg, kv, rows


@serving
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["float32", "int8"])
def test_pool_keeps_the_published_shape_at_the_host_boundary(rng, dtype):
    """The pool is STORED ``[L, pages, page, H_kv * D]``; what leaves
    and enters the device keeps the published ``[L, n, page, H_kv, D]``
    (scales ``[L, n, page, H_kv]``): ``read_pages`` -> ``write_pages``
    is the identity on stored values, ``fork_page`` and ``zero_pages``
    move whole pages of every layer, and ``layer_pages`` is the same
    bytes one layer at a time."""
    cfg, kv, rows = _filled_pool(rng, dtype)
    quant = dtype == jnp.int8
    assert kv.k.shape == kv.v.shape == (3, 8, 4, 2 * 4)
    assert kv.kv_heads == 2 and kv.quantized == quant
    assert (kv.k_scale.shape == (3, 8, 4, 2)) if quant \
        else kv.k_scale is None

    k, v, ks, vs = read_pages(kv, [2, 5, 1])
    assert k.shape == v.shape == (3, 3, 4, 2, 4) and k.dtype == dtype
    if quant:
        assert ks.shape == vs.shape == (3, 3, 4, 2)
        got_k, got_v = k * ks[..., None], v * vs[..., None]
        tol = np.abs(rows).max() / 127
    else:
        assert ks is None and vs is None
        got_k, got_v, tol = k, v, 0.0
    for i, page in enumerate([2, 5, 1]):
        np.testing.assert_allclose(got_k[:, i], rows[0, :, page - 1],
                                   atol=tol)
        np.testing.assert_allclose(got_v[:, i], rows[1, :, page - 1],
                                   atol=tol)
    # one layer at a time: the same stored bytes, heads split out
    for l in range(3):
        kl, vl, ksl, vsl = layer_pages(kv, l)
        assert kl.shape == (8, 4, 2, 4)
        np.testing.assert_array_equal(np.asarray(kl)[[2, 5, 1]], k[l])
        np.testing.assert_array_equal(np.asarray(vl)[[2, 5, 1]], v[l])
        if quant:
            np.testing.assert_array_equal(np.asarray(ksl)[[2, 5, 1]], ks[l])

    # the splice: into a fresh pool at other page ids, bit for bit
    dst = write_pages(init_kv_pages(cfg), jnp.asarray([6, 3, 7], jnp.int32),
                      *(None if a is None else jnp.asarray(a)
                        for a in (k, v, ks, vs)))
    assert dst.k.shape == kv.k.shape and dst.head_dim == kv.head_dim
    for a, b_ in zip(read_pages(dst, [6, 3, 7]), (k, v, ks, vs)):
        assert (a is None and b_ is None) or np.array_equal(a, b_)
    assert float(jnp.abs(dst.k[:, 1].astype(jnp.float32)).max()) == 0.0

    # copy-on-write fork and the failure scrub, every layer at once
    forked = fork_page(kv, jnp.int32(2), jnp.int32(7))
    for a, b_ in zip(read_pages(forked, [7]), read_pages(kv, [2])):
        assert (a is None and b_ is None) or np.array_equal(a, b_)
    for a, b_ in zip(read_pages(forked, [1, 2, 3, 4, 5]),
                     read_pages(kv, [1, 2, 3, 4, 5])):
        assert (a is None and b_ is None) or np.array_equal(a, b_)
    scrubbed = zero_pages(forked, jnp.asarray([2, 7], jnp.int32))
    for a in read_pages(scrubbed, [2, 7]):
        assert a is None or (a.shape[:3] == (3, 2, 4) and not a.any())
    for a, b_ in zip(read_pages(scrubbed, [1, 3, 4, 5]),
                     read_pages(kv, [1, 3, 4, 5])):
        assert (a is None and b_ is None) or np.array_equal(a, b_)


# ---------------------------------------------------------------------------
# pool + scheduler invariants
# ---------------------------------------------------------------------------


@serving
def test_page_pool_all_or_nothing_and_null_page():
    pool = PagePool(6)
    assert pool.num_usable == 5
    got = pool.alloc(5)
    assert got is not None and 0 not in got and len(set(got)) == 5
    assert pool.alloc(1) is None          # empty: refuse
    assert pool.num_free == 0
    pool.free(got[:2])
    assert pool.alloc(3) is None          # all-or-nothing: 2 < 3
    assert pool.num_free == 2             # refusal didn't consume
    pool.free(got[2:])
    assert pool.num_free == 5


@serving
def test_bucket_ladder():
    assert bucket_for(3, (4, 8, 16), 64) == 4
    assert bucket_for(8, (4, 8, 16), 64) == 8
    assert bucket_for(9, (4, 8, 16), 64) == 16
    assert bucket_for(17, (4, 8, 16), 64) == 32   # rounds up by top bucket
    assert bucket_for(60, (4, 8, 16), 64) == 64   # capped at max_seq_len


@serving
def test_scheduler_admission_refuses_when_pool_full():
    pool = PagePool(5)  # 4 usable pages
    sched = ContinuousBatchingScheduler(
        pool, SchedulerConfig(max_slots=4, page_size=4, max_pages_per_seq=4,
                              max_queue=2))
    # 7 prompt tokens + the 1-token decode margin = 8 -> 2 pages each
    a = Request(prompt=list(range(7)), max_tokens=4)
    b = Request(prompt=list(range(7)), max_tokens=4)
    c = Request(prompt=list(range(7)), max_tokens=4)
    assert sched.submit(a, now=0.0) and sched.submit(b, now=1.0)
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [a.rid, b.rid]
    assert pool.num_free == 0
    # pool exhausted: c queues but is NOT admitted
    assert sched.submit(c, now=2.0)
    assert sched.admit() == []
    assert c.status == "queued" and sched.queue_depth == 1
    # backpressure: queue is at max_queue=2 after d... submit d, e
    d = Request(prompt=[1, 2], max_tokens=2)
    assert sched.submit(d, now=3.0)
    e = Request(prompt=[1, 2], max_tokens=2)
    assert not sched.submit(e, now=4.0)   # queue full -> rejected
    assert e.status == "rejected"
    # infeasible requests are rejected outright, not queued
    f = Request(prompt=list(range(15)), max_tokens=4)  # 19 > 16 max_seq
    assert not sched.submit(f, now=5.0)
    # completion returns pages; c then fits
    sched.release(a)
    assert pool.num_free == 2
    assert [r.rid for r in sched.admit()] == [c.rid]


# ---------------------------------------------------------------------------
# end-to-end engine
# ---------------------------------------------------------------------------


def _small_model(seed=0, **kw):
    kw.setdefault("vocab_size", 50)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 2)
    kw.setdefault("head_dim", 8)
    kw.setdefault("max_positions", 128)
    model = DecoderLM(**kw)
    return model, model.init_params(jax.random.PRNGKey(seed))


@serving
def test_engine_parity_vs_nonpaged_oracle(rng):
    model, params = _small_model()
    eng = ServingEngine(model, params, eos_id=1, page_size=4, num_pages=40,
                        max_pages_per_seq=10, max_slots=4, buckets=(4, 8, 16))
    prompts = [rng.randint(2, 50, size=n).tolist()
               for n in (3, 4, 7, 11, 5, 2)]   # ragged; > max_slots
    rids = [eng.submit(p, max_tokens=10) for p in prompts]
    assert all(r is not None for r in rids)
    streamed = {}
    # exercise the streaming callback on one request
    rids[0] = eng.submit(prompts[0], max_tokens=10,
                         on_token=lambda t: streamed.setdefault("toks", []).append(t))
    res = eng.run(max_ticks=300)
    for p, rid in zip(prompts, rids):
        assert res[rid] == greedy_decode_reference(model, params, p, 10, 1)
    assert streamed["toks"] == res[rids[0]]
    # invariant: every page back (free or cached-reclaimable), no refs
    assert_drained(eng)
    snap = eng.metrics.snapshot()
    assert snap["requests_completed"] == len(prompts) + 1
    assert snap["tokens_generated"] >= len(prompts) + 1
    assert snap["page_occupancy"] == 0.0 and snap["page_occupancy_peak"] > 0


@serving
def test_one_tick_shape_and_one_attention_entry_in_the_package():
    """The tick has one shape and the serving attention one entry form:
    the engine takes no ``fuse_tick``, and the package exports none of
    the v1 decode wrappers or aliases, and only names that resolve."""
    from paddle_tpu import serving
    from paddle_tpu.serving import decode_attention, kv_cache

    model, params = _small_model(num_layers=1)
    with pytest.raises(TypeError, match="fuse_tick"):
        ServingEngine(model, params, eos_id=1, fuse_tick=False)
    gone = {"paged_decode_attention", "paged_decode_attention_reference",
            "_paged_decode_pallas", "_kernel_shape_ok", "_stored",
            "write_prompt"}
    assert not gone & set(serving.__all__)
    assert not [n for n in gone
                if any(hasattr(mod, n)
                       for mod in (serving, decode_attention, kv_cache))]
    assert not [n for n in serving.__all__ if not hasattr(serving, n)]
    assert len(set(serving.__all__)) == len(serving.__all__)


@serving
def test_engine_parity_with_pallas_kernel(rng):
    model, params = _small_model(num_layers=1)
    eng = ServingEngine(model, params, eos_id=1, page_size=8, num_pages=16,
                        max_pages_per_seq=4, max_slots=2, buckets=(4, 8),
                        use_kernel=True)   # force the kernel (interpret on CPU)
    prompts = [rng.randint(2, 50, size=n).tolist() for n in (3, 9)]
    rids = [eng.submit(p, max_tokens=6) for p in prompts]
    res = eng.run(max_ticks=100)
    for p, rid in zip(prompts, rids):
        assert res[rid] == greedy_decode_reference(model, params, p, 6, 1)


@serving
def test_engine_preemption_recovers_and_frees_pages(rng):
    model, params = _small_model(num_layers=1)
    # 7 usable pages of 4 tokens; 3 concurrent requests growing to
    # ceil((4+12)/4)=4 pages each -> growth must preempt
    eng = ServingEngine(model, params, eos_id=1, page_size=4, num_pages=8,
                        max_pages_per_seq=4, max_slots=3, buckets=(4, 8))
    prompts = [rng.randint(2, 50, size=4).tolist() for _ in range(3)]
    rids = [eng.submit(p, max_tokens=12) for p in prompts]
    res = eng.run(max_ticks=500)
    for p, rid in zip(prompts, rids):
        assert res[rid] == greedy_decode_reference(model, params, p, 12, 1)
    assert eng.metrics.preemptions > 0          # the pool actually thrashed
    assert_drained(eng)                         # nothing leaked


# ---------------------------------------------------------------------------
# greedy parity vs beam_search(beam_size=1)
# ---------------------------------------------------------------------------

V_B, H_B, D_B, T_B = 13, 2, 4, 6
E_B = H_B * D_B
BOS, EOS = 0, 1


class _OneLayerAttnLM:
    """Single attention layer, no positions, no residual/FFN: the exact
    math the beam-search cell below implements, as a DecodeModel."""

    num_layers, num_heads, head_dim, vocab_size = 1, H_B, D_B, V_B

    def embed(self, params, tokens, positions):
        return params["srv_emb"][tokens]

    def qkv(self, params, layer, x):
        shape = x.shape[:-1] + (H_B, D_B)
        return ((x @ params["srv_wq"]).reshape(shape),
                (x @ params["srv_wk"]).reshape(shape),
                (x @ params["srv_wv"]).reshape(shape))

    def attn_out(self, params, layer, ctx, x):
        return ctx.reshape(x.shape[:-1] + (E_B,))

    def logits(self, params, x):
        return x @ params["srv_wout"]


def _attn_beam_cell(token_emb, mem):
    """beam_search step layer: the memory carries the cell's whole output
    [probs | position | flattened K cache | flattened V cache] so
    single-layer causal attention decode is expressible as a dense
    recurrent memory — the in-graph twin of the serving engine's paged
    cache.  The memory links to the cell itself (so it sits on the
    probability layer's path) and the cell ignores the probs slice."""

    def cell_fn(ctx, p, ins):
        emb, m = ins
        n = emb.shape[0]
        pos = m[:, V_B].astype(jnp.int32)
        kv = m[:, V_B + 1:].reshape(n, 2, T_B, H_B, D_B)
        q = (emb @ p["wq"]).reshape(n, H_B, D_B)
        k = (emb @ p["wk"]).reshape(n, H_B, D_B)
        v = (emb @ p["wv"]).reshape(n, H_B, D_B)
        onehot = (jnp.arange(T_B)[None, :] == pos[:, None])
        kv = kv.at[:, 0].set(jnp.where(onehot[:, :, None, None],
                                       k[:, None], kv[:, 0]))
        kv = kv.at[:, 1].set(jnp.where(onehot[:, :, None, None],
                                       v[:, None], kv[:, 1]))
        s = jnp.einsum("nhd,nthd->nht", q, kv[:, 0]) * D_B ** -0.5
        live = jnp.arange(T_B)[None, None, :] <= pos[:, None, None]
        s = jnp.where(live, s, -1e30)
        attn = jax.nn.softmax(s, axis=-1)
        ctx_v = jnp.einsum("nht,nthd->nhd", attn, kv[:, 1])
        probs = jax.nn.softmax(ctx_v.reshape(n, E_B) @ p["wout"], axis=-1)
        return jnp.concatenate(
            [probs, (pos + 1)[:, None].astype(jnp.float32),
             kv.reshape(n, -1)], axis=1)

    cell = LayerOutput(
        name="srv_attn_cell",
        layer_type="serving_cell", inputs=[token_emb, mem], fn=cell_fn,
        params={
            "wq": ParamSpec((E_B, E_B), ParamAttr(name="srv_wq")),
            "wk": ParamSpec((E_B, E_B), ParamAttr(name="srv_wk")),
            "wv": ParamSpec((E_B, E_B), ParamAttr(name="srv_wv")),
            "wout": ParamSpec((E_B, V_B), ParamAttr(name="srv_wout")),
        },
        size=V_B + 1 + 2 * T_B * E_B)
    probs = layer.mixed(input=[layer.identity_projection(cell, offset=0,
                                                         size=V_B)],
                        size=V_B, name="srv_probs")
    return probs


@serving
def test_engine_greedy_matches_beam_size_1():
    paddle.topology.reset_name_scope()
    start = layer.data(name="start", type=paddle.data_type.dense_vector(E_B))

    def step(token_emb, _static_start):
        mem = layer.memory(name="srv_attn_cell",
                           size=V_B + 1 + 2 * T_B * E_B)
        return _attn_beam_cell(token_emb, mem)

    beam = beam_search(
        step=step,
        input=[GeneratedInput(size=V_B, embedding_name="srv_emb",
                              embedding_size=E_B),
               layer.StaticInput(start)],
        bos_id=BOS, eos_id=EOS, beam_size=1, max_length=T_B, name="srv_gen")
    topo = paddle.topology.Topology([beam])
    params = paddle.Parameters.from_topology(topo, seed=7)

    outs, _ = topo.forward(params.as_dict(), topo.init_state(),
                           {"start": jnp.zeros((1, E_B), jnp.float32)})
    tokens, lengths, _scores = (np.asarray(o) for o in outs[0])
    beam_tokens = tokens[0, 0, :int(lengths[0, 0])].tolist()

    # the serving engine decodes the same weights from prompt [BOS]
    model = _OneLayerAttnLM()
    eng = ServingEngine(model, params.as_dict(), eos_id=EOS, page_size=2,
                        num_pages=8, max_pages_per_seq=4, max_slots=2,
                        buckets=(2, 4))
    rid = eng.submit([BOS], max_tokens=T_B)
    res = eng.run(max_ticks=50)
    assert res[rid] == beam_tokens
    # and both match the non-paged oracle
    assert res[rid] == greedy_decode_reference(model, params.as_dict(),
                                               [BOS], T_B, EOS)


# ---------------------------------------------------------------------------
# Inference.infer tail padding + model_state forwarding (satellites)
# ---------------------------------------------------------------------------


def test_infer_pads_partial_tail_batch(rng):
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(4))
    y = layer.fc(input=x, size=3, act="softmax", name="y")
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([y]), seed=0)
    data = [(rng.randn(4).astype(np.float32),) for _ in range(11)]
    inf = paddle.Inference(y, params)
    out = inf.infer(data, batch_size=4)       # 4+4+3: tail padded to 4
    assert out.shape == (11, 3)
    ref = inf.infer(data[:4], batch_size=4)   # full batch, no padding
    np.testing.assert_allclose(out[:4], ref, rtol=1e-6)
    # single short batch pads to a power of two and still slices back
    out3 = inf.infer(data[:3], batch_size=256)
    assert out3.shape == (3, 3)
    np.testing.assert_allclose(out3, out[:3], rtol=1e-6)


def test_module_infer_forwards_model_state(rng):
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(4))
    h = layer.fc(input=x, size=4, act="relu", name="h")
    hb = layer.batch_norm(input=h, name="hb")
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([hb]), seed=0)
    data = [(rng.randn(4).astype(np.float32),) for _ in range(3)]
    # fake trained moving stats: shift the mean, make variance tiny
    state = paddle.topology.Topology([hb]).init_state()
    assert "hb" in state
    state = {"hb": {k: v + 0.5 for k, v in state["hb"].items()}}
    base = paddle.infer(output_layer=hb, parameters=params, input=data)
    shifted = paddle.infer(output_layer=hb, parameters=params, input=data,
                           model_state=state)
    assert not np.allclose(base, shifted)
