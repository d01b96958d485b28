"""Flash attention kernel vs plain-JAX oracle (interpret mode on CPU).

Mirrors the reference's CPU-vs-GPU parity strategy
(paddle/math/tests/test_matrixCompare.cpp): same op, two execution paths,
outputs and gradients compared.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention


def _mk(rng, b, s, h, d):
    q = rng.randn(b, s, h, d).astype(np.float32)
    k = rng.randn(b, s, h, d).astype(np.float32)
    v = rng.randn(b, s, h, d).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _segments(rng, b, s, n_seq):
    # packed segments: random cut points
    out = np.zeros((b, s), np.int32)
    for i in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s), n_seq - 1, replace=False))
        seg = 0
        prev = 0
        for c in list(cuts) + [s]:
            out[i, prev:c] = seg
            seg += 1
            prev = c
    return jnp.asarray(out)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(rng, causal):
    q, k, v = _mk(rng, 2, 128, 2, 32)
    out = attention.flash_attention(q, k, v, causal=causal, block_q=64,
                                    block_k=64)
    ref = attention.mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_masking(rng, causal):
    q, k, v = _mk(rng, 2, 128, 2, 32)
    seg = _segments(rng, 2, 128, 4)
    out = attention.flash_attention(q, k, v, segment_ids=seg, causal=causal,
                                    block_q=64, block_k=64)
    ref = attention.mha_reference(q, k, v, segment_ids=seg, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_grad_matches_reference(rng):
    q, k, v = _mk(rng, 1, 64, 2, 16)
    seg = _segments(rng, 1, 64, 3)

    def loss_flash(q, k, v):
        o = attention.flash_attention(q, k, v, segment_ids=seg, causal=True,
                                      block_q=32, block_k=32)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = attention.mha_reference(q, k, v, segment_ids=seg, causal=True)
        return jnp.sum(o * o)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_cross_attention(rng):
    q = jnp.asarray(rng.randn(2, 64, 2, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 128, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 128, 2, 16).astype(np.float32))
    out = attention.flash_attention(q, k, v, block_q=32, block_k=64)
    ref = attention.mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_causal_cross_attention_grads(rng):
    """Causal CROSS-attention with seq_k > seq_q through the backward pass:
    the dK/dV kernel's streamed q-tile index (kj*block_k)//block_q exceeds
    the last q block for late key blocks, which an earlier clamp let
    through as an out-of-range block index (ADVICE r5 item 1). Forward and
    all three grads must match the oracle."""
    q = jnp.asarray(rng.randn(2, 32, 2, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 128, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 128, 2, 16).astype(np.float32))

    out = attention.flash_attention(q, k, v, causal=True, block_q=32,
                                    block_k=32)
    ref = attention.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    g1 = jax.grad(lambda q, k, v: jnp.sum(attention.flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(attention.mha_reference(
        q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   err_msg=f"d{name}")


def test_flash_pv_f32_matches_default_in_f32(rng):
    """FLAGS.attn_pv_f32 only changes the PV/dS operand dtype: in an f32
    model both paths are identical math (the flag's effect is bf16-only)."""
    from paddle_tpu.platform.flags import FLAGS

    q, k, v = _mk(rng, 2, 64, 2, 16)
    seg = _segments(rng, 2, 64, 3)

    def loss(q, k, v):
        o = attention.flash_attention(q, k, v, segment_ids=seg, causal=True,
                                      block_q=32, block_k=32)
        return jnp.sum(o * o)

    old = FLAGS.attn_pv_f32
    try:
        FLAGS.attn_pv_f32 = False
        o0 = attention.flash_attention(q, k, v, segment_ids=seg, causal=True,
                                       block_q=32, block_k=32)
        g0 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        FLAGS.attn_pv_f32 = True
        o1 = attention.flash_attention(q, k, v, segment_ids=seg, causal=True,
                                       block_q=32, block_k=32)
        g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        FLAGS.attn_pv_f32 = old
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o0))
    for a, b in zip(g1, g0):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_matches_reference_grad(rng, causal):
    """The pallas dQ/dK/dV kernels against ``jax.grad`` through
    ``mha_reference`` on the same segments and a non-trivial cotangent
    (d sin(o)), at unequal query and key blocks."""
    q, k, v = _mk(rng, 2, 128, 2, 32)
    seg = _segments(rng, 2, 128, 3)

    def loss_flash(q, k, v):
        o = attention.flash_attention(q, k, v, segment_ids=seg,
                                      causal=causal, block_q=32, block_k=64)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = attention.mha_reference(q, k, v, segment_ids=seg, causal=causal)
        return jnp.sum(jnp.sin(o))

    g_pallas = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pallas, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_block_segment_skip_parity(rng, causal):
    """Segments aligned to block boundaries (the packed-LM bench layout):
    most (q, k) block pairs are cross-segment and take the runtime
    disjoint-range skip; output and grads must still match the oracle."""
    b, s, h, d = 1, 256, 2, 32
    q, k, v = _mk(rng, b, s, h, d)
    # 4 segments of 64 = exactly 2 blocks each at block 32
    seg = jnp.asarray(np.repeat(np.arange(4, dtype=np.int32), 64)[None, :])

    def loss_flash(q, k, v):
        o = attention.flash_attention(q, k, v, segment_ids=seg,
                                      causal=causal, block_q=32, block_k=32)
        return jnp.sum(jnp.cos(o))

    def loss_ref(q, k, v):
        o = attention.mha_reference(q, k, v, segment_ids=seg, causal=causal)
        return jnp.sum(jnp.cos(o))

    np.testing.assert_allclose(
        np.asarray(loss_flash(q, k, v)), np.asarray(loss_ref(q, k, v)),
        rtol=1e-5)
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4)


def test_flash_bf16_inputs_match_oracle(rng):
    """bf16 tiles ride the MXU natively (no f32 upcast before the dots);
    outputs and grads must match the f32 oracle within bf16 tolerance."""
    q, k, v = _mk(rng, 1, 128, 2, 32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    seg = _segments(rng, 1, 128, 2)

    out = attention.flash_attention(qb, kb, vb, segment_ids=seg,
                                    causal=True, block_q=64, block_k=64)
    ref = attention.mha_reference(q, k, v, segment_ids=seg, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=3e-2)

    def loss_flash(q_, k_, v_):
        o = attention.flash_attention(q_, k_, v_, segment_ids=seg,
                                      causal=True, block_q=64, block_k=64)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q_, k_, v_):
        o = attention.mha_reference(q_, k_, v_, segment_ids=seg, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_), atol=0.15)


# ---------------------------------------------------------------------------
# The block schedule: the kernels' streamed axis visits live blocks only
# ---------------------------------------------------------------------------

def _equal(n, s=8192):
    return np.repeat(np.arange(n, dtype=np.int32), s // n)


def _docs(lengths, s):
    """Documents back to back, then a padding tail under the next id."""
    seg = np.full(s, len(lengths), np.int32)
    seg[:sum(lengths)] = np.repeat(np.arange(len(lengths)), lengths)
    return seg


_DOCS20 = _docs([310, 122, 640, 256, 97, 1480, 233, 512, 61, 305, 188, 420,
                 75, 266, 904, 150, 333, 47, 211, 590], 8192)      # 7200 + pad


def _walk_by_hand(q_seg, kv_seg, bq, bk, causal, by="q"):
    """The live (i, j) pairs, counted pair by pair from the ids, in the
    order the forward and dQ (``by='q'``) or dKV (``by='k'``) take them."""
    nq, nk = len(q_seg) // bq, len(kv_seg) // bk
    pairs = []
    for i in range(nq):
        qs = q_seg[i * bq:(i + 1) * bq]
        for j in range(nk):
            ks = kv_seg[j * bk:(j + 1) * bk]
            meet = qs.max() >= ks.min() and qs.min() <= ks.max()
            if meet and (not causal or j * bk < (i + 1) * bq):
                pairs.append((i, j))
    return pairs if by == "q" else sorted(pairs, key=lambda p: (p[1], p[0]))


@pytest.mark.parametrize("name,seg,causal,visits", [
    ("4x2048", _equal(4), True, 40),
    ("1x8192", _equal(1), True, 136),
    ("2x4096", _equal(2), True, 72),
    ("20docs", _DOCS20, True, None),
    ("4x2048-full", _equal(4), False, 64),
    ("1x8192-full", _equal(1), False, 256),
])
def test_schedule_counts_by_hand(name, seg, causal, visits):
    """One row of 8192 tokens at block 512 (the train cells' call): the
    square has 256 blocks, the walk has the live ones, in order."""
    by_q, by_k = attention.block_schedule(jnp.asarray(seg)[None],
                                          jnp.asarray(seg)[None], 512, 512,
                                          causal)
    want = _walk_by_hand(seg, seg, 512, 512, causal)
    if visits is not None:
        assert len(want) == visits
    assert int(by_q.count[0]) == int(by_k.count[0]) == len(want)
    assert by_q.resident.shape == (1, 136 if causal else 256)
    n = len(want)
    got = list(zip(np.asarray(by_q.resident[0, :n]).tolist(),
                   np.asarray(by_q.streamed[0, :n]).tolist()))
    assert got == want
    # dKV's walk is the forward's transposed: k block resident, by j then i
    got_k = list(zip(np.asarray(by_k.streamed[0, :n]).tolist(),
                     np.asarray(by_k.resident[0, :n]).tolist()))
    assert got_k == _walk_by_hand(seg, seg, 512, 512, causal, by="k")
    for walk in (by_q, by_k):
        res, flags = np.asarray(walk.resident[0]), np.asarray(walk.flags[0])
        first = np.r_[True, res[1:n] != res[:n - 1]]
        last = np.r_[res[1:n] != res[:n - 1], True]
        assert np.array_equal(flags[:n] & 1, first)
        assert np.array_equal((flags[:n] & 2) != 0, last)
        assert np.all(flags[:n] & 4) and not flags[n:].any()
        # the tail repeats the last visit: no tile moves, nothing is done
        assert np.all(res[n:] == res[n - 1])
        assert np.all(np.asarray(walk.streamed[0, n:]) ==
                      np.asarray(walk.streamed[0, n - 1]))


def test_schedule_keeps_one_visit_for_a_row_without_live_blocks(rng):
    """Cross-attention with disjoint ids: no block is live, every resident
    block keeps one visit that computes nothing, and the kernel still writes
    zeros (and zero gradients) as the square grid's ``_finalize`` did."""
    q, k, v = _mk(rng, 1, 128, 2, 16)
    q_seg = jnp.full((1, 128), 7, jnp.int32)
    kv_seg = jnp.asarray(np.repeat(np.arange(2, dtype=np.int32), 64))[None]
    by_q, by_k = attention.block_schedule(q_seg, kv_seg, 32, 32, False)
    assert by_q.count.tolist() == [4] and by_k.count.tolist() == [4]
    assert by_q.resident[0, :4].tolist() == [0, 1, 2, 3]
    assert by_q.flags[0, :4].tolist() == [3, 3, 3, 3]     # first | last

    def loss(q, k, v):
        o = attention.flash_attention(q, k, v, segment_ids=q_seg,
                                      kv_segment_ids=kv_seg, block_q=32,
                                      block_k=32)
        return jnp.sum(o * o) + jnp.sum(o)

    out = attention.flash_attention(q, k, v, segment_ids=q_seg,
                                    kv_segment_ids=kv_seg, block_q=32,
                                    block_k=32)
    assert not np.asarray(out).any()
    for g in jax.grad(loss, argnums=(0, 1, 2))(q, k, v):
        assert not np.asarray(g).any()


def test_schedule_of_a_batch_is_a_walk_a_row():
    """Rows of one batch differ: the arrays' extent is the bound's, the
    grid's is the longest row's, and a shorter row idles behind its count."""
    seg = jnp.asarray(np.stack([_equal(4, 256), _equal(1, 256)]))
    by_q, _ = attention.block_schedule(seg, seg, 32, 32, True)
    assert by_q.count.tolist() == [4 * 3, 36]
    assert not np.asarray(by_q.flags[0, 12:]).any()


_S = 128        # 4 x 4 blocks of 32


def _layout(name):
    if name == "4 equal":
        return _equal(4, _S)[None]
    if name == "one":
        return _equal(1, _S)[None]
    if name == "2 equal":
        return _equal(2, _S)[None]
    if name == "docs and pad":
        return _docs([21, 40, 9, 33], _S)[None]
    assert name == "batch of two"
    return np.stack([_equal(4, _S), _docs([50, 13, 30], _S)])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("name", ["4 equal", "one", "2 equal", "docs and pad",
                                  "batch of two"])
def test_live_block_walk_matches_reference(rng, name, causal, dtype):
    """Forward and lse-consistent gradients (dq, dk, dv) of the scheduled
    kernels against ``mha_reference`` / ``jax.grad``, layout by layout."""
    seg = jnp.asarray(_layout(name))
    q, k, v = _mk(rng, seg.shape[0], _S, 2, 16)
    qd, kd, vd = (x.astype(dtype) for x in (q, k, v))
    out_tol, grad_tol = (2e-5, 5e-4) if dtype == "float32" else (3e-2, 0.15)

    def loss_flash(q_, k_, v_):
        o = attention.flash_attention(q_, k_, v_, segment_ids=seg,
                                      causal=causal, block_q=32, block_k=32)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q_, k_, v_):
        o = attention.mha_reference(q_, k_, v_, segment_ids=seg,
                                    causal=causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    out = attention.flash_attention(qd, kd, vd, segment_ids=seg,
                                    causal=causal, block_q=32, block_k=32)
    ref = attention.mha_reference(q, k, v, segment_ids=seg, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=out_tol)
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(qd, kd, vd)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_), atol=grad_tol)


# name: (segment ids, sha256[:16] of mha_reference's output, of the kernels'
# out + dq + dk + dv) as commit 6866502 (the square (i, j) grid) gave them
# here in interpret mode: float32, causal, blocks of 32, RandomState(1234)
_PARENT = {
    "4 equal": (_equal(4, _S)[None], "9d258cfb6fa26788", "1bd8649b5cdc943c"),
    "docs and pad": (_docs([21, 40, 9, 33], _S)[None],
                     "adbc2feb26555076", "63997e1df97dbeac"),
    "batch of two": (np.stack([_equal(2, _S), _docs([50, 13, 30], _S)]),
                     "ff170b59405fb594", "7138900ef8d499b4"),
}


def _digest(*arrays):
    import hashlib
    return hashlib.sha256(b"".join(np.asarray(a).tobytes()
                                   for a in arrays)).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(_PARENT))
def test_walk_equals_the_square_grid_bit_for_bit(name):
    """A row's live blocks are accumulated in the order the square grid took
    them and a dead step did nothing, so the results are the parent's to the
    last bit.  The plain-JAX reference's digest tells whether this host's
    XLA rounds as the recording's did; where it does not, the bits of the
    recording say nothing and closeness to the reference stands in."""
    seg, probe, want = _PARENT[name]
    seg = jnp.asarray(seg)
    rng = np.random.RandomState(1234)
    q, k, v = (jnp.asarray(rng.randn(seg.shape[0], _S, 2, 16)
                           .astype(np.float32)) for _ in range(3))

    def loss(q, k, v):
        o = attention.flash_attention(q, k, v, segment_ids=seg, causal=True,
                                      block_q=32, block_k=32)
        return jnp.sum(o * o), o

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    ref = attention.mha_reference(q, k, v, segment_ids=seg, causal=True)
    if _digest(ref) == probe:
        assert _digest(out, *grads) == want
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
