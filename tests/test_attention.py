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
