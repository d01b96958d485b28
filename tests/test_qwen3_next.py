"""The ``qwen3_next`` layers and model against the benchmark's plain
reference (``benchmarks/references/qwen3_next.py``), at tiny widths on the
CPU with seeded weights: the chunked gated delta rule against the
per-token recurrence, the whole delta-rule layer with its convolution,
gated grouped-query attention, softmax routing, the expert layer whole and
as a rank's share, and one whole model through ``trainer.SGD``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import event, optimizer, trainer
from paddle_tpu.ops import gated_delta as gd
from paddle_tpu.ops import gdn_conv_kernels as gck
from paddle_tpu.ops.gated_attention import gated_attention
from paddle_tpu.parallel import moe as pmoe
from paddle_tpu.platform.flags import FLAGS

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import cells, weights  # noqa: E402

REF = cells.load_module(os.path.join(BENCH, "references", "qwen3_next.py"))
FAMILY = cells.load_module(os.path.join(BENCH, "families", "qwen3_next.py"))
TINY = cells.load_json(os.path.join(BENCH, "tests", "configs",
                                    "tiny-qwen3next.json"))


@pytest.fixture
def f32_products():
    """The program's products in float32, so that it and the reference
    differ by summation order only."""
    was = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    yield
    FLAGS.use_bf16 = was


def normal(key, *shape, std=1.0):
    return std * jax.random.normal(jax.random.PRNGKey(key), shape,
                                   jnp.float32)


def uniform(key, lo, hi, *shape):
    return jax.random.uniform(jax.random.PRNGKey(key), shape, jnp.float32,
                              lo, hi)


def close(got, want, rtol=2e-4):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= rtol * scale


def segments(lengths, cap=None):
    """(positions, segment ids) of a packed buffer, padded to ``cap``."""
    cap = cap or sum(lengths)
    pad = cap - sum(lengths)
    pos = np.concatenate([np.arange(n) for n in lengths] + [np.zeros(pad)])
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lengths)]
                         + [np.full(pad, len(lengths))])
    return jnp.asarray(pos, jnp.int32), jnp.asarray(seg, jnp.int32)


# ---- the gated delta rule ------------------------------------------------------

HK, HV, DK, DV = 2, 4, 16, 8


@pytest.mark.parametrize("lengths", [[128], [200], [50], [70, 130, 100]],
                         ids=["two-chunks", "not-a-multiple", "under-a-chunk",
                              "three-segments"])
def test_chunked_delta_rule_is_the_recurrence(f32_products, lengths):
    """Values and the gradient of every input; decays slow enough (0.9 to
    0.999 a token) that the state crosses chunks, and segments that start
    inside a chunk (state from zero at each)."""
    t = sum(lengths)
    _, seg = segments(lengths)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(normal(1, t, HK, DK)) * DK ** -0.5
    k = unit(normal(2, t, HK, DK))
    v = normal(3, t, HV, DV)
    g = -uniform(4, 0.001, 0.1, t, HV)
    beta = uniform(5, 0.1, 0.9, t, HV)
    wide = lambda a: jnp.repeat(a, HV // HK, axis=1)  # noqa: E731

    def ours(q, k, v, g, beta):
        return gd.gated_delta_rule(q, k, v, g, beta, seg)

    def theirs(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return REF.delta_rule(wide(q), wide(k), v, g, beta, seg, "f32",
                                  block_rows=t)

    args = (q, k, v, g, beta)
    want = jax.jit(theirs)(*args)
    assert float(jnp.abs(want).max()) > 0.1
    close(jax.jit(ours)(*args), want, rtol=1e-5)
    probe = normal(6, t, HV, DV)
    grads = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(grads(ours), grads(theirs)):
        close(a, b, rtol=2e-5)


@pytest.mark.parametrize("keys", ["aligned", "alternating"])
def test_chunked_delta_rule_with_keys_that_line_up(f32_products, keys):
    """All of a chunk's keys nearly the same vector (or its negative in
    turn), beta near 1, hardly any decay: ``I + A`` is then a triangle of
    ones, whose inverse is benign but whose powers reach binomial(64, 32).
    A trained layer's keys do line up; the form has to survive it."""
    t = 192
    _, seg = segments([t])
    k0 = normal(7, 1, HK, DK)
    sign = (-1.0) ** jnp.arange(t)[:, None, None] if keys == "alternating" \
        else 1.0
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    k = unit(k0 * sign + 0.01 * normal(8, t, HK, DK))
    q = unit(normal(1, t, HK, DK)) * DK ** -0.5
    v = normal(3, t, HV, DV)
    g = -uniform(4, 0.0005, 0.01, t, HV)
    beta = uniform(5, 0.9, 0.999, t, HV)
    wide = lambda a: jnp.repeat(a, HV // HK, axis=1)  # noqa: E731
    args = (q, k, v, g, beta)
    ours = lambda *a: gd.gated_delta_rule(*a, seg)  # noqa: E731

    def theirs(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return REF.delta_rule(wide(q), wide(k), v, g, beta, seg, "f32",
                                  block_rows=t)

    close(jax.jit(ours)(*args), jax.jit(theirs)(*args), rtol=2e-5)
    probe = normal(6, t, HV, DV)
    grads = lambda f: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(grads(ours), grads(theirs)):
        close(a, b, rtol=1e-4)


def test_a_later_segment_sees_nothing_of_an_earlier_one(f32_products):
    _, seg = segments([70, 130])
    args = [normal(i, 200, h, d) for i, (h, d) in
            enumerate([(HK, DK), (HK, DK), (HV, DV)])]
    args[1] = args[1] / jnp.linalg.norm(args[1], axis=-1, keepdims=True)
    g, beta = -uniform(4, 0.001, 0.1, 200, HV), uniform(5, 0.1, 0.9, 200, HV)
    whole = gd.gated_delta_rule(*args, g, beta, seg)
    alone = gd.gated_delta_rule(*(a[70:] for a in args), g[70:], beta[70:],
                                seg[70:])
    close(whole[70:], alone, rtol=1e-5)


def rule_inputs(t, hk, hv):
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    return (unit(normal(1, t, hk, DK)) * DK ** -0.5,
            unit(normal(2, t, hk, DK)), normal(3, t, hv, DV),
            -uniform(4, 0.001, 0.1, t, hv), uniform(5, 0.1, 0.9, t, hv))


def recurrence(seg, rep):
    """The reference's per-token recurrence in float32, a key head's
    arrays repeated for the value heads it serves."""
    wide = lambda a: jnp.repeat(a, rep, axis=1)  # noqa: E731

    def theirs(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return REF.delta_rule(wide(q), wide(k), v, g, beta, seg, "f32",
                                  block_rows=seg.shape[0])
    return theirs


def grads_of(f, args, probe):
    return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * probe),
                            argnums=(0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("lengths", [[100], [63, 130]],
                         ids=["not-a-multiple", "starts-in-a-last-row"])
@pytest.mark.parametrize("rep", [1, 2])
def test_delta_rule_kernels_serve_one_or_two_value_heads_a_key_head(
        f32_products, rep, lengths):
    """A grid cell of the ``gdn_*`` kernels is a chunk of one key head and
    its ``Hv / Hk`` value heads; a buffer that ends inside a chunk (its
    padding a segment of its own), and a sequence whose first row is a
    chunk's last (it reads no state there, and alone writes it)."""
    t, hv = sum(lengths), 2 * rep
    _, seg = segments(lengths)
    args = rule_inputs(t, 2, hv)
    ours = lambda *a: gd.gated_delta_rule(*a, seg)  # noqa: E731
    theirs = recurrence(seg, rep)
    want = jax.jit(theirs)(*args)
    assert float(jnp.abs(want).max()) > 0.1
    close(jax.jit(ours)(*args), want, rtol=1e-5)
    probe = normal(6, t, hv, DV)
    for a, b in zip(grads_of(ours, args, probe),
                    grads_of(theirs, args, probe)):
        close(a, b, rtol=2e-5)


def test_delta_rule_with_bfloat16_operands_stays_near_the_recurrence():
    """Under ``FLAGS.use_bf16`` (the default) the products take bfloat16
    operands and accumulate in float32; the inverse, the decays and the
    state stay float32.  Against the float32 recurrence the values read
    5.0e-3 of their largest here and the five gradients 4.4e-3 to 6.6e-3
    (the plain chunked form that the kernels replaced: 5.0e-3, and 4.8e-3
    to 7.1e-3); the limits are twice that."""
    assert FLAGS.use_bf16
    lengths = [70, 130, 100]
    t = sum(lengths)
    _, seg = segments(lengths)
    args = rule_inputs(t, HK, HV)
    ours = lambda *a: gd.gated_delta_rule(*a, seg)  # noqa: E731
    theirs = recurrence(seg, HV // HK)
    close(jax.jit(ours)(*args), jax.jit(theirs)(*args), rtol=1e-2)
    probe = normal(6, t, HV, DV)
    for a, b in zip(grads_of(ours, args, probe),
                    grads_of(theirs, args, probe)):
        close(a, b, rtol=1.5e-2)


E = 32


def delta_weights():
    nq, nv = HK * DK, HV * DV
    return {"w_qkvz": normal(11, E, 2 * nq + 2 * nv, std=E ** -0.5),
            "w_ba": normal(12, E, 2 * HV, std=E ** -0.5),
            "conv": normal(13, 2 * nq + nv, 4, std=0.5),
            "a_log": normal(14, HV, std=0.5) - 2.0,
            "dt_bias": normal(15, HV, std=0.5) - 1.0,
            "norm_g": 1 + normal(16, DV, std=0.1),
            "wo": normal(17, nv, E, std=nv ** -0.5)}


def test_delta_rule_layer_forward_and_gradients_with_packed_segments(
        f32_products):
    """The whole mixing layer (projections, convolution that starts anew
    with each sequence, gates, recurrence, gated norm, output) on three
    sequences and padding."""
    w = delta_weights()
    prog = {k.replace("norm_g", "norm"): v for k, v in w.items()}
    _, seg = segments([90, 37, 65], 200)
    x = normal(18, 200, E)
    real = (seg < 3)[:, None]

    def ours(x, p):
        y = gd.gated_delta_net(x, seg, p, num_k_heads=HK, num_v_heads=HV,
                               head_k_dim=DK, head_v_dim=DV, eps=1e-6)
        return jnp.where(real, y, 0.0)

    def theirs(x, p):
        with jax.default_matmul_precision("highest"):
            y = REF.gated_delta_net(x, seg, p, k_heads=HK, v_heads=HV, dk=DK,
                                    dv=DV, eps=1e-6, mode="f32",
                                    block_rows=40)
        return jnp.where(real, y, 0.0)

    close(ours(x, prog), theirs(x, w))
    probe = normal(19, 200, E)
    g_ours = jax.jit(jax.grad(lambda x, p: jnp.sum(ours(x, p) * probe),
                              argnums=(0, 1)))(x, prog)
    g_ref = jax.jit(jax.grad(lambda x, p: jnp.sum(theirs(x, p) * probe),
                             argnums=(0, 1)))(x, w)
    close(g_ours[0], g_ref[0], rtol=1e-3)
    for k, g in g_ref[1].items():
        close(g_ours[1][k.replace("norm_g", "norm")], g, rtol=1e-3)


# ---- the fused prologue: convolution, SiLU, q/k normalisation -------------------

WIDE = gck.Dims(2, 4, 128, 128)       # two heads a column block


@pytest.fixture
def small_tiles(monkeypatch):
    """Row tiles of 16 and chunks of 8, so that a buffer of some tens of
    rows has several tiles (the calls are jitted on their shapes)."""
    monkeypatch.setattr(gck, "ROWS", 16)
    monkeypatch.setattr(gck, "CHUNK_ROWS", 8)
    gck._conv_fwd.clear_cache()
    gck._conv_bwd.clear_cache()
    yield
    gck._conv_fwd.clear_cache()
    gck._conv_bwd.clear_cache()


@pytest.mark.parametrize("lengths", [
    [16, 32], [15, 33], [14, 34], [13, 1, 2, 3, 29], [20, 21], [5]],
    ids=["begins-at-a-tiles-first-row", "begins-at-a-tiles-last-row",
         "begins-inside-the-halo", "shorter-than-the-taps",
         "not-a-multiple-of-the-tile", "under-a-sublane-tile"])
def test_fused_prologue_is_the_xla_composition(small_tiles, lengths):
    """``qkv_conv`` against ``silu(causal_conv(...))`` and the
    normalisation (``qkv_conv_xla``), forward and through ``jax.grad`` for the projection's
    columns (the ``z`` columns' share is zero) and the taps."""
    t = sum(lengths)
    _, seg = segments(lengths)
    qkvz = normal(51, t, WIDE.channels + WIDE.nv)
    w = normal(52, WIDE.channels, 4, std=0.5)
    want = gd.qkv_conv_xla(qkvz, w, seg, WIDE)
    got = gck.qkv_conv(qkvz, w, seg, WIDE)
    for a, b in zip(got, want):
        close(a, b, rtol=1e-5)
    probes = [normal(53 + i, *a.shape) for i, a in enumerate(want)]

    def loss(f):
        return lambda x, w: sum(jnp.sum(o * p) for o, p in
                                zip(f(x, w, seg, WIDE), probes))

    g_got = jax.grad(loss(gck.qkv_conv), argnums=(0, 1))(qkvz, w)
    g_want = jax.grad(loss(gd.qkv_conv_xla), argnums=(0, 1))(qkvz, w)
    close(g_got[0], g_want[0], rtol=1e-5)
    close(g_got[1], g_want[1], rtol=1e-5)
    assert not np.asarray(g_got[0][:, WIDE.channels:]).any()


def test_fused_prologue_at_the_tile_the_chip_runs():
    """The row tile and chunk as they stand, one whole tile and a part."""
    dims = gck.Dims(1, 2, 128, 128)
    _, seg = segments([200, 3, 97])
    qkvz, w = normal(56, 300, dims.channels + dims.nv), normal(57, 512, 4)
    for a, b in zip(gck.qkv_conv(qkvz, w, seg, dims),
                    gd.qkv_conv_xla(qkvz, w, seg, dims)):
        close(a, b, rtol=1e-5)


@pytest.mark.parametrize("widths, lanes", [
    ((16, 32, 128, 128), 1024), ((2, 4, 128, 128), 256),
    ((2, 2, 128, 256), 256), ((2, 2, 256, 128), 256),
    ((2, 4, 16, 8), None), ((1, 2, 4, 4), None), ((1, 1, 128, 256), None)],
    ids=["the-cell", "two-heads", "wide-values", "wide-keys", "tiny",
         "layer-sweep", "no-common-block"])
def test_column_blocks_are_whole_heads_in_whole_lane_tiles(widths, lanes):
    dims = gck.Dims(*widths)
    assert gck.lane_block(dims) == lanes
    assert gd.conv_is_fused(*widths) == (lanes is not None)
    if lanes:
        assert dims.nq % lanes == 0 and dims.nv % lanes == 0
        assert lanes % dims.dk == 0 and lanes % dims.dv == 0


def test_delta_rule_layer_takes_the_fused_prologue_at_heads_of_128(
        f32_products, monkeypatch):
    """The whole layer at one key and two value heads of 128 lanes: with
    the kernels and, the shape test turned off, with the XLA composition;
    forward and gradients."""
    hk, hv, d = 1, 2, 128
    p = {"w_qkvz": normal(61, E, 6 * d, std=E ** -0.5),
         "w_ba": normal(62, E, 2 * hv, std=E ** -0.5),
         "conv": normal(63, 4 * d, 4, std=0.5),
         "a_log": normal(64, hv, std=0.5) - 2.0,
         "dt_bias": normal(65, hv, std=0.5) - 1.0,
         "norm": 1 + normal(66, d, std=0.1),
         "wo": normal(67, hv * d, E, std=(hv * d) ** -0.5)}
    _, seg = segments([70, 2, 56])
    x, probe = normal(68, 128, E), normal(69, 128, E)

    def layer_(x, p):
        return jnp.sum(probe * gd.gated_delta_net(
            x, seg, p, num_k_heads=hk, num_v_heads=hv, head_k_dim=d,
            head_v_dim=d))

    calls = []
    fused = gck.qkv_conv
    monkeypatch.setattr(gck, "qkv_conv",
                        lambda *a: calls.append(1) or fused(*a))
    got = jax.value_and_grad(layer_, argnums=(0, 1))(x, p)
    assert calls
    monkeypatch.setattr(gd, "conv_is_fused", lambda *a: False)
    del calls[:]
    want = jax.value_and_grad(layer_, argnums=(0, 1))(x, p)
    assert not calls
    close(got[0], want[0], rtol=1e-5)
    close(got[1][0], want[1][0], rtol=1e-4)
    for k, g in want[1][1].items():
        close(got[1][1][k], g, rtol=1e-4)


def test_delta_rule_layer_counts_the_rows_its_kernels_took():
    """``gdn_conv_fused_rows_total``: the buffer's rows a step where the
    layer's heads fill whole lane tiles, and no series at other widths."""
    from paddle_tpu import layer
    from paddle_tpu.obs import default_registry

    def train(name, d):
        paddle.topology.reset_name_scope()
        words = layer.data(
            name="w", type=paddle.data_type.integer_value_sequence(30))
        y = layer.data(name="y", type=paddle.data_type.integer_value(2))
        mixed = layer.gated_delta_net(
            layer.embedding(input=words, size=16), num_k_heads=1,
            num_v_heads=2, head_k_dim=d, head_v_dim=d, name=name)
        cost = layer.classification_cost(
            input=layer.fc(input=layer.pooling(input=mixed), size=2),
            label=y)
        sgd = trainer.SGD(
            cost=cost, update_equation=optimizer.Adam(learning_rate=1e-3),
            parameters=paddle.Parameters.from_topology(
                paddle.topology.Topology([cost]), seed=0))
        rng = np.random.RandomState(4)
        rows = [([int(t) for t in rng.randint(0, 30, size=n)], 1)
                for n in (40, 20)]
        before = default_registry().snapshot()
        sgd.train(lambda: iter([rows, rows]), num_passes=1,
                  event_handler=lambda ev: None)
        after = default_registry().snapshot()
        key = "gdn_conv_fused_rows_total{layer=%s}" % name
        return after.get(key, 0) - before.get(key, 0), key in after

    grown, there = train("wide_gdn", 128)
    assert there and grown > 0 and grown % 2 == 0      # two steps' buffers
    assert train("narrow_gdn", 8) == (0, False)


# ---- gated attention -----------------------------------------------------------

H, KV, D, ROT = 4, 2, 16, 4


def test_gated_attention_forward_and_gradients_two_segments(f32_products):
    """Against plain softmax attention with the key/value heads repeated."""
    w = {"wq": normal(21, E, H * 2 * D, std=E ** -0.5),
         "wk": normal(22, E, KV * D, std=E ** -0.5),
         "wv": normal(23, E, KV * D, std=E ** -0.5),
         "q_norm_g": 1 + normal(24, D, std=0.1),
         "k_norm_g": 1 + normal(25, D, std=0.1),
         "wo": normal(26, H * D, E, std=(H * D) ** -0.5)}
    prog = {k.replace("_norm_g", "_norm"): v for k, v in w.items()}
    pos, seg = segments([40, 17], 64)
    x = normal(27, 64, E)
    real = (seg < 2)[:, None]

    def ours(x, p):
        y = gated_attention(x, pos, seg, p, num_heads=H, num_kv_heads=KV,
                            head_dim=D, rotary_dim=ROT, eps=1e-6, theta=1e7)
        return jnp.where(real, y, 0.0)

    def theirs(x, p):
        with jax.default_matmul_precision("highest"):
            y = REF.gated_attention(x, pos, seg, p, n_head=H, n_kv=KV,
                                    head_dim=D, rotary_dim=ROT, theta=1e7,
                                    eps=1e-6, mode="f32", block_rows=16)
        return jnp.where(real, y, 0.0)

    close(ours(x, prog), theirs(x, w))
    probe = normal(28, 64, E)
    g_ours = jax.jit(jax.grad(lambda x, p: jnp.sum(ours(x, p) * probe),
                              argnums=(0, 1)))(x, prog)
    g_ref = jax.jit(jax.grad(lambda x, p: jnp.sum(theirs(x, p) * probe),
                             argnums=(0, 1)))(x, w)
    close(g_ours[0], g_ref[0], rtol=1e-3)
    for k, g in g_ref[1].items():
        close(g_ours[1][k.replace("_norm_g", "_norm")], g, rtol=1e-3)


# ---- the expert layer --------------------------------------------------------

N, K, F = 16, 3, 16


def moe_weights():
    return {"router": normal(31, E, N, std=E ** -0.5),
            "experts": {"w_gate": normal(33, N, E, F, std=E ** -0.5),
                        "w_up": normal(34, N, E, F, std=E ** -0.5),
                        "w_down": normal(35, N, F, E, std=F ** -0.5)},
            "shared": {"w_gate": normal(36, E, F, std=E ** -0.5),
                       "w_up": normal(37, E, F, std=E ** -0.5),
                       "w_down": normal(38, F, E, std=F ** -0.5)},
            "shared_mix": normal(39, E, 1, std=E ** -0.5)}


def share_of(p, first, count, shared=True):
    """The parameter dict ``moe_dropless`` takes for one rank's share."""
    out = {"router": p["router"]}
    for k, v in p["experts"].items():
        out[k] = v[first:first + count]
    if shared:
        out.update({"shared_" + k[2:]: v for k, v in p["shared"].items()})
        out["shared_mix"] = p["shared_mix"]
    return out


def ref_moe(x, p):
    with jax.default_matmul_precision("highest"):
        return REF.moe(x, p, top_k=K, first=0, mode="f32")


def test_softmax_routing_picks_the_top_k_and_its_weights_sum_to_one():
    x, w = normal(41, 64, E), normal(42, E, N, std=E ** -0.5)
    experts, g = pmoe.route_softmax_topk(x, w, K)
    probs = jax.nn.softmax(jnp.matmul(
        x, w, precision=jax.lax.Precision.HIGHEST), axis=-1)
    top, want = jax.lax.top_k(probs, K)
    assert np.array_equal(np.asarray(experts), np.asarray(want))
    close(jnp.sum(g, axis=-1), np.ones(64), rtol=1e-6)
    close(g, top / jnp.sum(top, axis=-1, keepdims=True), rtol=1e-6)


def test_expert_layer_forward_and_gradients_all_held(f32_products):
    p, x = moe_weights(), normal(43, 48, E)
    ours = lambda x, q: pmoe.moe_dropless(  # noqa: E731
        x, q, top_k=K, held=(0, N), routing="softmax", tile_m=8)[0]
    close(ours(x, share_of(p, 0, N)), ref_moe(x, p))
    probe = normal(44, 48, E)
    got = jax.jit(jax.grad(lambda x, q: jnp.sum(ours(x, q) * probe),
                           argnums=(0, 1)))(x, share_of(p, 0, N))
    want = jax.jit(jax.grad(lambda x, q: jnp.sum(ref_moe(x, q) * probe),
                            argnums=(0, 1)))(x, p)
    close(got[0], want[0], rtol=1e-3)
    close(got[1]["router"], want[1]["router"], rtol=1e-3)
    close(got[1]["shared_mix"], want[1]["shared_mix"], rtol=1e-3)
    for k in ("w_gate", "w_up", "w_down"):
        close(got[1][k], want[1]["experts"][k], rtol=1e-3)
        close(got[1]["shared_" + k[2:]], want[1]["shared"][k], rtol=1e-3)


def test_the_shares_add_up_to_the_uncut_layer(f32_products):
    """Four ranks hold four of the 16 experts each: their routed parts, and
    the gated shared expert counted once, are the reference's whole layer;
    the pairs they count make up every (token, choice) pair."""
    p, x = moe_weights(), normal(45, 40, E)
    total, held_rows = 0.0, 0.0
    for rank in range(4):
        y, stats = pmoe.moe_dropless(
            x, share_of(p, 4 * rank, 4, shared=rank == 0), top_k=K,
            held=(4 * rank, 4), routing="softmax", tile_m=8)
        total = total + y
        held_rows += float(stats["rows_held"])
        assert float(stats["rows_total"]) == 40 * K
    assert held_rows == 40 * K
    close(total, ref_moe(x, p))


def test_an_unknown_routing_is_refused():
    p, x = moe_weights(), normal(46, 8, E)
    with pytest.raises(Exception, match="routing"):
        pmoe.moe_dropless(x, share_of(p, 0, N), top_k=K, held=(0, N),
                          routing="argmax")


# ---- the whole model through trainer.SGD --------------------------------------

def test_the_layer_pattern_follows_full_attention_interval():
    leaves = FAMILY.leaves(TINY, "train")
    kinds = ["attn" if f"blocks.{l}.attn.wq" in leaves else "delta"
             for l in range(4)]
    assert kinds == ["delta", "delta", "delta", "attn"]
    assert leaves["blocks.0.moe.router"][0] == (32, 16)
    assert leaves["blocks.0.moe.experts.w_gate"][0] == (4, 32, 16)


def test_tiny_model_trains_as_the_reference(f32_products):
    """Four layers ``[delta, delta, delta, attention]``, experts 4-7 of 16
    held: the loss of three steps, the first gradient of every leaf
    (Adam's first moment after one step) and the parameters after three
    Adam steps, leaf by leaf."""
    cfg, opt = TINY, TINY["train"]["optimizer"]
    leaves = FAMILY.leaves(cfg, "train")
    made = weights.make(leaves, 7)
    paddle.topology.reset_name_scope()
    prog = FAMILY.train_program(cfg)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([prog["cost"]]))
    assert set(params.names()) == set(prog["names"])
    assert set(prog["names"].values()) == set(leaves)
    for ours, theirs in prog["names"].items():
        params[ours] = made[theirs]
    sgd = trainer.SGD(cost=prog["cost"], parameters=params,
                      update_equation=optimizer.Adam(
                          learning_rate=opt["learning_rate"],
                          beta1=opt["beta1"], beta2=opt["beta2"],
                          epsilon=opt["epsilon"]))
    rng = np.random.RandomState(3)
    batches = []
    for _ in range(3):
        rows = []
        for n in (100, 28):
            t = rng.randint(0, cfg["vocab_size"], n + 1).astype(np.int32)
            rows.append((t[:-1], np.arange(n, dtype=np.int32), t[1:]))
        batches.append(rows)
    losses, first = [], {}

    def on_event(ev):
        if isinstance(ev, event.EndIteration):
            losses.append(float(ev.cost))
        elif isinstance(ev, event.EndPass) and not first:
            # the trainer's state is written back at a pass's end
            m = sgd.opt_state["slots"]["m"]
            first.update({k: np.asarray(v) / (1 - opt["beta1"])
                          for k, v in m.items()})

    feed = iter(batches)
    sgd.train(lambda: iter([next(feed)]), num_passes=3,
              event_handler=on_event, feeding=prog["feeding"])

    step = FAMILY.reference_train_step(
        REF, cfg, mode="f32", optimizer=opt,
        reduce_grads=lambda g, key: weights.flatten(g), block_rows=32,
        head_rows=32)
    w = weights.unflatten(weights.make(leaves, 7))
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    for i, rows in enumerate(batches):
        cols = [np.concatenate([r[c] for r in rows]) for c in range(3)]
        seg = np.concatenate([np.full(len(r[0]), j)
                              for j, r in enumerate(rows)]).astype(np.int32)
        loss, grads, w, m, v = step(w, m, v, i, jax.random.PRNGKey(0),
                                    cols[0], cols[1], cols[2], seg,
                                    seg < len(rows), float(len(rows)))
        assert abs(losses[i] - float(loss)) <= 2e-5 * float(loss)
        if i == 0:
            for ours, theirs in prog["names"].items():
                close(first[ours], grads[theirs], rtol=2e-3)
    now = sgd.parameters.as_dict()
    after = weights.flatten(w)
    for ours, theirs in prog["names"].items():
        moved = np.asarray(after[theirs]) - np.asarray(made[theirs])
        close(np.asarray(now[ours]) - np.asarray(made[theirs]), moved,
              rtol=2e-2)
