"""The ``glm4_moe_lite`` layers and model against the benchmark's plain
reference (``benchmarks/references/glm_moe_lite.py``), at tiny widths on
the CPU with seeded weights: latent attention, the dropless expert layer
(whole and as a rank's share), the multi-token-prediction loss's shift and
mask, the flash kernels at head width 256, and one whole model through
``trainer.SGD``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import event, layer, optimizer, trainer
from paddle_tpu.ops.attention import flash_attention, mha_reference
from paddle_tpu.ops.mla import mla_attention
from paddle_tpu.parallel import moe as pmoe
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.sequence import SequenceBatch

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import cells, weights  # noqa: E402

REF = cells.load_module(os.path.join(BENCH, "references", "glm_moe_lite.py"))
FAMILY = cells.load_module(os.path.join(BENCH, "families", "glm_moe_lite.py"))
TINY = cells.load_json(os.path.join(BENCH, "tests", "configs",
                                    "tiny-glm.json"))


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


def close(got, want, rtol=2e-4):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= rtol * scale


# ---- latent attention --------------------------------------------------------

H, NOPE, ROPE, V, E, QR, KR = 2, 8, 8, 16, 32, 16, 8


def mla_weights():
    std = E ** -0.5
    return {"wq_a": normal(1, E, QR, std=std),
            "q_norm_g": 1 + normal(2, QR, std=0.1),
            "wq_b": normal(3, QR, H * (NOPE + ROPE), std=QR ** -0.5),
            "wkv_a": normal(4, E, KR + ROPE, std=std),
            "kv_norm_g": 1 + normal(5, KR, std=0.1),
            "wkv_b": normal(6, KR, H * (NOPE + V), std=KR ** -0.5),
            "wo": normal(7, H * V, E, std=(H * V) ** -0.5)}


def packed(lengths, cap):
    """(positions, segment ids) of a packed buffer with padding."""
    pos = np.concatenate([np.arange(n) for n in lengths]
                         + [np.zeros(cap - sum(lengths), np.int64)])
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lengths)]
                         + [np.full(cap - sum(lengths), len(lengths))])
    return jnp.asarray(pos, jnp.int32), jnp.asarray(seg, jnp.int32)


def test_mla_forward_and_gradients_with_packed_segments(f32_products):
    w = mla_weights()
    prog = {k.replace("_norm_g", "_norm"): v for k, v in w.items()}
    pos, seg = packed([24, 9, 23], 64)
    x = normal(8, 64, E)
    real = (seg < 3)[:, None]

    def ours(x, p):
        y = mla_attention(x, pos, seg, p, num_heads=H, qk_nope_dim=NOPE,
                          qk_rope_dim=ROPE, v_dim=V, eps=1e-5, theta=1e4)
        return jnp.where(real, y, 0.0)

    def theirs(x, p):
        with jax.default_matmul_precision("highest"):
            y = REF.mla(x, pos, seg, p, n_head=H, nope=NOPE, rope=ROPE,
                        theta=1e4, eps=1e-5, mode="f32", block_rows=16)
        return jnp.where(real, y, 0.0)

    close(ours(x, prog), theirs(x, w))
    probe = normal(9, 64, E)
    g_ours = jax.grad(lambda x, p: jnp.sum(ours(x, p) * probe),
                      argnums=(0, 1))(x, prog)
    g_ref = jax.grad(lambda x, p: jnp.sum(theirs(x, p) * probe),
                     argnums=(0, 1))(x, w)
    close(g_ours[0], g_ref[0], rtol=1e-3)
    for k, g in g_ref[1].items():
        close(g_ours[1][k.replace("_norm_g", "_norm")], g, rtol=1e-3)


@pytest.mark.parametrize("what", ["forward", "backward"])
def test_flash_attention_at_head_width_256(what):
    """q/k heads of 192 + 64 and v heads of 256 give the kernels D = 256,
    twice what every other cell runs."""
    q, k, v = (normal(i, 1, 256, 2, 256, std=0.5).astype(jnp.bfloat16)
               for i in (1, 2, 3))
    seg = jnp.asarray(np.repeat([0, 1], 128)[None], jnp.int32)

    def ours(q, k, v):
        return flash_attention(q, k, v, segment_ids=seg, causal=True,
                               block_q=128, block_k=128)

    def plain(q, k, v):
        return mha_reference(q, k, v, segment_ids=seg, causal=True)

    if what == "forward":
        close(ours(q, k, v).astype(jnp.float32),
              plain(q, k, v).astype(jnp.float32), rtol=2e-2)
        return
    probe = normal(4, 1, 256, 2, 256)
    loss = lambda f: lambda q, k, v: jnp.sum(  # noqa: E731
        f(q, k, v).astype(jnp.float32) * probe)
    got = jax.grad(loss(ours), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        close(a.astype(jnp.float32), b.astype(jnp.float32), rtol=3e-2)


# ---- the expert layer --------------------------------------------------------

N, K, F = 8, 2, 16


def moe_weights(skew=None):
    p = {"router": normal(11, E, N, std=E ** -0.5),
         "bias": normal(12, N, std=0.05),
         "experts": {"w_gate": normal(13, N, E, F, std=E ** -0.5),
                     "w_up": normal(14, N, E, F, std=E ** -0.5),
                     "w_down": normal(15, N, F, E, std=F ** -0.5)},
         "shared": {"w_gate": normal(16, E, F, std=E ** -0.5),
                    "w_up": normal(17, E, F, std=E ** -0.5),
                    "w_down": normal(18, F, E, std=F ** -0.5)}}
    if skew is not None:       # every token's first choice is one expert
        p["bias"] = p["bias"].at[skew].add(10.0)
    return p


def share_of(p, first, count, shared=True):
    """The parameter dict ``moe_dropless`` takes for one rank's share."""
    out = {"router": p["router"], "bias": p["bias"]}
    for k, v in p["experts"].items():
        out[k] = v[first:first + count]
    if shared:
        out.update({"shared_" + k[2:]: v for k, v in p["shared"].items()})
    return out


def ref_moe(x, p, shared=True):
    with jax.default_matmul_precision("highest"):
        return REF.moe(x, p, top_k=K, scaling=1.8, first=0, mode="f32",
                       shared=shared)


def test_expert_layer_forward_and_gradients_all_held(f32_products):
    p, x = moe_weights(), normal(19, 48, E)
    ours = lambda x, q: pmoe.moe_dropless(  # noqa: E731
        x, q, top_k=K, held=(0, N), scaling=1.8, tile_m=8)[0]
    close(ours(x, share_of(p, 0, N)), ref_moe(x, p))
    probe = normal(20, 48, E)
    got = jax.grad(lambda x, q: jnp.sum(ours(x, q) * probe),
                   argnums=(0, 1))(x, share_of(p, 0, N))
    want = jax.grad(lambda x, q: jnp.sum(ref_moe(x, q) * probe),
                    argnums=(0, 1))(x, p)
    close(got[0], want[0], rtol=1e-3)
    close(got[1]["router"], want[1]["router"], rtol=1e-3)
    assert float(jnp.abs(got[1]["bias"]).max()) == 0.0
    for k in ("w_gate", "w_up", "w_down"):
        close(got[1][k], want[1]["experts"][k], rtol=1e-3)
        close(got[1]["shared_" + k[2:]], want[1]["shared"][k], rtol=1e-3)


def test_the_shares_add_up_to_the_uncut_layer(f32_products):
    """Four ranks hold two experts each: their routed parts, and the shared
    expert counted once, are the reference's whole layer; the pairs they
    count make up every (token, choice) pair."""
    p, x = moe_weights(), normal(21, 40, E)
    total, held_rows = 0.0, 0.0
    for rank in range(4):
        y, stats = pmoe.moe_dropless(
            x, share_of(p, 2 * rank, 2, shared=rank == 0), top_k=K,
            held=(2 * rank, 2), scaling=1.8, tile_m=8)
        total = total + y
        held_rows += float(stats["rows_held"])
        assert float(stats["rows_total"]) == 40 * K
    assert held_rows == 40 * K
    close(total, ref_moe(x, p))


def test_no_token_is_dropped_under_a_skewed_router(f32_products):
    """Every token's first choice is expert 3: it gets all 64 rows, eight
    times the even share, and the result is still the reference's."""
    p, x = moe_weights(skew=3), normal(22, 64, E)
    y, stats = pmoe.moe_dropless(x, share_of(p, 2, 2), top_k=K, held=(2, 2),
                                 scaling=1.8, tile_m=8)
    assert float(stats["max_expert_rows"]) == 64
    cut = dict(p, experts={k: v[2:4] for k, v in p["experts"].items()})
    with jax.default_matmul_precision("highest"):
        want = REF.moe(x, cut, top_k=K, scaling=1.8, first=2, mode="f32")
    close(y, want)


def test_padding_rows_route_nowhere(f32_products):
    p, x = moe_weights(), normal(23, 32, E)
    valid = jnp.arange(32) < 20
    _, stats = pmoe.moe_dropless(x, share_of(p, 0, N), top_k=K, held=(0, N),
                                 scaling=1.8, valid=valid, tile_m=8)
    assert float(stats["rows_total"]) == float(stats["rows_held"]) == 20 * K


# ---- the multi-token-prediction loss ------------------------------------------

def test_next_token_cost_shifts_and_masks_at_sequence_ends():
    vocab, cap = 11, 16
    pos, seg = packed([7, 5], cap)
    lengths = jnp.asarray([7, 5], jnp.int32)
    logits = normal(31, cap, vocab)
    target = jnp.asarray(np.random.RandomState(0).randint(0, vocab, cap),
                         jnp.int32)
    lg = layer.data(name="lg", type=paddle.data_type.dense_vector_sequence(
        vocab))
    tg = layer.data(name="tg", type=paddle.data_type.integer_value_sequence(
        vocab))
    node = layer.next_token_cost(lg, tg, shift=1, weight=0.5)
    feeds = {"lg": SequenceBatch(logits, seg, lengths),
             "tg": SequenceBatch(target, seg, lengths)}
    (out,), _ = paddle.topology.Topology([node]).forward({}, {}, feeds)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = np.zeros(cap, np.float32)
    for i in list(range(0, 6)) + list(range(7, 11)):   # not rows 6 and 11
        want[i] = -0.5 * float(logp[i, target[i + 1]])
    close(out.data, want, rtol=1e-5)


# ---- the whole model through trainer.SGD --------------------------------------

def test_tiny_model_trains_as_the_reference(f32_products):
    """Loss of three steps, the first gradient (Adam's first moment after
    one step) and the parameters after three Adam steps, leaf by leaf."""
    cfg, opt = TINY, TINY["train"]["optimizer"]
    leaves = FAMILY.leaves(cfg, "train")
    made = weights.make(leaves, 7)
    paddle.topology.reset_name_scope()
    prog = FAMILY.train_program(cfg)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology(prog["cost"]))
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
        for n in (40, 24):
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
        reduce_grads=lambda g, key: weights.flatten(g), block_rows=16,
        head_rows=32)
    w = weights.unflatten(weights.make(leaves, 7))
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    frozen = set(FAMILY.frozen(cfg))
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
                if theirs not in frozen:
                    close(first[ours], grads[theirs], rtol=2e-3)
    now = sgd.parameters.as_dict()
    after = weights.flatten(w)
    for ours, theirs in prog["names"].items():
        moved = np.asarray(after[theirs]) - np.asarray(made[theirs])
        if theirs in frozen:
            assert not moved.any()
            assert np.array_equal(np.asarray(now[ours]),
                                  np.asarray(made[theirs]))
        else:
            close(np.asarray(now[ours]) - np.asarray(made[theirs]), moved,
                  rtol=2e-2)
