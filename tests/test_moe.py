"""Expert-parallel MoE FFN tests: sharded dispatch/combine vs the dense
single-device oracle, capacity semantics, gradients through all_to_all.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.moe import (MoEParams, aux_load_balance_loss,
                                     init_moe_params, moe_ffn,
                                     moe_ffn_reference)

T, D, H, E = 64, 8, 16, 8


@pytest.fixture(scope="module")
def params():
    return init_moe_params(jax.random.PRNGKey(0), D, H, E, scale=0.5)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)


def test_sharded_matches_dense_oracle(params, tokens):
    """With generous capacity (nothing drops anywhere) the expert-parallel
    all_to_all formulation computes EXACTLY the dense result per token."""
    mesh = make_mesh((8,), ("expert",))
    y_ref, aux_ref = moe_ffn_reference(tokens, params, capacity_factor=8.0)
    y_ep, aux_ep = jax.jit(
        lambda x, p: moe_ffn(mesh, x, p, capacity_factor=8.0))(
        tokens, params)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-5)


def test_capacity_drops_pass_through_as_zero(params, tokens):
    """Tiny capacity: over-capacity tokens emit zeros (Switch drop)."""
    y, _ = moe_ffn_reference(tokens, params, capacity_factor=0.125)
    zero_rows = np.where(np.abs(np.asarray(y)).sum(-1) == 0)[0]
    assert len(zero_rows) > 0
    y_full, _ = moe_ffn_reference(tokens, params, capacity_factor=8.0)
    kept = np.abs(np.asarray(y)).sum(-1) > 0
    np.testing.assert_allclose(np.asarray(y)[kept],
                               np.asarray(y_full)[kept], rtol=1e-5)


def test_sharded_matches_oracle_multiple_experts_per_shard(tokens):
    """E=16 on 8 shards (two experts per shard): the combine path must
    keep the [owner, local] -> global expert order straight."""
    p16 = init_moe_params(jax.random.PRNGKey(4), D, H, 16, scale=0.5)
    mesh = make_mesh((8,), ("expert",))
    y_ref, _ = moe_ffn_reference(tokens, p16, capacity_factor=16.0)
    y_ep, _ = jax.jit(lambda x, p: moe_ffn(mesh, x, p,
                                           capacity_factor=16.0))(
        tokens, p16)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-6)


def test_capacity_is_ceil():
    """docstring promise: ceil(T/E * factor), not floor: 10 tokens over 8
    experts at factor 1.25 -> cap ceil(1.5625)=2; deterministic routing
    puts 2 tokens on experts 0/1, so NOTHING drops (floor cap 1 would
    drop two tokens)."""
    p = init_moe_params(jax.random.PRNGKey(0), D, H, 8, scale=0.5)
    p = p._replace(router=jnp.eye(D, 8) * 10.0)
    x = jnp.eye(8, D)[jnp.arange(10) % 8] * 5.0   # token i -> expert i%8
    y, _ = moe_ffn_reference(x, p, capacity_factor=1.25)
    dropped = int((np.abs(np.asarray(y)).sum(-1) == 0).sum())
    assert dropped == 0


def test_gradients_flow_through_all_to_all(params, tokens):
    mesh = make_mesh((8,), ("expert",))

    def loss(p, x):
        y, aux = moe_ffn(mesh, x, p, capacity_factor=8.0)
        return jnp.sum(y ** 2) + 0.01 * aux

    grads = jax.jit(jax.grad(loss))(params, tokens)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()
    assert float(jnp.abs(grads.w1).sum()) > 0
    assert float(jnp.abs(grads.router).sum()) > 0


def test_aux_loss_uniform_is_one():
    probs = jnp.full((32, E), 1.0 / E)
    expert = jnp.arange(32, dtype=jnp.int32) % E   # perfectly balanced
    assert abs(float(aux_load_balance_loss(probs, expert)) - 1.0) < 1e-6


def test_moe_trains_toward_balanced_experts(params):
    """A few steps of aux-weighted training reduce routing imbalance."""
    x = jax.random.normal(jax.random.PRNGKey(3), (T, D)) * 2.0
    p = params

    def imbalance(p):
        from paddle_tpu.parallel.moe import _route
        _, _, probs = _route(x, p.router)
        expert = jnp.argmax(probs, -1)
        counts = jnp.bincount(expert, length=E)
        return float(counts.max() - counts.min())

    def loss(p):
        _, aux = moe_ffn_reference(x, p, capacity_factor=8.0)
        return aux

    before = imbalance(p)
    g = jax.jit(jax.grad(loss))(p)
    p2 = jax.tree.map(lambda a, b: a - 0.5 * b, p, g)
    for _ in range(10):
        g = jax.jit(jax.grad(loss))(p2)
        p2 = jax.tree.map(lambda a, b: a - 0.5 * b, p2, g)
    assert float(loss(p2)) <= float(loss(p)) + 1e-6


def test_moe_transformer_trains():
    """transformer.build(moe_experts=4): multi-cost training (xent + aux)
    converges on tiny shapes; aux stays finite and bounded."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer, trainer
    from paddle_tpu.models import transformer

    vocab, d = 61, 16
    paddle.topology.reset_name_scope()
    tokens, pos, target, logits, costs = transformer.build(
        vocab_size=vocab, d_model=d, n_layers=2, n_heads=2, max_len=32,
        moe_experts=4)
    assert isinstance(costs, list) and len(costs) == 3  # xent + 2 aux
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology(costs), seed=0)
    sgd = trainer.SGD(cost=costs, parameters=params,
                      update_equation=optimizer.Adam(learning_rate=1e-2))
    step = sgd._build_step()
    rng = np.random.RandomState(0)
    samples = []
    for _ in range(4):
        t = rng.randint(0, vocab, size=12)
        samples.append((t.tolist(), list(range(12)),
                        np.roll(t, -1).tolist()))
    feeds = sgd._make_feeder(
        {"tokens": 0, "pos": 1, "target": 2}).feed(samples)
    p, o, m = sgd.parameters.as_dict(), sgd.opt_state, sgd.model_state
    key = jax.random.PRNGKey(0)
    losses = []
    for _ in range(25):
        loss, p, o, m, _ = step(p, o, m, key, feeds)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# The dropless path: the sorted buffer is as long as the step's rows need
# ---------------------------------------------------------------------------
#
# One rank of sixteen: 2 of 32 experts held, 64 tokens x 2 choices, tiles of
# 8 rows.  ``dropless_rungs`` gives (32, 48, 144): twice and four times what
# even routing sends (8 pairs, plus a tile a held expert), then the worst
# case.  The router is a scaled identity, so a token's two largest features
# ARE its experts and a test places every pair where it wants it.

from paddle_tpu.parallel import moe as pmoe  # noqa: E402

RT, RK, RN, RF, RTILE = 64, 2, 32, 16, 8     # tokens, top-k, experts, F, tile
FIRST, COUNT = 4, 2                          # the held experts: 4 and 5
RUNGS = (32, 48, 144)


def _normal(seed, *shape, std=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32) * std


def routed(on_first: int, both: int = 0):
    """[RT, RN] tokens: the first ``both`` choose the two held experts, the
    next ``on_first`` the first held expert and expert 9, the rest experts
    20 and 21; a little noise on every feature keeps the gradients of the
    router's columns apart."""
    first = np.full(RT, 20)
    second = np.full(RT, 21)
    first[:both + on_first] = FIRST
    second[:both] = FIRST + 1
    second[both:both + on_first] = 9
    x = np.asarray(_normal(50, RT, RN, std=0.05)).copy()
    x[np.arange(RT), first] += 4.0
    x[np.arange(RT), second] += 3.0
    return jnp.asarray(x)


def rank_weights():
    return {"router": 10.0 * jnp.eye(RN, dtype=jnp.float32),
            "w_gate": _normal(51, COUNT, RN, RF, std=RN ** -0.5),
            "w_up": _normal(52, COUNT, RN, RF, std=RN ** -0.5),
            "w_down": _normal(53, COUNT, RF, RN, std=RF ** -0.5),
            "shared_gate": _normal(54, RN, RF, std=RN ** -0.5),
            "shared_up": _normal(55, RN, RF, std=RN ** -0.5),
            "shared_down": _normal(56, RF, RN, std=RF ** -0.5)}


def dropless(x, p):
    return pmoe.moe_dropless(x, p, top_k=RK, held=(FIRST, COUNT),
                             routing="softmax", tile_m=RTILE,
                             operand_dtype=jnp.float32)


def dense_reference(x, p):
    """Every held expert over every token, weighted by the router's
    renormalised top-k weight where the token chose it, else by zero."""
    hi = jax.lax.Precision.HIGHEST
    experts, g = pmoe.route_softmax_topk(x, p["router"], RK)
    mm = lambda a, b: jnp.matmul(a, b, precision=hi)  # noqa: E731
    out = mm(jax.nn.silu(mm(x, p["shared_gate"])) * mm(x, p["shared_up"]),
             p["shared_down"])
    for e in range(COUNT):
        w = jnp.sum(jnp.where(experts == FIRST + e, g, 0.0), axis=-1)
        y = mm(jax.nn.silu(mm(x, p["w_gate"][e])) * mm(x, p["w_up"][e]),
               p["w_down"][e])
        out = out + w[:, None] * y
    return out


def step_of(fn, x, p):
    """(out, the rung's rows the step ran at, gradients of a probed sum
    with respect to x and every matrix)."""
    probe = _normal(57, RT, RN)

    def loss(x, p):
        y, stats = fn(x, p)
        return jnp.sum(y * probe), (y, stats)

    (_, (y, stats)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(x, p)
    ran = [rows for rows, n in stats["rung_steps"].items() if float(n)]
    assert len(ran) == 1 and sum(map(float, stats["rung_steps"].values())) == 1
    return y, ran[0], grads


def reference_step(x, p):
    return step_of(lambda x, p: (dense_reference(x, p),
                                 {"rung_steps": {0: 1.0}}), x, p)


@pytest.fixture
def worst_case_only(monkeypatch):
    """Call it to force every later call through the worst-case buffer
    alone: the program as it was before there were rungs."""
    rungs = pmoe.dropless_rungs
    return lambda: monkeypatch.setattr(
        pmoe, "dropless_rungs", lambda *a: rungs(*a)[-1:])


def assert_same_step(got, want, exact):
    y, _, (dx, dp) = got
    y2, _, (dx2, dp2) = want
    same = np.testing.assert_array_equal if exact else \
        (lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5))
    same(np.asarray(y), np.asarray(y2))
    same(np.asarray(dx), np.asarray(dx2))
    for k in ("router", "w_gate", "w_up", "w_down", "shared_gate",
              "shared_up", "shared_down"):
        same(np.asarray(dp[k]), np.asarray(dp2[k]))
        assert np.asarray(dp[k]).any(), k


@pytest.mark.parametrize("shapes,want", [
    ((8192, 10, 32, 512, 128), (14336, 24576, 86016)),   # qwen3-next cell
    ((8192, 4, 8, 64, 128), (9216, 33792)),              # glm cell
    ((RT, RK, COUNT, RN, RTILE), RUNGS),
    ((1056, 8, 128, 256, 32), (1056 * 8 + 128 * 32,)),   # half the experts
    ((128, 8, 128, 128, 32), (128 * 8 + 128 * 32,)),     # all of them
], ids=["qwen3next", "glm", "tiny", "half_held", "all_held"])
def test_the_rungs_come_from_the_calls_shapes(shapes, want):
    assert pmoe.dropless_rungs(*shapes) == want


def test_a_step_at_the_smallest_rung_equals_the_worst_case_branch(
        worst_case_only):
    """Even-ish load (8 of 128 pairs on the held experts): the step runs
    over 32 rows and gives ``out`` and every gradient as the 144-row
    program does, bit for bit, and as the dense reference."""
    x, p = routed(on_first=6, both=1), rank_weights()
    got = step_of(dropless, x, p)
    assert got[1] == RUNGS[0]
    worst_case_only()
    want = step_of(dropless, x, p)
    assert want[1] == RUNGS[-1]
    assert_same_step(got, want, exact=True)
    assert_same_step(got, reference_step(x, p), exact=False)


def test_a_step_routed_wholly_onto_held_experts_takes_the_worst_case():
    """Every choice of every token on a held expert: 16 live tiles, past
    every smaller rung; nothing is dropped."""
    x, p = routed(on_first=0, both=RT), rank_weights()
    y, stats = dropless(x, p)
    assert float(stats["rows_held"]) == float(stats["rows_total"]) == RT * RK
    got = step_of(dropless, x, p)
    assert got[1] == RUNGS[-1]
    assert_same_step(got, reference_step(x, p), exact=False)


@pytest.mark.parametrize("on_first,rows", [
    (24, 32), (25, 48), (40, 48), (41, 144)],
    ids=["at_rung0", "one_tile_over_rung0", "at_rung1",
         "one_tile_over_rung1"])
def test_the_boundary_of_a_rung_is_its_last_tile(on_first, rows,
                                                 worst_case_only):
    """``on_first`` rows on the first held expert and none on the second:
    ``ceil(on_first / 8) + 1`` live tiles.  Exactly a rung's tiles run at
    it; one tile more runs at the next."""
    x, p = routed(on_first=on_first), rank_weights()
    y, stats = dropless(x, p)
    assert float(stats["live_tiles"]) == -(-on_first // RTILE) + 1
    got = step_of(dropless, x, p)
    assert got[1] == rows
    worst_case_only()
    assert_same_step(got, step_of(dropless, x, p), exact=True)


def _primitives(jaxpr, found=None):
    """Primitive names of a jaxpr and of everything it calls, a kernel's
    body apart (interpret mode or not, a kernel branches inside)."""
    found = [] if found is None else found
    for e in jaxpr.eqns:
        found.append(e.primitive.name)
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    _primitives(j, found)
    return found


@pytest.mark.parametrize("count", [RN // 2, RN], ids=["half", "all"])
def test_a_rank_that_holds_half_or_all_of_the_experts_has_no_branch(count):
    """The served families' shares (128 of 256, 128 of 128): one rung, so
    the layer and its gradient lower with no ``cond``: 3 + 6 + 3 kernels
    inline, the program they compiled before."""
    p = rank_weights()
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = jnp.concatenate([p[k]] * (count // COUNT))
    x = routed(on_first=6, both=1)
    fn = lambda x, p: jnp.sum(pmoe.moe_dropless(  # noqa: E731
        x, p, top_k=RK, held=(0, count), routing="softmax", tile_m=RTILE,
        operand_dtype=jnp.float32)[0])
    prims = _primitives(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(
        x, p).jaxpr)
    assert "cond" not in prims and prims.count("pallas_call") == 9
    assert "cond" in _primitives(jax.make_jaxpr(jax.grad(
        lambda x, p: jnp.sum(dropless(x, p)[0]), argnums=(0, 1)))(
        x, rank_weights()).jaxpr)


def test_the_registry_counts_the_steps_a_rung_and_layer(monkeypatch):
    """``layer.moe_dropless`` through ``trainer.SGD``: two steps, the first
    with 8 pairs on the held experts, the second with all 128; the registry
    has one step at 32 rows, none at 48, one at the worst case, beside
    ``moe_rows_held_total``."""
    import paddle_tpu as paddle
    from paddle_tpu import layer, optimizer, trainer
    from paddle_tpu.obs import default_registry
    from paddle_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "TILE_M", RTILE)
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(RN))
    want = layer.data(name="y", type=paddle.data_type.dense_vector(RN))
    moe = layer.moe_dropless(x, n_routed=RN, held=(FIRST, COUNT),
                             expert_hidden=RF, top_k=RK, routing="softmax",
                             name="rung_probe")
    cost = layer.square_error_cost(input=moe, label=want)
    params = paddle.Parameters.from_topology(paddle.topology.Topology([cost]))
    params["rung_probe.router"] = 10.0 * np.eye(RN, dtype=np.float32)
    sgd = trainer.SGD(cost=cost, parameters=params,
                      update_equation=optimizer.Adam(learning_rate=1e-6))
    batches = [np.asarray(routed(on_first=6, both=1)),
               np.asarray(routed(on_first=0, both=RT))]

    def reader():
        return iter([[(row, row) for row in b] for b in batches])

    keys = [f"moe_rung_steps_total{{layer=rung_probe,rows={r}}}"
            for r in RUNGS] + ["moe_rows_held_total{layer=rung_probe}"]
    before = default_registry().snapshot()
    sgd.train(reader, num_passes=1, event_handler=lambda ev: None,
              feeding={"x": 0, "y": 1})
    snap = default_registry().snapshot()
    grown = [snap[k] - before.get(k, 0.0) for k in keys]
    assert grown == [1.0, 0.0, 1.0, 8.0 + RT * RK]
