"""layer.multi_head_attention: packed-sequence flash attention as a layer.

Oracle: each sequence unpacked and run through dense mha_reference —
packed segment masking must match per-sequence attention exactly.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import layer, optimizer, trainer
from paddle_tpu.ops import attention as pattn
from paddle_tpu.platform.flags import FLAGS


@pytest.fixture(autouse=True)
def f32_math():
    old = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    yield
    FLAGS.use_bf16 = old


def _build(dim, heads, causal):
    paddle.topology.reset_name_scope()
    x = layer.data(name="x",
                   type=paddle.data_type.dense_vector_sequence(dim))
    mha = layer.multi_head_attention(x, num_heads=heads, causal=causal,
                                     name="mha")
    return x, mha


@pytest.mark.parametrize("causal", [False, True])
def test_mha_layer_matches_per_sequence_reference(rng, causal):
    dim, heads = 16, 4
    x, mha = _build(dim, heads, causal)
    topo = paddle.topology.Topology([mha])
    cost = layer.sum_cost(input=layer.fc(input=mha, size=1))
    sgd = trainer.SGD(cost=cost,
                      parameters=paddle.Parameters.from_topology(
                          paddle.topology.Topology([cost]), seed=0),
                      update_equation=optimizer.Sgd())

    seqs = [rng.randn(int(n), dim).astype(np.float32) for n in (5, 9, 3)]
    feeder = sgd._make_feeder({"x": 0})
    feeds = feeder.feed([(s,) for s in seqs])
    p = sgd.parameters.as_dict()
    outs, _ = topo.forward({k: p[k] for k in topo.param_specs()},
                           {}, {"x": feeds["x"]}, train=False)
    sb = outs[0]
    got = np.asarray(sb.data)

    # oracle: per-sequence dense attention with the same projections
    wq, wk, wv, wo = (np.asarray(p["mha.wq"]), np.asarray(p["mha.wk"]),
                      np.asarray(p["mha.wv"]), np.asarray(p["mha.wo"]))
    off = 0
    for s in seqs:
        n = s.shape[0]
        q = (s @ wq).reshape(1, n, heads, -1)
        k = (s @ wk).reshape(1, n, heads, -1)
        v = (s @ wv).reshape(1, n, heads, -1)
        ref = pattn.mha_reference(jnp.asarray(q), jnp.asarray(k),
                                  jnp.asarray(v), causal=causal)
        want = np.asarray(ref).reshape(n, -1) @ wo
        np.testing.assert_allclose(got[off:off + n], want, atol=2e-4)
        off += n


def test_mha_layer_trains(rng):
    """Self-attention classifier learns a token-lookup task."""
    dim, heads, vocab = 16, 4, 30
    paddle.topology.reset_name_scope()
    words = layer.data(name="w",
                       type=paddle.data_type.integer_value_sequence(vocab))
    y = layer.data(name="y", type=paddle.data_type.integer_value(2))
    emb = layer.embedding(input=words, size=dim)
    att = layer.multi_head_attention(emb, num_heads=heads)
    pooled = layer.pooling(input=att,
                           pooling_type=paddle.pooling.AvgPooling())
    cost = layer.classification_cost(input=layer.fc(input=pooled, size=2),
                                     label=y)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=0)
    sgd = trainer.SGD(cost=cost, parameters=params,
                      update_equation=optimizer.Adam(learning_rate=5e-3))

    def reader():
        for _ in range(25):
            batch = []
            for _ in range(16):
                n = int(rng.randint(4, 12))
                toks = rng.randint(0, vocab, size=n)
                batch.append(([int(t) for t in toks],
                              int(toks.min() < vocab // 3)))
            yield batch

    costs = []
    sgd.train(reader, num_passes=3,
              event_handler=lambda ev: costs.append(float(ev.cost))
              if isinstance(ev, paddle.event.EndIteration) else None)
    assert np.mean(costs[-5:]) < np.mean(costs[:5]) / 2


def test_mha_layer_counts_its_live_blocks(rng):
    """The layer publishes what the flash kernel's schedule visits beside
    the blocks a one-sequence buffer would take: after a ``SGD.train``
    window the registry holds the schedule's counts times the steps."""
    from paddle_tpu.obs import default_registry

    dim, heads, vocab = 16, 2, 30
    paddle.topology.reset_name_scope()
    words = layer.data(name="w",
                       type=paddle.data_type.integer_value_sequence(vocab))
    y = layer.data(name="y", type=paddle.data_type.integer_value(2))
    emb = layer.embedding(input=words, size=dim)
    att = layer.multi_head_attention(emb, num_heads=heads, causal=True,
                                     name="att")
    pooled = layer.pooling(input=att,
                           pooling_type=paddle.pooling.AvgPooling())
    cost = layer.classification_cost(input=layer.fc(input=pooled, size=2),
                                     label=y)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=0)
    sgd = trainer.SGD(cost=cost, parameters=params,
                      update_equation=optimizer.Adam(learning_rate=1e-3))
    # each batch fills a 1024-token buffer (two blocks of 512: a triangle
    # of 3).  256 + 256 | 200 + pad: the second block's ids do not meet
    # the first's, 2 live.  300 + 300 (straddles) + 100: all 3 live.
    steps = [[256, 256, 200], [300, 300, 100], [512, 40]]
    live = [2, 3, 2]
    for lengths, want in zip(steps, live):
        seg = np.full(1024, len(lengths), np.int32)
        seg[:sum(lengths)] = np.repeat(np.arange(len(lengths)), lengths)
        got = pattn.flash_block_counts(jnp.asarray(seg)[None], causal=True)
        assert (int(got[0]), int(got[1])) == (want, 3)

    def reader():
        for lengths in steps:
            yield [([int(t) for t in rng.randint(0, vocab, size=n)], 1)
                   for n in lengths]

    before = default_registry().snapshot()
    sgd.train(reader, num_passes=2, event_handler=lambda ev: None)
    after = default_registry().snapshot()

    def grown(name):
        key = name + "{layer=att}"
        return after[key] - before.get(key, 0)

    assert grown("flash_live_blocks_total") == 2 * sum(live)
    assert grown("flash_tri_blocks_total") == 2 * 3 * len(steps)
