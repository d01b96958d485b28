"""The testLayerGrad sweep: numeric-vs-analytic gradients across the
layer registry.

Reference analog: paddle/gserver/tests/test_LayerGrad.cpp (2.4k lines,
every layer type gradient-checked by perturbation, LayerGradUtil.h:298).
Here jax.grad supplies the analytic side; central differences on a few
sampled coordinates of every parameter and input supply the numeric side.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import layer
from paddle_tpu.sequence import SequenceBatch
from paddle_tpu.topology import Topology

RNG = np.random.RandomState(11)


@pytest.fixture(autouse=True)
def f32_math():
    # numeric-vs-analytic comparison needs f32 kernels; the bf16 MXU
    # policy is benchmarked separately (test_ops.py does the same)
    from paddle_tpu.platform.flags import FLAGS
    old = FLAGS.use_bf16
    FLAGS.use_bf16 = False
    yield
    FLAGS.use_bf16 = old


def check_layer_grad(out_node, feeds, check_inputs=(), delta=1e-3,
                     rtol=4e-2, atol=4e-3, seed=5, coords=8):
    """Mean-of-output loss; numeric grad on sampled coords of every param
    (and named float inputs) vs jax.grad.

    ``coords`` per tensor (reference perturbs systematically,
    LayerGradUtil.h:203; 8 spread coords is the fast CI gate)."""
    topo = Topology([out_node])
    params = paddle.Parameters.from_topology(topo, seed=seed)
    state = topo.init_state()
    pdict = {k: np.asarray(v, np.float32) for k, v in
             params.as_dict().items()}

    def loss_fn(p, f):
        outs, _ = topo.forward(p, state, f, train=False)
        o = outs[0]
        d = o.data if isinstance(o, SequenceBatch) else o
        return jnp.mean(d)

    loss = jax.jit(loss_fn)
    ana_p = jax.grad(lambda p: loss(p, feeds))(pdict)

    def sample_coords(arr, k=None):
        flat = arr.size
        k = coords if k is None else k
        return np.unique(np.linspace(0, flat - 1, min(k, flat)).astype(int))

    for name, val in pdict.items():
        for i in sample_coords(val):
            up = {k: v.copy() for k, v in pdict.items()}
            up[name].ravel()[i] += delta
            down = {k: v.copy() for k, v in pdict.items()}
            down[name].ravel()[i] -= delta
            num = (float(loss(up, feeds)) - float(loss(down, feeds))) \
                / (2 * delta)
            ana = float(np.asarray(ana_p[name]).ravel()[i])
            assert abs(num - ana) <= atol + rtol * abs(num), \
                (out_node.layer_type, name, i, num, ana)

    for fname in check_inputs:
        base = np.asarray(feeds[fname], np.float32)
        ana_f = jax.grad(
            lambda x: loss(pdict, {**feeds, fname: x}))(jnp.asarray(base))
        for i in sample_coords(base):
            up = base.copy()
            up.ravel()[i] += delta
            down = base.copy()
            down.ravel()[i] -= delta
            num = (float(loss(pdict, {**feeds, fname: up}))
                   - float(loss(pdict, {**feeds, fname: down}))) / (2 * delta)
            ana = float(np.asarray(ana_f).ravel()[i])
            assert abs(num - ana) <= atol + rtol * abs(num), \
                (out_node.layer_type, fname, i, num, ana)


def dense(name, dim, n=4):
    v = layer.data(name=name, type=paddle.data_type.dense_vector(dim))
    feed = RNG.randn(n, dim).astype(np.float32)
    return v, feed


def make_seq(name, dim, lengths, rng=None):
    """``rng``: a generator of the case's own, for a layer whose numeric
    gradient is input-dependent (the module's RNG depends on which cases a
    worker ran before)."""
    v = layer.data(name=name,
                   type=paddle.data_type.dense_vector_sequence(dim))
    total = sum(lengths)
    seg = np.concatenate([np.full(L, i, np.int32)
                          for i, L in enumerate(lengths)])
    sb = SequenceBatch(
        jnp.asarray((rng or RNG).randn(total, dim).astype(np.float32)),
        jnp.asarray(seg),
        jnp.asarray(np.asarray(lengths, np.int32)),
        max_len=max(lengths))
    return v, sb


def test_grad_fc_family():
    paddle.topology.reset_name_scope()
    x, fx = dense("x", 6)
    check_layer_grad(layer.fc(x, size=5, act="tanh"), {"x": fx},
                     check_inputs=["x"])

    paddle.topology.reset_name_scope()
    x, fx = dense("x", 6)
    check_layer_grad(layer.selective_fc(x, size=5), {"x": fx})


def test_grad_mixed_projections():
    paddle.topology.reset_name_scope()
    x, fx = dense("x", 6)
    y, fy = dense("y", 4)
    out = layer.mixed(size=5, input=[
        layer.full_matrix_projection(x, size=5),
        layer.full_matrix_projection(y, size=5)], act="sigmoid")
    check_layer_grad(out, {"x": fx, "y": fy}, check_inputs=["x", "y"])

    paddle.topology.reset_name_scope()
    x, fx = dense("x", 6)
    out = layer.mixed(size=6, input=[layer.dotmul_projection(x),
                                     layer.scaling_projection(x)])
    check_layer_grad(out, {"x": fx})


def test_grad_conv_pool_norm(seed=0):
    # its own generator: this module's RNG is shared with test_layer_sweep,
    # which imports it, so the inputs here used to depend on which file a
    # worker ran first, and max-pooling's numeric gradient is input-dependent
    rng = np.random.RandomState(seed)
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(6 * 6 * 2),
                   height=6, width=6)
    fx = rng.randn(3, 72).astype(np.float32)
    c = layer.img_conv(input=x, filter_size=3, num_filters=3,
                       num_channels=2, padding=1, act="relu")
    p = layer.img_pool(c, pool_size=2)
    check_layer_grad(p, {"x": fx}, delta=5e-3)

    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(4 * 4 * 2),
                   height=4, width=4)
    fx = rng.randn(3, 32).astype(np.float32)
    bn = layer.batch_norm(layer.img_conv(
        input=x, filter_size=3, num_filters=2, num_channels=2, padding=1))
    check_layer_grad(bn, {"x": fx}, delta=5e-3, rtol=8e-2)

    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(4 * 4 * 2),
                   height=4, width=4)
    fx = rng.randn(2, 32).astype(np.float32)
    check_layer_grad(layer.img_cmrnorm(x, size=3), {"x": fx},
                     check_inputs=["x"])


def test_grad_recurrent_layers():
    paddle.topology.reset_name_scope()
    s, fs = make_seq("s", 4, [3, 2])
    check_layer_grad(layer.lstmemory(layer.fc(s, size=4 * 4)),
                     {"s": fs}, delta=5e-3, rtol=8e-2)

    paddle.topology.reset_name_scope()
    s, fs = make_seq("s", 4, [3, 2])
    check_layer_grad(layer.grumemory(layer.fc(s, size=4 * 3)),
                     {"s": fs}, delta=5e-3, rtol=8e-2)

    paddle.topology.reset_name_scope()
    s, fs = make_seq("s", 4, [4, 2])
    check_layer_grad(layer.recurrent(s), {"s": fs}, delta=5e-3)


def test_grad_sequence_layers():
    for make in [lambda s: layer.pooling(s),
                 lambda s: layer.first_seq(s),
                 lambda s: layer.last_seq(s),
                 lambda s: layer.expand(layer.pooling(s), s)]:
        paddle.topology.reset_name_scope()
        s, fs = make_seq("s", 3, [3, 2])
        check_layer_grad(make(s), {"s": fs})


def test_grad_cost_layers():
    paddle.topology.reset_name_scope()
    x, fx = dense("x", 5)
    lab = layer.data(name="lab", type=paddle.data_type.integer_value(5))
    flab = RNG.randint(0, 5, (4,)).astype(np.int32)
    out = layer.classification_cost(input=layer.fc(x, size=5), label=lab)
    check_layer_grad(out, {"x": fx, "lab": flab}, check_inputs=["x"])

    paddle.topology.reset_name_scope()
    x, fx = dense("x", 5)
    t, ft = dense("t", 5)
    check_layer_grad(layer.square_error_cost(input=x, label=t),
                     {"x": fx, "t": ft}, check_inputs=["x"])

    paddle.topology.reset_name_scope()
    x, fx = dense("x", 1)
    t, _ = dense("t", 1)
    ft = (RNG.rand(4, 1) > 0.5).astype(np.float32)
    check_layer_grad(layer.huber_regression_cost(input=x, label=t),
                     {"x": fx, "t": ft}, check_inputs=["x"])


def test_grad_misc_new_layers():
    paddle.topology.reset_name_scope()
    x, fx = dense("x", 8)
    check_layer_grad(layer.prelu(x, partial_sum=2), {"x": fx},
                     check_inputs=["x"])

    paddle.topology.reset_name_scope()
    a, fa = dense("a", 3)
    b, fb = dense("b", 4)
    check_layer_grad(layer.tensor(a, b, size=3), {"a": fa, "b": fb},
                     check_inputs=["a", "b"])

    paddle.topology.reset_name_scope()
    s, fs = make_seq("s", 3, [3, 2])
    check_layer_grad(layer.row_conv(s, context_len=2), {"s": fs})

    paddle.topology.reset_name_scope()
    x, fx = dense("x", 4)
    check_layer_grad(layer.scale_shift(x), {"x": fx}, check_inputs=["x"])


def test_grad_crf():
    paddle.topology.reset_name_scope()
    s, fs = make_seq("s", 3, [3, 2])
    lab = layer.data(name="lab",
                     type=paddle.data_type.integer_value_sequence(3))
    total = 5
    flab = SequenceBatch(
        jnp.asarray(RNG.randint(0, 3, (total,)).astype(np.int32)),
        fs.segment_ids, fs.lengths, max_len=fs.max_len)
    feat = layer.fc(s, size=3)
    out = layer.crf(input=feat, label=lab, size=3)
    check_layer_grad(out, {"s": fs, "lab": flab}, delta=5e-3, rtol=8e-2)
