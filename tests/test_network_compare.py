"""Network-equivalence pairs through utils.compare_topologies.

Reference analog: paddle/gserver/tests/test_NetworkCompare.cpp and
trainer/tests/test_CompareTwoNets.cpp — the same computation expressed as
two different configs must produce identical outputs AND gradients. Each
test here is one such pair, with weights linked by ParamAttr name.
"""

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import layer, networks
from paddle_tpu.attr import ParamAttr
from paddle_tpu.sequence import SequenceBatch
from paddle_tpu.utils import compare_topologies

RNG = np.random.RandomState(31)


def _seq(dim, lens, cap=None, seed=5):
    rng = np.random.RandomState(seed)
    return SequenceBatch.from_list(
        [rng.randn(l, dim).astype(np.float32) * 0.5 for l in lens],
        capacity=cap or sum(lens))


def test_fc_vs_mixed_projection():
    """fc == mixed([full_matrix_projection]) with the same weight."""
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(6))
    a = layer.fc(x, size=5, act="tanh", bias_attr=False,
                 param_attr=ParamAttr(name="cmp_w"))
    b = layer.mixed(size=5, act="tanh", input=[
        layer.full_matrix_projection(x, size=5,
                                     param_attr=ParamAttr(name="cmp_w"))])
    fx = RNG.randn(4, 6).astype(np.float32)
    compare_topologies(a, b, {"x": fx}, check_inputs=("x",))


def test_fc_two_inputs_vs_mixed_two_projections():
    """Multi-input fc == mixed of two full_matrix_projections."""
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(6))
    y = layer.data(name="y", type=paddle.data_type.dense_vector(3))
    a = layer.fc([x, y], size=5, act="sigmoid", bias_attr=False,
                 param_attr=[ParamAttr(name="wx"), ParamAttr(name="wy")])
    b = layer.mixed(size=5, act="sigmoid", input=[
        layer.full_matrix_projection(x, size=5,
                                     param_attr=ParamAttr(name="wx")),
        layer.full_matrix_projection(y, size=5,
                                     param_attr=ParamAttr(name="wy"))])
    feeds = {"x": RNG.randn(4, 6).astype(np.float32),
             "y": RNG.randn(4, 3).astype(np.float32)}
    compare_topologies(a, b, feeds, check_inputs=("x", "y"))


def test_lstmemory_vs_recurrent_group_lstm_step():
    """lstmemory == recurrent_group over lstm_step with linked weights
    (the reference's test_RecurrentLayer strategy, one scan vs explicit
    per-frame steps)."""
    paddle.topology.reset_name_scope()
    H = 4
    s = layer.data(name="s",
                   type=paddle.data_type.dense_vector_sequence(4 * H))
    a = layer.lstmemory(s, size=H, param_attr=ParamAttr(name="lstm_w"),
                        bias_attr=ParamAttr(name="lstm_b"))

    def step(frame):
        c_mem = layer.memory(name="c_out", size=H)
        h_mem = layer.memory(name="h_out", size=H)
        st = layer.lstm_step(input=frame, state_mem=c_mem, output_mem=h_mem,
                             size=H, param_attr=ParamAttr(name="lstm_w"),
                             bias_attr=ParamAttr(name="lstm_b"), name="cell")
        h = layer.lstm_step_output(st, name="h_out")
        c = layer.lstm_step_state(st, name="c_out")
        return [h, c]

    outs = layer.recurrent_group(step=step, input=s, name="rg_cmp")
    b = outs[0]
    sb = _seq(4 * H, [3, 5], cap=8)
    compare_topologies(a, b, {"s": sb})


def test_recurrent_vs_group_elman():
    """layer.recurrent == recurrent_group(fc-on-memory + addto) — the flat
    built-in vs the user-composed group."""
    paddle.topology.reset_name_scope()
    H = 6
    x = layer.data(name="x", type=paddle.data_type.dense_vector_sequence(H))
    a = layer.recurrent(input=x, size=H, act="tanh", bias_attr=False,
                        param_attr=ParamAttr(name="shared_w"))

    def step(frame):
        m = layer.memory(name="h_out", size=H)
        proj = layer.fc(input=m, size=H, bias_attr=False,
                        param_attr=ParamAttr(name="shared_w"), name="h_proj")
        return layer.addto(input=[frame, proj], act="tanh", name="h_out")

    b = layer.recurrent_group(step=step, input=x, name="rg_elman")
    compare_topologies(a, b, {"x": _seq(H, [3, 5], cap=8)})


def test_img_conv_vs_conv_operator():
    """img_conv (static filter parameter) == conv_operator in mixed (filter
    arriving as a layer value) when the operator is fed the conv's weight."""
    paddle.topology.reset_name_scope()
    fs, C, F, HW = 3, 2, 2, 4
    x = layer.data(name="x", type=paddle.data_type.dense_vector(HW * HW * C),
                   height=HW, width=HW)
    a = layer.img_conv(x, filter_size=fs, num_filters=F, num_channels=C,
                       padding=0, bias_attr=False,
                       param_attr=ParamAttr(name="conv_w"), name="ca")
    out = (HW - fs + 1)
    filt = layer.data(name="filt",
                      type=paddle.data_type.dense_vector(fs * fs * C * F))
    b = layer.mixed(size=out * out * F, input=[
        layer.conv_operator(x, filt, filter_size=fs, num_filters=F,
                            num_channels=C)])

    # the operator needs the SAME filter values the parameter got at init:
    # rebuild A's topology at the same seed and extract them
    wv = np.asarray(paddle.Parameters.from_topology(
        paddle.topology.Topology([a]), seed=0)["conv_w"]).reshape(1, -1)
    n = 3
    fx = RNG.randn(n, HW * HW * C).astype(np.float32)
    ffilt = np.tile(wv, (n, 1)).astype(np.float32)
    compare_topologies(a, b, {"x": fx}, {"x": fx, "filt": ffilt},
                       check_inputs=("x",), rtol=2e-4, atol=2e-5)


def test_simple_lstm_vs_explicit_fc_lstmemory():
    """networks.simple_lstm == fc(4H) -> lstmemory built by hand."""
    paddle.topology.reset_name_scope()
    H, D = 4, 6
    s = layer.data(name="s", type=paddle.data_type.dense_vector_sequence(D))
    # same layer names on both sides link every parameter automatically
    a = networks.simple_lstm(input=s, size=H, name="lm")
    paddle.topology.reset_name_scope()
    s = layer.data(name="s", type=paddle.data_type.dense_vector_sequence(D))
    b = layer.lstmemory(
        layer.fc(s, size=4 * H, bias_attr=True, name="lm_input_proj"),
        size=H, name="lm")
    compare_topologies(a, b, {"s": _seq(D, [4, 2], cap=8)})


def test_compare_catches_inequivalent_networks():
    """The harness must FAIL when the two configs genuinely differ."""
    paddle.topology.reset_name_scope()
    x = layer.data(name="x", type=paddle.data_type.dense_vector(6))
    a = layer.fc(x, size=5, act="tanh", bias_attr=False,
                 param_attr=ParamAttr(name="cmp_w"))
    b = layer.fc(x, size=5, act="sigmoid", bias_attr=False,
                 param_attr=ParamAttr(name="cmp_w"))
    fx = RNG.randn(4, 6).astype(np.float32)
    with pytest.raises(AssertionError):
        compare_topologies(a, b, {"x": fx})


def test_lm_head_cost_vs_unfused_pair():
    """Fused blockwise LM-head xent == fc(vocab) -> classification_cost
    with the same weights, outputs AND grads (incl. through the input)."""
    paddle.topology.reset_name_scope()
    V, D = 37, 6   # 37 % 8 != 0 exercises the padded last block
    x = layer.data(name="x", type=paddle.data_type.dense_vector(D))
    lab = layer.data(name="lab", type=paddle.data_type.integer_value(V))
    a = layer.classification_cost(
        input=layer.fc(x, size=V, param_attr=ParamAttr(name="head_w"),
                       bias_attr=ParamAttr(name="head_b")), label=lab)
    b = layer.lm_head_cost(x, lab, vocab_size=V,
                           param_attr=ParamAttr(name="head_w"),
                           bias_attr=ParamAttr(name="head_b"), block_size=8)
    fx = RNG.randn(5, D).astype(np.float32)
    flab = RNG.randint(0, V, (5,)).astype(np.int32)
    compare_topologies(a, b, {"x": fx, "lab": flab}, check_inputs=("x",))
