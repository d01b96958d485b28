"""The layer DSL — the ``paddle.v2.layer`` / trainer_config_helpers analog.

Reference: python/paddle/trainer_config_helpers/layers.py (131 functions → the
95 registered C++ layer types in paddle/gserver/layers) and
python/paddle/v2/layer.py. Each function here returns a ``LayerOutput`` graph
node whose compute fn is pure jax; the whole graph compiles to one XLA program
(see paddle_tpu/topology.py).

Values flowing through the graph are either dense ``jax.Array`` ([batch, ...])
or ``SequenceBatch`` (ragged). Cost layers return per-example losses; the
trainer applies masking/averaging.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from paddle_tpu import activation as act_mod
from paddle_tpu import pooling as pooling_mod
from paddle_tpu.attr import ExtraAttr, ParamAttr
from paddle_tpu.data_type import InputType, SeqKind, SlotKind
from paddle_tpu.initializer import Constant
from paddle_tpu.ops import conv as pconv
from paddle_tpu.ops import losses as ploss
from paddle_tpu.ops import math as pmath
from paddle_tpu.ops import norm as pnorm
from paddle_tpu.ops import pool as ppool
from paddle_tpu.ops import rnn as prnn
from paddle_tpu.ops import sequence_ops as pseq
from paddle_tpu.ops.embedding import embedding_lookup
from paddle_tpu.ops.kernel_util import per_device
from paddle_tpu.platform.enforce import EnforceError, enforce_that
from paddle_tpu.sequence import SequenceBatch
from paddle_tpu.topology import (Context, LayerOutput, ParamSpec, StateSpec,
                                 unique_name)

__all__: List[str] = []


def _export(fn):
    __all__.append(fn.__name__)
    return fn


def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _resolve_act(act):
    return act_mod.get(act)


def _cast_value(value, dtype):
    if isinstance(value, SequenceBatch):
        return value.with_data(value.data.astype(dtype))
    return value.astype(dtype)


def _act_then_cast(activation, value, dtype):
    """Apply an activation and cast the result to the storage dtype.

    Softmax-family activations normalize across a row — computing them in
    bf16 collapses small probabilities, so they run on the f32 pre-activation
    (the matmul accumulator dtype) and only the activated output is cast.
    Other activations are pointwise and monotone-precision, so the cheaper
    order (cast first, activate in storage dtype) is used.
    """
    if isinstance(activation, (act_mod.SoftmaxActivation,
                               act_mod.SequenceSoftmaxActivation)):
        return _cast_value(_apply_act(activation, value), dtype)
    return _apply_act(activation, _cast_value(value, dtype))


def _apply_act(activation, value):
    """Apply an activation to a dense array or tokenwise to a SequenceBatch."""
    if isinstance(activation, act_mod.SequenceSoftmaxActivation):
        enforce_that(isinstance(value, SequenceBatch),
                     "sequence_softmax needs a sequence input", context="layer")
        return pseq.sequence_softmax(value)
    fn = activation.fn
    if fn is None:
        return value
    if isinstance(value, SequenceBatch):
        return value.with_data(fn(value.data))
    return fn(value)


@jax.custom_vjp
def _clip_error(x, threshold):
    return x


def _clip_error_fwd(x, threshold):
    return x, threshold


def _clip_error_bwd(threshold, g):
    # identity forward, clipped backward: the reference's per-layer
    # error_clipping_threshold (Layer.cpp backwardActivation clips the
    # output-grad to [-t, t] before it propagates)
    return jnp.clip(g, -threshold, threshold), None


_clip_error.defvjp(_clip_error_fwd, _clip_error_bwd)


def _apply_extra(ctx: Context, name: str, value, layer_attr: Optional[ExtraAttr]):
    attr = ExtraAttr.to_attr(layer_attr)
    if attr.drop_rate > 0.0:
        key = ctx.rng_for(name)
        if isinstance(value, SequenceBatch):
            value = value.with_data(
                pmath.dropout(value.data, attr.drop_rate, key, ctx.train))
        else:
            value = pmath.dropout(value, attr.drop_rate, key, ctx.train)
    if attr.sharding is not None and getattr(ctx, "mesh", None) is not None:
        # activation half of model parallelism: constrain this layer's
        # output over the mesh; XLA inserts the collectives (the
        # ParallelNeuralNetwork dispatchByDeviceId analog)
        from jax.sharding import NamedSharding, PartitionSpec as P

        ns = NamedSharding(ctx.mesh, P(*attr.sharding))
        if isinstance(value, SequenceBatch):
            value = value.with_data(
                jax.lax.with_sharding_constraint(value.data, ns))
        else:
            value = jax.lax.with_sharding_constraint(value, ns)
    if attr.error_clipping_threshold > 0.0:
        # LAST in forward order = FIRST in backward: the raw upstream
        # gradient is clipped before dropout's 1/(1-p) rescale, matching
        # the reference (Layer.cpp backwardActivation clips the incoming
        # output-grad before any other backward work)
        t = float(attr.error_clipping_threshold)
        if isinstance(value, SequenceBatch):
            value = value.with_data(_clip_error(value.data, t))
        else:
            value = _clip_error(value, t)
    return value


def _data_of(v):
    return v.data if isinstance(v, SequenceBatch) else v


def _like(template, data):
    if isinstance(template, SequenceBatch):
        return template.with_data(data)
    return data


def _propagate_img_shape(node: LayerOutput, *sources) -> LayerOutput:
    """Copy (H, W, C) metadata through shape-preserving layers so the image
    stack (conv/pool/bn/addto chains in ResNet etc.) keeps its geometry.
    Uses _img_shape_of so data(height=, width=) geometry also propagates."""
    for src in sources:
        shp = _img_shape_of(src)
        if shp is not None:
            node.img_shape = shp
            break
    return node


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


_data_counter = [0]


@_export
def data(name: str, type: InputType, height: int = None, width: int = None,
         **_ignored) -> LayerOutput:
    """Input placeholder (reference: data_layer, v2 layer.data)."""
    node = LayerOutput(
        name=name, layer_type="data", inputs=[], fn=None,
        size=type.dim, is_sequence=type.seq != SeqKind.NO_SEQUENCE)
    node.input_type = type
    node.height, node.width = height, width
    # declaration order drives the default feeding column order (v2
    # semantics: sample tuples align with data layers as declared)
    node.declare_idx = _data_counter[0]
    _data_counter[0] += 1
    return node


# ---------------------------------------------------------------------------
# fc / embedding / mixed projections
# ---------------------------------------------------------------------------


@_export
def fc(input, size: int, act=None, name: Optional[str] = None,
       param_attr=None, bias_attr=True, layer_attr=None) -> LayerOutput:
    """Fully connected layer; multiple inputs are projected and summed
    (reference: fc_layer, gserver/layers/FullyConnectedLayer.cpp:69-139)."""
    inputs = _as_list(input)
    name = name or unique_name("fc")
    activation = _resolve_act(act)
    attrs = _as_list(param_attr) if isinstance(param_attr, (list, tuple)) else [param_attr] * len(inputs)
    params: Dict[str, ParamSpec] = {}
    for i, (inp, pa) in enumerate(zip(inputs, attrs)):
        enforce_that(inp.size is not None, f"input {inp.name} has no size", context="fc")
        params[f"w{i}"] = ParamSpec((inp.size, size), ParamAttr.to_attr(pa))
    has_bias = bool(bias_attr)
    if has_bias:
        battr = ParamAttr.to_attr(None if bias_attr is True else bias_attr)
        params["b"] = ParamSpec((size,), battr)

    def compute(ctx: Context, p, ins):
        total = None
        for i, v in enumerate(ins):
            d = _data_of(v)
            if not isinstance(v, SequenceBatch) and d.ndim > 2:
                d = d.reshape(d.shape[0], -1)  # flatten image maps (NHWC)
            y = pmath.matmul(d, p[f"w{i}"])
            total = y if total is None else total + y
        if has_bias:
            total = total + p["b"]
        out = _like(ins[0], total) if isinstance(ins[0], SequenceBatch) else total
        out = _act_then_cast(activation, out, pmath.dense_activation_dtype())
        return _apply_extra(ctx, name, out, layer_attr)

    return LayerOutput(name=name, layer_type="fc", inputs=inputs, fn=compute,
                       params=params, size=size,
                       is_sequence=inputs[0].is_sequence)


@_export
def embedding(input, size: int, name: Optional[str] = None,
              param_attr=None, layer_attr=None) -> LayerOutput:
    """Table lookup (reference: embedding_layer → TableProjection)."""
    inp = input
    name = name or unique_name("embedding")
    attr = ParamAttr.to_attr(param_attr)
    params = {"w": ParamSpec((inp.size, size), attr)}

    def compute(ctx, p, ins):
        v = ins[0]
        ids = _data_of(v)
        out = embedding_lookup(p["w"], ids)
        return _like(v, out.astype(pmath.dense_activation_dtype()))

    return LayerOutput(name=name, layer_type="embedding", inputs=[inp],
                       fn=compute, params=params, size=size,
                       is_sequence=inp.is_sequence)


# ---- mixed layer & projections (reference: MixedLayer.cpp, Projection.h) ---


class Projection:
    """Projection descriptor for mixed(); computes a [*, size] contribution."""

    def __init__(self, input: LayerOutput, size: Optional[int]):
        self.input = input
        self.size = size
        self.params: Dict[str, ParamSpec] = {}

    def compute(self, p: Dict[str, jax.Array], value):
        raise NotImplementedError


class _FullMatrixProjection(Projection):
    def __init__(self, input, size, param_attr=None, trans=False):
        super().__init__(input, size)
        self.trans = trans
        shape = (size, input.size) if trans else (input.size, size)
        self.params["w"] = ParamSpec(shape, ParamAttr.to_attr(param_attr))

    def compute(self, p, value):
        return pmath.matmul(_data_of(value), p["w"], trans_b=self.trans)


@_export
def full_matrix_projection(input, size: int, param_attr=None) -> Projection:
    return _FullMatrixProjection(input, size, param_attr)


@_export
def trans_full_matrix_projection(input, size: int, param_attr=None) -> Projection:
    """Uses W^T (reference: TransposedFullMatrixProjection)."""
    return _FullMatrixProjection(input, size, param_attr, trans=True)


class _IdentityProjection(Projection):
    def __init__(self, input, offset=0, size=None):
        out_size = size or input.size
        super().__init__(input, out_size)
        self.offset = offset

    def compute(self, p, value):
        d = _data_of(value)
        return jax.lax.slice_in_dim(d, self.offset, self.offset + self.size, axis=-1)


@_export
def identity_projection(input, offset: int = 0, size: int = None) -> Projection:
    return _IdentityProjection(input, offset, size)


@_export
def slice_projection(input, slices: Sequence[Tuple[int, int]], **kw) -> Projection:
    class _Slice(Projection):
        def __init__(self):
            total = sum(e - s for s, e in slices)
            super().__init__(input, total)

        def compute(self, p, value):
            d = _data_of(value)
            parts = [jax.lax.slice_in_dim(d, s, e, axis=-1) for s, e in slices]
            return jnp.concatenate(parts, axis=-1)

    return _Slice()


class _DotMulProjection(Projection):
    def __init__(self, input, param_attr=None):
        super().__init__(input, input.size)
        self.params["w"] = ParamSpec((input.size,), ParamAttr.to_attr(param_attr))

    def compute(self, p, value):
        return _data_of(value) * p["w"]


@_export
def dotmul_projection(input, param_attr=None) -> Projection:
    return _DotMulProjection(input, param_attr)


class _ScalingProjection(Projection):
    def __init__(self, input, param_attr=None):
        super().__init__(input, input.size)
        self.params["w"] = ParamSpec((1,), ParamAttr.to_attr(param_attr))

    def compute(self, p, value):
        return _data_of(value) * p["w"][0]


@_export
def scaling_projection(input, param_attr=None) -> Projection:
    return _ScalingProjection(input, param_attr)


class _TableProjection(Projection):
    def __init__(self, input, size, param_attr=None):
        super().__init__(input, size)
        self.params["w"] = ParamSpec((input.size, size), ParamAttr.to_attr(param_attr))

    def compute(self, p, value):
        return embedding_lookup(p["w"], _data_of(value))


@_export
def table_projection(input, size: int, param_attr=None) -> Projection:
    return _TableProjection(input, size, param_attr)


class _ContextProjection(Projection):
    """Sliding window concat over sequence tokens (reference:
    ContextProjection / function/ContextProjectionOp.cpp)."""

    def __init__(self, input, context_len, context_start, param_attr=None,
                 trainable_padding=False):
        super().__init__(input, input.size * context_len)
        self.context_len = context_len
        self.context_start = context_start
        self.trainable_padding = trainable_padding
        if trainable_padding:
            pad_rows = max(0, -context_start) + max(0, context_start + context_len - 1)
            self.params["pad"] = ParamSpec((max(1, pad_rows), input.size),
                                           ParamAttr.to_attr(param_attr))

    def compute(self, p, value):
        enforce_that(isinstance(value, SequenceBatch),
                     "context projection needs sequence input", context="mixed")
        padded, mask = value.to_padded()
        B, T, D = padded.shape
        cols = []
        for k in range(self.context_len):
            off = self.context_start + k
            shifted = jnp.roll(padded, -off, axis=1)
            # zero (or learned pad) outside range
            t = jnp.arange(T)[None, :]
            valid = (t + off >= 0) & (t + off < value.lengths[:, None])
            col = jnp.where(valid[..., None], shifted, 0.0)
            cols.append(col)
        out = jnp.concatenate(cols, axis=-1)
        flat = SequenceBatch.from_padded(out, value.lengths, capacity=value.capacity)
        return flat.data


@_export
def context_projection(input, context_len: int, context_start: int = None,
                       padding_attr=False, **kw) -> Projection:
    start = context_start if context_start is not None else -(context_len // 2)
    trainable = padding_attr is not False and padding_attr is not None
    return _ContextProjection(input, context_len, start,
                              param_attr=None if padding_attr in (False, True, None) else padding_attr,
                              trainable_padding=trainable)


class Operator:
    """Mixed-layer operator (reference: Operator.h — conv, dot_mul)."""

    def __init__(self, inputs: List[LayerOutput], size: Optional[int]):
        self.inputs = inputs
        self.size = size

    def compute(self, values: list):
        raise NotImplementedError


@_export
def dotmul_operator(a: LayerOutput, b: LayerOutput, scale: float = 1.0) -> Operator:
    class _DotMul(Operator):
        def __init__(self):
            super().__init__([a, b], a.size)

        def compute(self, values):
            return scale * _data_of(values[0]) * _data_of(values[1])

    return _DotMul()


@_export
def conv_operator(img: LayerOutput, filter: LayerOutput, filter_size: int,
                  num_filters: int, num_channels: int, stride: int = 1,
                  padding: int = 0) -> Operator:
    """Conv with filter coming from a layer (dynamic filter conv)."""

    class _ConvOp(Operator):
        def __init__(self):
            super().__init__([img, filter], None)

        def compute(self, values):
            x, f = _data_of(values[0]), _data_of(values[1])
            B = x.shape[0]
            if x.ndim == 2:
                # flat dense image slots are CHW-major like every other
                # image layer (_to_nhwc; reference PyDataProvider2 layout)
                h = int(round((x.shape[-1] // num_channels) ** 0.5))
                x = x.reshape(B, num_channels, h, h).transpose(0, 2, 3, 1)
            w = f.reshape(B, filter_size, filter_size, num_channels, num_filters)

            def one(xi, wi):
                return pconv.conv2d(xi[None], wi, stride=stride, padding=padding)[0]

            y = jax.vmap(one)(x, w)
            return y.reshape(B, -1)

    return _ConvOp()


@_export
def mixed(size: int = None, input=None, name: Optional[str] = None, act=None,
          bias_attr=False, layer_attr=None) -> LayerOutput:
    """Sum of projections/operators (reference: mixed_layer, MixedLayer.cpp)."""
    name = name or unique_name("mixed")
    comps = _as_list(input)
    enforce_that(len(comps) > 0, "mixed needs at least one projection", context="mixed")
    activation = _resolve_act(act)
    # infer size
    sizes = [c.size for c in comps if c.size is not None]
    if size is None:
        enforce_that(len(sizes) > 0, "mixed size cannot be inferred", context="mixed")
        size = sizes[0]

    graph_inputs: List[LayerOutput] = []
    proj_params: Dict[str, ParamSpec] = {}
    plan = []  # (kind, component, input_indices, param_prefix)
    for ci, comp in enumerate(comps):
        if isinstance(comp, Projection):
            graph_inputs.append(comp.input)
            prefix = f"p{ci}_"
            for pn, spec in comp.params.items():
                proj_params[prefix + pn] = spec
            plan.append(("proj", comp, [len(graph_inputs) - 1], prefix))
        elif isinstance(comp, Operator):
            idxs = []
            for inp in comp.inputs:
                graph_inputs.append(inp)
                idxs.append(len(graph_inputs) - 1)
            plan.append(("op", comp, idxs, None))
        elif isinstance(comp, LayerOutput):
            proj = identity_projection(comp)
            graph_inputs.append(comp)
            plan.append(("proj", proj, [len(graph_inputs) - 1], f"p{ci}_"))
        else:
            raise EnforceError(f"bad mixed component {comp!r}", context="mixed")

    has_bias = bool(bias_attr)
    if has_bias:
        battr = ParamAttr.to_attr(None if bias_attr is True else bias_attr)
        proj_params["b"] = ParamSpec((size,), battr)

    is_seq = graph_inputs[0].is_sequence

    def compute(ctx, p, ins):
        total = None
        template = ins[0]
        for kind, comp, idxs, prefix in plan:
            if kind == "proj":
                local = {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}
                y = comp.compute(local, ins[idxs[0]])
            else:
                y = comp.compute([ins[i] for i in idxs])
            total = y if total is None else total + y
        if has_bias:
            total = total + p["b"]
        out = _like(template, total) if isinstance(template, SequenceBatch) else total
        out = _apply_act(activation, out)
        return _apply_extra(ctx, name, out, layer_attr)

    return LayerOutput(name=name, layer_type="mixed", inputs=graph_inputs,
                       fn=compute, params=proj_params, size=size,
                       is_sequence=is_seq)


# ---------------------------------------------------------------------------
# elementwise / math layers
# ---------------------------------------------------------------------------


@_export
def addto(input, act=None, name: Optional[str] = None, bias_attr=False,
          layer_attr=None) -> LayerOutput:
    """Elementwise sum (reference: addto_layer/AddtoLayer.cpp)."""
    inputs = _as_list(input)
    name = name or unique_name("addto")
    activation = _resolve_act(act)
    params = {}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((inputs[0].size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        total = _data_of(ins[0])
        for v in ins[1:]:
            total = total + _data_of(v)
        if has_bias:
            total = total + p["b"].astype(total.dtype)
        out = _like(ins[0], total)
        out = _apply_act(activation, out)
        return _apply_extra(ctx, name, out, layer_attr)

    node = LayerOutput(name=name, layer_type="addto", inputs=inputs, fn=compute,
                       params=params, size=inputs[0].size,
                       is_sequence=inputs[0].is_sequence)
    return _propagate_img_shape(node, *inputs)


@_export
def concat(input, name: Optional[str] = None, act=None, layer_attr=None) -> LayerOutput:
    """Feature-dim concat (reference: concat_layer/ConcatenateLayer)."""
    inputs = _as_list(input)
    name = name or unique_name("concat")
    activation = _resolve_act(act)
    size = sum(i.size for i in inputs)

    def compute(ctx, p, ins):
        out = jnp.concatenate([_data_of(v) for v in ins], axis=-1)
        out = _like(ins[0], out)
        out = _apply_act(activation, out)
        return _apply_extra(ctx, name, out, layer_attr)

    node = LayerOutput(name=name, layer_type="concat", inputs=inputs, fn=compute,
                       size=size, is_sequence=inputs[0].is_sequence)
    # channel concat of same-geometry images (inception towers): carry
    # (H, W, sum C) so downstream conv/pool keep the geometry
    shapes = [_img_shape_of(i) for i in inputs]
    if all(s is not None for s in shapes) and \
            len({(h, w) for h, w, _ in shapes}) == 1:
        h, w, _ = shapes[0]
        node.img_shape = (h, w, sum(c for _, _, c in shapes))
    return node


@_export
def dotmul(a, b, name: Optional[str] = None) -> LayerOutput:
    """Elementwise product of two layers."""
    name = name or unique_name("dotmul")

    def compute(ctx, p, ins):
        return _like(ins[0], _data_of(ins[0]) * _data_of(ins[1]))

    return LayerOutput(name=name, layer_type="dotmul", inputs=[a, b], fn=compute,
                       size=a.size, is_sequence=a.is_sequence)


@_export
def interpolation(input, weight, name: Optional[str] = None) -> LayerOutput:
    """out = w*a + (1-w)*b with per-example scalar w (reference:
    interpolation_layer/InterpolationLayer.cpp). input=[a, b]."""
    a, b = _as_list(input)
    name = name or unique_name("interpolation")

    def compute(ctx, p, ins):
        va, vb, w = _data_of(ins[0]), _data_of(ins[1]), _data_of(ins[2])
        w = w.reshape(w.shape[0], *([1] * (va.ndim - 1)))
        return _like(ins[0], w * va + (1.0 - w) * vb)

    return LayerOutput(name=name, layer_type="interpolation", inputs=[a, b, weight],
                       fn=compute, size=a.size, is_sequence=a.is_sequence)


@_export
def scaling(input, weight, name: Optional[str] = None) -> LayerOutput:
    """Row-wise scale by a per-example scalar (reference: scaling_layer)."""
    name = name or unique_name("scaling")

    def compute(ctx, p, ins):
        v, w = _data_of(ins[0]), _data_of(ins[1])
        w = w.reshape(w.shape[0], *([1] * (v.ndim - 1)))
        return _like(ins[0], w * v)

    return LayerOutput(name=name, layer_type="scaling", inputs=[input, weight],
                       fn=compute, size=input.size, is_sequence=input.is_sequence)


@_export
def power(input, weight, name: Optional[str] = None) -> LayerOutput:
    """Elementwise x^w with per-example scalar w (reference: power_layer)."""
    name = name or unique_name("power")

    def compute(ctx, p, ins):
        v, w = _data_of(ins[0]), _data_of(ins[1])
        w = w.reshape(w.shape[0], *([1] * (v.ndim - 1)))
        return _like(ins[0], jnp.power(v, w))

    return LayerOutput(name=name, layer_type="power", inputs=[input, weight],
                       fn=compute, size=input.size, is_sequence=input.is_sequence)


@_export
def slope_intercept(input, slope: float = 1.0, intercept: float = 0.0,
                    name: Optional[str] = None) -> LayerOutput:
    """y = slope*x + intercept (reference: slope_intercept_layer)."""
    name = name or unique_name("slope_intercept")

    def compute(ctx, p, ins):
        return _like(ins[0], slope * _data_of(ins[0]) + intercept)

    return LayerOutput(name=name, layer_type="slope_intercept", inputs=[input],
                       fn=compute, size=input.size, is_sequence=input.is_sequence)


@_export
def sum_to_one_norm(input, name: Optional[str] = None) -> LayerOutput:
    name = name or unique_name("sum_to_one_norm")

    def compute(ctx, p, ins):
        return _like(ins[0], pnorm.sum_to_one_norm(_data_of(ins[0])))

    return LayerOutput(name=name, layer_type="sum_to_one_norm", inputs=[input],
                       fn=compute, size=input.size, is_sequence=input.is_sequence)


@_export
def row_l2_norm(input, name: Optional[str] = None) -> LayerOutput:
    name = name or unique_name("row_l2_norm")

    def compute(ctx, p, ins):
        return _like(ins[0], pnorm.row_l2_norm(_data_of(ins[0])))

    return LayerOutput(name=name, layer_type="row_l2_norm", inputs=[input],
                       fn=compute, size=input.size, is_sequence=input.is_sequence)


@_export
def cos_sim(a, b, scale: float = 1.0, name: Optional[str] = None) -> LayerOutput:
    """Cosine similarity (reference: cos_sim/CosSimLayer.cpp)."""
    name = name or unique_name("cos_sim")

    def compute(ctx, p, ins):
        return ploss.cosine_similarity(_data_of(ins[0]), _data_of(ins[1]), scale)[..., None]

    return LayerOutput(name=name, layer_type="cos_sim", inputs=[a, b], fn=compute,
                       size=1, is_sequence=a.is_sequence)


@_export
def clip(input, min: float, max: float, name: Optional[str] = None) -> LayerOutput:
    """Elementwise clip (reference: ClipLayer.cpp)."""
    name = name or unique_name("clip")

    def compute(ctx, p, ins):
        return _like(ins[0], jnp.clip(_data_of(ins[0]), min, max))

    return LayerOutput(name=name, layer_type="clip", inputs=[input], fn=compute,
                       size=input.size, is_sequence=input.is_sequence)


@_export
def resize(input, size: int, name: Optional[str] = None) -> LayerOutput:
    """Reshape the batch matrix to `size` columns, keeping the total element
    count — the row count becomes B*input.size/size (reference: ResizeLayer).
    Sequences keep their token structure elsewhere; use seq_reshape for them."""
    name = name or unique_name("resize")
    enforce_that(not input.is_sequence,
                 "resize reshapes the dense batch matrix; use seq_reshape "
                 "for sequences", context="resize")

    def compute(ctx, p, ins):
        return _data_of(ins[0]).reshape(-1, size)

    return LayerOutput(name=name, layer_type="resize", inputs=[input], fn=compute,
                       size=size, is_sequence=False)


@_export
def dropout(input, dropout_rate: float, name: Optional[str] = None) -> LayerOutput:
    """Standalone dropout (reference: dropout_layer helper)."""
    name = name or unique_name("dropout")

    def compute(ctx, p, ins):
        v = ins[0]
        key = ctx.rng_for(name)
        if isinstance(v, SequenceBatch):
            return v.with_data(pmath.dropout(v.data, dropout_rate, key, ctx.train))
        return pmath.dropout(v, dropout_rate, key, ctx.train)

    node = LayerOutput(name=name, layer_type="dropout", inputs=[input], fn=compute,
                       size=input.size, is_sequence=input.is_sequence)
    return _propagate_img_shape(node, input)


# ---------------------------------------------------------------------------
# image layers
# ---------------------------------------------------------------------------


def _img_shape_of(node: LayerOutput) -> Optional[Tuple[int, int, int]]:
    """(H, W, C) metadata threaded through the image stack."""
    shp = getattr(node, "img_shape", None)
    if shp is not None:
        return shp
    h = getattr(node, "height", None)
    w = getattr(node, "width", None)
    if h and w and node.size and node.size % (h * w) == 0:
        return (h, w, node.size // (h * w))
    return None


def _to_nhwc(v: jax.Array, shape_hwc: Tuple[int, int, int]) -> jax.Array:
    """Accept [B, H, W, C] passthrough or flat [B, C*H*W] (reference layout is
    CHW-major, matching PyDataProvider2 dense image slots)."""
    if v.ndim == 4:
        return v
    h, w, c = shape_hwc
    return v.reshape(v.shape[0], c, h, w).transpose(0, 2, 3, 1)


def _conv_out_dim(in_size, k, pad, stride):
    return (in_size + 2 * pad - k) // stride + 1


@_export
def img_conv(input, filter_size: int, num_filters: int, num_channels: int = None,
             stride: int = 1, padding: int = 0, groups: int = 1, act=None,
             name: Optional[str] = None, param_attr=None, bias_attr=True,
             shared_biases: bool = True, trans: bool = False,
             dilation: int = 1, layer_attr=None) -> LayerOutput:
    """2-D convolution (reference: img_conv_layer → ExpandConvLayer /
    CudnnConvLayer; trans=True → ConvTransLayer).

    Weights are HWIO; compute is NHWC on the MXU (ops/conv.py)."""
    inp = input
    name = name or unique_name("conv")
    activation = _resolve_act(act)
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None or num_channels is not None,
                 "img_conv needs image shape metadata or num_channels", context="img_conv")
    if in_shape is None:
        # assume square image
        import math as _math
        hw = int(round(_math.sqrt(inp.size // num_channels)))
        in_shape = (hw, hw, num_channels)
    h, w, c = in_shape
    num_channels = num_channels or c
    if trans:
        oh = (h - 1) * stride + filter_size - 2 * padding
        ow = (w - 1) * stride + filter_size - 2 * padding
        wshape = (filter_size, filter_size, num_channels, num_filters)
    else:
        oh = _conv_out_dim(h, filter_size, padding, stride)
        ow = _conv_out_dim(w, filter_size, padding, stride)
        wshape = (filter_size, filter_size, num_channels // groups, num_filters)
    params = {"w": ParamSpec(wshape, ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        bshape = (num_filters,) if shared_biases else (num_filters * oh * ow,)
        params["b"] = ParamSpec(bshape, ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        if trans:
            y = pconv.conv2d_transpose(x, p["w"], stride=stride, padding=padding)
        else:
            y = pconv.conv2d(x, p["w"], stride=stride, padding=padding,
                             dilation=dilation, groups=groups)
        if has_bias:
            # cast the f32 bias into the activation dtype: a plain add would
            # promote bf16 activations back to f32 and double HBM traffic
            if shared_biases:
                y = y + p["b"].astype(y.dtype)
            else:
                y = y + p["b"].reshape(1, oh, ow, num_filters).astype(y.dtype)
        y = _apply_act(activation, y)
        return _apply_extra(ctx, name, y, layer_attr)

    node = LayerOutput(name=name, layer_type="conv", inputs=[inp], fn=compute,
                       params=params, size=oh * ow * num_filters)
    node.img_shape = (oh, ow, num_filters)
    return node


@_export
def img_pool(input, pool_size: int, pool_type=None, stride: int = None,
             padding: int = 0, name: Optional[str] = None,
             layer_attr=None, **_kw) -> LayerOutput:
    """Image pooling (reference: img_pool_layer → PoolLayer/CudnnPoolLayer)."""
    inp = input
    name = name or unique_name("pool")
    ptype = pooling_mod.get(pool_type)
    stride = stride if stride is not None else pool_size
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "img_pool needs image shape", context="img_pool")
    h, w, c = in_shape
    oh = _conv_out_dim(h, pool_size, padding, stride)
    ow = _conv_out_dim(w, pool_size, padding, stride)

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        if isinstance(ptype, pooling_mod.MaxPooling):
            y = ppool.max_pool2d(x, pool_size, stride, padding)
        else:
            y = ppool.avg_pool2d(x, pool_size, stride, padding)
        return _apply_extra(ctx, name, y, layer_attr)

    node = LayerOutput(name=name, layer_type="pool", inputs=[inp], fn=compute,
                       size=oh * ow * c)
    node.img_shape = (oh, ow, c)
    return node


@_export
def spp(input, pyramid_height: int, num_channels: int = None, pool_type=None,
        name: Optional[str] = None) -> LayerOutput:
    """Spatial pyramid pooling (reference: spp_layer)."""
    inp = input
    name = name or unique_name("spp")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "spp needs image shape", context="spp")
    c = in_shape[2]
    ptype = pooling_mod.get(pool_type)
    out_size = sum(4 ** l for l in range(pyramid_height)) * c

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        return ppool.spatial_pyramid_pool(
            x, pyramid_height,
            "max" if isinstance(ptype, pooling_mod.MaxPooling) else "avg")

    return LayerOutput(name=name, layer_type="spp", inputs=[inp], fn=compute,
                       size=out_size)


@_export
def maxout(input, groups: int, num_channels: int = None,
           name: Optional[str] = None) -> LayerOutput:
    """Maxout over channel groups (reference: maxout_layer)."""
    inp = input
    name = name or unique_name("maxout")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "maxout needs image shape", context="maxout")
    h, w, c = in_shape
    oc = c // groups

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        return ppool.maxout(x, groups)

    node = LayerOutput(name=name, layer_type="maxout", inputs=[inp], fn=compute,
                       size=h * w * oc)
    node.img_shape = (h, w, oc)
    return node


@_export
def batch_norm(input, act=None, name: Optional[str] = None,
               num_channels: int = None, bias_attr=None, param_attr=None,
               use_global_stats: bool = None, moving_average_fraction: float = 0.9,
               layer_attr=None, **_kw) -> LayerOutput:
    """Batch normalization with moving stats in the state pytree
    (reference: batch_norm_layer → BatchNormalizationLayer/CudnnBatchNormLayer)."""
    inp = input
    name = name or unique_name("batch_norm")
    activation = _resolve_act(act)
    in_shape = _img_shape_of(inp)
    c = in_shape[2] if in_shape is not None else inp.size
    params = {
        "gamma": ParamSpec((c,), ParamAttr.to_attr(param_attr) if param_attr
                           else ParamAttr(initializer=Constant(1.0))),
        "beta": ParamSpec((c,), ParamAttr.to_attr(bias_attr) if bias_attr
                          else ParamAttr(initializer=Constant(0.0))),
    }
    state = {
        "moving_mean": StateSpec((c,), 0.0),
        "moving_var": StateSpec((c,), 1.0),
    }

    def compute(ctx, p, ins):
        v = ins[0]
        x = _data_of(v)
        if in_shape is not None:
            x = _to_nhwc(x, in_shape)
        y, nm, nv = pnorm.batch_norm(
            x, p["gamma"], p["beta"],
            ctx.get_state(name, "moving_mean"), ctx.get_state(name, "moving_var"),
            train=ctx.train, momentum=moving_average_fraction,
            use_global_stats=use_global_stats)
        ctx.set_state(name, "moving_mean", nm)
        ctx.set_state(name, "moving_var", nv)
        y = _apply_act(activation, y)
        y = _apply_extra(ctx, name, y, layer_attr)
        return _like(v, y) if isinstance(v, SequenceBatch) else y

    node = LayerOutput(name=name, layer_type="batch_norm", inputs=[inp],
                       fn=compute, params=params, state=state, size=inp.size,
                       is_sequence=inp.is_sequence)
    if in_shape is not None:
        node.img_shape = in_shape
    return node


@_export
def layer_norm(input, act=None, name: Optional[str] = None, param_attr=None,
               bias_attr=None, epsilon: float = 1e-5, **_kw) -> LayerOutput:
    """Per-row layer normalization over the feature axis (ops/norm.py
    layer_norm) — transformer-era extension beyond the reference's norm
    inventory (BatchNorm/CrossMapNorm, gserver/layers/*NormLayer.cpp);
    the normalization of the transformer LM family (models/transformer.py).
    Stats are per row, so packed variable-length sequences need no segment
    metadata."""
    inp = input
    name = name or unique_name("layer_norm")
    activation = _resolve_act(act)
    params = {
        "gamma": ParamSpec((inp.size,), ParamAttr.to_attr(param_attr)
                           if param_attr else ParamAttr(initializer=Constant(1.0))),
        "beta": ParamSpec((inp.size,), ParamAttr.to_attr(bias_attr)
                          if bias_attr else ParamAttr(initializer=Constant(0.0))),
    }

    def compute(ctx, p, ins):
        v = ins[0]
        x = _data_of(v)
        # pnorm.layer_norm reduces stats in f32 and emits x.dtype
        y = pnorm.layer_norm(x, p["gamma"], p["beta"], eps=epsilon)
        y = _apply_act(activation, y)
        return _like(v, y) if isinstance(v, SequenceBatch) else y

    return LayerOutput(name=name, layer_type="layer_norm", inputs=[inp],
                       fn=compute, params=params, size=inp.size,
                       is_sequence=inp.is_sequence)


@_export
def img_cmrnorm(input, size: int = 5, scale: float = 0.0001, power: float = 0.75,
                name: Optional[str] = None, **_kw) -> LayerOutput:
    """Local response normalization across maps (reference: img_cmrnorm_layer
    → CMRProjectionNormLayer, function/CrossMapNormalOp.cpp)."""
    inp = input
    name = name or unique_name("cmrnorm")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "cmrnorm needs image shape", context="cmrnorm")

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        return pnorm.cross_map_norm(x, size, scale, power)

    node = LayerOutput(name=name, layer_type="cmrnorm", inputs=[inp], fn=compute,
                       size=inp.size)
    node.img_shape = in_shape
    return node


@_export
def bilinear_interp(input, out_size_x: int, out_size_y: int,
                    name: Optional[str] = None) -> LayerOutput:
    """Bilinear upsampling (reference: bilinear_interp_layer, hl_cnn bilinear)."""
    inp = input
    name = name or unique_name("bilinear_interp")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "bilinear_interp needs image shape",
                 context="bilinear_interp")
    h, w, c = in_shape

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        return jax.image.resize(x, (x.shape[0], out_size_y, out_size_x, c),
                                method="bilinear")

    node = LayerOutput(name=name, layer_type="bilinear_interp", inputs=[inp],
                       fn=compute, size=out_size_x * out_size_y * c)
    node.img_shape = (out_size_y, out_size_x, c)
    return node


@_export
def pad(input, pad_c=(0, 0), pad_h=(0, 0), pad_w=(0, 0),
        name: Optional[str] = None) -> LayerOutput:
    """Zero-pad image dims (reference: pad_layer, function/PadOp.cpp)."""
    inp = input
    name = name or unique_name("pad")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "pad needs image shape", context="pad")
    h, w, c = in_shape
    oshape = (h + sum(pad_h), w + sum(pad_w), c + sum(pad_c))

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        return jnp.pad(x, ((0, 0), tuple(pad_h), tuple(pad_w), tuple(pad_c)))

    node = LayerOutput(name=name, layer_type="pad", inputs=[inp], fn=compute,
                       size=oshape[0] * oshape[1] * oshape[2])
    node.img_shape = oshape
    return node


@_export
def crop(input, offset_h: int = 0, offset_w: int = 0, crop_h: int = None,
         crop_w: int = None, name: Optional[str] = None) -> LayerOutput:
    """Crop image dims (reference: crop_layer, function/CropOp.cpp)."""
    inp = input
    name = name or unique_name("crop")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "crop needs image shape", context="crop")
    h, w, c = in_shape
    ch = crop_h or h - offset_h
    cw = crop_w or w - offset_w

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        return x[:, offset_h:offset_h + ch, offset_w:offset_w + cw, :]

    node = LayerOutput(name=name, layer_type="crop", inputs=[inp], fn=compute,
                       size=ch * cw * c)
    node.img_shape = (ch, cw, c)
    return node


@_export
def rotate(input, name: Optional[str] = None) -> LayerOutput:
    """90-degree CCW rotation (reference: rotate_layer/RotateLayer.cpp)."""
    inp = input
    name = name or unique_name("rotate")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "rotate needs image shape", context="rotate")
    h, w, c = in_shape

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        return jnp.rot90(x, k=1, axes=(1, 2))

    node = LayerOutput(name=name, layer_type="rotate", inputs=[inp], fn=compute,
                       size=inp.size)
    node.img_shape = (w, h, c)
    return node


@_export
def block_expand(input, block_x: int, block_y: int, stride_x: int = 1,
                 stride_y: int = 1, padding_x: int = 0, padding_y: int = 0,
                 num_channels: int = None, name: Optional[str] = None) -> LayerOutput:
    """im2col layer (reference: block_expand_layer/BlockExpandLayer)."""
    inp = input
    name = name or unique_name("block_expand")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "block_expand needs image shape",
                 context="block_expand")
    h, w, c = in_shape
    oh = (h + 2 * padding_y - block_y) // stride_y + 1
    ow = (w + 2 * padding_x - block_x) // stride_x + 1

    def compute(ctx, p, ins):
        x = _to_nhwc(_data_of(ins[0]), in_shape)
        return pconv.block_expand(x, (block_y, block_x), (stride_y, stride_x),
                                  (padding_y, padding_x))

    return LayerOutput(name=name, layer_type="block_expand", inputs=[inp],
                       fn=compute, size=block_x * block_y * c)


# ---------------------------------------------------------------------------
# sequence layers
# ---------------------------------------------------------------------------


def _need_seq(node, ctx_name):
    enforce_that(node.is_sequence, f"{ctx_name} needs a sequence input",
                 context=ctx_name)


@_export
def pooling(input, pooling_type=None, name: Optional[str] = None,
            **_kw) -> LayerOutput:
    """Sequence pooling to one vector per sequence (reference: pooling_layer
    → SequencePoolLayer max/avg/sum/sqrtn)."""
    inp = input
    _need_seq(inp, "pooling")
    name = name or unique_name("seq_pool")
    ptype = pooling_mod.get(pooling_type)

    def compute(ctx, p, ins):
        sb = ins[0]
        if isinstance(ptype, pooling_mod.MaxPooling):
            return pseq.seq_pool_max(sb)
        if isinstance(ptype, pooling_mod.AvgPooling):
            return pseq.seq_pool_avg(sb)
        if isinstance(ptype, pooling_mod.SumPooling):
            return pseq.seq_pool_sum(sb)
        return pseq.seq_pool_sqrtn(sb)

    return LayerOutput(name=name, layer_type="seq_pool", inputs=[inp],
                       fn=compute, size=inp.size, is_sequence=False)


@_export
def last_seq(input, name: Optional[str] = None, **_kw) -> LayerOutput:
    """Last token of each sequence (reference: last_seq → SequenceLastInstance)."""
    inp = input
    _need_seq(inp, "last_seq")
    name = name or unique_name("last_seq")

    def compute(ctx, p, ins):
        return pseq.seq_last(ins[0])

    return LayerOutput(name=name, layer_type="last_seq", inputs=[inp], fn=compute,
                       size=inp.size, is_sequence=False)


@_export
def first_seq(input, name: Optional[str] = None, **_kw) -> LayerOutput:
    """First token of each sequence (reference: first_seq)."""
    inp = input
    _need_seq(inp, "first_seq")
    name = name or unique_name("first_seq")

    def compute(ctx, p, ins):
        return pseq.seq_first(ins[0])

    return LayerOutput(name=name, layer_type="first_seq", inputs=[inp], fn=compute,
                       size=inp.size, is_sequence=False)


@_export
def expand(input, expand_as, name: Optional[str] = None, **_kw) -> LayerOutput:
    """Broadcast per-sequence rows to token layout (reference: expand_layer)."""
    name = name or unique_name("expand")

    def compute(ctx, p, ins):
        return pseq.seq_expand(ins[0], ins[1])

    return LayerOutput(name=name, layer_type="expand", inputs=[input, expand_as],
                       fn=compute, size=input.size, is_sequence=True)


@_export
def seq_concat(a, b, name: Optional[str] = None, **_kw) -> LayerOutput:
    """Concat along time (reference: seq_concat_layer)."""
    name = name or unique_name("seq_concat")

    def compute(ctx, p, ins):
        return pseq.seq_concat(ins[0], ins[1])

    return LayerOutput(name=name, layer_type="seq_concat", inputs=[a, b],
                       fn=compute, size=a.size, is_sequence=True)


@_export
def seq_reshape(input, reshape_size: int, name: Optional[str] = None,
                **_kw) -> LayerOutput:
    """Reshape token dim (reference: seq_reshape_layer)."""
    inp = input
    _need_seq(inp, "seq_reshape")
    name = name or unique_name("seq_reshape")

    def compute(ctx, p, ins):
        return pseq.seq_reshape(ins[0], reshape_size)

    return LayerOutput(name=name, layer_type="seq_reshape", inputs=[inp],
                       fn=compute, size=reshape_size, is_sequence=True)


@_export
def seq_slice(input, starts=None, ends=None, name: Optional[str] = None) -> LayerOutput:
    """Slice each sequence by per-sequence [start, end) (reference:
    seq_slice_layer). starts/ends are layers carrying int positions or None."""
    inp = input
    _need_seq(inp, "seq_slice")
    name = name or unique_name("seq_slice")
    extra = [l for l in (starts, ends) if l is not None]

    def compute(ctx, p, ins):
        sb = ins[0]
        idx = 1
        if starts is not None:
            s = _data_of(ins[idx]).reshape(-1).astype(jnp.int32)
            idx += 1
        else:
            s = jnp.zeros((sb.num_seqs,), jnp.int32)
        if ends is not None:
            e = _data_of(ins[idx]).reshape(-1).astype(jnp.int32)
        else:
            e = sb.lengths
        return pseq.seq_slice(sb, s, e)

    return LayerOutput(name=name, layer_type="seq_slice", inputs=[inp] + extra,
                       fn=compute, size=inp.size, is_sequence=True)


@_export
def kmax_seq_score(input, beam_size: int, name: Optional[str] = None) -> LayerOutput:
    """Top-k positions by score in each sequence (reference: kmax_seq_score)."""
    inp = input
    _need_seq(inp, "kmax_seq_score")
    name = name or unique_name("kmax_seq_score")

    def compute(ctx, p, ins):
        return pseq.kmax_seq_score(ins[0], beam_size)

    return LayerOutput(name=name, layer_type="kmax_seq_score", inputs=[inp],
                       fn=compute, size=beam_size, is_sequence=False)


@_export
def sub_nested_seq(input, selected_indices, name: Optional[str] = None) -> LayerOutput:
    """Select inner sequences of a nested sequence (reference: sub_nested_seq)."""
    name = name or unique_name("sub_nested_seq")

    def compute(ctx, p, ins):
        return pseq.sub_nested_seq(ins[0], _data_of(ins[1]).astype(jnp.int32))

    return LayerOutput(name=name, layer_type="sub_nested_seq",
                       inputs=[input, selected_indices], fn=compute,
                       size=input.size, is_sequence=True)


@_export
def max_id(input, name: Optional[str] = None) -> LayerOutput:
    """Argmax id (reference: maxid_layer/MaxIdLayer.cpp)."""
    inp = input
    name = name or unique_name("max_id")

    def compute(ctx, p, ins):
        v = ins[0]
        return _like(v, pseq.max_id(_data_of(v)))

    return LayerOutput(name=name, layer_type="max_id", inputs=[inp], fn=compute,
                       size=1, is_sequence=inp.is_sequence)


@_export
def sampling_id(input, name: Optional[str] = None) -> LayerOutput:
    """Sample an id from a row distribution (reference: sampling_id_layer)."""
    inp = input
    name = name or unique_name("sampling_id")

    def compute(ctx, p, ins):
        v = ins[0]
        probs = _data_of(v)
        key = ctx.rng_for(name)
        ids = jax.random.categorical(key, jnp.log(jnp.clip(probs, 1e-20, 1.0)))
        return _like(v, ids.astype(jnp.int32))

    return LayerOutput(name=name, layer_type="sampling_id", inputs=[inp],
                       fn=compute, size=1, is_sequence=inp.is_sequence)


# ---------------------------------------------------------------------------
# recurrent layers
# ---------------------------------------------------------------------------


@_export
def lstmemory(input, size: int = None, reverse: bool = False, act=None,
              gate_act=None, state_act=None, name: Optional[str] = None,
              param_attr=None, bias_attr=True, layer_attr=None) -> LayerOutput:
    """LSTM over a sequence whose input is ALREADY projected to 4*size
    (reference contract: lstmemory, gserver/layers/LstmLayer.cpp — the input
    projection lives in the upstream fc/mixed layer; simple_lstm in networks
    composes both). One lax.scan; gates fused by XLA (hl_cuda_lstm.cu analog).
    """
    inp = input
    _need_seq(inp, "lstmemory")
    enforce_that(inp.size % 4 == 0, "lstmemory input size must be 4*size",
                 context="lstmemory")
    size = size or inp.size // 4
    name = name or unique_name("lstmemory")
    out_act = _resolve_act(act or "tanh")
    g_act = _resolve_act(gate_act or "sigmoid")
    s_act = _resolve_act(state_act or "tanh")
    params = {"w": ParamSpec((size, 4 * size), ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((4 * size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        sb: SequenceBatch = ins[0]
        padded, mask = sb.to_padded()
        hs, _ = per_device(
            lambda x, m, w, b: prnn.lstm_scan(
                x, m, None, w, b, reverse=reverse, gate_act=g_act.fn,
                cell_act=s_act.fn, out_act=out_act.fn),
            ctx.mesh)(padded, mask, p["w"], p.get("b"))
        out = SequenceBatch.from_padded(hs, sb.lengths, capacity=sb.capacity)
        return _apply_extra(ctx, name, out, layer_attr)

    return LayerOutput(name=name, layer_type="lstmemory", inputs=[inp],
                       fn=compute, params=params, size=size, is_sequence=True)


@_export
def grumemory(input, size: int = None, reverse: bool = False, act=None,
              gate_act=None, name: Optional[str] = None, param_attr=None,
              bias_attr=True, layer_attr=None) -> LayerOutput:
    """GRU over a sequence with input pre-projected to 3*size (reference:
    grumemory → GatedRecurrentLayer.cpp / hl_gpu_gru.cuh)."""
    inp = input
    _need_seq(inp, "grumemory")
    enforce_that(inp.size % 3 == 0, "grumemory input size must be 3*size",
                 context="grumemory")
    size = size or inp.size // 3
    name = name or unique_name("grumemory")
    params = {"w": ParamSpec((size, 3 * size), ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((3 * size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        sb: SequenceBatch = ins[0]
        padded, mask = sb.to_padded()
        hs, _ = per_device(
            lambda x, m, w, b: prnn.gru_scan(x, m, None, w, b,
                                             reverse=reverse),
            ctx.mesh)(padded, mask, p["w"], p.get("b"))
        out = SequenceBatch.from_padded(hs, sb.lengths, capacity=sb.capacity)
        return _apply_extra(ctx, name, out, layer_attr)

    return LayerOutput(name=name, layer_type="grumemory", inputs=[inp],
                       fn=compute, params=params, size=size, is_sequence=True)


@_export
def recurrent(input, size: int = None, act=None, reverse: bool = False,
              name: Optional[str] = None, param_attr=None,
              bias_attr=True) -> LayerOutput:
    """Simple (Elman) recurrent layer: h_t = act(x_t + W h_{t-1})
    (reference: recurrent_layer/RecurrentLayer.cpp)."""
    inp = input
    _need_seq(inp, "recurrent")
    size = size or inp.size
    name = name or unique_name("recurrent")
    activation = _resolve_act(act or "tanh")
    params = {"w": ParamSpec((size, size), ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        sb: SequenceBatch = ins[0]
        padded, mask = sb.to_padded()
        B, T, D = padded.shape

        def step(h, xm):
            x, m = xm
            nh = activation.fn(x + pmath.matmul(h, p["w"]) +
                               (p["b"] if has_bias else 0.0))
            m = m[:, None].astype(nh.dtype)
            nh = m * nh + (1 - m) * h
            return nh, nh

        xs = (jnp.swapaxes(padded, 0, 1), jnp.swapaxes(mask, 0, 1))
        _, hs = jax.lax.scan(step, jnp.zeros((B, size), padded.dtype), xs,
                             reverse=reverse)
        hs = jnp.swapaxes(hs, 0, 1)
        return SequenceBatch.from_padded(hs, sb.lengths, capacity=sb.capacity)

    return LayerOutput(name=name, layer_type="recurrent", inputs=[inp],
                       fn=compute, params=params, size=size, is_sequence=True)


# ---------------------------------------------------------------------------
# special layers: selective_fc, nce, hsigmoid, crf, ctc
# ---------------------------------------------------------------------------


@_export
def selective_fc(input, size: int, select=None, act=None,
                 name: Optional[str] = None, param_attr=None,
                 bias_attr=True, **_kw) -> LayerOutput:
    """FC where only selected output columns matter (reference:
    selective_fc_layer/SelectiveFullyConnectedLayer.cpp).

    TPU-native: the full matmul runs on the MXU (dense is faster than gather
    on TPU); unselected columns are masked to -inf/0 — semantics preserved,
    the 'skip computation' trick is deliberately NOT ported."""
    inputs = [input] + ([select] if select is not None else [])
    name = name or unique_name("selective_fc")
    activation = _resolve_act(act)
    params = {"w": ParamSpec((input.size, size), ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        y = pmath.matmul(_data_of(ins[0]), p["w"])
        if has_bias:
            y = y + p["b"]
        if select is not None:
            sel = _data_of(ins[1])  # [B, size] 0/1 mask (sparse_binary rows)
            y = jnp.where(sel > 0, y, 0.0)
        out = _like(ins[0], y)
        return _apply_act(activation, out)

    return LayerOutput(name=name, layer_type="selective_fc", inputs=inputs,
                       fn=compute, params=params, size=size,
                       is_sequence=input.is_sequence)


@_export
def nce(input, label, num_classes: int, num_neg_samples: int = 10,
        name: Optional[str] = None, param_attr=None, bias_attr=True,
        neg_distribution=None) -> LayerOutput:
    """Noise-contrastive estimation cost (reference: nce_layer/NCELayer.cpp).

    Uniform (or given) noise; logistic loss over 1 positive + k sampled
    negatives per example. Returns per-example cost."""
    inputs = [input, label]
    name = name or unique_name("nce")
    params = {"w": ParamSpec((num_classes, input.size), ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((num_classes,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        x = _data_of(ins[0])            # [B, D]
        y = _data_of(ins[1]).reshape(-1).astype(jnp.int32)  # [B]
        B = x.shape[0]
        key = ctx.rng_for(name)
        if neg_distribution is not None:
            dist = jnp.asarray(neg_distribution)
            logits_dist = jnp.log(jnp.clip(dist, 1e-20, 1.0))
            neg = jax.random.categorical(key, logits_dist[None, :],
                                         shape=(B, num_neg_samples))
        else:
            neg = jax.random.randint(key, (B, num_neg_samples), 0, num_classes)
        ids = jnp.concatenate([y[:, None], neg], axis=1)      # [B, 1+k]
        w_rows = p["w"][ids]                                   # [B, 1+k, D]
        logits = jnp.einsum("bd,bkd->bk", x, w_rows)
        if has_bias:
            logits = logits + p["b"][ids]
        labels01 = jnp.concatenate(
            [jnp.ones((B, 1)), jnp.zeros((B, num_neg_samples))], axis=1)
        return ploss.sigmoid_cross_entropy_with_logits(logits, labels01)

    return LayerOutput(name=name, layer_type="nce", inputs=inputs, fn=compute,
                       params=params, size=1, is_cost=True)


@_export
def hsigmoid(input, label, num_classes: int, name: Optional[str] = None,
             param_attr=None, bias_attr=True) -> LayerOutput:
    """Hierarchical sigmoid cost over a complete binary tree (reference:
    hsigmoid_layer/HierarchicalSigmoidLayer.cpp)."""
    inputs = [input, label]
    name = name or unique_name("hsigmoid")
    num_nodes = num_classes - 1
    import math as _math
    code_len = max(1, int(_math.ceil(_math.log2(max(2, num_classes)))))
    params = {"w": ParamSpec((num_nodes, input.size), ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((num_nodes,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        x = _data_of(ins[0])
        y = _data_of(ins[1]).reshape(-1).astype(jnp.int32)
        # heap path: leaf id = y + num_nodes + 1 (1-based heap); ancestors =
        # successive >>1; bit = node & 1 gives left/right label.
        leaf = y + num_nodes + 1
        losses = 0.0
        node = leaf
        for _ in range(code_len):
            parent = node >> 1
            bit = (node & 1).astype(jnp.float32)      # 1 = right child
            valid = parent >= 1
            idx = jnp.clip(parent - 1, 0, num_nodes - 1)
            logit = jnp.einsum("bd,bd->b", x, p["w"][idx])
            if has_bias:
                logit = logit + p["b"][idx]
            # label 1 for left (bit==0) as in reference's sign convention
            t = 1.0 - bit
            step_loss = jnp.maximum(logit, 0) - logit * t + jnp.log1p(jnp.exp(-jnp.abs(logit)))
            losses = losses + jnp.where(valid, step_loss, 0.0)
            node = parent
        return losses

    return LayerOutput(name=name, layer_type="hsigmoid", inputs=inputs,
                       fn=compute, params=params, size=1, is_cost=True)


def _crf_forward(emissions, mask, transitions, start, stop, labels):
    """Linear-chain CRF negative log-likelihood per sequence.

    emissions [B,T,K], mask [B,T] bool, labels [B,T] int.
    """
    B, T, K = emissions.shape
    lab = labels.astype(jnp.int32)

    # score of the gold path
    first_score = start[lab[:, 0]] + emissions[:, 0, :][jnp.arange(B), lab[:, 0]]

    def score_step(carry, t):
        s, prev = carry
        e = emissions[:, t, :][jnp.arange(B), lab[:, t]]
        tr = transitions[prev, lab[:, t]]
        m = mask[:, t].astype(e.dtype)
        s = s + m * (e + tr)
        prev = jnp.where(mask[:, t], lab[:, t], prev)
        return (s, prev), None

    (gold, last_lab), _ = jax.lax.scan(score_step, (first_score, lab[:, 0]),
                                       jnp.arange(1, T))
    gold = gold + stop[last_lab]

    # log partition via forward algorithm
    alpha0 = start[None, :] + emissions[:, 0, :]

    def fwd_step(alpha, t):
        e = emissions[:, t, :]
        scores = alpha[:, :, None] + transitions[None, :, :] + e[:, None, :]
        new_alpha = jax.nn.logsumexp(scores, axis=1)
        m = mask[:, t][:, None]
        alpha = jnp.where(m, new_alpha, alpha)
        return alpha, None

    alpha, _ = jax.lax.scan(fwd_step, alpha0, jnp.arange(1, T))
    logz = jax.nn.logsumexp(alpha + stop[None, :], axis=-1)
    return logz - gold


def _crf_viterbi(emissions, mask, transitions, start, stop):
    B, T, K = emissions.shape
    alpha0 = start[None, :] + emissions[:, 0, :]

    def vit_step(alpha, t):
        e = emissions[:, t, :]
        scores = alpha[:, :, None] + transitions[None, :, :] + e[:, None, :]
        best_prev = jnp.argmax(scores, axis=1)
        new_alpha = jnp.max(scores, axis=1)
        m = mask[:, t][:, None]
        alpha_out = jnp.where(m, new_alpha, alpha)
        bp = jnp.where(m, best_prev, jnp.broadcast_to(jnp.arange(K)[None, :], (B, K)))
        return alpha_out, bp

    alpha, bps = jax.lax.scan(vit_step, alpha0, jnp.arange(1, T))
    last = jnp.argmax(alpha + stop[None, :], axis=-1)

    def back_step(nxt, bp):
        cur = bp[jnp.arange(B), nxt]
        return cur, nxt

    # reverse scan emits y[t] = state at position t+1 and carries the
    # chain back to position 0 (the final carry) — prepend it, don't
    # re-append `last`
    first, path_rev = jax.lax.scan(back_step, last, bps, reverse=True)
    path = jnp.concatenate([first[None, :], path_rev], axis=0)  # [T, B]
    return jnp.swapaxes(path, 0, 1).astype(jnp.int32)


def _crf_params(size: int, param_attr) -> Dict[str, "ParamSpec"]:
    """CRF parameter table. An explicit ParamAttr.name becomes a PREFIX so
    a crf cost layer and its crf_decoding twin can share the learned
    transitions (the reference shares via parameter_name on both layers)."""
    import dataclasses

    attr = ParamAttr.to_attr(param_attr)

    def per(pname):
        if attr.name:
            return dataclasses.replace(attr, name=f"{attr.name}.{pname}")
        return attr

    return {
        "transitions": ParamSpec((size, size), per("transitions")),
        "start": ParamSpec((size,), per("start")),
        "stop": ParamSpec((size,), per("stop")),
    }


@_export
def crf(input, label, size: int = None, name: Optional[str] = None,
        param_attr=None, **_kw) -> LayerOutput:
    """Linear-chain CRF cost (reference: crf_layer/CRFLayer.cpp,
    LinearChainCRF.cpp — its transition matrix packs start/stop weights; here
    they are separate parameters)."""
    inp, lab = input, label
    _need_seq(inp, "crf")
    size = size or inp.size
    name = name or unique_name("crf")
    params = _crf_params(size, param_attr)

    def compute(ctx, p, ins):
        sb, lb = ins[0], ins[1]
        emissions, mask = sb.to_padded()
        labels, _ = lb.to_padded() if isinstance(lb, SequenceBatch) else (lb, None)
        if labels.ndim == 3:
            labels = labels[..., 0]
        return _crf_forward(emissions, mask, p["transitions"], p["start"],
                            p["stop"], labels)

    return LayerOutput(name=name, layer_type="crf", inputs=[inp, lab],
                       fn=compute, params=params, size=1, is_cost=True)


@_export
def crf_decoding(input, size: int = None, label=None,
                 name: Optional[str] = None, param_attr=None, **_kw) -> LayerOutput:
    """Viterbi decode (reference: crf_decoding_layer). With a label input,
    outputs per-token error like the reference; else the best path ids."""
    inp = input
    _need_seq(inp, "crf_decoding")
    size = size or inp.size
    name = name or unique_name("crf_decoding")
    params = _crf_params(size, param_attr)
    inputs = [inp] + ([label] if label is not None else [])

    def compute(ctx, p, ins):
        sb = ins[0]
        emissions, mask = sb.to_padded()
        path = _crf_viterbi(emissions, mask, p["transitions"], p["start"], p["stop"])
        if label is not None:
            lb = ins[1]
            labels, _ = lb.to_padded() if isinstance(lb, SequenceBatch) else (lb, None)
            if labels.ndim == 3:
                labels = labels[..., 0]
            err = (path != labels.astype(path.dtype)) & mask
            flat = SequenceBatch.from_padded(
                err[..., None].astype(jnp.float32), sb.lengths, capacity=sb.capacity)
            return flat
        flat = SequenceBatch.from_padded(path[..., None], sb.lengths,
                                         capacity=sb.capacity)
        return flat

    return LayerOutput(name=name, layer_type="crf_decoding", inputs=inputs,
                       fn=compute, params=params, size=1, is_sequence=True)


@_export
def ctc(input, label, size: int = None, blank: int = 0, norm_by_times: bool = False,
        name: Optional[str] = None) -> LayerOutput:
    """CTC cost (reference: ctc_layer/CTCLayer.cpp & warp_ctc_layer; the TPU
    path uses a jax-native CTC — optax.ctc_loss — instead of warpctc)."""
    inp, lab = input, label
    _need_seq(inp, "ctc")
    name = name or unique_name("ctc")

    def compute(ctx, p, ins):
        import optax

        sb, lb = ins[0], ins[1]
        logits, mask = sb.to_padded()
        labels, lab_mask = lb.to_padded()
        if labels.ndim == 3:
            labels = labels[..., 0]
        logit_pad = 1.0 - mask.astype(jnp.float32)
        label_pad = 1.0 - lab_mask.astype(jnp.float32)
        loss = optax.ctc_loss(logits, logit_pad, labels.astype(jnp.int32),
                              label_pad, blank_id=blank)
        if norm_by_times:
            loss = loss / jnp.maximum(sb.lengths.astype(loss.dtype), 1.0)
        return loss

    return LayerOutput(name=name, layer_type="ctc", inputs=[inp, lab],
                       fn=compute, size=1, is_cost=True)


@_export
def warp_ctc(input, label, size: int = None, blank: int = 0,
             norm_by_times: bool = False, name: Optional[str] = None) -> LayerOutput:
    """Alias of ctc — warpctc was a CUDA-perf variant; XLA needs no second path."""
    return ctc(input, label, size=size, blank=blank, norm_by_times=norm_by_times,
               name=name or unique_name("warp_ctc"))


# ---------------------------------------------------------------------------
# cost layers
# ---------------------------------------------------------------------------


def _cost_node(name, ltype, inputs, fn) -> LayerOutput:
    return LayerOutput(name=name, layer_type=ltype, inputs=inputs, fn=fn,
                       size=1, is_cost=True)


def _per_example(fn_dense, value, *args):
    """Run a per-row loss on dense or sequence (per-token) input."""
    if isinstance(value, SequenceBatch):
        out = fn_dense(value.data, *[_data_of(a) for a in args])
        masked = jnp.where(value.valid_mask, out, 0.0)
        return value.with_data(masked)
    return fn_dense(value, *[_data_of(a) for a in args])


def _count_flash_blocks(ctx, name: str, q_seg, kv_seg=None, *, causal: bool):
    """Publish what one forward call of the flash kernel visits for these
    segment ids (``flash_live_blocks_total``) beside what it would visit
    were the buffer one sequence (``flash_tri_blocks_total``)."""
    from paddle_tpu.ops.attention import flash_block_counts

    live, whole = flash_block_counts(
        q_seg[None, :], None if kv_seg is None else kv_seg[None, :],
        causal=causal)
    ctx.count("flash_live_blocks_total", live, layer=name)
    ctx.count("flash_tri_blocks_total", whole, layer=name)


@_export
def multi_head_attention(query, key=None, value=None, *, num_heads: int,
                         size: int = None, causal: bool = False,
                         name: Optional[str] = None, param_attr=None,
                         layer_attr=None) -> LayerOutput:
    """Multi-head (flash) attention over packed variable-length sequences —
    the long-context extension of the reference's attention helpers
    (networks.py:1304 simple_attention, :1402 dot_product_attention),
    built on the blockwise pallas kernel (ops/attention.py).

    Sequence inputs ride the packed SequenceBatch form: segment ids ARE
    the attention mask (tokens never attend across sequences — the
    padding-free Argument.sequenceStartPositions capability), so no
    [B, T, T] mask is ever materialised. ``causal=True`` adds
    per-sequence causal masking (positions are absolute in the packed
    buffer, combined with segment ids). key/value default to query
    (self-attention); pass an encoder sequence for cross-attention."""
    q_in = query
    k_in = key if key is not None else query
    v_in = value if value is not None else k_in
    _need_seq(q_in, "multi_head_attention")
    _need_seq(k_in, "multi_head_attention")
    _need_seq(v_in, "multi_head_attention")
    # causal masking uses absolute positions in the packed buffer; two
    # independently packed buffers have incomparable positions, so causal
    # cross-attention would silently mask wrong keys
    enforce_that(not (causal and key is not None and key is not query),
                 "causal=True is self-attention only (packed positions "
                 "are incomparable across different key/query buffers)",
                 context="multi_head_attention")
    size = size or q_in.size
    enforce_that(size % num_heads == 0,
                 f"num_heads {num_heads} must divide size {size}",
                 context="multi_head_attention")
    name = name or unique_name("mha")
    attr = ParamAttr.to_attr(param_attr)
    params = {
        "wq": ParamSpec((q_in.size, size), attr),
        "wk": ParamSpec((k_in.size, size), attr),
        "wv": ParamSpec((v_in.size, size), attr),
        "wo": ParamSpec((size, size), attr),
    }
    head_dim = size // num_heads

    def compute(ctx, p, ins):
        from paddle_tpu.ops import attention as pattn

        qs, ks, vs = ins[0], ins[1], ins[2]
        cap_q, cap_k = qs.capacity, ks.capacity
        enforce_that(vs.capacity == cap_k,
                     f"key/value capacities differ ({cap_k} vs "
                     f"{vs.capacity}) — they must come from the same "
                     "feeder bucket", context="multi_head_attention")
        # q/k/v ride bf16 into the flash kernel under the global policy
        # (the kernel accumulates scores/output in f32). The projections
        # still ACCUMULATE in f32 (matmul's preferred_element_type) and
        # round once on the way out — the policy ops/math.py documents.
        # two scopes, bound differently: the four products (the MXU) and
        # the kernel with its layout changes
        qkv_t = pmath.compute_dtype(qs.data)
        with jax.named_scope("attn.proj"):
            q = pmath.matmul(qs.data, p["wq"]).astype(qkv_t)
            k = pmath.matmul(ks.data, p["wk"]).astype(qkv_t)
            v = pmath.matmul(vs.data, p["wv"]).astype(qkv_t)
        with jax.named_scope("attn.core"):
            out = per_device(
                lambda q, k, v, q_seg, k_seg: pattn.flash_attention(
                    q, k, v, segment_ids=q_seg, kv_segment_ids=k_seg,
                    causal=causal),
                ctx.mesh)(q.reshape(1, cap_q, num_heads, head_dim),
                          k.reshape(1, cap_k, num_heads, head_dim),
                          v.reshape(1, cap_k, num_heads, head_dim),
                          qs.segment_ids[None, :], ks.segment_ids[None, :])
            _count_flash_blocks(ctx, name, qs.segment_ids, ks.segment_ids,
                                causal=causal)
            out = out.reshape(cap_q, size)
        with jax.named_scope("attn.proj"):
            y = pmath.matmul(out, p["wo"])
            y = y.astype(pmath.dense_activation_dtype())
        return _apply_extra(ctx, name, qs.with_data(y), layer_attr)

    node = LayerOutput(name=name, layer_type="multi_head_attention",
                       inputs=[q_in, k_in, v_in], fn=compute, params=params,
                       size=size, is_sequence=True)
    return node


@_export
class BeamInput:
    """One beam expansion for cross_entropy_over_beam (reference:
    trainer_config_helpers/layers.py BeamInput): candidate scores over the
    expansion's search space, the selected top-k candidate ids, and the
    gold candidate id. ``prev_ids`` (optional) links each selected
    candidate to the beam slot of the PREVIOUS expansion it extends —
    the dense analog of the reference's seqInfo path bookkeeping; with
    it, path scores accumulate across expansions and every expansion's
    scorer receives gradient."""

    def __init__(self, candidate_scores, selected_candidates, gold,
                 prev_ids=None):
        self.candidate_scores = candidate_scores
        self.selected_candidates = selected_candidates
        self.gold = gold
        self.prev_ids = prev_ids


@_export
def cross_entropy_over_beam(input, name: Optional[str] = None) -> LayerOutput:
    """Training-through-beam cost for learning-to-search models
    (reference: CrossEntropyOverBeam.cpp:131-162 + the
    cross_entropy_over_beam helper). Takes a list of BeamInput (one per
    beam expansion); the cost is -log P(gold path) under a softmax over
    the beam at the expansion where gold falls off (gold joins the
    normalizer as an extra path). Works with kmax_seq_score /
    sub_nested_seq / seq_slice to trim the search space."""
    beams = [input] if isinstance(input, BeamInput) else list(input)
    for b in beams:
        enforce_that(isinstance(b, BeamInput),
                     "cross_entropy_over_beam takes BeamInput(s)",
                     context="cross_entropy_over_beam")
    name = name or unique_name("cross_entropy_over_beam")
    inputs = []
    arity = []
    for b in beams:
        ins_b = [b.candidate_scores, b.selected_candidates, b.gold]
        if b.prev_ids is not None:
            ins_b.append(b.prev_ids)
        arity.append(len(ins_b))
        inputs += ins_b

    def compute(ctx, p, ins):
        triples = []
        i = 0
        for n in arity:
            scores = _data_of(ins[i])
            selected = _data_of(ins[i + 1])
            gold = _data_of(ins[i + 2]).reshape(-1)
            if scores.ndim == 1:
                scores = scores.reshape(1, -1)
            if selected.ndim == 1:
                selected = selected.reshape(1, -1)
            entry = [scores, selected.astype(jnp.int32), gold]
            if n == 4:
                prev = _data_of(ins[i + 3])
                if prev.ndim == 1:
                    prev = prev.reshape(1, -1)
                entry.append(prev.astype(jnp.int32))
            triples.append(tuple(entry))
            i += n
        return ploss.cross_entropy_over_beam(triples)

    return _cost_node(name, "cross_entropy_over_beam", inputs, compute)


@_export
def classification_cost(input, label, weight=None, name: Optional[str] = None,
                        **_kw) -> LayerOutput:
    """Softmax cross-entropy on logits (reference: classification_cost —
    the fused softmax+xent path, CostLayer.cpp MultiClassCrossEntropy).

    NOTE: `input` should be pre-softmax logits; if the final layer used a
    softmax activation the reference computed log on probabilities — we fuse
    for numerical stability either way."""
    name = name or unique_name("classification_cost")
    inputs = [input, label] + ([weight] if weight is not None else [])

    def compute(ctx, p, ins):
        logits, lab = ins[0], ins[1]

        def f(lg, lb):
            lb = lb.reshape(lb.shape[0]).astype(jnp.int32)
            return ploss.softmax_cross_entropy(lg, lb)

        out = _per_example(f, logits, lab)
        if weight is not None:
            w = _data_of(ins[2]).reshape(-1)
            out = _like(out, _data_of(out) * w) if isinstance(out, SequenceBatch) else out * w
        return out

    return _cost_node(name, "classification_cost", inputs, compute)


@_export
def cross_entropy_cost(input, label, name: Optional[str] = None, **_kw) -> LayerOutput:
    """Cross entropy on probabilities (reference: cross_entropy)."""
    name = name or unique_name("cross_entropy")

    def compute(ctx, p, ins):
        def f(pr, lb):
            lb = lb.reshape(lb.shape[0]).astype(jnp.int32)
            picked = jnp.take_along_axis(pr, lb[:, None], axis=-1)[:, 0]
            return -jnp.log(jnp.clip(picked, 1e-10, 1.0))

        return _per_example(f, ins[0], ins[1])

    return _cost_node(name, "cross_entropy", [input, label], compute)


@_export
def cross_entropy_with_selfnorm_cost(input, label, softmax_selfnorm_alpha: float = 0.1,
                                     name: Optional[str] = None) -> LayerOutput:
    name = name or unique_name("cross_entropy_with_selfnorm")

    def compute(ctx, p, ins):
        def f(lg, lb):
            lb = lb.reshape(lb.shape[0]).astype(jnp.int32)
            return ploss.cross_entropy_with_selfnorm(lg, lb, softmax_selfnorm_alpha)

        return _per_example(f, ins[0], ins[1])

    return _cost_node(name, "cross_entropy_with_selfnorm", [input, label], compute)


@_export
def square_error_cost(input, label, name: Optional[str] = None, **_kw) -> LayerOutput:
    """0.5*||p-t||^2 (reference: square_error_cost / regression_cost)."""
    name = name or unique_name("square_error")

    def compute(ctx, p, ins):
        def f(a, b):
            return ploss.square_error(a, b.reshape(a.shape))

        return _per_example(f, ins[0], ins[1])

    return _cost_node(name, "square_error", [input, label], compute)


regression_cost = square_error_cost
__all__.append("regression_cost")


@_export
def multi_binary_label_cross_entropy_cost(input, label,
                                          name: Optional[str] = None) -> LayerOutput:
    name = name or unique_name("multi_binary_label_xent")

    def compute(ctx, p, ins):
        def f(lg, lb):
            # an integer [B] label against [B, 1] logits must not broadcast
            # to [B, B]
            if lb.size == lg.size:
                lb = lb.reshape(lg.shape)
            return ploss.multi_binary_label_cross_entropy(
                lg, lb.astype(lg.dtype))

        return _per_example(f, ins[0], ins[1])

    return _cost_node(name, "multi_binary_label_xent", [input, label], compute)


@_export
def soft_binary_class_cross_entropy_cost(input, label,
                                         name: Optional[str] = None) -> LayerOutput:
    """Soft-label binary xent on probabilities (reference:
    SoftBinaryClassCrossEntropy)."""
    name = name or unique_name("soft_binary_xent")

    def compute(ctx, p, ins):
        def f(pr, lb):
            pr = jnp.clip(pr, 1e-7, 1 - 1e-7)
            return -jnp.sum(lb * jnp.log(pr) + (1 - lb) * jnp.log(1 - pr), axis=-1)

        return _per_example(f, ins[0], ins[1])

    return _cost_node(name, "soft_binary_xent", [input, label], compute)


@_export
def rank_cost(left, right, label, weight=None, name: Optional[str] = None) -> LayerOutput:
    name = name or unique_name("rank_cost")
    inputs = [left, right, label] + ([weight] if weight is not None else [])

    def compute(ctx, p, ins):
        w = _data_of(ins[3]) if weight is not None else None
        return ploss.rank_cost(_data_of(ins[0]), _data_of(ins[1]),
                               _data_of(ins[2]), w)

    return _cost_node(name, "rank_cost", inputs, compute)


@_export
def lambda_cost(input, score, NDCG_num: int = 5, max_sort_size: int = -1,
                name: Optional[str] = None) -> LayerOutput:
    """LambdaRank cost over each query's documents (reference: lambda_cost /
    LambdaCost.cpp). input: sequence of scores, score: sequence of relevance."""
    name = name or unique_name("lambda_cost")
    _need_seq(input, "lambda_cost")

    def compute(ctx, p, ins):
        sb_pred, sb_rel = ins[0], ins[1]
        pred, mask = sb_pred.to_padded()
        rel, _ = sb_rel.to_padded()
        pred = pred[..., 0] if pred.ndim == 3 else pred
        rel = rel[..., 0] if rel.ndim == 3 else rel
        B, T = pred.shape
        # ideal DCG from top-NDCG_num relevances
        sorted_rel = -jnp.sort(-jnp.where(mask, rel, -jnp.inf), axis=1)
        k = jnp.arange(T)
        disc = 1.0 / jnp.log2(k + 2.0)
        topk_mask = (k < NDCG_num)[None, :]
        gains = (jnp.power(2.0, jnp.where(jnp.isfinite(sorted_rel), sorted_rel, 0.0)) - 1.0)
        idcg = jnp.sum(gains * disc * topk_mask * jnp.isfinite(sorted_rel), axis=1)
        # pairwise lambda loss approximation: logistic on score diffs weighted
        # by |delta NDCG| of swapping
        sdiff = pred[:, :, None] - pred[:, None, :]
        rdiff = rel[:, :, None] - rel[:, None, :]
        pair_mask = mask[:, :, None] & mask[:, None, :] & (rdiff > 0)
        logistic = jnp.log1p(jnp.exp(-sdiff))
        loss = jnp.sum(jnp.where(pair_mask, logistic, 0.0), axis=(1, 2))
        denom = jnp.maximum(jnp.sum(pair_mask, axis=(1, 2)), 1)
        return loss / denom / jnp.maximum(idcg, 1.0)

    return _cost_node(name, "lambda_cost", [input, score], compute)


@_export
def huber_regression_cost(input, label, delta: float = 1.0,
                          name: Optional[str] = None) -> LayerOutput:
    name = name or unique_name("huber_regression")

    def compute(ctx, p, ins):
        def f(a, b):
            return ploss.huber_regression(a, b.reshape(a.shape), delta)

        return _per_example(f, ins[0], ins[1])

    return _cost_node(name, "huber_regression", [input, label], compute)


@_export
def huber_classification_cost(input, label, name: Optional[str] = None) -> LayerOutput:
    name = name or unique_name("huber_classification")

    def compute(ctx, p, ins):
        return _per_example(ploss.huber_classification, ins[0], ins[1])

    return _cost_node(name, "huber_classification", [input, label], compute)


@_export
def smooth_l1_cost(input, label, name: Optional[str] = None) -> LayerOutput:
    name = name or unique_name("smooth_l1")

    def compute(ctx, p, ins):
        def f(a, b):
            return ploss.smooth_l1(a, b.reshape(a.shape))

        return _per_example(f, ins[0], ins[1])

    return _cost_node(name, "smooth_l1", [input, label], compute)


@_export
def moe_ffn(input, num_experts: int = 0, expert_hidden: int = 0,
            capacity_factor: float = 1.25, aux_weight: float = 0.01,
            top_k: int = 1, config=None,
            name: Optional[str] = None, param_attr=None):
    """Mixture-of-Experts FFN layer WITH A CAPACITY (new-build extension;
    parallel/moe.py holds the kernels): a softmax router, two-matrix GELU
    experts and a dense one-hot dispatch into ``ceil(T / E *
    capacity_factor)`` slots an expert; (token, choice) pairs past an
    expert's capacity are DROPPED.  The dropless path (a bias-corrected
    sigmoid router over all experts, a rank's held experts by a sort and
    grouped matrix products, a shared expert, nothing dropped) is
    :func:`moe_dropless`.  Switch-style top-1 — or, with ``top_k=2``,
    GShard-style top-2 with renormalized gates — routing into per-expert
    two-layer FFNs. Returns ``(out, aux_cost)`` — add ``aux_cost`` to the
    SGD cost list (multi-cost training, the MultiNetwork path) so routing
    stays load-balanced; its value is ``aux_weight *`` the Switch
    balance loss.

    ``config=`` takes a :class:`paddle_tpu.parallel.moe.MoEConfig` in
    place of the scalar kwargs (explicit kwargs win where both are
    given).  The expert weights declare leading-dim sharding over the
    config's ``expert`` axis (MoEConfig.param_plan through the one
    placement layer), so on an expert mesh each device holds only its
    E/N experts — on a mesh WITHOUT that axis the declared dim falls
    back to replicated and the dense path runs.

    Under a mesh with an ``'expert'`` axis the experts shard and dispatch
    rides two all_to_alls (parallel.moe.moe_ffn); otherwise the dense
    single-device formulation runs. Over-capacity tokens pass through as
    zeros (callers add the residual). On packed SequenceBatch inputs the
    padding slots also route (they waste a little capacity; their outputs
    are zeroed)."""
    import dataclasses

    from paddle_tpu.parallel import moe as pmoe

    inp = input
    axis = "expert"
    if config is not None:
        num_experts = int(num_experts or config.num_experts)
        expert_hidden = int(expert_hidden or config.expert_hidden)
        capacity_factor = float(config.capacity_factor)
        top_k = int(config.top_k)
        aux_weight = float(config.aux_weight)
        axis = str(config.axis)
        if expert_hidden <= 0:
            # MoEConfig.expert_hidden == 0: derive from the model width
            expert_hidden = 4 * int(inp.size)
    if num_experts <= 0 or expert_hidden <= 0:
        raise ValueError("moe_ffn needs num_experts/expert_hidden > 0 "
                         "(directly or via config=MoEConfig(...))")

    name = name or unique_name("moe_ffn")
    attr = ParamAttr.to_attr(param_attr)

    def _expert(base, ndim):
        # stacked [E, ...] expert weights: leading dim over the expert
        # axis unless the caller pinned a sharding explicitly
        if base.sharding is not None:
            return base
        return dataclasses.replace(
            base, sharding=(axis,) + (None,) * (ndim - 1))

    d = inp.size
    params = {
        "router": ParamSpec((d, num_experts), attr),
        "w1": ParamSpec((num_experts, d, expert_hidden), _expert(attr, 3)),
        "b1": ParamSpec((num_experts, expert_hidden),
                        _expert(ParamAttr.to_attr(None), 2)),
        "w2": ParamSpec((num_experts, expert_hidden, d), _expert(attr, 3)),
        "b2": ParamSpec((num_experts, d),
                        _expert(ParamAttr.to_attr(None), 2)),
    }

    def compute(ctx, p, ins):
        v = ins[0]
        x = _data_of(v)
        mp = pmoe.MoEParams(p["router"], p["w1"], p["b1"], p["w2"], p["b2"])
        mesh = ctx.mesh
        if mesh is not None and axis in tuple(
                getattr(mesh, "axis_names", ())):
            y, aux = pmoe.moe_ffn(mesh, x, mp, axis=axis,
                                  capacity_factor=capacity_factor,
                                  top_k=top_k)
        else:
            y, aux = pmoe.moe_ffn_reference(
                x, mp, capacity_factor=capacity_factor, top_k=top_k)
        if isinstance(v, SequenceBatch):
            y = jnp.where(v.valid_mask[:, None], y, 0)
        out = _like(v, y.astype(pmath.dense_activation_dtype()))
        return (out, aux * aux_weight)

    core = LayerOutput(name=name, layer_type="moe_ffn", inputs=[inp],
                       fn=compute, params=params, size=d,
                       is_sequence=inp.is_sequence)

    def pick_out(ctx, p, ins):
        return ins[0][0]

    def pick_aux(ctx, p, ins):
        return jnp.reshape(ins[0][1], (1,))

    out_node = LayerOutput(name=f"{name}_out", layer_type="moe_out",
                           inputs=[core], fn=pick_out, size=d,
                           is_sequence=inp.is_sequence)
    aux_node = LayerOutput(name=f"{name}_aux", layer_type="moe_aux",
                           inputs=[core], fn=pick_aux, size=1, is_cost=True)
    return out_node, aux_node


@_export
def rms_norm(input, name: Optional[str] = None, epsilon: float = 1e-5,
             param_attr=None) -> LayerOutput:
    """Weighted RMSNorm over the feature axis (ops/norm.py rms_norm): no
    mean, no bias, one gain (``gamma``, initialised to 1)."""
    inp = input
    name = name or unique_name("rms_norm")
    params = {"gamma": ParamSpec((inp.size,), ParamAttr.to_attr(param_attr)
                                 if param_attr
                                 else ParamAttr(initializer=Constant(1.0)))}

    def compute(ctx, p, ins):
        v = ins[0]
        return _like(v, pnorm.rms_norm(_data_of(v), p["gamma"], epsilon))

    return LayerOutput(name=name, layer_type="rms_norm", inputs=[inp],
                       fn=compute, params=params, size=inp.size,
                       is_sequence=inp.is_sequence)


@_export
def swiglu_ffn(input, size: int, name: Optional[str] = None,
               param_attr=None) -> LayerOutput:
    """Gated feed-forward ``(silu(x Wg) * (x Wu)) Wd`` of inner width
    ``size``, no biases (ops/math.py swiglu)."""
    inp = input
    name = name or unique_name("swiglu_ffn")
    attr = ParamAttr.to_attr(param_attr)
    d = inp.size
    params = {"w_gate": ParamSpec((d, size), attr),
              "w_up": ParamSpec((d, size), attr),
              "w_down": ParamSpec((size, d), attr)}

    def compute(ctx, p, ins):
        v = ins[0]
        y = pmath.swiglu(_data_of(v), p["w_gate"], p["w_up"], p["w_down"])
        return _like(v, y.astype(pmath.dense_activation_dtype()))

    return LayerOutput(name=name, layer_type="swiglu_ffn", inputs=[inp],
                       fn=compute, params=params, size=d,
                       is_sequence=inp.is_sequence)


@_export
def mla_attention(input, positions, *, num_heads: int, q_lora_rank: int,
                  kv_lora_rank: int, qk_nope_head_dim: int,
                  qk_rope_head_dim: int, v_head_dim: int,
                  rope_theta: float = 10000.0, epsilon: float = 1e-5,
                  name: Optional[str] = None, param_attr=None
                  ) -> LayerOutput:
    """Causal multi-head latent attention over packed sequences, the
    expanded (training) form: low-rank query and key/value paths with a
    weighted RMSNorm inside, a rotary part beside a position-free part in
    every head, one rotary key shared by the heads, values of their own
    width (ops/mla.py).  ``positions`` is the integer sequence of each
    token's position inside its own sequence.  No biases, no cache."""
    from paddle_tpu.ops.mla import mla_attention as mla

    _need_seq(input, "mla_attention")
    _need_seq(positions, "mla_attention")
    name = name or unique_name("mla")
    attr = ParamAttr.to_attr(param_attr)
    gain = ParamAttr(initializer=Constant(1.0))
    d, h = input.size, num_heads
    params = {
        "wq_a": ParamSpec((d, q_lora_rank), attr),
        "q_norm": ParamSpec((q_lora_rank,), gain),
        "wq_b": ParamSpec((q_lora_rank,
                           h * (qk_nope_head_dim + qk_rope_head_dim)), attr),
        "wkv_a": ParamSpec((d, kv_lora_rank + qk_rope_head_dim), attr),
        "kv_norm": ParamSpec((kv_lora_rank,), gain),
        "wkv_b": ParamSpec((kv_lora_rank,
                            h * (qk_nope_head_dim + v_head_dim)), attr),
        "wo": ParamSpec((h * v_head_dim, d), attr),
    }

    def compute(ctx, p, ins):
        xs, pos = ins
        y = mla(xs.data, pos.data.reshape(-1), xs.segment_ids, p,
                num_heads=h, qk_nope_dim=qk_nope_head_dim,
                qk_rope_dim=qk_rope_head_dim, v_dim=v_head_dim,
                eps=epsilon, theta=rope_theta, mesh=ctx.mesh)
        _count_flash_blocks(ctx, name, xs.segment_ids, causal=True)
        return xs.with_data(y.astype(pmath.dense_activation_dtype()))

    return LayerOutput(name=name, layer_type="mla_attention",
                       inputs=[input, positions], fn=compute, params=params,
                       size=d, is_sequence=True)


@_export
def moe_dropless(input, *, n_routed: int, held: Tuple[int, int],
                 expert_hidden: int, top_k: int, routing: str = "sigmoid",
                 scaling: float = 1.0, shared_hidden: int = 0,
                 shared_gated: bool = False, name: Optional[str] = None,
                 param_attr=None) -> LayerOutput:
    """One rank's share of a DROPLESS expert layer (parallel/moe.py
    moe_dropless): a router over all ``n_routed`` experts, the ``held =
    (first, count)`` experts this rank holds computed by grouped matrix
    products over the sorted (token, choice) pairs, and a shared expert of
    width ``shared_hidden``; SwiGLU experts, no capacity, nothing dropped,
    no auxiliary loss.  ``routing`` is ``"sigmoid"`` (bias-corrected
    sigmoid scores, weights renormalised and times ``scaling``; ``bias``,
    the router's correction bias, is a static parameter: it enters the
    choice of experts only and the optimiser leaves it alone) or
    ``"softmax"`` (a softmax over all the experts, the chosen weights
    renormalised; no bias, no scaling).  ``shared_gated`` multiplies the
    shared expert by ``sigmoid(x shared_mix)``, ``shared_mix`` [D, 1].
    (The path with a capacity and dropped tokens is :func:`moe_ffn`.)

    Publishes per step, labelled ``layer=<name>``: ``moe_rows_total``,
    ``moe_rows_held_total``, ``moe_max_expert_rows`` and, for each length
    the sorted buffer may run at (``rows=<length>``; the longest is the
    worst case), ``moe_rung_steps_total``: the steps that ran at it."""
    from paddle_tpu.parallel import moe as pmoe

    inp = input
    name = name or unique_name("moe_dropless")
    attr = ParamAttr.to_attr(param_attr)
    d, (first, count) = inp.size, held
    enforce_that(0 <= first and first + count <= n_routed and count > 0,
                 f"held experts {held} are not among {n_routed}",
                 context="moe_dropless")
    enforce_that(shared_hidden or not shared_gated,
                 "shared_gated needs a shared expert (shared_hidden)",
                 context="moe_dropless")
    params = {
        "router": ParamSpec((d, n_routed), attr),
        "w_gate": ParamSpec((count, d, expert_hidden), attr),
        "w_up": ParamSpec((count, d, expert_hidden), attr),
        "w_down": ParamSpec((count, expert_hidden, d), attr),
    }
    if routing == "sigmoid":
        params["bias"] = ParamSpec((n_routed,), ParamAttr(
            initializer=Constant(0.0), is_static=True))
    if shared_hidden:
        params.update({
            "shared_gate": ParamSpec((d, shared_hidden), attr),
            "shared_up": ParamSpec((d, shared_hidden), attr),
            "shared_down": ParamSpec((shared_hidden, d), attr)})
    if shared_gated:
        params["shared_mix"] = ParamSpec((d, 1), attr)

    def compute(ctx, p, ins):
        v = ins[0]
        valid = v.valid_mask if isinstance(v, SequenceBatch) else None
        y, stats = pmoe.moe_dropless(_data_of(v), p, top_k=top_k, held=held,
                                     routing=routing, scaling=scaling,
                                     valid=valid)
        ctx.count("moe_rows_total", stats["rows_total"], layer=name)
        ctx.count("moe_rows_held_total", stats["rows_held"], layer=name)
        ctx.count("moe_max_expert_rows", stats["max_expert_rows"],
                  layer=name)
        for rows, ran in stats["rung_steps"].items():
            ctx.count("moe_rung_steps_total", ran, layer=name, rows=rows)
        if valid is not None:
            y = jnp.where(valid[:, None], y, 0)
        return _like(v, y.astype(pmath.dense_activation_dtype()))

    return LayerOutput(name=name, layer_type="moe_dropless", inputs=[inp],
                       fn=compute, params=params, size=d,
                       is_sequence=inp.is_sequence)


@_export
def gated_delta_net(input, *, num_k_heads: int, num_v_heads: int,
                    head_k_dim: int, head_v_dim: int, conv_kernel: int = 4,
                    epsilon: float = 1e-6, name: Optional[str] = None,
                    param_attr=None) -> LayerOutput:
    """Gated delta-rule mixing layer (linear attention) over packed
    sequences (ops/gated_delta.py): one projection to ``[q | k | v | z]``
    and one to the per-head gates ``[b | a]``, a causal depthwise
    convolution of ``conv_kernel`` taps and SiLU over ``q | k | v``, the
    delta-rule recurrence per value head in chunked form (state and
    convolution start anew with each sequence), a gated RMSNorm per head
    and the output projection.  ``a_log`` starts at 0 and ``dt_bias`` at
    -4.6, a decay of about 0.99 a token.  No biases, no cache."""
    from paddle_tpu.ops.gated_delta import conv_is_fused
    from paddle_tpu.ops.gated_delta import gated_delta_net as gdn

    _need_seq(input, "gated_delta_net")
    name = name or unique_name("gated_delta_net")
    attr = ParamAttr.to_attr(param_attr)
    d = input.size
    nq, nv = num_k_heads * head_k_dim, num_v_heads * head_v_dim
    const = lambda c: ParamAttr(initializer=Constant(c))  # noqa: E731
    params = {
        "w_qkvz": ParamSpec((d, 2 * nq + 2 * nv), attr),
        "w_ba": ParamSpec((d, 2 * num_v_heads), attr),
        "conv": ParamSpec((2 * nq + nv, conv_kernel), attr),
        "a_log": ParamSpec((num_v_heads,), const(0.0)),
        "dt_bias": ParamSpec((num_v_heads,), const(-4.6)),
        "norm": ParamSpec((head_v_dim,), const(1.0)),
        "wo": ParamSpec((nv, d), attr),
    }

    heads = dict(num_k_heads=num_k_heads, num_v_heads=num_v_heads,
                 head_k_dim=head_k_dim, head_v_dim=head_v_dim)

    def compute(ctx, p, ins):
        xs = ins[0]
        y = gdn(xs.data, xs.segment_ids, p, eps=epsilon, **heads)
        if conv_is_fused(**heads):
            # rows the fused convolution kernels took from the projection
            ctx.count("gdn_conv_fused_rows_total", xs.data.shape[0],
                      layer=name)
        return xs.with_data(y.astype(pmath.dense_activation_dtype()))

    return LayerOutput(name=name, layer_type="gated_delta_net",
                       inputs=[input], fn=compute, params=params, size=d,
                       is_sequence=True)


@_export
def gated_attention(input, positions, *, num_heads: int, num_kv_heads: int,
                    head_dim: int, rotary_dim: int,
                    rope_theta: float = 10000.0, epsilon: float = 1e-6,
                    name: Optional[str] = None, param_attr=None
                    ) -> LayerOutput:
    """Causal grouped-query attention over packed sequences with a
    weighted RMSNorm on each head's query and key, rotary positions on the
    first ``rotary_dim`` of a head's ``head_dim`` and an output gate
    ``sigmoid(g)`` that the query projection makes beside the query
    (ops/gated_attention.py).  ``positions`` as :func:`mla_attention`'s.
    No biases, no cache."""
    from paddle_tpu.ops.gated_attention import gated_attention as gattn

    _need_seq(input, "gated_attention")
    _need_seq(positions, "gated_attention")
    name = name or unique_name("gated_attention")
    attr = ParamAttr.to_attr(param_attr)
    gain = ParamAttr(initializer=Constant(1.0))
    d = input.size
    params = {
        "wq": ParamSpec((d, num_heads * 2 * head_dim), attr),
        "wk": ParamSpec((d, num_kv_heads * head_dim), attr),
        "wv": ParamSpec((d, num_kv_heads * head_dim), attr),
        "q_norm": ParamSpec((head_dim,), gain),
        "k_norm": ParamSpec((head_dim,), gain),
        "wo": ParamSpec((num_heads * head_dim, d), attr),
    }

    def compute(ctx, p, ins):
        xs, pos = ins
        y = gattn(xs.data, pos.data.reshape(-1), xs.segment_ids, p,
                  num_heads=num_heads, num_kv_heads=num_kv_heads,
                  head_dim=head_dim, rotary_dim=rotary_dim, eps=epsilon,
                  theta=rope_theta, mesh=ctx.mesh)
        _count_flash_blocks(ctx, name, xs.segment_ids, causal=True)
        return xs.with_data(y.astype(pmath.dense_activation_dtype()))

    return LayerOutput(name=name, layer_type="gated_attention",
                       inputs=[input, positions], fn=compute, params=params,
                       size=d, is_sequence=True)


@_export
def next_token_cost(input, label, *, shift: int = 0, weight: float = 1.0,
                    publish: Optional[str] = None,
                    name: Optional[str] = None) -> LayerOutput:
    """Softmax cross-entropy of a packed sequence's logits against
    ``label`` moved ``shift`` rows up inside each sequence: row ``i`` is
    scored against ``label[i + shift]``, and a row whose target lies
    beyond its own sequence is masked.  ``shift=1`` on the next-token
    column is a depth-1 multi-token-prediction loss (the token after
    next) with no second feed column.  The per-token loss is scaled by
    ``weight``.  ``publish`` names a gauge that shows the unscaled loss
    as the trainer reduces it (summed over tokens, per sequence)."""
    _need_seq(input, "next_token_cost")
    name = name or unique_name("next_token_cost")

    def compute(ctx, p, ins):
        logits, lab = ins
        ids = lab.data.reshape(-1).astype(jnp.int32)
        seg = logits.segment_ids
        ok = logits.valid_mask
        if shift:
            cap = ids.shape[0]
            ids = jnp.roll(ids, -shift)
            ok = ok & (jnp.roll(seg, -shift) == seg) & \
                (jnp.arange(cap) < cap - shift)
        loss = jnp.where(ok, ploss.softmax_cross_entropy(logits.data, ids),
                         0.0)
        if publish:
            ctx.gauge(publish,
                      jnp.sum(loss) / jnp.maximum(logits.num_seqs, 1))
        return logits.with_data(loss * weight)

    return _cost_node(name, "next_token_cost", [input, label], compute)


@_export
def lm_head_cost(input, label, vocab_size: int, name: Optional[str] = None,
                 param_attr=None, bias_attr=True,
                 block_size: int = 4096) -> LayerOutput:
    """Fused LM-head + softmax cross-entropy over a large vocabulary — the
    TPU-first replacement for ``fc(vocab) -> classification_cost`` on LM
    heads (new-build extension; the reference's era had selective_fc/NCE
    for big-softmax costs). Computes per-token loss in vocab blocks with
    an online logsumexp, so the [tokens, vocab] logits matrix never
    reaches HBM in forward OR backward (ops/losses.py:lm_head_xent) —
    at d=2048/V=32k bench shapes that is ~0.5-1 GB of traffic saved per
    step and the activation memory to run bigger batches. Equivalent to
    the unfused pair to f32 rounding (test_network_compare pins it)."""
    inputs = [input, label]
    name = name or unique_name("lm_head_cost")
    params = {"w": ParamSpec((input.size, vocab_size),
                             ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((vocab_size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        def f(x, lb):
            return ploss.lm_head_xent(x, p["w"], p.get("b"),
                                      lb.reshape(x.shape[0]),
                                      block_v=block_size)

        return _per_example(f, ins[0], ins[1])

    return LayerOutput(name=name, layer_type="lm_head_cost", inputs=inputs,
                       fn=compute, params=params, size=1, is_cost=True)


@_export
def sum_cost(input, name: Optional[str] = None) -> LayerOutput:
    """Sum of the input as a cost (reference: sum_cost/SumCostLayer)."""
    name = name or unique_name("sum_cost")

    def compute(ctx, p, ins):
        v = ins[0]
        d = _data_of(v)
        out = jnp.sum(d, axis=tuple(range(1, d.ndim)))
        if isinstance(v, SequenceBatch):
            out = jnp.where(v.valid_mask, out, 0.0)
            seg = jnp.where(v.valid_mask, v.segment_ids, v.num_seqs)
            return jax.ops.segment_sum(out, seg, num_segments=v.num_seqs + 1)[:v.num_seqs]
        return out

    return _cost_node(name, "sum_cost", [input], compute)


@_export
def eos(input, eos_id: int, name: Optional[str] = None) -> LayerOutput:
    """Truncate sequences at the end-of-sequence id (reference: eos_layer)."""
    inp = input
    _need_seq(inp, "eos")
    name = name or unique_name("eos")

    def compute(ctx, p, ins):
        sb: SequenceBatch = ins[0]
        ids, mask = sb.to_padded()
        tok = ids[..., 0] if ids.ndim == 3 else ids
        is_eos = (tok == eos_id) & mask
        # new length = index of first eos (exclusive), else original length
        T = tok.shape[1]
        first_eos = jnp.argmax(is_eos, axis=1)
        has_eos = jnp.any(is_eos, axis=1)
        new_len = jnp.where(has_eos, first_eos, sb.lengths).astype(jnp.int32)
        return pseq.seq_slice(sb, jnp.zeros_like(new_len), new_len)

    return LayerOutput(name=name, layer_type="eos", inputs=[inp], fn=compute,
                       size=inp.size, is_sequence=True)


@_export
def dotmul_bcast(a, b, name: Optional[str] = None) -> LayerOutput:
    """Tokenwise multiply with broadcasting over the feature dim — used to
    scale sequence tokens by per-token scalar weights (attention)."""
    name = name or unique_name("dotmul_bcast")

    def compute(ctx, p, ins):
        va, vb = _data_of(ins[0]), _data_of(ins[1])
        if vb.ndim < va.ndim:
            vb = vb[..., None]
        return _like(ins[0], va * vb)

    return LayerOutput(name=name, layer_type="dotmul_bcast", inputs=[a, b],
                       fn=compute, size=a.size, is_sequence=a.is_sequence)


# ---------------------------------------------------------------------------
# recurrent group surface (paddle_tpu/recurrent.py) + step cells
# ---------------------------------------------------------------------------

from paddle_tpu.recurrent import (StaticInput, SubsequenceInput,  # noqa: E402
                                  memory, recurrent_group)

__all__ += ["StaticInput", "SubsequenceInput", "memory",
            "recurrent_group", "gru_step", "lstm_step"]


def gru_step(input, output_mem, size: int = None, act=None, gate_act=None,
             name: Optional[str] = None, param_attr=None,
             bias_attr=True) -> LayerOutput:
    """One GRU step for use inside recurrent_group (reference:
    gru_step_layer/GruStepLayer.cpp). input: [B, 3*size] projected x_t;
    output_mem: the memory holding h_{t-1}."""
    size = size or output_mem.size
    name = name or unique_name("gru_step")
    params = {"w": ParamSpec((size, 3 * size), ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((3 * size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))
    cand = _resolve_act(act or "tanh")
    gate = _resolve_act(gate_act or "sigmoid")

    def compute(ctx, p, ins):
        x, h = _data_of(ins[0]), _data_of(ins[1])
        return prnn.gru_cell(x, h, p["w"], p.get("b"), gate_act=gate.fn,
                             cand_act=cand.fn)

    return LayerOutput(name=name, layer_type="gru_step",
                       inputs=[input, output_mem], fn=compute, params=params,
                       size=size, is_sequence=False)


def lstm_step(input, state_mem, output_mem=None, size: int = None, act=None,
              gate_act=None, state_act=None, name: Optional[str] = None,
              param_attr=None, bias_attr=True) -> LayerOutput:
    """One LSTM step (reference: lstm_step_layer). input: [B, 4*size]
    pre-projected; state_mem: memory of c_{t-1}; output_mem: memory of
    h_{t-1}. Returns h_t; ``.state`` output is exposed as a second node via
    lstm_step_state()."""
    size = size or state_mem.size
    name = name or unique_name("lstm_step")
    # the h-recurrence weight only exists when the step actually carries an
    # h memory; without output_mem the recurrence must be pre-projected into
    # ``input`` (the reference lstm_step contract) and a weight here would be
    # a dead randomly-initialised parameter
    params = {}
    if output_mem is not None:
        params["w"] = ParamSpec((size, 4 * size), ParamAttr.to_attr(param_attr))
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((4 * size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))
    o_act = _resolve_act(act or "tanh")
    g_act = _resolve_act(gate_act or "sigmoid")
    s_act = _resolve_act(state_act or "tanh")
    inputs = [input, state_mem] + ([output_mem] if output_mem is not None else [])

    def compute(ctx, p, ins):
        x, c = _data_of(ins[0]), _data_of(ins[1])
        h = _data_of(ins[2]) if len(ins) > 2 else jnp.zeros_like(c)
        new_h, st = prnn.lstm_cell(x, prnn.LSTMState(h, c), p.get("w"),
                                   p.get("b"),
                                   gate_act=g_act.fn, cell_act=s_act.fn,
                                   out_act=o_act.fn)
        # pack h and c side by side; callers split with lstm_step_state
        return jnp.concatenate([new_h, st.c], axis=-1)

    node = LayerOutput(name=name, layer_type="lstm_step", inputs=inputs,
                       fn=compute, params=params, size=2 * size,
                       is_sequence=False)
    node.lstm_size = size
    return node


def lstm_step_output(step_node, name: Optional[str] = None) -> LayerOutput:
    """h_t half of an lstm_step node."""
    size = step_node.lstm_size
    name = name or unique_name("lstm_h")

    def compute(ctx, p, ins):
        return _data_of(ins[0])[..., :size]

    return LayerOutput(name=name, layer_type="lstm_h", inputs=[step_node],
                       fn=compute, size=size, is_sequence=False)


def lstm_step_state(step_node, name: Optional[str] = None) -> LayerOutput:
    """c_t half of an lstm_step node."""
    size = step_node.lstm_size
    name = name or unique_name("lstm_c")

    def compute(ctx, p, ins):
        return _data_of(ins[0])[..., size:]

    return LayerOutput(name=name, layer_type="lstm_c", inputs=[step_node],
                       fn=compute, size=size, is_sequence=False)


__all__ += ["lstm_step_output", "lstm_step_state"]


# ---------------------------------------------------------------------------
# round-2 completeness batch: the remaining registered layer types of the
# reference (REGISTER_LAYER list, SURVEY.md §2.1 "Layers (95 types)")
# ---------------------------------------------------------------------------


@_export
def prelu(input, partial_sum: int = 1, param_attr=None,
          name: Optional[str] = None) -> LayerOutput:
    """Parametric ReLU; one slope per group of `partial_sum` features
    (reference: prelu_layer → ParameterReluLayer.cpp)."""
    inp = input
    name = name or unique_name("prelu")
    enforce_that(inp.size % partial_sum == 0,
                 "prelu partial_sum must divide input size", context="prelu")
    n_slopes = inp.size // partial_sum
    params = {"w": ParamSpec((n_slopes,), ParamAttr.to_attr(param_attr))}

    def compute(ctx, p, ins):
        v = ins[0]
        x = _data_of(v)
        flat = x.reshape(x.shape[0], n_slopes, partial_sum)
        slope = p["w"].reshape(1, n_slopes, 1)
        y = jnp.where(flat >= 0, flat, slope * flat).reshape(x.shape)
        return _like(v, y)

    node = LayerOutput(name=name, layer_type="prelu", inputs=[inp],
                       fn=compute, params=params, size=inp.size,
                       is_sequence=inp.is_sequence)
    return _propagate_img_shape(node, inp)


@_export
def scale_shift(input, param_attr=None, bias_attr=True,
                name: Optional[str] = None) -> LayerOutput:
    """y = w * x + b with scalar w, b (reference: scale_shift_layer →
    ScaleShiftLayer.cpp)."""
    inp = input
    name = name or unique_name("scale_shift")
    params = {"w": ParamSpec((1,), ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((1,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        v = ins[0]
        y = _data_of(v) * p["w"][0]
        if has_bias:
            y = y + p["b"][0]
        return _like(v, y)

    return LayerOutput(name=name, layer_type="scale_shift", inputs=[inp],
                       fn=compute, params=params, size=inp.size,
                       is_sequence=inp.is_sequence)


@_export
def data_norm(input, mean=None, std=None, mode: str = "z-score",
              name: Optional[str] = None) -> LayerOutput:
    """Input normalization with fixed statistics (reference: data_norm_layer
    → DataNormLayer.cpp; stats are precomputed, never trained).

    mean/std are python arrays or scalars; mode ∈ {z-score, min-max,
    decimal-scaling} (min-max interprets mean/std as min/range)."""
    inp = input
    name = name or unique_name("data_norm")
    mean_a = jnp.asarray(0.0 if mean is None else mean, jnp.float32)
    std_a = jnp.asarray(1.0 if std is None else std, jnp.float32)

    def compute(ctx, p, ins):
        v = ins[0]
        x = _data_of(v)
        if mode == "z-score":
            y = (x - mean_a) / jnp.maximum(std_a, 1e-8)
        elif mode == "min-max":
            y = (x - mean_a) / jnp.maximum(std_a, 1e-8)
        elif mode == "decimal-scaling":
            y = x / jnp.power(10.0, jnp.ceil(jnp.log10(
                jnp.maximum(std_a, 1e-8))))
        else:
            raise EnforceError(f"bad data_norm mode {mode}", context="data_norm")
        return _like(v, y)

    return LayerOutput(name=name, layer_type="data_norm", inputs=[inp],
                       fn=compute, size=inp.size,
                       is_sequence=inp.is_sequence)


@_export
def trans(input, name: Optional[str] = None) -> LayerOutput:
    """Transpose the (flattened) feature matrix of a non-sequence batch
    (reference: trans_layer → TransLayer.cpp: batch-size x size matrix
    transposed). Output batch dim becomes the feature dim."""
    inp = input
    name = name or unique_name("trans")

    def compute(ctx, p, ins):
        return _data_of(ins[0]).T

    return LayerOutput(name=name, layer_type="trans", inputs=[inp],
                       fn=compute, size=None, is_sequence=False)


@_export
def switch_order(input, reshape_to=("h", "w", "c"),
                 name: Optional[str] = None) -> LayerOutput:
    """Switch image memory layout between HWC and CHW flattenings
    (reference: switch_order_layer → SwitchOrderLayer.cpp)."""
    inp = input
    name = name or unique_name("switch_order")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "switch_order needs image shape",
                 context="switch_order")
    h, w, c = in_shape
    to_hwc = tuple(reshape_to) == ("h", "w", "c")

    def compute(ctx, p, ins):
        x = _data_of(ins[0])
        n = x.shape[0]
        if to_hwc:   # stored CHW → emit HWC
            y = x.reshape(n, c, h, w).transpose(0, 2, 3, 1)
        else:        # stored HWC → emit CHW
            y = x.reshape(n, h, w, c).transpose(0, 3, 1, 2)
        return y.reshape(n, -1)

    node = LayerOutput(name=name, layer_type="switch_order", inputs=[inp],
                       fn=compute, size=inp.size)
    node.img_shape = (h, w, c)
    return node


@_export
def tensor(a, b, size: int, act=None, param_attr=None,
           name: Optional[str] = None) -> LayerOutput:
    """Bilinear tensor product: out[k] = a · W_k · bᵀ (reference:
    tensor_layer → TensorLayer.cpp)."""
    name = name or unique_name("tensor")
    activation = _resolve_act(act)
    params = {"w": ParamSpec((size, a.size, b.size),
                             ParamAttr.to_attr(param_attr))}

    def compute(ctx, p, ins):
        x, y = _data_of(ins[0]), _data_of(ins[1])
        out = jnp.einsum("bi,kij,bj->bk", x, p["w"], y)
        return _apply_act(activation, out)

    return LayerOutput(name=name, layer_type="tensor", inputs=[a, b],
                       fn=compute, params=params, size=size)


@_export
def out_prod(a, b, name: Optional[str] = None) -> LayerOutput:
    """Row-wise outer product, flattened (reference: out_prod_layer →
    OuterProdLayer.cpp)."""
    name = name or unique_name("out_prod")

    def compute(ctx, p, ins):
        x, y = _data_of(ins[0]), _data_of(ins[1])
        return jnp.einsum("bi,bj->bij", x, y).reshape(x.shape[0], -1)

    return LayerOutput(name=name, layer_type="out_prod", inputs=[a, b],
                       fn=compute, size=a.size * b.size)


@_export
def multiplex(index, inputs, name: Optional[str] = None) -> LayerOutput:
    """Row-wise select among candidate layers by index layer (reference:
    multiplex_layer → MultiplexLayer.cpp)."""
    cands = _as_list(inputs)
    name = name or unique_name("multiplex")

    def compute(ctx, p, ins):
        idx = _data_of(ins[0]).reshape(-1).astype(jnp.int32)
        stack = jnp.stack([_data_of(v) for v in ins[1:]], axis=0)  # [K,B,D]
        return jnp.take_along_axis(
            stack, idx[None, :, None], axis=0)[0]

    return LayerOutput(name=name, layer_type="multiplex",
                       inputs=[index] + cands, fn=compute,
                       size=cands[0].size)


@_export
def conv_shift(a, b, name: Optional[str] = None) -> LayerOutput:
    """Circular convolution of each row of `a` with the (odd-width) kernel
    rows of `b` (reference: conv_shift_layer → ConvShiftLayer.cpp; used by
    NTM-style addressing)."""
    name = name or unique_name("conv_shift")
    enforce_that(b.size % 2 == 1, "conv_shift kernel width must be odd",
                 context="conv_shift")
    half = b.size // 2

    def compute(ctx, p, ins):
        x, k = _data_of(ins[0]), _data_of(ins[1])
        m = x.shape[1]
        shifts = [jnp.roll(x, half - j, axis=1) for j in range(k.shape[1])]
        stack = jnp.stack(shifts, axis=-1)            # [B, M, K]
        return jnp.einsum("bmk,bk->bm", stack, k)

    return LayerOutput(name=name, layer_type="conv_shift", inputs=[a, b],
                       fn=compute, size=a.size)


@_export
def linear_comb(weights, vectors, size: int,
                name: Optional[str] = None) -> LayerOutput:
    """Weighted combination of M sub-vectors: out = Σ_m w[:,m]·x[:,m,:]
    (reference: linear_comb_layer / convex_comb_layer →
    LinearChainCRF... LinearCombLayer.cpp)."""
    name = name or unique_name("linear_comb")

    def compute(ctx, p, ins):
        w, x = _data_of(ins[0]), _data_of(ins[1])
        m = w.shape[1]
        return jnp.einsum("bm,bmd->bd", w, x.reshape(x.shape[0], m, size))

    return LayerOutput(name=name, layer_type="linear_comb",
                       inputs=[weights, vectors], fn=compute, size=size)


@_export
def convex_comb(weights, vectors, size: int,
                name: Optional[str] = None) -> LayerOutput:
    """Alias of linear_comb (reference registers convex_comb as the same
    layer)."""
    return linear_comb(weights, vectors, size, name=name)


@_export
def cos_vm(a, b, size: int, scale: float = 1.0,
           name: Optional[str] = None) -> LayerOutput:
    """Cosine similarity of vector `a` against each of the M rows packed in
    `b` (reference: cos_vm → CosSimVecMatLayer.cpp)."""
    name = name or unique_name("cos_vm")

    def compute(ctx, p, ins):
        x, y = _data_of(ins[0]), _data_of(ins[1])
        m = y.shape[1] // x.shape[1]
        ym = y.reshape(y.shape[0], m, x.shape[1])
        num = jnp.einsum("bd,bmd->bm", x, ym)
        den = (jnp.linalg.norm(x, axis=1, keepdims=True)
               * jnp.linalg.norm(ym, axis=2))
        return scale * num / jnp.maximum(den, 1e-8)

    return LayerOutput(name=name, layer_type="cos_vm", inputs=[a, b],
                       fn=compute, size=size)


@_export
def row_conv(input, context_len: int, act=None, param_attr=None,
             name: Optional[str] = None) -> LayerOutput:
    """Lookahead row convolution over future frames within each sequence
    (reference: row_conv_layer → RowConvLayer.cpp, Deep Speech 2)."""
    inp = input
    _need_seq(inp, "row_conv")
    name = name or unique_name("row_conv")
    activation = _resolve_act(act)
    params = {"w": ParamSpec((context_len, inp.size),
                             ParamAttr.to_attr(param_attr))}

    def compute(ctx, p, ins):
        sb = ins[0]
        x, seg = sb.data, sb.segment_ids
        total = jnp.zeros_like(x)
        cap = x.shape[0]
        for j in range(context_len):
            shifted = jnp.concatenate(
                [x[j:], jnp.zeros((j,) + x.shape[1:], x.dtype)], axis=0)
            seg_sh = jnp.concatenate(
                [seg[j:], jnp.full((j,), -1, seg.dtype)], axis=0)
            ok = (seg_sh == seg)[:, None]
            total = total + jnp.where(ok, shifted * p["w"][j][None, :], 0.0)
        return sb.with_data(_apply_act(activation, total))

    return LayerOutput(name=name, layer_type="row_conv", inputs=[inp],
                       fn=compute, params=params, size=inp.size,
                       is_sequence=True)


@_export
def subseq(input, offsets, sizes, name: Optional[str] = None) -> LayerOutput:
    """Per-sequence sub-range [offset, offset+size) (reference: subseq →
    SubSequenceLayer.cpp); offsets/sizes are int layers, one per sequence."""
    inp = input
    _need_seq(inp, "subseq")
    name = name or unique_name("subseq")

    def compute(ctx, p, ins):
        sb = ins[0]
        s = _data_of(ins[1]).reshape(-1).astype(jnp.int32)
        n = _data_of(ins[2]).reshape(-1).astype(jnp.int32)
        return pseq.seq_slice(sb, s, s + n)

    return LayerOutput(name=name, layer_type="subseq",
                       inputs=[inp, offsets, sizes], fn=compute,
                       size=inp.size, is_sequence=True)


@_export
def featmap_expand(input, num_filters: int, as_row_vector: bool = True,
                   name: Optional[str] = None) -> LayerOutput:
    """Tile each feature map `num_filters` times (reference:
    featmap_expand → FeatureMapExpandLayer.cpp)."""
    inp = input
    name = name or unique_name("featmap_expand")

    def compute(ctx, p, ins):
        v = ins[0]
        x = _data_of(v)
        if as_row_vector:
            y = jnp.tile(x, (1, num_filters))
        else:
            y = jnp.repeat(x, num_filters, axis=1)
        return _like(v, y)

    return LayerOutput(name=name, layer_type="featmap_expand", inputs=[inp],
                       fn=compute, size=inp.size * num_filters,
                       is_sequence=inp.is_sequence)


@_export
def get_output(input, arg_name: str = "default",
               name: Optional[str] = None) -> LayerOutput:
    """Expose a named internal output of a multi-output layer (reference:
    get_output_layer → GetOutputLayer.cpp). For lstm step nodes,
    arg_name="state" selects c_t (the reference's 'state' output)."""
    if arg_name in ("state", "cell") and getattr(input, "lstm_size", None):
        return lstm_step_state(input, name=name)
    inp = input
    name = name or unique_name("get_output")

    def compute(ctx, p, ins):
        return ins[0]

    node = LayerOutput(name=name, layer_type="get_output", inputs=[inp],
                       fn=compute, size=inp.size,
                       is_sequence=inp.is_sequence)
    return _propagate_img_shape(node, inp)


@_export
def print_layer(input, format: Optional[str] = None,
                name: Optional[str] = None) -> LayerOutput:
    """Debug-print the input at step time (reference: print layer →
    PrintLayer.cpp). jax.debug.print fires from inside the compiled
    program; the layer passes its input through unchanged."""
    inp = input
    name = name or unique_name("print")
    fmt = format or (name + ": {x}")

    def compute(ctx, p, ins):
        v = ins[0]
        jax.debug.print(fmt, x=_data_of(v))
        return v

    node = LayerOutput(name=name, layer_type="print", inputs=[inp],
                       fn=compute, size=inp.size,
                       is_sequence=inp.is_sequence)
    return _propagate_img_shape(node, inp)


# ---------------------------------------------------------------------------
# 3-D convolution stack (reference: Conv3DLayer/DeConv3DLayer/Pool3DLayer)
# ---------------------------------------------------------------------------


def _vol_shape_of(node: LayerOutput):
    """(D, H, W, C) metadata threaded through the 3-D stack."""
    return getattr(node, "vol_shape", None)


@_export
def img_conv3d(input, filter_size, num_filters: int, num_channels=None,
               stride: int = 1, padding: int = 0, act=None,
               bias_attr=True, param_attr=None, trans: bool = False,
               depth: int = None, height: int = None, width: int = None,
               name: Optional[str] = None) -> LayerOutput:
    """3-D (de)convolution, NDHWC on the MXU (reference: conv3d/deconv3d →
    Conv3DLayer.cpp / DeConv3DLayer.cpp)."""
    inp = input
    name = name or unique_name("conv3d")
    activation = _resolve_act(act)
    vol = _vol_shape_of(inp)
    if vol is None:
        enforce_that(None not in (depth, height, width, num_channels),
                     "img_conv3d needs vol shape metadata or "
                     "depth/height/width/num_channels", context="conv3d")
        vol = (depth, height, width, num_channels)
    d, h, w, c = vol
    k = (filter_size,) * 3 if isinstance(filter_size, int) \
        else tuple(filter_size)
    if trans:
        od = (d - 1) * stride + k[0] - 2 * padding
        oh = (h - 1) * stride + k[1] - 2 * padding
        ow = (w - 1) * stride + k[2] - 2 * padding
    else:
        od = _conv_out_dim(d, k[0], padding, stride)
        oh = _conv_out_dim(h, k[1], padding, stride)
        ow = _conv_out_dim(w, k[2], padding, stride)
    wshape = k + ((num_filters, c) if trans else (c, num_filters))
    params = {"w": ParamSpec(wshape, ParamAttr.to_attr(param_attr))}
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((num_filters,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        x = _data_of(ins[0]).reshape(-1, d, h, w, c)
        if trans:
            # lhs_dilation = fractional stride; k-1-p pads convert to the
            # equivalent forward conv (same scheme as ops/conv.py 2-D path)
            wk = jnp.flip(p["w"], (0, 1, 2)).transpose(0, 1, 2, 4, 3)
            y = jax.lax.conv_general_dilated(
                x, wk, window_strides=(1, 1, 1),
                padding=[(kk - 1 - padding, kk - 1 - padding) for kk in k],
                lhs_dilation=(stride,) * 3,
                dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        else:
            y = pconv.conv3d(x, p["w"], stride=stride, padding=padding)
        if has_bias:
            y = y + p["b"]
        y = _apply_act(activation, y)
        return _apply_extra(ctx, name, y.reshape(y.shape[0], -1), None)

    node = LayerOutput(name=name, layer_type="conv3d", inputs=[inp],
                       fn=compute, params=params,
                       size=od * oh * ow * num_filters)
    node.vol_shape = (od, oh, ow, num_filters)
    return node


@_export
def img_pool3d(input, pool_size, pool_type=None, stride: int = None,
               padding: int = 0, name: Optional[str] = None,
               **_kw) -> LayerOutput:
    """3-D pooling (reference: pool3d → Pool3DLayer.cpp)."""
    inp = input
    name = name or unique_name("pool3d")
    ptype = pooling_mod.get(pool_type)
    stride = stride if stride is not None else pool_size
    vol = _vol_shape_of(inp)
    enforce_that(vol is not None, "img_pool3d needs vol shape",
                 context="pool3d")
    d, h, w, c = vol
    k = (pool_size,) * 3 if isinstance(pool_size, int) else tuple(pool_size)
    od = _conv_out_dim(d, k[0], padding, stride)
    oh = _conv_out_dim(h, k[1], padding, stride)
    ow = _conv_out_dim(w, k[2], padding, stride)
    is_max = isinstance(ptype, pooling_mod.MaxPooling)

    def compute(ctx, p, ins):
        x = _data_of(ins[0]).reshape(-1, d, h, w, c)
        window = (1,) + k + (1,)
        strides = (1,) + (stride,) * 3 + (1,)
        pads = ((0, 0),) + ((padding, padding),) * 3 + ((0, 0),)
        if is_max:
            y = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window,
                                      strides, pads)
        else:
            y = jax.lax.reduce_window(x, 0.0, jax.lax.add, window,
                                      strides, pads) / (k[0] * k[1] * k[2])
        return y.reshape(y.shape[0], -1)

    node = LayerOutput(name=name, layer_type="pool3d", inputs=[inp],
                       fn=compute, size=od * oh * ow * c)
    node.vol_shape = (od, oh, ow, c)
    return node


# ---------------------------------------------------------------------------
# MDLSTM (reference: mdlstmemory → MDLstmLayer.cpp) — 2-D LSTM whose cell
# (i, j) sees states from (i-1, j) and (i, j-1). TPU-native: a lax.scan over
# rows whose body is a lax.scan over columns (row-major wavefront), all
# compiled into one XLA while-loop nest.
# ---------------------------------------------------------------------------


@_export
def mdlstmemory(input, size: int, height: int, width: int,
                param_attr=None, bias_attr=True,
                name: Optional[str] = None) -> LayerOutput:
    """2-D multidimensional LSTM over an image laid out [B, H*W*C].

    Gates: input, output, cell candidate + one forget gate per direction
    (MDLstmLayer.cpp). Output is [B, H*W*size]."""
    inp = input
    name = name or unique_name("mdlstm")
    enforce_that(inp.size % (height * width) == 0,
                 "mdlstm input size must be H*W*C", context="mdlstm")
    c_in = inp.size // (height * width)
    # x proj -> 5*size (i, f_row, f_col, o, g); two recurrent projections
    params = {
        "wx": ParamSpec((c_in, 5 * size), ParamAttr.to_attr(param_attr)),
        "wr": ParamSpec((size, 5 * size), ParamAttr.to_attr(param_attr)),
        "wc": ParamSpec((size, 5 * size), ParamAttr.to_attr(param_attr)),
    }
    has_bias = bool(bias_attr)
    if has_bias:
        params["b"] = ParamSpec((5 * size,), ParamAttr.to_attr(
            None if bias_attr is True else bias_attr))

    def compute(ctx, p, ins):
        x = _data_of(ins[0])
        b = x.shape[0]
        grid = x.reshape(b, height, width, c_in)
        xs = jnp.einsum("bhwc,cg->hwbg", grid, p["wx"])
        if has_bias:
            xs = xs + p["b"]

        def cell(pre, h_up, c_up, h_left, c_left):
            z = pre + h_up @ p["wr"] + h_left @ p["wc"]
            i, f_r, f_c, o, g = jnp.split(z, 5, axis=-1)
            c_new = (jax.nn.sigmoid(f_r) * c_up
                     + jax.nn.sigmoid(f_c) * c_left
                     + jax.nn.sigmoid(i) * jnp.tanh(g))
            h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
            return h_new, c_new

        zeros = jnp.zeros((b, size), x.dtype)

        def row_step(carry_row, xrow):
            h_prev_row, c_prev_row = carry_row   # [W, B, size] each

            def col_step(carry_col, inputs):
                h_left, c_left = carry_col
                pre, h_up, c_up = inputs
                h_new, c_new = cell(pre, h_up, c_up, h_left, c_left)
                return (h_new, c_new), (h_new, c_new)

            (_, _), (h_row, c_row) = jax.lax.scan(
                col_step, (zeros, zeros), (xrow, h_prev_row, c_prev_row))
            return (h_row, c_row), h_row

        h0 = jnp.zeros((width, b, size), x.dtype)
        (_, _), hs = jax.lax.scan(row_step, (h0, h0), xs)  # [H, W, B, size]
        return hs.transpose(2, 0, 1, 3).reshape(b, -1)

    node = LayerOutput(name=name, layer_type="mdlstm", inputs=[inp],
                       fn=compute, params=params,
                       size=height * width * size)
    node.img_shape = (height, width, size)
    return node


# ---------------------------------------------------------------------------
# detection suite (reference: priorbox/multibox_loss/detection_output —
# PriorBoxLayer.cpp, MultiBoxLossLayer.cpp, DetectionOutputLayer.cpp)
# ---------------------------------------------------------------------------


@_export
def priorbox(input, image_size, min_size, max_size=(), aspect_ratio=(2.0,),
             variance=(0.1, 0.1, 0.2, 0.2), name: Optional[str] = None
             ) -> LayerOutput:
    """Prior (anchor) boxes for a feature map: output [1, P*8] = boxes then
    variances (reference priorbox emits boxes+variances rows)."""
    from paddle_tpu.ops import detection as pdet
    inp = input
    name = name or unique_name("priorbox")
    in_shape = _img_shape_of(inp)
    enforce_that(in_shape is not None, "priorbox needs image shape",
                 context="priorbox")
    fh, fw, _ = in_shape
    ih, iw = (image_size, image_size) if isinstance(image_size, int) \
        else tuple(image_size)
    min_sizes = [min_size] if isinstance(min_size, (int, float)) else list(min_size)
    max_sizes = [max_size] if isinstance(max_size, (int, float)) else list(max_size)
    boxes_np, var_np = pdet.prior_boxes(fh, fw, ih, iw, min_sizes,
                                        max_sizes, list(aspect_ratio),
                                        list(variance))
    num_p = boxes_np.shape[0]

    def compute(ctx, p, ins):
        flat = jnp.concatenate([jnp.asarray(boxes_np).reshape(-1),
                                jnp.asarray(var_np).reshape(-1)])
        return flat[None, :]

    node = LayerOutput(name=name, layer_type="priorbox", inputs=[inp],
                       fn=compute, size=num_p * 8)
    node.num_priors = num_p
    return node


def _gather_ssd_preds(ins, k, num_classes):
    """Concat per-feature-map loc/conf predictions + split the prior blob
    (shared by multibox_loss and detection_output so train-time matching
    and inference-time decoding can never disagree on packing)."""
    loc = jnp.concatenate(
        [_data_of(v).reshape(_data_of(v).shape[0], -1, 4)
         for v in ins[:k]], axis=1)
    conf = jnp.concatenate(
        [_data_of(v).reshape(_data_of(v).shape[0], -1, num_classes)
         for v in ins[k:2 * k]], axis=1)
    pb = _data_of(ins[2 * k])[0]
    return loc, conf, pb


def _split_priors(pb_flat, num_p):
    boxes = pb_flat[: num_p * 4].reshape(num_p, 4)
    var = pb_flat[num_p * 4:].reshape(num_p, 4)
    return boxes, var


@_export
def multibox_loss(input_loc, input_conf, priorbox, label, num_classes: int,
                  overlap_threshold: float = 0.5, neg_pos_ratio: float = 3.0,
                  background_id: int = 0, max_boxes: int = 16,
                  name: Optional[str] = None) -> LayerOutput:
    """SSD loss. ``label`` is a dense [B, max_boxes*5] layer of
    (class, xmin, ymin, xmax, ymax) rows, class<0 ⇒ padding (the reference
    feeds the same records as a sequence; dense-with-padding is the
    static-shape TPU equivalent)."""
    from paddle_tpu.ops import detection as pdet
    locs = _as_list(input_loc)
    confs = _as_list(input_conf)
    name = name or unique_name("multibox_loss")
    num_p = priorbox.num_priors

    def compute(ctx, p, ins):
        k = len(locs)
        loc, conf, pb = _gather_ssd_preds(ins, k, num_classes)
        gt = _data_of(ins[2 * k + 1]).reshape(loc.shape[0], max_boxes, 5)
        boxes, var = _split_priors(pb, num_p)

        def one(loc_i, conf_i, gt_i):
            valid = gt_i[:, 0] >= 0
            return pdet.multibox_loss(
                loc_i, conf_i, boxes, var, gt_i[:, 1:5],
                jnp.maximum(gt_i[:, 0], 0).astype(jnp.int32), valid,
                num_classes, overlap_threshold, neg_pos_ratio,
                background_id)

        return jax.vmap(one)(loc, conf, gt)[:, None]

    node = LayerOutput(name=name, layer_type="multibox_loss",
                       inputs=locs + confs + [priorbox, label], fn=compute,
                       size=1, is_cost=True)
    return node


@_export
def detection_output(input_loc, input_conf, priorbox, num_classes: int,
                     nms_threshold: float = 0.45,
                     confidence_threshold: float = 0.01,
                     keep_top_k: int = 100, background_id: int = 0,
                     name: Optional[str] = None) -> LayerOutput:
    """Decode + per-class NMS → [B, keep_top_k*6] detections of
    (label, score, xmin, ymin, xmax, ymax), label −1 = empty slot."""
    from paddle_tpu.ops import detection as pdet
    locs = _as_list(input_loc)
    confs = _as_list(input_conf)
    name = name or unique_name("detection_output")
    num_p = priorbox.num_priors

    def compute(ctx, p, ins):
        k = len(locs)
        loc, conf, pb = _gather_ssd_preds(ins, k, num_classes)
        boxes, var = _split_priors(pb, num_p)

        def one(loc_i, conf_i):
            return pdet.detection_output(
                loc_i, conf_i, boxes, var, num_classes, nms_threshold,
                confidence_threshold, keep_top_k, background_id)

        return jax.vmap(one)(loc, conf).reshape(loc.shape[0], -1)

    return LayerOutput(name=name, layer_type="detection_output",
                       inputs=locs + confs + [priorbox], fn=compute,
                       size=keep_top_k * 6)


# v1-compatible aliases for registered type names
gated_recurrent = grumemory
__all__ += ["gated_recurrent"]
