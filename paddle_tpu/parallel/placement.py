"""Per-layer placement / model parallelism over a mesh axis.

Reference: paddle/gserver/gradientmachines/ParallelNeuralNetwork.h:15-70 —
the v1 engine places layers on devices via a per-layer ``device`` attr
(--parallel_nn) and runs one compute thread per device with queue dispatch.

TPU-native redesign: manual thread/queue placement becomes SPMD sharding.
A "stage" here is a (weight sharding, activation sharding) pair over a
named mesh axis; XLA inserts the transfers/collectives that the
reference's dispatchByDeviceId did by hand:

- ``part="col"``: W sharded [in, axis] — output features sharded over the
  axis (no collective on the forward matmul);
- ``part="row"``: W sharded [axis, out] — input features expected sharded,
  output replicated (XLA inserts the psum).

A col->row pair is the classic tensor-parallel block: the model's weights
never exist replicated on any device, which is the capability the
reference's layer placement provided (models too big for one device).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

from paddle_tpu.attr import ExtraAttr, ParamAttr


#: The mesh-axis vocabulary every placement plan draws from (one axis,
#: one meaning — MIGRATION.md "Pod-scale training" spells out the
#: composition rules):
#:   data   — batch replication; the grad-psum / ZeRO domain
#:   zero   — alias role of ``data`` when ZeRO shards optimizer state
#:   stage  — pipeline stages (stacked layer dim, leading-dim sharded)
#:   expert — MoE experts (stacked expert dim, leading-dim sharded)
#:   model  — tensor parallelism (megatron col/row feature sharding)
KNOWN_AXES = ("data", "zero", "stage", "expert", "model")


@dataclass(frozen=True)
class _PlanSpec:
    """Adapter so a serving ``shard_plan`` entry plugs into the
    ``specs[name].attr`` shape :func:`~paddle_tpu.parallel.api.param_sharding`
    and :func:`~paddle_tpu.parallel.zero.build_zero_plan` consume."""

    attr: ParamAttr


def plan_param_attrs(plan: Dict[str, Tuple]) -> Dict[str, _PlanSpec]:
    """Bridge a model's tensor-parallel ``shard_plan`` ({param name:
    per-dim axis tuple}) into the explicit-``ParamAttr.sharding`` spec
    dict the data-parallel/ZeRO machinery takes — the train→serve
    "one placement story": ``build_zero_plan(mesh, params,
    specs=plan_param_attrs(model.shard_plan()))`` keeps every
    TP-sharded weight in its declared megatron layout (explicit
    sharding wins the precedence rules) while the replicated remainder
    (embeddings, the vocab head) still gets its optimizer state
    ZeRO-sharded over the ``data`` axis.  Entries with no real axis are
    OMITTED rather than declared ``P()`` — an explicit empty spec would
    opt them out of ZeRO, which is exactly backwards."""
    out: Dict[str, _PlanSpec] = {}
    for name, spec in plan.items():
        dims = tuple(spec)
        if any(a is not None for a in dims):
            out[name] = _PlanSpec(attr=ParamAttr(sharding=dims))
    return out


def leading_axis_plan(params: Dict[str, object],
                      axis: str) -> Dict[str, Tuple]:
    """{name: (axis, None, ...)} plan for stacked-leading-dim weights —
    the layout pipeline stages (``axis="stage"``: [L, ...] layer stacks)
    and MoE experts (``axis="expert"``: [E, ...] expert stacks) share.
    ``params`` maps names to arrays (or anything with ``ndim``/``shape``).
    Feed the result to :func:`plan_param_attrs`; it composes with TP and
    ZeRO entries in the same plan — the one-placement-layer story."""
    out: Dict[str, Tuple] = {}
    for name, v in params.items():
        nd = getattr(v, "ndim", None)
        if nd is None:
            nd = len(getattr(v, "shape", ()))
        out[name] = (axis,) + (None,) * (int(nd) - 1)
    return out


def pipeline_param_attrs(params: Dict[str, object],
                         axis: str = "stage") -> Dict[str, _PlanSpec]:
    """``plan_param_attrs`` of the pipeline leading-dim plan: every
    stacked body weight [L, ...] shards its layer dim over ``axis`` so
    each stage's device holds exactly its L/S layers.  The stacked [L,
    ...] layout itself is LAYOUT-INDEPENDENT: checkpoints save the full
    gathered stack and reload into any stage count dividing L
    (gather-on-save / scatter-on-load, same as every sharded param)."""
    return plan_param_attrs(leading_axis_plan(params, axis))


def expert_param_attrs(params: Dict[str, object],
                       axis: str = "expert") -> Dict[str, _PlanSpec]:
    """``plan_param_attrs`` of the MoE leading-dim plan ([E, ...] expert
    stacks over ``axis``) — :meth:`paddle_tpu.parallel.moe.MoEConfig.
    param_plan` names which weights; this shards any stacked dict."""
    return plan_param_attrs(leading_axis_plan(params, axis))


def stage_attrs(part: str, axis: str = "model"):
    """(param_attr, layer_attr) for one model-parallel fc stage."""
    if part == "col":
        pa = ParamAttr(sharding=(None, axis))
        la = ExtraAttr(sharding=(None, axis))
    elif part == "row":
        pa = ParamAttr(sharding=(axis, None))
        la = ExtraAttr(sharding=(None, None))
    else:
        raise ValueError(f"part must be 'col' or 'row', got {part!r}")
    return pa, la


def model_parallel_fc(input, size: int, *, part: str, axis: str = "model",
                      act=None, name: Optional[str] = None,
                      bias_attr=True):
    """fc whose weight AND activation are sharded over ``axis``.

    col-part biases are feature-sharded too (they live with the output
    features); row-part biases stay replicated (they add to the psum
    result).
    """
    from paddle_tpu import layer

    pa, la = stage_attrs(part, axis)
    if bias_attr is True and part == "col":
        bias_attr = ParamAttr(sharding=(axis,))
    return layer.fc(input=input, size=size, act=act, name=name,
                    param_attr=pa, bias_attr=bias_attr, layer_attr=la)


def model_parallel_mlp(input, hidden_sizes: Sequence[int], out_size: int,
                       *, axis: str = "model", act: str = "relu",
                       out_act=None, name_prefix: str = "mp"):
    """Alternating col/row tensor-parallel MLP (megatron-style pairs).

    Hidden layers shard features over ``axis``; the final row-parallel
    projection returns a replicated [batch, out_size] output ready for a
    loss layer. With an even number of hidden layers every weight is
    sharded; no device ever holds a full replica.
    """
    net = input
    part = "col"
    for i, h in enumerate(hidden_sizes):
        net = model_parallel_fc(net, h, part=part, axis=axis, act=act,
                                name=f"{name_prefix}_fc{i}")
        part = "row" if part == "col" else "col"
    return model_parallel_fc(net, out_size, part="row", axis=axis,
                             act=out_act, name=f"{name_prefix}_out")


