"""Topology: the layer graph and its compilation to a pure jax function.

Reference analog: the ModelConfig protobuf built by config_parser.py plus the
C++ NeuralNetwork layer-graph executor (gserver/gradientmachines/
NeuralNetwork.cpp:245-295) and paddle.v2.topology.Topology
(python/paddle/v2/topology.py:33).

TPU-native design: layer functions build a DAG of ``LayerOutput`` nodes; a
``Topology`` freezes the transitive closure of requested outputs into a
topologically-ordered node list and exposes ``forward(params, state, feeds)``
— a *pure function* executed under ``jax.jit``. There is no interpreter at
runtime: the whole graph is traced once and compiled by XLA, so "layers" cost
nothing at step time (the reference pays a C++ virtual call + kernel launch
per layer; here XLA fuses across layer boundaries).

Backward pass: none is built by hand — ``jax.grad`` of ``forward`` replaces
the reference's per-layer ``backward()`` methods and Gen-2 AppendBackward
(framework/backward.cc:434).
"""

from __future__ import annotations

import contextlib
import hashlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.attr import ParamAttr
from paddle_tpu.platform.enforce import EnforceError, enforce_that
from paddle_tpu.sequence import SequenceBatch

# ---------------------------------------------------------------------------
# Graph nodes
# ---------------------------------------------------------------------------

_name_counters: Dict[str, int] = {}


def unique_name(prefix: str) -> str:
    idx = _name_counters.get(prefix, 0)
    _name_counters[prefix] = idx + 1
    return f"{prefix}_{idx}"


def reset_name_scope() -> None:
    _name_counters.clear()


# ---------------------------------------------------------------------------
# Remat (activation checkpointing) scopes
# ---------------------------------------------------------------------------

_remat_stack: List[str] = []


class remat_scope:
    """Tag every layer created inside with a remat group.

    The classic TPU memory/compute trade: nodes sharing a group are executed
    as ONE ``jax.checkpoint``-wrapped segment by ``Topology.forward``, so the
    backward pass recomputes the segment's activations from its boundary
    inputs instead of keeping them in HBM, all but the few an op has
    tagged with :func:`keep` (``KEPT``: dear to rebuild, small to hold).
    Wrapping each transformer block buys O(n_layers) activation memory for
    ~1 extra forward of FLOPs, less what is kept — the lever that lets
    the bench run bigger batch/sequence tiers.

    Reference analog: none — the reference keeps every layer's output alive
    for backward (gserver NeuralNetwork keeps per-layer Arguments); remat is
    the XLA-era replacement.

    Usage::

        with topology.remat_scope("blk0"):
            x = layer.fc(...)
    """

    def __init__(self, group: str):
        self.group = group

    def __enter__(self):
        _remat_stack.append(self.group)
        return self

    def __exit__(self, *exc):
        _remat_stack.pop()
        return False


# What a recomputed segment holds on to from its forward pass, by the name
# its maker tags it with (``keep``), in order of milliseconds saved a byte
# held.  A kernel behind ``jax.custom_vjp`` tags its residuals INSIDE the
# forward rule: a name on its output alone leaves the residuals missing and
# the kernel runs again.  Not here, because the benchmark's readers count
# their calls a step: what ``moe_gmm`` / ``moe_tgmm`` and ``flash_*`` make.
KEPT = (
    "moe_route",        # the router's scores, choice, weights and row plan
    "gdn_scan",         # the delta rule's o and the state a chunk starts from
    "gdn_proj",         # the delta layer's projections qkvz and ba
    "gdn_operands",     # the scan's q, k, v and scalars, in its own layout
)

# (one object for every group: JAX's caches key on the policy's identity)
_KEEP_POLICY = jax.checkpoint_policies.save_only_these_names(*KEPT)

# bytes tagged while the innermost remat group is being traced
_kept_bytes: List[int] = []


def keep(name: str, *values):
    """Tag ``values`` as worth keeping across a recomputed segment: under
    a ``remat_scope`` the backward pass reads them where it would build
    them again; anywhere else this is the identity and lowers to nothing.
    ``name`` is one of ``KEPT``.  Returns the values (one: itself)."""
    enforce_that(name in KEPT, f"{name!r} is not one of {KEPT}",
                 context="remat")
    if _kept_bytes:
        _kept_bytes[-1] += sum(v.size * v.dtype.itemsize for v in values)
    out = tuple(checkpoint_name(v, name) for v in values)
    return out[0] if len(out) == 1 else out


@dataclass
class ParamSpec:
    """Declared parameter of a layer node."""

    shape: Tuple[int, ...]
    attr: ParamAttr = field(default_factory=ParamAttr)
    dtype: Any = jnp.float32


@dataclass
class StateSpec:
    """Non-trainable state slot (e.g. batch-norm moving stats)."""

    shape: Tuple[int, ...]
    init_value: float = 0.0
    dtype: Any = jnp.float32


def _labels(labels: Dict[str, Any]) -> tuple:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class Context:
    """Per-forward execution context handed to each node's compute fn.

    ``mesh`` (when set) enables per-layer activation sharding constraints
    (ExtraAttr.sharding — the ParallelNeuralNetwork layer-placement
    analog, see paddle_tpu.parallel.placement)."""

    def __init__(self, train: bool, rng: Optional[jax.Array],
                 state: Dict[str, Dict[str, jax.Array]], mesh=None):
        self.train = train
        self._rng = rng
        self.state_in = state
        self.state_out: Dict[str, Dict[str, jax.Array]] = {}
        # device scalars a layer publishes beside its value: they leave a
        # compiled train step with the cost and reach the obs registry a
        # log window late (trainer.SGD), keyed (kind, name, labels)
        self.counters: Dict[tuple, jax.Array] = {}
        self._current: Optional[str] = None
        self.mesh = mesh

    def rng_for(self, node_name: str) -> jax.Array:
        if self._rng is None:
            return jax.random.PRNGKey(0)
        # stable per-node stream derived from the step key
        h = int.from_bytes(hashlib.md5(node_name.encode()).digest()[:4], "little")
        return jax.random.fold_in(self._rng, h)

    def count(self, name: str, value, **labels) -> None:
        """Add ``value`` (a device scalar) to the counter ``name`` of this
        step; the trainer adds each step's sum to the registry's counter."""
        self.publish(("counter", name, _labels(labels)), value)

    def gauge(self, name: str, value, **labels) -> None:
        """Set the gauge ``name`` for this step (the last step of a log
        window is what the registry's gauge shows)."""
        self.publish(("gauge", name, _labels(labels)), value)

    def publish(self, key: tuple, value) -> None:
        """A counter adds to what the step has under ``key``, a gauge
        replaces it."""
        value = jnp.asarray(value, jnp.float32)
        add = key[0] == "counter" and key in self.counters
        self.counters[key] = self.counters[key] + value if add else value

    def get_state(self, node_name: str, key: str) -> jax.Array:
        return self.state_in[node_name][key]

    def set_state(self, node_name: str, key: str, value: jax.Array) -> None:
        self.state_out.setdefault(node_name, {})[key] = value


@dataclass
class LayerOutput:
    """A node in the layer graph; also the user-facing handle (v2 LayerOutput
    analog, python/paddle/v2/layer.py)."""

    name: str
    layer_type: str
    inputs: List["LayerOutput"]
    # fn(ctx, params: dict, inputs: list of values) -> value
    fn: Callable[[Context, Dict[str, jax.Array], List[Any]], Any]
    params: Dict[str, ParamSpec] = field(default_factory=dict)
    state: Dict[str, StateSpec] = field(default_factory=dict)
    # State slots this node manages under OTHER namespaces (sub-layer names
    # of a hosted step graph). Keyed namespace -> slot -> spec. Lets a
    # training recurrent_group and a beam_search generator built from the
    # same step SHARE stateful slots (batch-norm moving stats) the same way
    # pinned param names share weights.
    foreign_state: Dict[str, Dict[str, StateSpec]] = field(default_factory=dict)
    size: Optional[int] = None          # feature dimension, v2-API compatible
    is_sequence: bool = False           # value is a SequenceBatch
    is_cost: bool = False               # per-example loss output
    remat_group: Optional[str] = None   # set by the enclosing remat_scope

    def __post_init__(self):
        enforce_that(self.name is not None, "layer needs a name")
        if self.remat_group is None and _remat_stack and self.fn is not None:
            self.remat_group = _remat_stack[-1]

    # Graph sugar: l1 + l2 = addto
    def __add__(self, other: "LayerOutput") -> "LayerOutput":
        from paddle_tpu import layer as L

        return L.addto(input=[self, other])

    def __repr__(self):
        return f"LayerOutput({self.name!r}, type={self.layer_type!r}, size={self.size})"


@contextlib.contextmanager
def node_scope(node: LayerOutput):
    """The scope a node computes under: its kind outside its name, so that
    an ``op_name`` of the compiled step reads ``.../jvp(layer_norm)/jvp(
    blk0_ln1)/...`` and a reader of a profiler trace can add up a kind
    (``benchmarks/harness/step_parts.py``) as well as find an instance (the
    REGISTER_TIMER-per-layer analog, NeuralNetwork.cpp:259).  No kind is
    named as one of the scopes the layers open inside themselves
    (``gdn.*``, ``moe.*``, ``attn.*``, ...; tests/test_step_scopes.py)."""
    with jax.named_scope(node.layer_type), jax.named_scope(node.name):
        yield


def topological_order(outputs: Sequence[LayerOutput]) -> List[LayerOutput]:
    seen: Dict[str, LayerOutput] = {}
    order: List[LayerOutput] = []

    def visit(node: LayerOutput, stack: Tuple[int, ...]):
        if node.name in seen:
            enforce_that(seen[node.name] is node,
                         f"two different layers named {node.name!r}", context="topology")
            return
        if id(node) in stack:
            raise EnforceError(f"cycle through layer {node.name!r}", context="topology")
        for inp in node.inputs:
            visit(inp, stack + (id(node),))
        # a transitively-visited input may have claimed this name already
        enforce_that(seen.get(node.name, node) is node,
                     f"two different layers named {node.name!r}", context="topology")
        seen[node.name] = node
        order.append(node)

    for out in outputs:
        visit(out, ())
    return order


# ---------------------------------------------------------------------------
# Topology
# ---------------------------------------------------------------------------


class Topology:
    """Frozen graph over the transitive closure of ``outputs``.

    ``forward`` is pure: (params, state, feeds, train, rng) -> (outputs, new_state).
    """

    def __init__(self, outputs: Union[LayerOutput, Sequence[LayerOutput]]):
        if isinstance(outputs, LayerOutput):
            outputs = [outputs]
        self.outputs: List[LayerOutput] = list(outputs)
        self.nodes: List[LayerOutput] = topological_order(self.outputs)
        self.by_name: Dict[str, LayerOutput] = {n.name: n for n in self.nodes}
        self.data_nodes: List[LayerOutput] = sorted(
            (n for n in self.nodes if n.layer_type == "data"),
            key=lambda n: getattr(n, "declare_idx", 0))

    # ---- specs -----------------------------------------------------------

    def param_specs(self) -> Dict[str, ParamSpec]:
        """Flat parameter table: '<layer>.<param>' -> spec. Explicit
        ParamAttr.name aliases share storage (the reference's parameter
        sharing via param names)."""
        specs: Dict[str, ParamSpec] = {}
        for node in self.nodes:
            for pname, spec in node.params.items():
                full = spec.attr.name or f"{node.name}.{pname}"
                if full in specs:
                    enforce_that(tuple(specs[full].shape) == tuple(spec.shape),
                                 f"shared parameter {full!r} shape mismatch "
                                 f"{specs[full].shape} vs {spec.shape}", context="topology")
                else:
                    specs[full] = spec
        return specs

    def param_key(self, node: LayerOutput, pname: str) -> str:
        spec = node.params[pname]
        return spec.attr.name or f"{node.name}.{pname}"

    def state_specs(self) -> Dict[str, Dict[str, StateSpec]]:
        out: Dict[str, Dict[str, StateSpec]] = {}
        for n in self.nodes:
            if n.state:
                out.setdefault(n.name, {}).update(n.state)
            for ns, slots in n.foreign_state.items():
                have = out.setdefault(ns, {})
                for k, spec in slots.items():
                    if k in have:
                        enforce_that(
                            tuple(have[k].shape) == tuple(spec.shape),
                            f"shared state slot {ns}/{k} shape mismatch "
                            f"{have[k].shape} vs {spec.shape}", context="topology")
                    else:
                        have[k] = spec
        return out

    def init_state(self) -> Dict[str, Dict[str, jax.Array]]:
        out: Dict[str, Dict[str, jax.Array]] = {}
        for lname, slots in self.state_specs().items():
            out[lname] = {
                k: jnp.full(s.shape, s.init_value, dtype=s.dtype) for k, s in slots.items()
            }
        return out

    # ---- execution -------------------------------------------------------

    def forward(self, params: Dict[str, jax.Array],
                state: Dict[str, Dict[str, jax.Array]],
                feeds: Dict[str, Any], *, train: bool = False,
                rng: Optional[jax.Array] = None,
                outputs: Optional[Sequence[LayerOutput]] = None,
                mesh=None, counters: Optional[Dict[tuple, jax.Array]] = None
                ) -> Tuple[List[Any], Dict[str, Dict[str, jax.Array]]]:
        """``counters``, where given, is filled with what the layers
        published through ``Context.count`` / ``Context.gauge``."""
        wanted = list(outputs) if outputs is not None else self.outputs
        ctx = Context(train=train, rng=rng, state=state, mesh=mesh)
        values: Dict[str, Any] = {}
        order = topological_order(wanted)
        done_groups: set = set()
        # feeds first: a remat group may read a data layer (positions,
        # say) that the order reaches only through one of its own nodes
        values.update((n.name, feeds[n.name]) for n in order
                      if n.fn is None and n.name in feeds)
        for node in order:
            if node.fn is None:  # data layers and frame/memory placeholders
                if node.name not in feeds:
                    raise EnforceError(f"missing feed for data layer {node.name!r}",
                                       context="forward")
                values[node.name] = feeds[node.name]
                continue
            if node.remat_group is not None:
                if node.remat_group not in done_groups:
                    done_groups.add(node.remat_group)
                    self._run_remat_group(node.remat_group, order, values,
                                          params, ctx,
                                          {w.name for w in wanted})
                continue
            node_params = {p: params[self.param_key(node, p)] for p in node.params}
            ins = [values[i.name] for i in node.inputs]
            ctx._current = node.name
            try:
                with node_scope(node):
                    values[node.name] = node.fn(ctx, node_params, ins)
            except Exception as e:
                # the CustomStackTrace analog (utils/CustomStackTrace.h,
                # pushed per layer NeuralNetwork.cpp:260-262): name the
                # failing layer so shape/dtype errors point at the config
                e.add_note(
                    f"[paddle_tpu] while computing layer {node.name!r} "
                    f"(type={node.layer_type}, "
                    f"inputs={[i.name for i in node.inputs]})")
                raise
        new_state = dict(state)
        for ns, slots in ctx.state_out.items():
            # per-slot merge: a node updating one slot must not drop the
            # namespace's other slots
            new_state[ns] = {**new_state.get(ns, {}), **slots}
        if counters is not None:
            counters.update(ctx.counters)
        return [values[w.name] for w in wanted], new_state

    def _run_remat_group(self, group: str, order: List[LayerOutput],
                         values: Dict[str, Any],
                         params: Dict[str, jax.Array], ctx: Context,
                         wanted_names: set) -> None:
        """Execute one remat group as a single jax.checkpoint segment.

        The segment is a pure function of (its params, the step rng, its
        boundary inputs) -> (boundary outputs, state updates); XLA drops
        the segment's internal activations after forward and recomputes
        them during backward, all but those tagged with a name of ``KEPT``
        (``keep``): a segment that holds none compiles as it would with no
        policy.  What a differentiated segment keeps is published as the
        gauge ``remat_kept_bytes{group}`` (the tagged shapes' sum, fixed
        at trace time).
        """
        nodes = [n for n in order if n.remat_group == group]
        in_group = {n.name for n in nodes}
        ext_in: List[str] = []
        for n in nodes:
            for i in n.inputs:
                if i.name not in in_group and i.name not in ext_in:
                    ext_in.append(i.name)
                    enforce_that(
                        i.name in values,
                        f"remat group {group!r} input {i.name!r} is not "
                        f"available yet — the group is not a contiguous "
                        f"segment of the graph", context="remat")
        consumed_outside = set(wanted_names)
        for n in order:
            if n.remat_group != group:
                consumed_outside.update(i.name for i in n.inputs)
        ext_out = [n.name for n in nodes if n.name in consumed_outside]
        enforce_that(ext_out,
                     f"remat group {group!r} has no outputs used outside it",
                     context="remat")
        pkeys = sorted({self.param_key(n, p) for n in nodes for p in n.params})
        # rng=None must stay None inside the segment so per-node streams
        # derive exactly as in the un-rematted graph (rng_for's fallback)
        has_rng = ctx._rng is not None
        rng_arg = ctx._rng if has_rng else jax.random.PRNGKey(0)

        def segment(seg_params, seg_rng, ext_vals):
            local = dict(zip(ext_in, ext_vals))
            sub = Context(train=ctx.train, rng=seg_rng if has_rng else None,
                          state=ctx.state_in, mesh=ctx.mesh)
            for n in nodes:
                node_params = {p: seg_params[self.param_key(n, p)]
                               for p in n.params}
                ins = [local[i.name] for i in n.inputs]
                sub._current = n.name
                try:
                    with node_scope(n):
                        local[n.name] = n.fn(sub, node_params, ins)
                except Exception as e:
                    e.add_note(
                        f"[paddle_tpu] while computing layer {n.name!r} "
                        f"(type={n.layer_type}, remat group {group!r}, "
                        f"inputs={[i.name for i in n.inputs]})")
                    raise
            return [local[nm] for nm in ext_out], sub.state_out, sub.counters

        _kept_bytes.append(0)
        try:
            with jax.named_scope(f"remat_{group}"):
                outs, state_out, counted = jax.checkpoint(
                    segment, policy=_KEEP_POLICY)(
                    {k: params[k] for k in pkeys}, rng_arg,
                    [values[nm] for nm in ext_in])
        finally:
            kept = _kept_bytes.pop()
        for nm, v in zip(ext_out, outs):
            values[nm] = v
        for ns, slots in state_out.items():
            ctx.state_out.setdefault(ns, {}).update(slots)
        for key, v in counted.items():
            ctx.publish(key, v)
        if kept:
            ctx.gauge("remat_kept_bytes", kept, group=group)

    def __repr__(self):
        return f"Topology({len(self.nodes)} nodes, outputs={[o.name for o in self.outputs]})"
