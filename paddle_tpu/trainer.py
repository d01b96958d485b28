"""SGD trainer with events — the paddle.v2.trainer analog.

Reference: python/paddle/v2/trainer.py:124-202 (SGD.train event loop over a
reader), paddle/trainer/TrainerInternal.cpp:66-158 (per-batch
forwardBackward + update + stats), Tester.cpp.

TPU-native: one jitted ``train_step`` fuses forward+backward+optimizer into a
single XLA program (the reference pays a python→SWIG→C++ transition and one
kernel launch per layer per batch; here the whole step is one device
execution with buffer donation). Gradients come from ``jax.grad`` — there is
no hand-written backward graph. Data parallelism: pass ``mesh=`` and dense
feeds are sharded over the 'data' axis; XLA inserts the psum (the
MultiGradientMachine ring / pserver addGradient analog).
"""

from __future__ import annotations

import functools
import itertools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import event as v2_event
from paddle_tpu.analysis.retrace import SiteContract, audit_jit
from paddle_tpu.obs.registry import default_registry
from paddle_tpu.data_feeder import DataFeeder
from paddle_tpu.optimizer import Optimizer
from paddle_tpu.parameters import Parameters
from paddle_tpu.platform import plog, stats
from paddle_tpu.platform.enforce import EnforceError, enforce_that
from paddle_tpu.platform.flags import FLAGS
from paddle_tpu.sequence import SequenceBatch
from paddle_tpu.topology import LayerOutput, Topology


def _reduce_cost(value) -> jax.Array:
    """Total cost over the batch / num examples (reference divides summed cost
    by batch size, TrainerInternal.cpp trainOneBatch)."""
    if isinstance(value, SequenceBatch):
        total = jnp.sum(jnp.where(value.valid_mask, value.data.reshape(value.capacity, -1).sum(-1)
                                  if value.data.ndim > 1 else value.data, 0.0))
        return total / jnp.maximum(value.num_seqs, 1)
    return jnp.mean(value)


def _metric_scalar(value) -> jax.Array:
    """Mean of a metric layer's output over valid examples/tokens."""
    if isinstance(value, SequenceBatch):
        d = value.data.reshape(value.capacity, -1).sum(-1) if value.data.ndim > 1 else value.data
        total = jnp.sum(jnp.where(value.valid_mask, d, 0.0))
        count = jnp.sum(value.valid_mask)
        return total / jnp.maximum(count, 1)
    return jnp.mean(value)


class SGD:
    """v2-compatible trainer: SGD(cost, parameters, update_equation).train(...).

    ``extra_layers`` are metric LayerOutputs (the evaluator analog — see
    paddle_tpu.evaluator), kept by name in ``self.metrics``; they are
    computed in-graph per batch and averaged across the pass for EndPass
    events.

    ``zero`` (0 or None: replicated optimizer state; 1: ZeRO-1, arXiv
    2004.13336) reduce-scatters gradients, updates a 1/N optimizer-state
    shard a replica over the mesh's 'data' axis and all-gathers the
    updated weights.  ``guard`` (a ``resilience.BadStepGuard``; None: the
    classic unguarded step) fuses a global-norm + finiteness check over
    the gradients into the jitted step and skips bad steps in-graph.
    """

    def __init__(self, cost, parameters: Parameters, update_equation: Optimizer,
                 extra_layers: Optional[Sequence[LayerOutput]] = None,
                 mesh=None,
                 zero_axis: Optional[str] = None,
                 zero: Optional[int] = 0,
                 pipeline=None,
                 faults=None, guard=None, tracer=None):
        costs = [cost] if isinstance(cost, LayerOutput) else list(cost)
        # evaluator nodes, by name
        self.metrics = {n.name: n for n in (extra_layers or [])}
        outputs = costs + list(self.metrics.values())
        self.topology = Topology(outputs)
        self._n_costs = len(costs)
        self.parameters = parameters
        self.optimizer = update_equation
        self.optimizer.set_param_specs(self.topology.param_specs())
        self.model_state = self.topology.init_state()
        # pipeline-parallel training (pipeline=PipelineConfig): repack the
        # transformer body into stacked [L, ...] stage weights, build (or
        # validate) a (data, stage) mesh, and swap the compiled step for
        # the GPipe fill+drain schedule (parallel/pipeline.py). Placement
        # composes through ONE plan: stage weights shard their stacked
        # layer dim over 'stage' (placement.pipeline_param_attrs), and the
        # replicated remainder (embeddings, head) still ZeRO-shards its
        # optimizer state over 'data' when zero=1.
        self._pipeline = None
        self._pipe_specs: Dict[str, Any] = {}
        if pipeline is not None:
            mesh = self._setup_pipeline(pipeline, mesh)
        self.mesh = mesh
        self._zero_axis = zero_axis
        # commit params to their declared shardings (ParamAttr.sharding;
        # replicated by default, ZeRO-style largest-dim sharding with
        # zero_axis=) BEFORE optimizer slots are created: zeros_like slots
        # then inherit the committed shardings, so no device ever
        # materializes a full slot replica of a sharded weight
        self._place_on_mesh(slots_too=False)
        # ZeRO-1 (zero=1): shard optimizer state 1/N over the 'data'
        # axis while params stay replicated —
        # the plan threads through init_state so slots are sharded from
        # step 0, and through apply for the per-step reduce-scatter /
        # all-gather pair (parallel/zero.py)
        self._zero_plan = None
        stage = int(zero or 0)
        if stage:
            enforce_that(stage == 1, f"zero={stage} not implemented "
                         "(0 = off, 1 = optimizer-state sharding)",
                         context="trainer")
            # a zero= request that cannot take effect is an error
            # (silently training replicated would fake the N x memory
            # claim)
            enforce_that(mesh is not None and "data" in mesh.axis_names,
                         "zero=1 needs mesh= with a 'data' axis (got "
                         + ("no mesh" if mesh is None else
                            f"axes {tuple(mesh.axis_names)}") + ")",
                         context="trainer")
            from paddle_tpu.parallel.zero import build_zero_plan

            # merged specs: pipeline stage weights carry explicit
            # stage sharding and are therefore EXCLUDED from ZeRO —
            # "the ZeRO-sharded remainder" resolves through the same
            # placement plan as everything else
            self._zero_plan = build_zero_plan(
                mesh, parameters.as_dict(),
                specs=self._param_specs(),
                zero_axis=self._zero_axis)
        # unconditional (including None): a reused optimizer instance must
        # not carry a previous trainer's plan into this one
        self.optimizer.set_zero_plan(self._zero_plan)
        self.opt_state = self._replicate_unplaced(
            self.optimizer.init_state(parameters.as_dict()))
        self.model_state = self._replicate_unplaced(self.model_state)
        self._rng = jax.random.PRNGKey(FLAGS.seed or 0)
        self._step_fn = None
        self._test_fn = None
        # fault-tolerant runtime (paddle_tpu.resilience): a seedable
        # TrainFaultPlan drives injected deaths/NaNs/slow steps, the
        # BadStepGuard fuses the skip-or-rollback policy into the jitted
        # step, and the tracer puts guard/checkpoint edges on the obs
        # timeline.  guard=None is the unguarded step (its signature, and
        # every compiled program without a guard, is the classic one).
        self._faults = faults
        if faults is not None and faults.injects_grads():
            enforce_that(guard is not None,
                         "TrainFaultPlan injects non-finite gradients "
                         "but no bad-step guard is set — pass "
                         "SGD(guard=BadStepGuard()) so the poison "
                         "is screened instead of corrupting optimizer "
                         "slots", context="trainer")
        self._guard = guard
        if tracer is None:
            from paddle_tpu.obs.trace import NULL_TRACER

            tracer = NULL_TRACER
        self._tracer = tracer
        self._global_step = 0
        self._bad_steps_seen = 0   # per-train()-call device-counter mark
        self.bad_steps_total = 0   # lifetime skipped-step count
        self._async_ckpt = None

    # ------------------------------------------------------------------
    # pipeline parallelism (4D composition: stage x data/zero [x model])
    # ------------------------------------------------------------------

    def _param_specs(self):
        """Topology specs merged with the pipeline placement plan — the
        ONE spec dict both ``param_sharding`` and ``build_zero_plan``
        consume, so stacked stage weights (leading-dim 'stage'), stacked
        expert weights, TP-sharded weights and the ZeRO-sharded
        remainder all resolve through the same placement layer
        (parallel/placement.py)."""
        specs = dict(self.topology.param_specs())
        specs.update(self._pipe_specs)
        return specs

    def _setup_pipeline(self, cfg, mesh):
        """Resolve the pipeline geometry, build/validate the (data,
        stage) mesh, and repack the transformer body ``blk{i}_*`` params
        into stacked ``pipe_body.*`` [L, ...] stage weights.

        The stacked layout is LAYOUT-INDEPENDENT: checkpoints carry the
        full [L, ...] stack (gather-on-save), which reloads into any
        stage count dividing L (scatter-on-load happens in
        ``_place_on_mesh``) — the cross-layout resume contract."""
        import re

        from paddle_tpu.parallel import placement
        from paddle_tpu.parallel.pipeline import PipelineConfig

        enforce_that(isinstance(cfg, PipelineConfig),
                     "pipeline= takes a parallel.PipelineConfig, got "
                     f"{type(cfg).__name__}", context="trainer")
        enforce_that(not self.metrics and self._n_costs == 1,
                     "pipeline= supports a single cost and no metric "
                     "layers (the loss rides the last-stage boundary "
                     "hook, not topology.forward)", context="trainer")
        axis = str(cfg.axis)
        pat = re.compile(r"^blk(\d+)_(.+)$")
        groups: Dict[str, Dict[int, str]] = {}
        for name in self.parameters.names():
            mt = pat.match(name)
            if mt:
                groups.setdefault(mt.group(2), {})[int(mt.group(1))] = name
        enforce_that(bool(groups),
                     "pipeline= found no blk{i}_* body parameters — the "
                     "pipeline trainer partitions the model-zoo "
                     "transformer naming convention "
                     "(models/transformer.build)", context="trainer")
        n_layers = int(cfg.n_layers) or (
            max(i for d in groups.values() for i in d) + 1)
        for suffix, d in groups.items():
            enforce_that(sorted(d) == list(range(n_layers)),
                         f"blk*_{suffix} layer ids {sorted(d)} do not "
                         f"cover 0..{n_layers - 1}", context="trainer")
        # stage count: config > the mesh's stage axis > all devices
        s = int(cfg.num_stages)
        if not s:
            s = (int(mesh.shape[axis])
                 if mesh is not None and axis in mesh.axis_names
                 else jax.device_count())
        m = int(cfg.microbatches)
        enforce_that(m >= 1, f"microbatches={m} must be >= 1",
                     context="trainer")
        enforce_that(n_layers % s == 0,
                     f"n_layers={n_layers} does not divide into "
                     f"num_stages={s}", context="trainer")
        if mesh is None:
            from paddle_tpu.parallel.mesh import make_mesh

            ndev = jax.device_count()
            enforce_that(ndev % s == 0,
                         f"{ndev} devices do not divide into "
                         f"num_stages={s}", context="trainer")
            # the (data, stage) mesh: 'data' is the ZeRO/optimizer-state
            # sharding domain (feeds stay replicated — SequenceBatch)
            mesh = make_mesh((ndev // s, s), ("data", axis))
        enforce_that(axis in mesh.axis_names
                     and int(mesh.shape[axis]) == s,
                     f"mesh axes {dict(mesh.shape)} lack {axis!r}={s}",
                     context="trainer")
        # repack blk{i}_<suffix> -> pipe_body.<suffix> [L, ...] stacks;
        # their placement plan shards the stacked layer dim over 'stage'
        stacked = {}
        for suffix, d in sorted(groups.items()):
            vals = [self.parameters.pop(d[i]) for i in range(n_layers)]
            stacked[f"pipe_body.{suffix}"] = jnp.stack(vals)
        for k, v in stacked.items():
            self.parameters[k] = v
        self._pipe_specs = placement.pipeline_param_attrs(stacked, axis=axis)
        self._pipeline = cfg
        self._pipe_axis = axis
        self._pipe_stages = s
        self._pipe_m = m
        self._pipe_layers = n_layers
        self._pipe_heads = int(cfg.n_heads)
        self._pipe_remat = bool(cfg.remat)
        return mesh

    def _pipeline_forward_backward(self):
        """The pipeline replacement for the topology forward/backward:
        pad the packed feeds, split them into M microbatches, and run
        the GPipe fill+drain schedule (parallel.pipeline.pipeline_apply)
        with the embed as the first-stage hook and final-LN + vocab head
        + xent as the last-stage hook.  ``jax.grad`` differentiates
        through scan + ppermute, so the backward schedule is free.

        Loss semantics match ``_reduce_cost`` on a SequenceBatch cost
        exactly: each microbatch emits the SUM of its valid-token
        cross-entropies and the step divides by the global sequence
        count (per-SEQUENCE mean) — the loss-trajectory parity pin.
        With causal attention, trailing pad positions cannot leak into
        valid positions, so parity holds for ragged batches too."""
        from paddle_tpu.models import transformer as _tf
        from paddle_tpu.ops.losses import softmax_cross_entropy
        from paddle_tpu.parallel.pipeline import pipeline_apply

        mesh = self.mesh
        axis = self._pipe_axis
        s, m = self._pipe_stages, self._pipe_m
        n_heads = self._pipe_heads
        per_stage = self._pipe_layers // s
        remat = self._pipe_remat

        def stage_fn(stk, x):
            # stk: this stage's [L/S, ...] stacks — scan its blocks;
            # vmap the per-sequence block over the microbatch rows
            def one_block(h, blk):
                h = jax.vmap(
                    lambda seq: _tf.block_apply(blk, seq, n_heads=n_heads))(h)
                return h, None

            h, _ = jax.lax.scan(one_block, x, stk)
            return h

        def first_fn(fp, mb):
            return (fp["tok_embed.w"][mb["tokens"]]
                    + fp["pos_embed.w"][mb["pos"]])

        def last_fn(lp, y, mb):
            h = _tf._ln(y, lp["final_ln.gamma"], lp["final_ln.beta"])
            logits = h @ lp["lm_head.w0"] + lp["lm_head.b"]
            xe = softmax_cross_entropy(logits, mb["target"])
            return jnp.sum(jnp.where(mb["mask"], xe, 0.0))

        def microbatch_split(feeds):
            tok, mask = feeds["tokens"].to_padded()
            pos, _ = feeds["pos"].to_padded()
            tgt, _ = feeds["target"].to_padded()
            b = int(tok.shape[0])
            enforce_that(b % m == 0,
                         f"batch of {b} sequences does not divide into "
                         f"microbatches={m}", context="trainer")

            def split(a):
                return a.reshape((m, b // m) + a.shape[1:])

            return {"tokens": split(tok), "pos": split(pos),
                    "target": split(tgt), "mask": split(mask)}, b

        def forward_backward(params, model_state, rng, feeds):
            mbs, b = microbatch_split(feeds)

            def loss_fn(p):
                body = {k[len("pipe_body."):]: v for k, v in p.items()
                        if k.startswith("pipe_body.")}
                # [L, ...] -> [S, L/S, ...]: a leading-dim split, so the
                # stage sharding carries over without resharding comm
                stk = {k: v.reshape((s, per_stage) + v.shape[1:])
                       for k, v in body.items()}
                first_p = {k: p[k] for k in ("tok_embed.w", "pos_embed.w")}
                last_p = {k: p[k] for k in ("final_ln.gamma",
                                            "final_ln.beta",
                                            "lm_head.w0", "lm_head.b")}
                sums = pipeline_apply(mesh, stage_fn, stk, mbs, axis=axis,
                                      first_fn=first_fn, first_params=first_p,
                                      last_fn=last_fn, last_params=last_p,
                                      remat=remat)
                return jnp.sum(sums) / float(b), (model_state, {})

            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        return forward_backward

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------

    def _build_step(self):
        topo = self.topology
        optimizer = self.optimizer
        n_costs = self._n_costs
        metric_names = list(self.metrics.keys())
        mesh = self.mesh

        # grad stats ride in the same compiled step (TrainerInternal.cpp:
        # 80-110 computes avgAbsGrad/maxAbsGrad in the update callback).
        # captured once at build time: the compiled step and the logging
        # cadence must agree even if the flag changes later
        self._stats_period = int(FLAGS.show_parameter_stats_period or 0)
        stats_on = self._stats_period > 0
        guard = self._guard

        def forward_backward(params, model_state, rng, feeds):
            def loss_fn(p):
                counted: Dict[tuple, jax.Array] = {}
                outs, new_state = topo.forward(p, model_state, feeds,
                                               train=True, rng=rng, mesh=mesh,
                                               counters=counted)
                with jax.named_scope("step.loss"):
                    cost_vals = [_reduce_cost(o) for o in outs[:n_costs]]
                    total = functools.reduce(jnp.add, cost_vals)
                    metric_vals = {name: _metric_scalar(o) for name, o in
                                   zip(metric_names, outs[n_costs:])}
                    if counted:
                        # the layers' counters leave the step as ONE
                        # vector beside the cost (its keys are fixed at
                        # trace time) and are read with the costs, a log
                        # window late
                        self._counter_keys = sorted(counted)
                        metric_vals["__counters__"] = jnp.stack(
                            [counted[k] for k in self._counter_keys])
                return total, (new_state, metric_vals)

            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        if self._pipeline is not None:
            # same step/guard/stats wrapper, different forward/backward:
            # the GPipe schedule replaces topology.forward wholesale
            forward_backward = self._pipeline_forward_backward()

        def grad_stats(metric_vals, grads):
            if not stats_on:
                return metric_vals
            metric_vals = dict(metric_vals)
            with jax.named_scope("step.stats"):
                metric_vals["__param_stats__"] = {
                    k: (jnp.mean(jnp.abs(g)), jnp.max(jnp.abs(g)))
                    for k, g in grads.items()}
            return metric_vals

        def step(params, opt_state, model_state, rng, feeds):
            (loss, (new_mstate, metric_vals)), grads = forward_backward(
                params, model_state, rng, feeds)
            new_params, new_opt = optimizer.apply(params, grads, opt_state)
            return (loss, new_params, new_opt, new_mstate,
                    grad_stats(metric_vals, grads))

        def guarded_step(params, opt_state, model_state, rng, feeds,
                         guard_state):
            # bad-step guard (paddle_tpu.resilience.guard): screen the
            # gradients with ONE fused f32 sq-norm reduction (also the
            # fault plan's poison seam — `inject` is 0.0 outside
            # injection windows), run the usual update, and select every
            # params/slot/model-state leaf back to its old value when
            # the step is bad.  The counters stay on device; the host
            # reads them on the same lazy cadence as .cost — no new
            # per-step sync, no extra compile (the inject scalar is a
            # same-shape argument, not a trace constant).
            from paddle_tpu.resilience.guard import (guard_outputs,
                                                     screen_grads,
                                                     select_good)

            (loss, (new_mstate, metric_vals)), grads = forward_backward(
                params, model_state, rng, feeds)
            with jax.named_scope("step.guard"):
                grads, good, _ = screen_grads(grads, guard_state["inject"],
                                              guard.max_norm)
            new_params, new_opt = optimizer.apply(params, grads, opt_state)
            with jax.named_scope("step.guard"):
                new_params = select_good(good, new_params, params)
                new_opt = select_good(good, new_opt, opt_state)
                new_mstate = select_good(good, new_mstate, model_state)
            metric_vals = grad_stats(metric_vals, grads)
            with jax.named_scope("step.guard"):
                gout = guard_outputs(good, guard_state)
            return loss, new_params, new_opt, new_mstate, metric_vals, gout

        # With mesh-sharded (NamedSharding) inputs, jit partitions the whole
        # step SPMD automatically — XLA inserts the grad psum (the
        # MultiGradientMachine ring / pserver addGradient analog).
        return audit_jit(guarded_step if guard is not None else step,
                         site="trainer.train_step",
                         donate_argnums=(0, 1, 2),
                         xla_contract=self._step_contract())

    def _step_contract(self, donate=(0, 1, 2),
                       test: bool = False) -> SiteContract:
        """Compiled-path contract for the train/test steps, checked by
        the jaxpr auditor: params/opt-state/model-state must actually
        ride the requested donation (verified from the REQUESTED jit
        kwargs, so CPU tier-1 runs still check the TPU contract);
        collectives are the point of a sharded step (grad psum, ZeRO
        reduce-scatter/all-gather); bf16 operands deliberately reduce
        losses/norm statistics in f32 (the repo's precision model, see
        MIGRATION "The bf16 precision model").  The peak-bytes budget
        is a guardrail — activations scale with the batch, which the
        trainer cannot see at build time, so the budget is a generous
        multiple of the weights plus fixed slack, catching only
        duplicated-state-sized regressions.

        Sharding contract (the `analysis sharding` gate): on a mesh,
        feeds shard their batch dim over ``data`` (matching
        ``_shard_feeds``), params/model-state/rng replicate, and under
        ZeRO the flat optimizer slots arrive 1/N-sharded —
        ``expect_sharded`` pins that the plan actually reached them.
        The comm budget covers the worst of the two layouts: a full
        replicated-DP gradient psum (2x param bytes over the ring) or
        ZeRO's reduce-scatter + all-gather pair, with fixed slack for
        the loss/metric scalar reductions."""
        param_bytes = 0
        for v in self.parameters.as_dict().values():
            if hasattr(v, "shape") and hasattr(v, "dtype"):
                n = int(np.prod(v.shape)) if v.shape else 1
                param_bytes += n * jnp.dtype(v.dtype).itemsize
        mesh = self.mesh
        mesh_axes: tuple = ()
        in_specs = None
        expect: tuple = ()
        if mesh is not None:
            mesh_axes = tuple(
                (str(a), int(s))
                for a, s in zip(mesh.axis_names, mesh.devices.shape))
            # pipeline feeds are SequenceBatches (replicated); otherwise
            # dense feeds shard their batch dim over 'data'
            feed = (("data",) if "data" in mesh.axis_names
                    and self._pipeline is None else ())
            plan = getattr(self, "_zero_plan", None)
            opt = (plan.axis,) if plan is not None else ()
            if test:
                in_specs = ((), (), feed)        # params, mstate, feeds
            else:
                # params, opt_state, model_state, rng, feeds
                # (+ the replicated guard-state scalars when guarded)
                in_specs = ((), opt, (), (), feed)
                if self._guard is not None:
                    in_specs = in_specs + ((),)
                if plan is not None:
                    expect = (1,)
        # Under pipeline the step's comm scales with ticks x activation
        # bytes — batch-shaped, invisible at build time — so the
        # trainer-level budget stays unset (INFO); the inner
        # parallel.pipeline site carries the EXACT closed-form budget.
        comm = (None if self._pipeline is not None
                else 6.0 * param_bytes + (1 << 20))
        return SiteContract(
            donate=tuple(donate), allow_collectives=True,
            allow_upcast=("bfloat16",),
            peak_bytes=16 * param_bytes + (1 << 28),
            in_specs=in_specs, mesh_axes=mesh_axes,
            expect_sharded=expect,
            comm_bytes=comm)

    def _build_test(self):
        enforce_that(self._pipeline is None,
                     "test() is not supported under pipeline= (the "
                     "repacked body has no topology.forward view) — "
                     "evaluate with a sequential trainer sharing the "
                     "checkpoint", context="trainer")
        topo = self.topology
        n_costs = self._n_costs
        metric_names = list(self.metrics.keys())
        mesh = self.mesh

        def test_step(params, model_state, feeds):
            outs, _ = topo.forward(params, model_state, feeds, train=False,
                                   mesh=mesh)
            cost_vals = [_reduce_cost(o) for o in outs[:n_costs]]
            total = functools.reduce(jnp.add, cost_vals)
            metric_vals = {name: _metric_scalar(o) for name, o in
                           zip(metric_names, outs[n_costs:])}
            return total, metric_vals

        return audit_jit(test_step, site="trainer.test_step",
                         xla_contract=self._step_contract(donate=(),
                                                          test=True))

    def _place_on_mesh(self, slots_too: bool = True) -> None:
        """(Re)commit params — and optimizer state mirroring them — to
        their mesh shardings. Called at init and after ANY checkpoint
        load: load_checkpoint hands back host arrays, and without
        re-placement a resume would replicate 'too big to replicate'
        weights on every device."""
        if self.mesh is None:
            return
        from paddle_tpu.parallel.api import param_sharding

        shardings = param_sharding(self.mesh, self.parameters.as_dict(),
                                   specs=self._param_specs(),
                                   zero_axis=self._zero_axis)
        self.parameters.update_from(
            {k: _put_global(v, shardings[k])
             for k, v in self.parameters.as_dict().items()})
        if not slots_too or not isinstance(self.opt_state, dict):
            return
        plan = getattr(self, "_zero_plan", None)
        if plan is not None:
            # ZeRO: planned params' slots (and avg/prune masks) live as
            # flat 1/N shards; checkpoint loads hand back full-shape host
            # arrays, which shard_state flattens/pads/places. Passthrough
            # params fall to the declared shardings below.
            self.opt_state = plan.shard_state(self.opt_state)

        def _slot_put(k, v):
            if plan is not None and plan.is_sharded(k):
                return v  # already placed by shard_state
            return _put_global(v, shardings[k]) if k in shardings else v

        new_state = dict(self.opt_state)
        for key in ("slots",):
            if key in new_state:
                new_state[key] = {
                    s: {k: _slot_put(k, v) for k, v in d.items()}
                    for s, d in new_state[key].items()}
        for key in ("avg", "prune_masks"):
            if key in new_state:
                new_state[key] = {
                    k: _slot_put(k, v) for k, v in new_state[key].items()}
        self.opt_state = self._replicate_unplaced(new_state)
        self.model_state = self._replicate_unplaced(self.model_state)

    def _replicate_unplaced(self, tree):
        """Commit the leaves no placement plan covers (the step counter,
        averaging counts, model state) replicated on the mesh.  Left
        unplaced they come back from the first step typed on the mesh,
        the second step's inputs differ from the first's, and the whole
        train step compiles twice."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self.mesh, P())

        def place(x):
            sh = getattr(x, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh == self.mesh:
                return x
            return _put_global(x, repl)

        return jax.tree.map(place, tree)

    def _shard_feeds(self, feeds):
        if self.mesh is None:
            return feeds
        from jax.sharding import NamedSharding, PartitionSpec as P

        # batch shards ONLY over the 'data' axis; on a model-parallel-only
        # mesh feeds replicate (sharding the batch over 'model' would both
        # break on non-divisible trailing batches and force a per-step
        # all-gather against the stage constraints)
        axis = "data" if "data" in self.mesh.axis_names else None
        nproc = jax.process_count()
        out = {}
        for k, v in feeds.items():
            if isinstance(v, SequenceBatch):
                out[k] = v  # ragged feeds stay replicated (see parallel/)
            elif axis is None:
                out[k] = _put_global(v, NamedSharding(self.mesh, P()))
            elif nproc > 1:
                # multi-host DP: each process feeds its LOCAL rows; the
                # global batch is the concatenation over processes (every
                # process must feed the same local batch size — the
                # reference's fixed num_gradient_servers contract)
                sh = NamedSharding(self.mesh,
                                   P(axis, *([None] * (v.ndim - 1))))
                out[k] = jax.make_array_from_process_local_data(
                    sh, np.asarray(v))
            else:
                out[k] = jax.device_put(
                    v, NamedSharding(self.mesh, P(axis, *([None] * (v.ndim - 1)))))
        return out

    # ------------------------------------------------------------------
    # public API (reference: v2 trainer.py)
    # ------------------------------------------------------------------

    def train(self, reader=None, num_passes: int = 1, event_handler=None,
              feeding=None, save_dir: Optional[str] = None,
              start_pass: int = 0, saving_period: int = 1, master=None,
              record_parser=None, heartbeat_ttl_s: Optional[float] = None,
              prefetch: int = 0, save_period_steps: int = 0,
              resume: bool = False, async_save: bool = False,
              keep: int = 2) -> None:
        """``save_dir``/``start_pass``/``saving_period`` are the
        --save_dir/--start_pass/--saving_period flags of the reference
        trainer (ParamUtil.h:77-111): checkpoints (params + optimizer
        state) land in save_dir/pass-%05d every ``saving_period`` passes,
        and ``start_pass`` resumes from an existing one if present.

        Fault-tolerant mode (paddle_tpu.resilience): with
        ``save_period_steps=N`` checkpoints are STEP-granular — every N
        steps (and at each pass end) a checkpoint carrying a ``cursor``
        (pass id, step-in-pass, global step, rng state) is written under
        a monotonically increasing id; ``resume=True`` restores the
        newest INTACT checkpoint (corrupt dirs are rejected with a
        CKPT-CORRUPT line and the next-older one wins) and fast-forwards
        the data cursor, so a killed run re-joins mid-pass with the same
        rng stream — final params equal an uninterrupted run's.
        ``async_save=True`` writes blobs on a background thread
        (AsyncCheckpointer, depth-one pipelined: a new save first waits
        out the previous write): training stalls only for the
        device->host snapshot, never the tar/pkl/md5/meta disk commit.
        ``keep`` bounds the checkpoint dir to that many newest VERIFIED
        checkpoints (corrupt dirs never count, so torn young saves
        cannot reap the only good artifact; 0: no pruning).

        With ``master=MasterClient(...)`` training is elastic/task-driven
        instead of reader-driven (reference: cloud_reader + etcd
        registration, go/pserver/etcd_client.go:67-166): batches come from
        master tasks (``record_parser`` maps each record's bytes to a
        sample tuple), the lease is heartbeat per batch, and a lapsed
        lease triggers re-register + auto-resume from the latest
        checkpoint in ``save_dir``."""
        use_async, keep = bool(async_save), int(keep)
        if master is not None:
            enforce_that(record_parser is not None,
                         "master= training needs record_parser=",
                         context="trainer")
            enforce_that(start_pass == 0, "start_pass is reader-path only; "
                         "elastic training resumes from save_dir "
                         "automatically", context="trainer")
            enforce_that(save_period_steps == 0,
                         "save_period_steps is reader-path only; elastic "
                         "training checkpoints per saving_period tasks",
                         context="trainer")
            return self._train_elastic(master, record_parser, num_passes,
                                       event_handler, feeding, save_dir,
                                       heartbeat_ttl_s, saving_period,
                                       use_async, keep)
        enforce_that(reader is not None, "train() needs a reader "
                     "(or master=)", context="trainer")
        enforce_that(not (resume and start_pass > 0),
                     "resume= (step-granular, cursor-driven) and "
                     "start_pass= (pass-granular) are exclusive",
                     context="trainer")
        # silently no-opping these would make a supervised run restart
        # from scratch on every death — the elastic path already errors
        # on the same misuse ("lease lost with no save_dir")
        enforce_that(not (resume and save_dir is None),
                     "resume=True needs save_dir= (nothing to resume "
                     "from otherwise)", context="trainer")
        enforce_that(not (save_period_steps > 0 and save_dir is None),
                     "save_period_steps needs save_dir=",
                     context="trainer")
        if event_handler is None:
            event_handler = _default_event_handler
        feeder = self._make_feeder(feeding)
        if self._step_fn is None:
            self._step_fn = self._build_step()
        log = plog.logger()

        from paddle_tpu import checkpoint as ckpt

        if save_dir is not None and start_pass > 0:
            import os

            # resume from exactly pass start_pass-1 (newer checkpoints may
            # exist when re-branching; silently training from fresh init
            # would overwrite them with garbage)
            want = start_pass - 1
            enforce_that(os.path.isdir(ckpt.pass_dir(save_dir, want)),
                         f"start_pass={start_pass} but no checkpoint "
                         f"pass-{want:05d} under {save_dir}",
                         context="trainer")
            self.load_checkpoint(save_dir, want)

        resume_pass, resume_step = start_pass, 0
        if resume and save_dir is not None:
            loaded = ckpt.load_latest(save_dir)
            if loaded is not None:
                self.apply_checkpoint(loaded)
                meta = loaded[3]
                cur = meta.get("cursor") or {}
                # a cursor-less (legacy per-pass) artifact resumes at the
                # pass AFTER the one it closed
                resume_pass = int(cur.get("pass_id",
                                          meta.get("pass_id", -1) + 1))
                resume_step = int(cur.get("step_in_pass", 0))
                self._global_step = int(cur.get("global_step", 0))
                if cur.get("rng") is not None:
                    self._rng = jnp.asarray(
                        np.asarray(cur["rng"], dtype=np.uint32))
                log.info("resumed from checkpoint (pass %d, step-in-pass "
                         "%d, global step %d)", resume_pass, resume_step,
                         self._global_step)
                self._tracer.instant("train_resume", cat="train",
                                     pass_id=resume_pass,
                                     step=self._global_step)
        step_saves = save_dir is not None and save_period_steps > 0
        ck_next = 0
        if step_saves:
            # monotonic checkpoint counter above every existing dir
            # (id 0 is a real id — `or -1` would shift the numbering)
            lp = ckpt.latest_pass(save_dir)
            ck_next = (lp + 1) if lp is not None else 0
        # per-call checkpointer: a previous train() call's async writer
        # must neither leak into this call (async_save=False here would
        # silently stay async, with the OLD keep) nor race it — settle
        # and rebuild from this call's arguments
        if self._async_ckpt is not None:
            self._drain_async_writer("superseded by a new train() call")
            self._async_ckpt = None
        if step_saves and use_async:
            from paddle_tpu.resilience.checkpointer import AsyncCheckpointer

            self._async_ckpt = AsyncCheckpointer(keep=keep)

        params = self.parameters.as_dict()
        opt_state = self.opt_state
        mstate = self.model_state
        gstate = self._guard_init() if self._guard is not None else None
        self._bad_steps_seen = 0   # fresh device counter this train()
        faults = self._faults

        def sync_back():
            self.parameters.update_from(params)
            self.opt_state = opt_state
            self.model_state = mstate

        def save_cursor(pass_id: int, step_in_pass: int) -> None:
            """One step-granular checkpoint (sync or async) carrying the
            resume cursor; checkpoint ids are a monotonic counter, not
            pass ids, so mid-pass saves never collide."""
            nonlocal ck_next
            sync_back()
            self._save_with_cursor(save_dir, ck_next, pass_id,
                                   step_in_pass, keep)
            ck_next += 1

        try:
            # reference flag semantics (ParamUtil.h): num_passes is the
            # TOTAL pass count; resuming runs passes [resume_pass,
            # num_passes), not num_passes additional ones
            for pass_id in range(resume_pass, num_passes):
                skip = resume_step if pass_id == resume_pass else 0
                raw_it = reader()
                if skip:
                    # fast-forward the data cursor: the resumed pass
                    # consumed `skip` batches before the checkpoint, so
                    # drop them unconverted (no feed/transfer cost)
                    for _ in range(skip):
                        if next(raw_it, None) is None:
                            break
                    peek = next(raw_it, None)
                    if peek is None:
                        # the cursor sits exactly at the pass boundary
                        # (the pass-end save was torn): the pass already
                        # completed AND fired its events before the
                        # crash — repair the boundary cursor and move on
                        # without re-firing BeginPass/EndPass over an
                        # empty replay (a zero-metric duplicate EndPass
                        # would feed garbage to early-stopping handlers)
                        if step_saves:
                            save_cursor(pass_id + 1, 0)
                        continue
                    raw_it = itertools.chain([peek], raw_it)
                event_handler(v2_event.BeginPass(pass_id))
                # host-side floats; device scalars buffer in `pending` and
                # flush with ONE stacked transfer per stream per log window
                pass_costs: List[float] = []
                pass_metrics: Dict[str, List[float]] = {
                    n: [] for n in self.metrics}
                pending: List = []
                pending_metrics: Dict[str, List] = {
                    n: [] for n in self.metrics}
                pending_counters: List = []

                def flush():
                    # the one place the loop waits for the device
                    with self._tracer.phase("step.flush", cat="train"):
                        if pending:
                            pass_costs.extend(
                                np.asarray(jnp.stack(pending)).tolist())
                            pending.clear()
                        if pending_counters:
                            self._publish_counters(pending_counters)
                            pending_counters.clear()
                        for k, buf in pending_metrics.items():
                            if buf:
                                pass_metrics[k].extend(
                                    np.asarray(jnp.stack(buf)).tolist())
                                buf.clear()

                place = self._shard_feeds
                if prefetch > 0 and self.mesh is None:
                    from paddle_tpu.reader.prefetch import device_put_feeds

                    place = device_put_feeds

                def feed(batch):
                    # one batch's conversion and placement, on whichever
                    # thread runs it
                    with self._tracer.phase("step.feed", cat="train"):
                        return place(feeder.feed(batch))

                if prefetch > 0:
                    # device-resident double buffering: feed conversion +
                    # the host->device transfer of batch k+1 overlap batch
                    # k's compute (the async DataProvider pool analog)
                    from paddle_tpu.reader.prefetch import device_prefetch

                    feed_it = device_prefetch(raw_it, size=prefetch,
                                              transform=feed,
                                              place=lambda placed: placed)
                else:
                    feed_it = (feed(b) for b in raw_it)
                for batch_id, feeds in enumerate(feed_it, start=skip):
                    if faults is not None:
                        # injected clock tick + scheduled death, BEFORE
                        # the step runs (a killed step's work is lost and
                        # must replay from the last checkpoint)
                        faults.step_begin(self._global_step)
                    event_handler(v2_event.BeginIteration(pass_id, batch_id))
                    self._rng, key = jax.random.split(self._rng)
                    # the timer (the reference's name) and the phase
                    # inside it time the ASYNCHRONOUS dispatch of a step,
                    # not a batch: the device runs on after the call
                    # returns, and the loop waits for it in flush() alone
                    with stats.timer("trainOneBatch"):
                        guarded = ()
                        if gstate is not None:
                            gstate["inject"] = np.float32(
                                faults.grad_inject(self._global_step)
                                if faults is not None else 0.0)
                            guarded = (gstate,)
                        with self._tracer.phase("step.dispatch",
                                                cat="train"):
                            out = self._step_fn(params, opt_state, mstate,
                                                key, feeds, *guarded)
                        loss, params, opt_state, mstate, metric_vals = out[:5]
                        if gstate is not None:
                            gstate = {"inject": gstate["inject"], **out[5]}
                    self._global_step += 1
                    pstats = metric_vals.pop("__param_stats__", None)
                    counted = metric_vals.pop("__counters__", None)
                    if counted is not None:
                        pending_counters.append(counted)
                    period = getattr(self, "_stats_period", 0)
                    if pstats is not None and period > 0 \
                            and (batch_id + 1) % period == 0:
                        for k in sorted(pstats):
                            avg_abs, max_abs = pstats[k]
                            log.info("Param %s avgAbsGrad=%.6g "
                                     "maxAbsGrad=%.6g",
                                     k, float(avg_abs), float(max_abs))
                    # no host sync per batch (the device round-trip costs
                    # more than the step); events convert lazily
                    pending.append(loss)
                    for k, v in metric_vals.items():
                        pending_metrics[k].append(v)
                    event_handler(v2_event.EndIteration(pass_id, batch_id,
                                                        loss, metric_vals))
                    if step_saves and (batch_id + 1) % save_period_steps == 0:
                        save_cursor(pass_id, batch_id + 1)
                    if gstate is not None:
                        self._guard_check(gstate)
                    if FLAGS.log_period \
                            and (batch_id + 1) % FLAGS.log_period == 0:
                        flush()
                        mtxt = " ".join(
                            f"{k}={np.mean(v[-FLAGS.log_period:]):.5f}"
                            for k, v in pass_metrics.items())
                        log.info("Pass %d, Batch %d, Cost %.5f %s", pass_id,
                                 batch_id,
                                 np.mean(pass_costs[-FLAGS.log_period:]),
                                 mtxt)
                # pass end: sync back, fire event
                flush()
                sync_back()
                result_metrics = {k: float(np.mean(v)) if v else 0.0
                                  for k, v in pass_metrics.items()}
                event_handler(v2_event.EndPass(pass_id, result_metrics,
                                               self.parameters))
                if gstate is not None:
                    # before the pass-end save: a save-kill must not
                    # swallow this pass's bad-step accounting
                    self._flush_guard_stats(gstate)
                if step_saves:
                    # pass boundary in cursor terms: next pass, step 0
                    save_cursor(pass_id + 1, 0)
                elif save_dir is not None \
                        and (pass_id + 1) % saving_period == 0:
                    self.save_checkpoint(save_dir, pass_id)
                # scrape surface for the per-batch timers: publish the
                # StatSet into the obs registry each pass instead of
                # ad-hoc report() prints — training timings land next to
                # serving metrics on ONE export.  Wrap event_handler with
                # obs.trainer_event_bridge(tracer) to additionally put
                # every pass/iteration on a trace timeline.
                stats.timer_stats().publish(default_registry(),
                                            prefix="trainer_")
        except BaseException:
            # unwind (injected death, rollback, real error): let the
            # in-flight background write finish — deterministic, and a
            # half-written artifact would otherwise race the resume —
            # and loudly report (not raise) any recorded writer failure,
            # since the restart path builds a fresh trainer and would
            # otherwise drop it with this object
            self._drain_async_writer("train loop unwinding")
            raise

        sync_back()
        if self._async_ckpt is not None:
            # durability barrier: train() returning means the newest
            # checkpoint is committed (writer errors surface here)
            self._async_ckpt.wait()

    def _publish_counters(self, vectors: List) -> None:
        """The layers' per-step counters of one log window (one device
        vector a step, ``Context.count`` / ``Context.gauge``) into
        ``obs.default_registry()``: a counter grows by the window's sum, a
        gauge shows the window's last step.  Called where the costs are
        read, so it waits for nothing the cost flush does not."""
        rows = np.stack(jax.device_get(vectors))        # [steps, keys]
        reg = default_registry()
        for col, (kind, name, labels) in enumerate(self._counter_keys):
            if kind == "counter":
                reg.counter(name).labels(**dict(labels)).inc(
                    float(rows[:, col].sum()))
            else:
                reg.gauge(name).labels(**dict(labels)).set(
                    float(rows[-1, col]))

    # ------------------------------------------------------------------
    # bad-step guard + cursor-checkpoint plumbing (paddle_tpu.resilience)
    # ------------------------------------------------------------------

    def _guard_init(self):
        from paddle_tpu.resilience.guard import guard_init

        return guard_init()

    def _guard_check(self, gstate) -> None:
        """Rollback-policy hysteresis check, amortized: the consecutive
        counter is a device scalar read back only every
        ``guard.cadence`` steps (healthy steps stay on the lazy .cost
        sync contract).  A streak of ``rollback_after`` bad steps dumps
        the flight recorder and raises BadStepRollback — the supervisor
        restarts from the newest verified checkpoint."""
        g = self._guard
        if g is None or g.policy != "rollback" \
                or self._global_step % g.cadence:
            return
        consec = int(gstate["bad_consec"])
        if consec < g.rollback_after:
            return
        from paddle_tpu.resilience.faults import BadStepRollback

        self._tracer.instant("bad_step_rollback", cat="train",
                             consec=consec, step=self._global_step)
        if getattr(self._tracer, "enabled", False):
            self._tracer.dump_postmortem("bad-step-rollback")
        default_registry().counter(
            "train_rollbacks_total",
            "bad-step guard rollbacks to the last good checkpoint").inc()
        raise BadStepRollback(
            f"{consec} consecutive bad steps (>= {g.rollback_after}) at "
            f"global step {self._global_step}: rolling back to the last "
            "verified checkpoint")

    def _flush_guard_stats(self, gstate) -> None:
        """Lazy bad-step accounting (one host read per pass): newly
        skipped steps land on the obs timeline and the unified registry,
        and ``self.bad_steps_total`` accumulates the lifetime count
        (the device counter restarts at 0 on every train() call; the
        watermark ``_bad_steps_seen`` is reset with it)."""
        total = int(gstate["bad_total"])
        new = total - self._bad_steps_seen
        if new > 0:
            self.bad_steps_total += new
            self._tracer.instant("bad_steps_skipped", cat="train",
                                 count=new, total=self.bad_steps_total,
                                 step=self._global_step)
            default_registry().counter(
                "train_bad_steps_total",
                "train steps skipped by the bad-step guard "
                "(non-finite or over-norm gradients)").inc(new)
        self._bad_steps_seen = total

    def _drain_async_writer(self, why: str) -> None:
        """Join the in-flight async write and LOUDLY report — never
        raise — a recorded writer failure.  Used wherever the
        checkpointer is being discarded or the loop is already
        unwinding: the failed artifact is uncommitted (resume falls
        back to the previous checkpoint), but the failure must not die
        silently with the object."""
        ck = self._async_ckpt
        if ck is None:
            return
        ck.drain()
        err = ck.take_error()
        if err is not None:
            plog.logger().warning(
                "async checkpoint writer failed (%s): %r — artifact "
                "left uncommitted; resume falls back to the previous "
                "checkpoint", why, err)
            self._tracer.instant("ckpt_write_failed", cat="train",
                                 why=why)

    def _save_with_cursor(self, root: str, ck_id: int, pass_id: int,
                          step_in_pass: int, keep: int) -> None:
        """One step-granular checkpoint under the tmp+rename+md5 commit
        protocol, sync or async (``self._async_ckpt``).  The cursor
        records everything a replacement trainer needs to continue the
        SAME run: pass id, step-in-pass (the data cursor), global step
        (the fault/metric clock) and the rng key (the dropout/shuffle
        stream)."""
        from paddle_tpu import checkpoint as ckpt

        extra = {"cursor": {"pass_id": int(pass_id),
                            "step_in_pass": int(step_in_pass),
                            "global_step": int(self._global_step),
                            "rng": np.asarray(self._rng).tolist()}}
        hook = self._faults.save_hook(ck_id) \
            if self._faults is not None else None
        with self._tracer.span("checkpoint_save", cat="train", ck=ck_id,
                               step=self._global_step):
            if self._async_ckpt is not None:
                self._async_ckpt.save(
                    root, ck_id, self.parameters, opt_state=self.opt_state,
                    model_state=self.model_state, extra_meta=extra,
                    shard_plan=self._zero_plan, commit_hook=hook)
            else:
                ckpt.save_checkpoint(
                    root, ck_id, self.parameters, opt_state=self.opt_state,
                    model_state=self.model_state, extra_meta=extra,
                    shard_plan=self._zero_plan, commit_hook=hook)
                if keep > 0:
                    ckpt.prune_checkpoints(root, keep=keep)

    def _train_elastic(self, master, record_parser, num_passes: int,
                       event_handler, feeding, save_dir: Optional[str],
                       ttl_s: Optional[float], saving_period: int,
                       use_async: bool, keep: int) -> None:
        """Task-driven elastic training (the kill/resume e2e productized).

        One SGD step per master task; the step counter (== applied task
        count along this trainer lineage) drives the rng stream and is
        persisted in checkpoint meta, so a replacement trainer resumes
        the SAME stream — final params equal an uninterrupted run (the
        test_TrainerOnePass determinism bar extended to the crash path;
        single-lineage guarantee — with several concurrent trainers a
        requeued task may be re-run by a peer, the reference's async
        tolerance).

        Ack protocol: tasks are acked ONLY after a checkpoint covering
        them is durable (``saving_period`` = tasks per checkpoint; every
        task when save_dir is unset). The checkpoint meta records the
        covered-but-possibly-unacked (task_id, epoch) set plus the
        in-progress pass and next rng step, so a crash in ANY window —
        before the step, or after the checkpoint but before the acks —
        resumes without losing or double-applying a task. Old
        checkpoints are pruned (crash-resume only needs the latest; the
        previous one is kept as insurance while the newest is young).

        Async mode (``use_async``, an AsyncCheckpointer) PIPELINES the
        durability: flush N waits out write N-1, acks the tasks write
        N-1 covered, then submits write N and keeps training — the ack
        invariant ("ack strictly after durable") holds with the disk
        write off the step path.  A crash in any window still resumes
        exactly: write N's covered tasks are unacked, so they requeue
        and replay against checkpoint N-1 (or skip against N if its
        meta committed first).
        """
        import time as _time

        from paddle_tpu import checkpoint as ckpt

        if event_handler is None:
            event_handler = _default_event_handler
        feeder = self._make_feeder(feeding)
        if self._step_fn is None:
            self._step_fn = self._build_step()
        log = plog.logger()
        saving_period = max(1, int(saving_period))
        faults = self._faults
        # per-call checkpointer (same contract as the reader path); the
        # async prune budget keeps the sync path's >= 2 insurance floor,
        # or a keep=1 caller would lose the previous checkpoint the
        # elastic rejoin story depends on while the newest is young
        if self._async_ckpt is not None:
            self._drain_async_writer("superseded by a new train() call")
            self._async_ckpt = None
        if save_dir is not None and use_async:
            from paddle_tpu.resilience.checkpointer import AsyncCheckpointer

            # keep=0 stays "pruning disabled" (train()'s documented
            # semantics); only a positive budget gets the >= 2 floor
            self._async_ckpt = AsyncCheckpointer(
                keep=keep if keep == 0 else max(2, keep))

        def resume_state():
            """-> (next_step, skip_set, pass_id, next_ckpt_id)."""
            latest = ckpt.latest_pass(save_dir) if save_dir else None
            if latest is None:
                return 0, set(), 0, 0
            p, opt, mst, meta = ckpt.load_checkpoint(save_dir)
            self.parameters.update_from(p.as_dict())
            if opt is not None:
                self.opt_state = opt
            if mst is not None:
                self.model_state = mst
            self._place_on_mesh()
            log.info("elastic: resumed from checkpoint %d (pass %d, "
                     "next step %d)", latest, meta.get("pass_id", 0),
                     meta.get("next_step", latest + 1))
            skip = {(tid, meta.get("epoch", 0))
                    for tid in meta.get("task_ids", [])}
            return (meta.get("next_step", latest + 1), skip,
                    meta.get("pass_id", 0), latest + 1)

        if getattr(master, "_slot", None) is None:
            master.register(ttl_s=ttl_s)
        step, skip_set, pass_id, ck_id = resume_state()

        params = self.parameters.as_dict()
        opt_state = self.opt_state
        mstate = self.model_state
        gstate = self._guard_init() if self._guard is not None else None
        self._bad_steps_seen = 0   # fresh device counter this train()
        unacked: List[int] = []
        # async pipelining: tasks covered by the in-flight (submitted,
        # not yet provably durable) checkpoint — acked at the NEXT flush
        # once that write has committed
        covered: List[int] = []

        def sync_back():
            self.parameters.update_from(params)
            self.opt_state = opt_state
            self.model_state = mstate

        def settle_covered() -> None:
            """The durability-then-ack invariant, in ONE place: wait the
            in-flight write durable (writer errors raise HERE, on the
            training thread), then — and only then — ack the tasks that
            write covered."""
            self._async_ckpt.wait()
            for tid in covered:
                master.ack_task(tid)
            covered.clear()

        def flush(meta_pass: int, epoch: int, final: bool = False) -> None:
            """Checkpoint the current state, then ack everything a
            DURABLE checkpoint covers. Ack strictly AFTER the write: the
            reverse order could lose acked-but-not-durable updates.  On
            the async path the write of flush N commits in the
            background while training continues; flush N+1 (or the
            ``final`` drain) waits it out and acks its tasks."""
            nonlocal ck_id
            if save_dir is None:
                for tid in unacked:
                    master.ack_task(tid)
                unacked.clear()
                return
            hook = faults.save_hook(ck_id) if faults is not None else None
            meta = {"next_step": step, "pass_id": meta_pass,
                    "epoch": epoch}
            if self._async_ckpt is not None:
                # NOTE the lease math: a task acks at the latest one
                # full flush window after its write submits, so the
                # master's timeout_s must cover saving_period steps +
                # one checkpoint write (the per-step idle() early-ack
                # usually settles much sooner)
                settle_covered()                 # previous write durable
                covered[:] = list(unacked)
                unacked.clear()
                meta["task_ids"] = list(covered)
                sync_back()
                with self._tracer.span("checkpoint_save", cat="train",
                                       ck=ck_id):
                    self._async_ckpt.save(
                        save_dir, ck_id, self.parameters,
                        opt_state=self.opt_state,
                        model_state=self.model_state, extra_meta=meta,
                        shard_plan=self._zero_plan, commit_hook=hook)
                ck_id += 1
                if final:
                    settle_covered()
                return
            meta["task_ids"] = list(unacked)
            sync_back()
            with self._tracer.span("checkpoint_save", cat="train",
                                   ck=ck_id):
                ckpt.save_checkpoint(
                    save_dir, ck_id, self.parameters,
                    opt_state=self.opt_state, model_state=self.model_state,
                    extra_meta=meta, shard_plan=self._zero_plan,
                    commit_hook=hook)
                if keep > 0:
                    ckpt.prune_checkpoints(save_dir, keep=max(2, keep))
            ck_id += 1
            for tid in unacked:
                master.ack_task(tid)
            unacked.clear()

        try:
            while pass_id < num_passes:
                master.begin_pass()
                event_handler(v2_event.BeginPass(pass_id))
                pending_costs: List = []
                batch_id = 0
                epoch = 0
                rejoined = False
                resumed_acks = False
                while True:
                    if not master.heartbeat(ttl_s=ttl_s):
                        # declared dead (long GC/preemption): durable state
                        # is required to rejoin — silently restarting the
                        # rng stream from scratch would corrupt training
                        enforce_that(save_dir is not None,
                                     "elastic lease lost with no save_dir: "
                                     "cannot resume; pass save_dir= to "
                                     "train(master=...)", context="trainer")
                        log.info("elastic: lease lost, re-registering")
                        # settle the in-flight write before reloading
                        # (racing it would read a half-commit); its
                        # outcome is superseded by the reload either
                        # way, so a writer error is reported, not raised
                        self._drain_async_writer("lease lost, rejoining")
                        master.register(ttl_s=ttl_s)
                        unacked.clear()
                        covered.clear()
                        step, skip_set, pass_id, ck_id = resume_state()
                        params = self.parameters.as_dict()
                        opt_state = self.opt_state
                        mstate = self.model_state
                        rejoined = True
                        break
                    status, got = master.try_next_task()
                    if status == "done":
                        if resumed_acks and batch_id == 0:
                            # the only thing this pass did was ack stale
                            # tasks from the PREVIOUS pass (crash at a
                            # pass boundary): the queue just drained, so
                            # recycle it and actually train this pass
                            master.begin_pass()
                            resumed_acks = False
                            continue
                        break
                    if status == "empty":
                        # possibly blocked on our own unacked tasks: flush
                        if unacked:
                            flush(pass_id, epoch)
                        elif covered and self._async_ckpt is not None:
                            # the queue tail: only the in-flight write's
                            # tasks are outstanding — wait it durable and
                            # ack them, or the poll would spin forever
                            settle_covered()
                        else:
                            master.poll_wait()   # jittered backoff, not a
                        continue                 # fixed-interval hammer
                    task_id, epoch, records = got
                    master.poll_reset()
                    if skip_set:
                        if (task_id, epoch) in skip_set:
                            # already applied inside the restored
                            # checkpoint (crash hit between write and
                            # ack): ack, skip
                            skip_set.discard((task_id, epoch))
                            log.info("elastic: task %d already in "
                                     "checkpoint, skipping", task_id)
                            master.ack_task(task_id)
                            resumed_acks = True
                            continue
                        # requeued tasks come back FIRST; a non-match means
                        # the remaining skip entries are stale
                        skip_set.clear()
                    if faults is not None:
                        # injected clock + scheduled death BEFORE the
                        # batch is parsed or BeginIteration fires (the
                        # reader path's ordering: a killed step leaves
                        # no dangling iteration span on the obs
                        # timeline); the task stays leased-but-unacked,
                        # so it requeues when the lease lapses
                        faults.step_begin(step)
                    batch = [record_parser(r) for r in records]
                    event_handler(v2_event.BeginIteration(pass_id, batch_id))
                    feeds = self._shard_feeds(feeder.feed(batch))
                    with stats.timer("trainOneBatch"):
                        if gstate is not None:
                            gstate["inject"] = np.float32(
                                faults.grad_inject(step)
                                if faults is not None else 0.0)
                            (loss, params, opt_state, mstate, metric_vals,
                             gout) = self._step_fn(
                                params, opt_state, mstate,
                                jax.random.PRNGKey(step), feeds, gstate)
                            gstate = {"inject": gstate["inject"], **gout}
                        else:
                            loss, params, opt_state, mstate, metric_vals = \
                                self._step_fn(params, opt_state, mstate,
                                              jax.random.PRNGKey(step),
                                              feeds)
                    metric_vals.pop("__param_stats__", None)
                    metric_vals.pop("__counters__", None)
                    step += 1
                    self._global_step = step
                    unacked.append(task_id)
                    if len(unacked) >= saving_period:
                        flush(pass_id, epoch)
                    elif covered and self._async_ckpt is not None \
                            and self._async_ckpt.idle():
                        # opportunistic early ack: the background write
                        # already committed, so its tasks need not stay
                        # leased until the next flush — this keeps the
                        # unacked window near ONE saving_period (plus
                        # actual write time) instead of two, which is
                        # what the master's per-task timeout_s must
                        # cover to avoid requeuing work a live trainer
                        # already applied
                        settle_covered()
                    if gstate is not None:
                        self._guard_check(gstate)
                    batch_id += 1
                    pending_costs.append(loss)  # device scalar, no sync
                    event_handler(v2_event.EndIteration(
                        pass_id, batch_id - 1, loss, metric_vals))
                    if FLAGS.log_period and batch_id % FLAGS.log_period == 0:
                        window = pending_costs[-FLAGS.log_period:]
                        log.info("Elastic pass %d, Batch %d, Cost %.5f",
                                 pass_id, batch_id - 1,
                                 float(np.mean(np.asarray(
                                     jnp.stack(window)))))
                if rejoined:
                    continue  # restart the (possibly different) pass
                # pass complete: flush leftovers, mark the NEXT pass
                # durable so a crash right here doesn't re-run this pass
                # on resume (final=True drains the async pipeline — the
                # pass boundary is a full durability point)
                pass_id += 1
                flush(pass_id, epoch, final=True)
                sync_back()
                if gstate is not None:
                    self._flush_guard_stats(gstate)
                # same registry publish as the reader path: elastic passes
                # expose their trainOneBatch timings through obs too
                stats.timer_stats().publish(default_registry(),
                                            prefix="trainer_")
                event_handler(v2_event.EndPass(pass_id - 1, {},
                                               self.parameters))
        except BaseException:
            # unwind (injected death, rollback, real error): let the
            # in-flight write finish — its meta either commits (resume
            # skips its tasks) or not (they replay) — loudly reporting
            # any recorded writer failure instead of dropping it with
            # this trainer object
            self._drain_async_writer("elastic loop unwinding")
            raise
        sync_back()

    def test(self, reader, feeding=None) -> v2_event.TestResult:
        feeder = self._make_feeder(feeding)
        if self._test_fn is None:
            self._test_fn = self._build_test()
        params = self.parameters.as_dict()
        costs: List[float] = []
        metrics: Dict[str, List[float]] = {n: [] for n in self.metrics}
        for data_batch in reader():
            feeds = feeder.feed(data_batch)
            loss, metric_vals = self._test_fn(params, self.model_state, feeds)
            costs.append(float(loss))
            for k, v in metric_vals.items():
                metrics[k].append(float(v))
        result = {k: float(np.mean(v)) if v else 0.0 for k, v in metrics.items()}
        return v2_event.TestResult(float(np.mean(costs)) if costs else 0.0, result)

    # ------------------------------------------------------------------

    def _make_feeder(self, feeding) -> DataFeeder:
        data_types = [(n.name, n.input_type) for n in self.topology.data_nodes]
        return DataFeeder(data_types, feeding)

    def save_parameter_to_tar(self, f) -> None:
        self.parameters.to_tar(f)

    # ------------------------------------------------------------------
    # checkpoint/resume incl. optimizer state (ParamUtil + go/pserver
    # checkpoint analogs — see paddle_tpu/checkpoint.py)
    # ------------------------------------------------------------------

    def save_checkpoint(self, root: str, pass_id: int) -> str:
        from paddle_tpu import checkpoint as ckpt
        return ckpt.save_checkpoint(root, pass_id, self.parameters,
                                    opt_state=self.opt_state,
                                    model_state=self.model_state,
                                    shard_plan=self._zero_plan)

    def load_checkpoint(self, root: str, pass_id: Optional[int] = None) -> None:
        from paddle_tpu import checkpoint as ckpt
        self.apply_checkpoint(ckpt.load_checkpoint(root, pass_id))

    def apply_checkpoint(self, loaded) -> None:
        """Apply an already-read ``checkpoint.load_checkpoint`` result.

        Split from :meth:`load_checkpoint` so callers can separate disk-read
        failures (missing/corrupt artifact) from apply failures (shape or
        mesh-placement bugs that deserve a traceback)."""
        params, opt_state, model_state, meta = loaded
        self.parameters.update_from(params.as_dict())
        if opt_state is not None:
            self.opt_state = opt_state
        if model_state is not None:
            self.model_state = model_state
        self._place_on_mesh()


def _put_global(v, sharding) -> jax.Array:
    """Multi-process-safe placement — see parallel.api.put_global."""
    from paddle_tpu.parallel.api import put_global

    return put_global(v, sharding)


def _default_event_handler(ev) -> None:
    pass


# ---------------------------------------------------------------------------
# Multi-task / alternating training (the GAN capability)
# ---------------------------------------------------------------------------


class TaskSpec:
    """One optimization task: a cost node, its optimizer, and a predicate
    naming which parameters it updates (v1_api_demo/gan/gan_trainer.py
    analog — two networks, alternating training)."""

    def __init__(self, name: str, cost, update_equation: Optimizer,
                 trainable=None):
        self.name = name
        self.cost = cost
        self.optimizer = update_equation
        if trainable is None:
            self.trainable = lambda pname: True
        elif isinstance(trainable, str):
            prefix = trainable
            self.trainable = lambda pname: pname.startswith(prefix)
        elif isinstance(trainable, (list, tuple, set, frozenset)):
            names = set(trainable)
            self.trainable = lambda pname: pname in names
        else:
            self.trainable = trainable


class MultiTaskTrainer:
    """Alternating training of several cost graphs over ONE shared
    parameter store — the reference's GAN loop (gan_trainer.py: generator
    and discriminator configs trained alternately against shared
    parameters) without its separate GradientMachines: each task is its
    own jitted step that masks gradients to its parameter subset.

    Usage::

        t = MultiTaskTrainer([
            TaskSpec("d", d_cost, Adam(2e-4), trainable="dis_"),
            TaskSpec("g", g_cost, Adam(2e-4), trainable="gen_"),
        ], parameters)
        d_loss = t.step("d", {"pixel": real, "noise": z})
        g_loss = t.step("g", {"noise": z})
    """

    def __init__(self, tasks: Sequence[TaskSpec], parameters: Parameters,
                 mesh=None):
        enforce_that(len(tasks) > 0, "need at least one task",
                     context="MultiTaskTrainer")
        self.tasks = {t.name: t for t in tasks}
        self.parameters = parameters
        self.mesh = mesh
        self._topos: Dict[str, Topology] = {}
        self._opt_states: Dict[str, Any] = {}
        self._model_states: Dict[str, Any] = {}
        self._step_fns: Dict[str, Any] = {}
        self._rng = jax.random.PRNGKey(FLAGS.seed or 0)
        self._counts: Dict[str, int] = {}
        for t in tasks:
            topo = Topology([t.cost])
            self._topos[t.name] = topo
            t.optimizer.set_param_specs(topo.param_specs())
            subset = {k: v for k, v in parameters.as_dict().items()
                      if t.trainable(k)}
            enforce_that(len(subset) > 0,
                         f"task {t.name!r} trains no parameters",
                         context="MultiTaskTrainer")
            self._opt_states[t.name] = t.optimizer.init_state(subset)
            self._model_states[t.name] = topo.init_state()
            self._counts[t.name] = 0

    def _build(self, name: str):
        task = self.tasks[name]
        topo = self._topos[name]
        optimizer = task.optimizer
        trainable = task.trainable
        mesh = self.mesh

        def step(params, opt_state, model_state, rng, feeds):
            def loss_fn(p):
                outs, new_state = topo.forward(p, model_state, feeds,
                                               train=True, rng=rng, mesh=mesh)
                return _reduce_cost(outs[0]), new_state

            (loss, new_mstate), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            sub_p = {k: v for k, v in params.items() if trainable(k)}
            sub_g = {k: grads[k] for k in sub_p}
            new_sub, new_opt = optimizer.apply(sub_p, sub_g, opt_state)
            new_params = dict(params)
            new_params.update(new_sub)
            return loss, new_params, new_opt, new_mstate

        # only the task's opt-state is donated (params fan into every
        # task's graph, so the caller keeps them); same collective /
        # f32-reduction allowances as the SGD step
        return audit_jit(step, site=f"trainer.task.{name}",
                         donate_argnums=(1,),
                         xla_contract=SiteContract(
                             donate=(1,), allow_collectives=True,
                             allow_upcast=("bfloat16",)))

    def step(self, name: str, feeds: Dict[str, Any]) -> float:
        """Run one optimization step of the named task; other tasks'
        parameters flow through the graph but are not updated."""
        enforce_that(name in self.tasks, f"unknown task {name!r}",
                     context="MultiTaskTrainer")
        fn = self._step_fns.get(name)
        if fn is None:
            fn = self._step_fns[name] = self._build(name)
        self._rng, sub = jax.random.split(self._rng)
        loss, new_params, new_opt, new_mstate = fn(
            self.parameters.as_dict(), self._opt_states[name],
            self._model_states[name], sub, feeds)
        self.parameters.update_from(new_params)
        self._opt_states[name] = new_opt
        self._model_states[name] = new_mstate
        # stateful slots (batch-norm stats) shared across task graphs by
        # node name: propagate updates into the other tasks' state maps
        for other, st in self._model_states.items():
            if other != name:
                for node_name, slots in new_mstate.items():
                    if node_name in st:
                        st[node_name] = slots
        self._counts[name] += 1
        return float(loss)

    def steps_run(self, name: str) -> int:
        return self._counts[name]
