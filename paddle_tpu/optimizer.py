"""Optimizers, LR schedules, regularizers — the paddle/parameter optimizer suite.

Reference: paddle/parameter/FirstOrderOptimizer.h:24-346 (Sgd/SparseMomentum/
Adagrad/AdaDelta/RMSProp/DecayedAdagrad/Adam/Adamax + OptimizerWithGradient
Clipping), AverageOptimizer.h:23, Regularizer.h, LearningRateScheduler.cpp:
50-172 (constant, poly, caffe_poly, exp, discexp, linear, manual, pass_manual),
and python/paddle/v2/optimizer.py.

TPU-native design: an optimizer is a *pure transform* — ``init_state(params)``
builds the slot pytree (the reference's MOMENTUM/GRADIENT_SQURESUM buffers),
``apply(params, grads, state, step)`` returns new params+state. Everything
is jit-friendly and shards with the params under pjit (ZeRO-style optimizer
state sharding falls out for free — see parallel/).

Per-parameter attrs (lr mult, decay, static, clipping) come from ParamSpecs.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.platform.enforce import EnforceError, enforce_that
from paddle_tpu.topology import ParamSpec

# ---------------------------------------------------------------------------
# learning-rate schedules (LearningRateScheduler.cpp analog)
# ---------------------------------------------------------------------------


def make_lr_schedule(args: Dict[str, Any]) -> Callable[[jax.Array], jax.Array]:
    """Build step -> lr-multiplier fn from v1-style config keys:
    learning_rate_schedule ∈ {constant, poly, caffe_poly, exp, discexp,
    linear, manual, pass_manual}, with learning_rate_decay_a/_b and
    learning_rate_args (reference: LearningRateScheduler.cpp:50-172)."""
    kind = args.get("learning_rate_schedule", "constant")
    a = float(args.get("learning_rate_decay_a", 0.0))
    b = float(args.get("learning_rate_decay_b", 0.0))
    spec = args.get("learning_rate_args", "")

    if kind == "constant":
        return lambda step: jnp.ones(())
    if kind == "poly":
        return lambda step: jnp.power(1.0 + a * step, -b)
    if kind == "caffe_poly":
        return lambda step: jnp.power(jnp.maximum(0.0, 1.0 - step / a), b)
    if kind == "exp":
        return lambda step: jnp.power(a, step / b)
    if kind == "discexp":
        return lambda step: jnp.power(a, jnp.floor(step / b))
    if kind == "linear":
        return lambda step: jnp.maximum(1.0 - a * step, b)
    if kind in ("manual", "pass_manual"):
        # "seg1:lr1,seg2:lr2,..." — segments by sample count (manual) or pass
        segs = []
        for part in str(spec).split(","):
            if not part:
                continue
            s, lr = part.split(":")
            segs.append((float(s), float(lr)))
        enforce_that(len(segs) > 0, f"empty {kind} schedule", context="optimizer")
        bounds = jnp.asarray([s for s, _ in segs])
        rates = jnp.asarray([r for _, r in segs])

        def manual(step):
            idx = jnp.searchsorted(bounds, step, side="left")
            return rates[jnp.minimum(idx, len(segs) - 1)]

        return manual
    raise EnforceError(f"unknown lr schedule {kind!r}", context="optimizer")


# ---------------------------------------------------------------------------
# base optimizer
# ---------------------------------------------------------------------------


class Optimizer:
    """Base: handles lr schedule, per-param multipliers, decay, clipping,
    model averaging. Subclasses implement ``_update(g, slots, lr)``."""

    def __init__(self, learning_rate: float = 1e-3,
                 regularization=None, gradient_clipping_threshold: float = 0.0,
                 model_average=None, **sched_args):
        self.learning_rate = learning_rate
        self.schedule = make_lr_schedule(sched_args)
        self.regularization = regularization
        self.global_clip = float(gradient_clipping_threshold or 0.0)
        self.model_average = model_average
        self._specs: Dict[str, ParamSpec] = {}
        self._zero_plan = None  # ZeRO-1 shard plan (parallel/zero.py)

    # -- wiring ------------------------------------------------------------

    def set_param_specs(self, specs: Dict[str, ParamSpec]) -> None:
        self._specs = dict(specs)

    def _attr(self, name):
        spec = self._specs.get(name)
        return spec.attr if spec is not None else None

    def set_zero_plan(self, plan) -> None:
        """Enable ZeRO-1 optimizer-state sharding (parallel/zero.py): slot
        state is allocated and updated as padded 1/N flat shards per
        replica; params/grads pass through the same shard view around
        ``_update``.  One wrapper for every optimizer — subclasses keep
        their elementwise ``_update`` untouched."""
        self._zero_plan = plan

    # -- slots -------------------------------------------------------------

    def slot_names(self) -> Tuple[str, ...]:
        return ()

    def init_state(self, params: Dict[str, jax.Array]) -> Dict[str, Any]:
        # prune masks are value-quantile-based: always computed on the FULL
        # tensors (a padded flat view would skew the quantile with zeros)
        masks = self._make_prune_masks(params)
        if self._zero_plan is not None:
            # hand _init_state the flat sharded views so every slot
            # (zeros_like and param-copy alike) is BORN sharded — no device
            # ever materializes a replicated slot of a planned param
            params = self._zero_plan.shard_tree(params)
        state = self._init_state(params)
        if masks:
            state["prune_masks"] = (self._zero_plan.shard_tree(masks)
                                    if self._zero_plan is not None else masks)
        return state

    def _init_state(self, params: Dict[str, jax.Array]) -> Dict[str, Any]:
        """Build the slot pytree from (possibly ZeRO-shard-view) params."""
        slots = {
            s: {k: jnp.zeros_like(v) for k, v in params.items()}
            for s in self.slot_names()
        }
        state = {"step": jnp.zeros((), jnp.int32), "slots": slots}
        if self.model_average is not None:
            state["avg"] = {k: jnp.array(v) for k, v in params.items()}
            state["avg_count"] = jnp.zeros(())
        return state

    def _make_prune_masks(self, params) -> Dict[str, jax.Array]:
        """Static pruning masks from initial weights (StaticPruningHook,
        ParameterUpdaterHook.cpp:39-104): keep the largest
        (1 - sparsity_ratio) fraction by |value|. The reference partial-sorts
        on the host; a quantile threshold is the O(n) XLA-friendly analog."""
        from paddle_tpu.attr import HookAttr

        masks = {}
        for name, p in params.items():
            attr = self._attr(name)
            if attr is None:
                continue
            for hook in HookAttr.to_hooks(getattr(attr, "update_hooks", None)):
                enforce_that(hook.type == "pruning",
                             f"unknown update hook {hook.type!r}",
                             context="optimizer")
                thresh = jnp.quantile(jnp.abs(p).astype(jnp.float32).ravel(),
                                      float(hook.sparsity_ratio))
                masks[name] = (jnp.abs(p) >= thresh).astype(p.dtype)
        return masks

    def prune_mask(self, state, name: str):
        return state.get("prune_masks", {}).get(name)

    # -- update ------------------------------------------------------------

    def _update(self, name: str, p: jax.Array, g: jax.Array,
                slots: Dict[str, jax.Array], lr: jax.Array, step: jax.Array
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        raise NotImplementedError

    # optional scalar recursions computed once per apply (SparseMomentum's
    # alpha/beta/tau); default: stateless
    def _pre_update(self, state, base_lr):
        return None

    def _post_update(self, new_state, aux) -> None:
        pass

    def apply(self, params: Dict[str, jax.Array], grads: Dict[str, jax.Array],
              state: Dict[str, Any]) -> Tuple[Dict[str, jax.Array], Dict[str, Any]]:
        """The whole update lies under the scope ``opt`` of the compiled
        step (inside it ``opt.clip``, ``opt.update``, ``opt.average`` and,
        for ZeRO, ``opt.shard`` / ``opt.gather``), so that a profiler
        trace tells the update from the weight-gradient products XLA fuses
        it with (``benchmarks/harness/step_parts.py``)."""
        plan = self._zero_plan
        with jax.named_scope("opt"):
            if plan is None:
                return self._apply(params, grads, state)
            # ZeRO-1 (arXiv 2004.13336): grads reduce-scatter into 1/N
            # flat shards (GSPMD lowers psum + this constraint into
            # psum_scatter), the whole update pipeline below runs on the
            # shard views (slot state already lives flat-sharded), and the
            # updated weights all-gather back to full replicated tensors.
            with jax.named_scope("opt.shard"):
                flat_params = plan.shard_tree(params)
                flat_grads = plan.shard_tree(grads)
            new_flat, new_state = self._apply(flat_params, flat_grads, state)
            with jax.named_scope("opt.gather"):
                return plan.gather_tree(new_flat), new_state

    def _apply(self, params: Dict[str, jax.Array], grads: Dict[str, jax.Array],
               state: Dict[str, Any]) -> Tuple[Dict[str, jax.Array], Dict[str, Any]]:
        step = state["step"]
        with jax.named_scope("opt.update"):
            base_lr = self.learning_rate * self.schedule(
                step.astype(jnp.float32))
            self._aux = self._pre_update(state, base_lr)

        # global-norm clipping (reference: OptimizerWithGradientClipping used
        # per-parameter thresholds; pjit-era default is global norm, and
        # per-param thresholds from ParamAttr are applied below)
        if self.global_clip > 0.0:
            with jax.named_scope("opt.clip"):
                gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                     for g in grads.values()))
                scale = jnp.minimum(
                    1.0, self.global_clip / jnp.maximum(gnorm, 1e-12))
                grads = {k: g * scale for k, g in grads.items()}

        with jax.named_scope("opt.update"):
            new_params, new_slots = self._update_leaves(params, grads, state,
                                                        base_lr, step)
            new_state = {"step": step + 1, "slots": new_slots}
            if "prune_masks" in state:
                new_state["prune_masks"] = state["prune_masks"]
            self._post_update(new_state, self._aux)
        if self.model_average is not None:
            with jax.named_scope("opt.average"):
                w = self.model_average.average_window
                decay = jnp.minimum(
                    state["avg_count"] / (state["avg_count"] + 1.0),
                    jnp.asarray(1.0 - 1.0 / max(1.0, w * 1000)))
                new_state["avg"] = {
                    k: decay * state["avg"][k] + (1 - decay) * new_params[k]
                    for k in new_params
                }
                new_state["avg_count"] = state["avg_count"] + 1.0
        return new_params, new_state

    def _update_leaves(self, params, grads, state, base_lr, step):
        """The per-leaf rule with its decay, clipping threshold and prune
        masks: (new params, new slots)."""
        new_params: Dict[str, jax.Array] = {}
        new_slots = {s: {} for s in self.slot_names()}
        for name, p in params.items():
            g = grads[name]
            attr = self._attr(name)
            if attr is not None and attr.is_static:
                new_params[name] = p
                for s in self.slot_names():
                    new_slots[s][name] = state["slots"][s][name]
                continue
            if attr is not None and attr.gradient_clipping_threshold > 0.0:
                t = attr.gradient_clipping_threshold
                g = jnp.clip(g, -t, t)
            # decay (regularizer): applied as grad += decay * p, the
            # reference's L2Regularizer semantics; L1 adds sign(p)*decay.
            l1, l2 = 0.0, 0.0
            if self.regularization is not None:
                l1 = getattr(self.regularization, "l1", 0.0)
                l2 = getattr(self.regularization, "l2", 0.0)
            if attr is not None:
                l1 = attr.l1_decay or l1
                l2 = attr.l2_decay or l2
            if l2:
                g = g + l2 * p
            if l1:
                g = g + l1 * jnp.sign(p)
            mask = self.prune_mask(state, name)
            if mask is not None:
                # StaticPruningHook.update: grad *= mask before the rule
                g = g * mask
            lr = base_lr * (attr.learning_rate if attr is not None else 1.0)
            slots = {s: state["slots"][s][name] for s in self.slot_names()}
            np_, ns = self._update(name, p, g.astype(p.dtype), slots, lr, step)
            if mask is not None:
                # and value *= mask (the hook's init masking, re-asserted so
                # weight decay/averaging can never resurrect pruned weights)
                np_ = np_ * mask
            new_params[name] = np_
            for s in self.slot_names():
                new_slots[s][name] = ns[s]
        return new_params, new_slots


# ---------------------------------------------------------------------------
# concrete optimizers (FirstOrderOptimizer.h analogs)
# ---------------------------------------------------------------------------


class Sgd(Optimizer):
    """Plain SGD (reference: SgdOptimizer)."""

    def _update(self, name, p, g, slots, lr, step):
        return p - lr * g, {}


class Momentum(Optimizer):
    """Heavy-ball momentum; the reference folds momentum into Parameter
    MOMENTUM buffers (SgdOptimizer with momentum / SparseMomentumParameter
    Optimizer for the sparse path)."""

    def __init__(self, momentum: float = 0.9, sparse: bool = False, **kw):
        super().__init__(**kw)
        self.momentum = momentum
        self.sparse = sparse

    def slot_names(self):
        return ("momentum",)

    def _update(self, name, p, g, slots, lr, step):
        m = self.momentum * slots["momentum"] - lr * g
        return p + m, {"momentum": m}


class SparseMomentum(Optimizer):
    """Lazy-momentum scheme (reference SparseMomentumParameterOptimizer,
    FirstOrderOptimizer.h:61-125 / .cpp:30-115): momentum refactored into
    two additive accumulators u, v plus scalar recursions

        tau_t = tau_{t-1} + beta_t / alpha_t
        alpha_t = alpha_{t-1} / k,   beta_t = beta_{t-1} / (1 + lambda*lr)
        u_t = u_{t-1} - alpha_t*lr*g_t,   v_t = v_{t-1} + tau_t*alpha_t*lr*g_t
        theta_t = (tau_t/beta_t + 1/alpha_t)*u_t + v_t/beta_t

    so untouched (sparse) rows need no per-step work. Mathematically equal
    to heavy-ball momentum for decay_rate=0 (verified in
    tests/test_optimizers_hooks.py). alpha grows as k^-t, so past the
    reference's 1e6 threshold the scalars restart (u /= alpha, v = theta) —
    here as a jit-friendly masked select instead of a special traversal."""

    def __init__(self, momentum: float = 0.9, decay_rate: float = 0.0,
                 threshold: float = 1e6, **kw):
        super().__init__(**kw)
        enforce_that(0.0 < momentum < 1.0,
                     "SparseMomentum needs 0 < momentum < 1",
                     context="optimizer")
        self.momentum = momentum
        self.decay_rate = decay_rate
        self.threshold = threshold

    def slot_names(self):
        return ("u", "v")

    def _init_state(self, params):
        state = super()._init_state(params)
        # v_0 = theta_0 (the reference's first-touch assign, t0Vec_)
        state["slots"]["v"] = {k: jnp.array(v) for k, v in params.items()}
        state["sm"] = {"alpha": jnp.ones(()), "beta": jnp.ones(()),
                       "tau": -jnp.ones(())}
        return state

    def _pre_update(self, state, base_lr):
        sm = state["sm"]
        tau = sm["tau"] + sm["beta"] / sm["alpha"]
        alpha = sm["alpha"] / self.momentum
        beta = sm["beta"] / (1.0 + self.decay_rate * base_lr)
        return {"tau": tau, "alpha": alpha, "beta": beta, "lr": base_lr}

    def _update(self, name, p, g, slots, lr, step):
        a = self._aux
        tau, alpha, beta = a["tau"], a["alpha"], a["beta"]
        # per-param lr multipliers scale g via lr/base_lr
        scale = lr / jnp.maximum(a["lr"], 1e-30)
        u = slots["u"] - alpha * a["lr"] * scale * g
        v = slots["v"] + tau * alpha * a["lr"] * scale * g
        theta = (tau / beta + 1.0 / alpha) * u + v / beta
        # numeric restart (needSpecialTraversal): alpha ~ k^-t diverges
        restart = alpha > self.threshold
        u = jnp.where(restart, u / alpha, u)
        v = jnp.where(restart, theta, v)
        return theta, {"u": u, "v": v}

    def _post_update(self, new_state, aux) -> None:
        restart = aux["alpha"] > self.threshold
        one = jnp.ones(())
        new_state["sm"] = {
            "alpha": jnp.where(restart, one, aux["alpha"]),
            "beta": jnp.where(restart, one, aux["beta"]),
            "tau": jnp.where(restart, -one, aux["tau"]),
        }


class Adagrad(Optimizer):
    """Reference: AdagradParameterOptimizer (FirstOrderOptimizer.h:106)."""

    def __init__(self, epsilon: float = 1e-6, **kw):
        super().__init__(**kw)
        self.eps = epsilon

    def slot_names(self):
        return ("accum",)

    def _update(self, name, p, g, slots, lr, step):
        acc = slots["accum"] + jnp.square(g)
        return p - lr * g / (jnp.sqrt(acc) + self.eps), {"accum": acc}


class AdaDelta(Optimizer):
    """Reference: AdaDeltaParameterOptimizer (rou/epsilon)."""

    def __init__(self, rho: float = 0.95, epsilon: float = 1e-6, **kw):
        super().__init__(**kw)
        self.rho, self.eps = rho, epsilon

    def slot_names(self):
        return ("accum_g", "accum_dx")

    def _update(self, name, p, g, slots, lr, step):
        ag = self.rho * slots["accum_g"] + (1 - self.rho) * jnp.square(g)
        dx = -jnp.sqrt((slots["accum_dx"] + self.eps) / (ag + self.eps)) * g
        adx = self.rho * slots["accum_dx"] + (1 - self.rho) * jnp.square(dx)
        return p + lr * dx, {"accum_g": ag, "accum_dx": adx}


class RMSProp(Optimizer):
    """Reference: RMSPropParameterOptimizer (rou, epsilon, +mean-grad term)."""

    def __init__(self, rho: float = 0.95, epsilon: float = 1e-6, **kw):
        super().__init__(**kw)
        self.rho, self.eps = rho, epsilon

    def slot_names(self):
        return ("accum_g", "accum_mean")

    def _update(self, name, p, g, slots, lr, step):
        ag = self.rho * slots["accum_g"] + (1 - self.rho) * jnp.square(g)
        am = self.rho * slots["accum_mean"] + (1 - self.rho) * g
        denom = jnp.sqrt(ag - jnp.square(am) + self.eps)
        return p - lr * g / denom, {"accum_g": ag, "accum_mean": am}


class DecayedAdagrad(Optimizer):
    """Reference: DecayedAdagradParameterOptimizer."""

    def __init__(self, rho: float = 0.95, epsilon: float = 1e-6, **kw):
        super().__init__(**kw)
        self.rho, self.eps = rho, epsilon

    def slot_names(self):
        return ("accum",)

    def _update(self, name, p, g, slots, lr, step):
        acc = self.rho * slots["accum"] + (1 - self.rho) * jnp.square(g)
        return p - lr * g / jnp.sqrt(acc + self.eps), {"accum": acc}


class Adam(Optimizer):
    """Reference: AdamParameterOptimizer (FirstOrderOptimizer.h:274)."""

    def __init__(self, beta1: float = 0.9, beta2: float = 0.999,
                 epsilon: float = 1e-8, **kw):
        super().__init__(**kw)
        self.b1, self.b2, self.eps = beta1, beta2, epsilon

    def slot_names(self):
        return ("m", "v")

    def _update(self, name, p, g, slots, lr, step):
        t = step.astype(jnp.float32) + 1.0
        m = self.b1 * slots["m"] + (1 - self.b1) * g
        v = self.b2 * slots["v"] + (1 - self.b2) * jnp.square(g)
        mhat = m / (1 - jnp.power(self.b1, t))
        vhat = v / (1 - jnp.power(self.b2, t))
        return p - lr * mhat / (jnp.sqrt(vhat) + self.eps), {"m": m, "v": v}


class Adamax(Optimizer):
    """Reference: AdamaxParameterOptimizer (FirstOrderOptimizer.h:313)."""

    def __init__(self, beta1: float = 0.9, beta2: float = 0.999, **kw):
        super().__init__(**kw)
        self.b1, self.b2 = beta1, beta2

    def slot_names(self):
        return ("m", "u")

    def _update(self, name, p, g, slots, lr, step):
        t = step.astype(jnp.float32) + 1.0
        m = self.b1 * slots["m"] + (1 - self.b1) * g
        u = jnp.maximum(self.b2 * slots["u"], jnp.abs(g))
        return p - (lr / (1 - jnp.power(self.b1, t))) * m / (u + 1e-12), \
            {"m": m, "u": u}


# ---------------------------------------------------------------------------
# regularization / model average config objects (v2 API surface)
# ---------------------------------------------------------------------------


class L2Regularization:
    def __init__(self, rate: float):
        self.l1, self.l2 = 0.0, rate


class L1Regularization:
    def __init__(self, rate: float):
        self.l1, self.l2 = rate, 0.0


class L1L2Regularization:
    def __init__(self, l1: float, l2: float):
        self.l1, self.l2 = l1, l2


class ModelAverage:
    """Running average of parameters for eval (reference: AverageOptimizer.h,
    v2 ModelAverage(average_window=...))."""

    def __init__(self, average_window: float = 0.1,
                 max_average_window: Optional[int] = None):
        self.average_window = average_window
        self.max_average_window = max_average_window
