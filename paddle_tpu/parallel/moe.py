"""Expert-parallel Mixture-of-Experts FFN — the EP compute path.

New-build extension (the reference predates MoE; its expert-parallel
machinery is the sparse/pserver row distribution this module's dispatch
generalizes — SURVEY §2.3 "large model dist train"): a Switch-style
top-1 / GShard-style top-2 MoE FFN whose experts are sharded over a
mesh axis, with the classic dispatch/combine all_to_all pattern from
the scaling-book recipe:

  tokens (sharded over the axis) --router--> per-expert capacity buffers
  --all_to_all--> each shard runs ITS experts' FFN on tokens from every
  shard --all_to_all--> gated combine back to token order.

Two paths live here.  The CAPACITY path: ``moe_ffn_reference`` is the
collectives-free dense formulation used for single-device runs and as
the parity oracle; ``moe_ffn`` is the shard_map/all_to_all version.  The
DROPLESS path (``moe_dropless``, further down): one rank's share of an
expert-parallel layer with a router over all the experts (bias-corrected
sigmoid scores or a softmax, as the caller names), the held experts
computed by grouped matrix products over the sorted (token, choice) pairs
(``ops/grouped_matmul.py``), a shared expert (gated or not), no capacity
and nothing dropped; it has no exchange yet and computes what its own
experts give.

In the capacity path tokens over capacity are DROPPED (pass
through as zeros — callers add the residual), the Switch convention.
Top-2 routing renormalizes the two gates to sum to 1 (GShard); per-
expert capacity is UNCHANGED by ``top_k`` — k token-choices compete for
the same ``ceil(T/E * capacity_factor)`` slots, so raise the factor
toward ``k *`` the top-1 value when drops matter.

``MoEConfig`` is the model-zoo surface: it carries the routing
hyperparameters AND the placement plan that puts every expert weight's
leading E dim on the ``expert`` mesh axis through
``parallel.placement.plan_param_attrs`` — the one-placement-layer
story.  ``record_moe_stats`` lands the drop-rate/load statistics on the
obs metrics registry after a step.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.platform.enforce import enforce_that
from paddle_tpu.topology import keep


# the audited compiled-path site every expert-parallel dispatch runs
# through; its contract (below) declares the closed-form collective
# budget `python -m paddle_tpu.analysis sharding` checks
MOE_SITE = "parallel.moe"


def moe_contract(mesh, axis: str, e: int, cap: int, d: int,
                 with_stats: bool = False):
    """The REAL declared sharding contract for one EP dispatch geometry:
    tokens shard their leading dim over ``axis``, the router replicates,
    expert weights shard their leading E dim, outputs come back
    token-sharded with a replicated aux loss.

    The comm budget is the closed form of exactly the collectives the
    compiled program contains (the arXiv 2112.09017 cost model the
    auditor prices with — budget == estimate, so ANY extra collective
    trips the gate):

      - dispatch + combine all_to_all pair: each moves the per-shard
        [E, C, D] f32 capacity buffer, ``b = e*cap*d*4`` bytes, costed
        ``b*(n-1)/n`` per hop;
      - the two aux-stat pmeans ([E] f32 fraction / mean-prob), psum
        lowered: ``2*4e*(n-1)/n`` each;
      - the drop-rate pmean (scalar f32) when stats are requested.
    """
    from paddle_tpu.analysis.retrace import SiteContract
    from paddle_tpu.analysis.sharding import (all_reduce_bytes,
                                              all_to_all_bytes)

    n = int(mesh.shape[axis])
    comm = 2.0 * all_to_all_bytes(e * cap * d * 4, n)
    comm += 2.0 * all_reduce_bytes(4 * e, n)
    out_specs = ((axis,), ())
    if with_stats:
        comm += all_reduce_bytes(4, n)       # drop-rate scalar pmean
        out_specs = ((axis,), (), (), ())
    return SiteContract(
        allow_collectives=True,
        mesh_axes=tuple((a, int(mesh.shape[a])) for a in mesh.axis_names),
        comm_bytes=comm,
        in_specs=((axis,), (), (axis,), (axis,), (axis,), (axis,)),
        out_specs=out_specs)


@dataclass(frozen=True)
class MoEConfig:
    """Model-zoo MoE block configuration + expert placement.

    ``num_experts``/``expert_hidden`` size the block (``expert_hidden``
    0 lets the layer derive it from the model width); ``top_k`` selects
    Switch (1) or GShard (2) routing; ``axis`` names the mesh axis the
    expert weights' leading E dim shards over.  ``capacity_factor`` is
    per-expert and top_k-independent (see module docstring).
    """

    num_experts: int
    expert_hidden: int = 0
    capacity_factor: float = 1.25
    top_k: int = 1
    axis: str = "expert"
    aux_weight: float = 0.01

    def param_plan(self, prefix: str = "") -> Dict[str, Tuple]:
        """{param name: per-dim axis tuple} for the expert weights —
        the ``plan_param_attrs`` input that resolves this block through
        the one placement layer (router replicates: no entry)."""
        ax = self.axis
        return {f"{prefix}w1": (ax, None, None), f"{prefix}b1": (ax, None),
                f"{prefix}w2": (ax, None, None), f"{prefix}b2": (ax, None)}

    def param_attrs(self, prefix: str = "") -> Dict[str, object]:
        """{param name: ParamAttr} with the expert-axis sharding set —
        ready to attach to the zoo layer's ParamSpecs."""
        from paddle_tpu.parallel.placement import plan_param_attrs

        return {k: v.attr
                for k, v in plan_param_attrs(self.param_plan(prefix)).items()}


class MoEParams(NamedTuple):
    """Weights for a MoE FFN: router [D, E]; experts stacked on the
    leading axis — w1 [E, D, H], b1 [E, H], w2 [E, H, D], b2 [E, D]."""

    router: jax.Array
    w1: jax.Array
    b1: jax.Array
    w2: jax.Array
    b2: jax.Array


def init_moe_params(key, d_model: int, hidden: int, num_experts: int,
                    scale: float = 0.02) -> MoEParams:
    ks = jax.random.split(key, 3)
    return MoEParams(
        router=jax.random.normal(ks[0], (d_model, num_experts)) * scale,
        w1=jax.random.normal(ks[1], (num_experts, d_model, hidden)) * scale,
        b1=jnp.zeros((num_experts, hidden)),
        w2=jax.random.normal(ks[2], (num_experts, hidden, d_model)) * scale,
        b2=jnp.zeros((num_experts, d_model)))


def _route(x, router_w):
    """Top-1 routing: (expert [T], gate [T], probs [T, E])."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    return expert, gate, probs


def _route_topk(x, router_w, k: int):
    """Top-k routing: (experts [T, k], gates [T, k], probs [T, E]).

    k == 1 keeps the raw Switch gate (softmax prob of the winner);
    k > 1 renormalizes the k winning gates to sum to 1 (GShard top-2
    convention) so the combined output stays on the activation scale.
    """
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    experts = experts.astype(jnp.int32)
    if k > 1:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return experts, gates, probs


def _aux_stats(probs: jax.Array, expert: jax.Array):
    """Per-batch routing statistics: (fraction routed to e, mean prob e)."""
    e = probs.shape[-1]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)
    return jnp.mean(onehot, axis=0), jnp.mean(probs, axis=0)


def aux_load_balance_loss(probs: jax.Array, expert: jax.Array) -> jax.Array:
    """Switch aux loss: E * sum_e fraction_e * mean_prob_e (pushes routing
    toward uniform expert utilisation)."""
    fraction, mean_prob = _aux_stats(probs, expert)
    return probs.shape[-1] * jnp.sum(fraction * mean_prob)


def _dispatch_mask(expert, num_experts: int, capacity: int):
    """[T, E, C] one-hot dispatch tensor: token t occupies slot
    rank-of-t-within-its-expert of expert e; tokens past capacity drop."""
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)  # [T, E]
    pos = jnp.cumsum(onehot, axis=0) - 1                           # [T, E]
    keep = (pos < capacity) & (onehot > 0)
    slot = jnp.clip(pos, 0, capacity - 1)
    disp = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)       # [T,E,C]
    return disp * keep[..., None].astype(jnp.float32)


def _dispatch_mask_topk(experts, num_experts: int, capacity: int):
    """[T, k, E, C] dispatch tensor for top-k routing.

    Capacity slots are claimed CHOICE-MAJOR: every token's first choice
    ranks before any token's second choice (the GShard priority — a
    second choice never evicts a first choice).  k == 1 reduces exactly
    to :func:`_dispatch_mask`.
    """
    t, k = experts.shape
    onehot = jax.nn.one_hot(experts, num_experts, dtype=jnp.int32)  # [T,k,E]
    flat = jnp.swapaxes(onehot, 0, 1).reshape(k * t, num_experts)
    pos = (jnp.cumsum(flat, axis=0) - 1).reshape(k, t, num_experts)
    pos = jnp.swapaxes(pos, 0, 1)                                   # [T,k,E]
    keep = (pos < capacity) & (onehot > 0)
    slot = jnp.clip(pos, 0, capacity - 1)
    disp = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)       # [T,k,E,C]
    return disp * keep[..., None].astype(jnp.float32)


def _drop_rate(disp, t: int, k: int):
    """Fraction of (token, choice) dispatch slots that fell past their
    expert's capacity — 0.0 when nothing drops."""
    return 1.0 - jnp.sum(disp) / float(t * k)


def _expert_ffn(buf, w1, b1, w2, b2, act):
    """buf [E_loc, N, D] through each local expert's two-layer FFN."""
    h = act(jnp.einsum("end,edh->enh", buf, w1) + b1[:, None, :])
    return jnp.einsum("enh,ehd->end", h, w2) + b2[:, None, :]


def moe_ffn_reference(x: jax.Array, params: MoEParams,
                      capacity_factor: float = 1.25,
                      act=jax.nn.gelu, top_k: int = 1,
                      return_stats: bool = False):
    """Single-device dense formulation (and the parity oracle).

    x: [T, D] tokens. Returns (y [T, D], aux_loss scalar) — plus a
    ``{"drop_rate", "expert_fraction"}`` stats dict when
    ``return_stats`` (feed it to :func:`record_moe_stats`).  Tokens
    past an expert's capacity pass through as ZEROS (add the residual
    outside).
    """
    t, d = x.shape
    e = params.router.shape[1]
    cap = max(1, math.ceil(t / e * capacity_factor))
    experts, gates, probs = _route_topk(x, params.router, top_k)
    disp = _dispatch_mask_topk(experts, e, cap)            # [T, k, E, C]
    buf = jnp.einsum("tkec,td->ecd", disp,
                     x.astype(jnp.float32))                # [E, C, D]
    out = _expert_ffn(buf, params.w1, params.b1, params.w2, params.b2,
                      act)                                  # [E, C, D]
    wdisp = disp * gates[:, :, None, None]
    y = jnp.einsum("tkec,ecd->td", wdisp, out)             # gated combine
    aux = aux_load_balance_loss(probs, experts[:, 0])
    if not return_stats:
        return y.astype(x.dtype), aux
    fraction, _ = _aux_stats(probs, experts[:, 0])
    stats = {"drop_rate": _drop_rate(disp, t, top_k),
             "expert_fraction": fraction}
    return y.astype(x.dtype), aux, stats


def moe_ffn(mesh, x: jax.Array, params: MoEParams, axis: str = "expert",
            capacity_factor: float = 1.25, act=jax.nn.gelu,
            top_k: int = 1, return_stats: bool = False):
    """Expert-parallel MoE FFN: tokens AND experts sharded over ``axis``.

    x: [T, D] global tokens (T divisible by the axis size); expert weights
    shard on their leading E axis. Dispatch/combine ride two all_to_alls
    over ICI. Per-(shard, expert) capacity is
    ceil(T_local / E * capacity_factor) so capacity is enforced per
    SOURCE shard — the standard Switch sharded formulation (a globally
    unlucky routing can drop more tokens than the dense oracle; parity
    tests use uniform-ish routing or generous capacity).

    Returns (y [T, D] in token order, aux_loss scalar); with
    ``return_stats``, appends a ``{"drop_rate", "expert_fraction"}``
    dict of GLOBAL (pmean'd) routing statistics.
    """
    n = mesh.shape[axis]
    t, d = x.shape
    e = params.router.shape[1]
    enforce_that(t % n == 0, f"tokens {t} not divisible by {axis}={n}",
                 context="moe")
    enforce_that(e % n == 0, f"experts {e} not divisible by {axis}={n}",
                 context="moe")
    t_loc = t // n
    cap = max(1, math.ceil(t_loc / e * capacity_factor))
    fn = _moe_jit(mesh, axis, e, cap, int(d), act, int(top_k),
                  bool(return_stats))
    out = fn(x, params.router, params.w1, params.b1, params.w2,
             params.b2)
    if not return_stats:
        return out
    y, aux, drop, fraction = out
    return y, aux, {"drop_rate": drop, "expert_fraction": fraction}


@functools.lru_cache(maxsize=64)
def _moe_jit(mesh, axis: str, e: int, cap: int, d: int, act, top_k: int,
             with_stats: bool):
    """One audited jit per (mesh, axis, experts, capacity, width,
    activation, top_k, stats) — the zero.py identity idiom; bounded +
    stable-callable caveats as ``_pipeline_jit`` (``act`` keys by
    identity).  The geometry in the key is exactly what the closed-form
    comm budget needs, so the REAL contract is computed at wrap time."""
    n = mesh.shape[axis]

    def local(xl, router_w, w1, b1, w2, b2):
        # xl [T_loc, D]; w1 [E_loc, D, H] (this shard's experts)
        t_loc = xl.shape[0]
        experts, gates, probs = _route_topk(xl, router_w, top_k)
        disp = _dispatch_mask_topk(experts, e, cap)      # [T_loc, k, E, C]
        buf = jnp.einsum("tkec,td->ecd", disp,
                         xl.astype(jnp.float32))           # [E, C, D]
        # exchange: shard s sends buf rows of shard r's experts to r
        buf = buf.reshape(n, e // n, cap, d)
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                 tiled=False)              # [n, E_loc, C, D]
        # this shard now holds every source shard's buffers for ITS
        # experts: fold sources into the capacity dimension
        buf = jnp.swapaxes(buf, 0, 1).reshape(e // n, n * cap, d)
        out = _expert_ffn(buf, w1, b1, w2, b2, act)        # [E_loc, n*C, D]
        out = jnp.swapaxes(out.reshape(e // n, n, cap, d), 0, 1)
        out = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                                 tiled=False)   # [owner_shard, E_loc, C, D]
        # flat [owner, local] order IS global expert id owner*(E/n)+local
        out = out.reshape(e, cap, d)                       # [E, C, D]
        wdisp = disp * gates[:, :, None, None]
        y = jnp.einsum("tkec,ecd->td", wdisp, out)
        # GLOBAL routing statistics (pmean the components, THEN combine —
        # a mean of per-shard products is not the global aux loss)
        fraction, mean_prob = _aux_stats(probs, experts[:, 0])
        fraction = jax.lax.pmean(fraction, axis)
        mean_prob = jax.lax.pmean(mean_prob, axis)
        aux = e * jnp.sum(fraction * mean_prob)
        if not with_stats:
            return y.astype(xl.dtype), aux
        drop = jax.lax.pmean(_drop_rate(disp, t_loc, top_k), axis)
        return y.astype(xl.dtype), aux, drop, fraction

    out_specs = (P(axis, None), P(), P(), P()) if with_stats \
        else (P(axis, None), P())
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(axis, None, None),
                  P(axis, None), P(axis, None, None), P(axis, None)),
        out_specs=out_specs,
        check_vma=False)

    from paddle_tpu.analysis.retrace import audit_jit

    return audit_jit(fn, site=MOE_SITE,
                     xla_contract=moe_contract(mesh, axis, e, cap, d,
                                               with_stats))


# ---------------------------------------------------------------------------
# The dropless path: one rank's share of an expert-parallel layer
# ---------------------------------------------------------------------------


def _router_logits(x, router_w):
    """``x W_r`` in float32 at the highest matmul precision.  Kept across
    a recomputed segment (``topology.keep``): what follows it is
    elementwise and its gradient needs the scores, so with the logits held
    the backward pass forms no such product again."""
    return keep("moe_route", jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(p, k: int):
    """``jax.lax.top_k`` whose gradient reads the KEPT choice: the rule of
    ``lax.top_k`` gathers by the indices of its own forward, which a
    recomputed segment would have to sort again to get."""
    return tuple(jax.lax.top_k(p, k))


def _top_k_fwd(p, k):
    g, experts = keep("moe_route", *jax.lax.top_k(p, k))
    return (g, experts), (experts, jnp.arange(p.shape[-1],
                                              dtype=experts.dtype))


def _top_k_bwd(k, res, cots):
    experts, lanes = res
    # a row's choices differ, so a sum over them moves each weight's
    # cotangent to its expert's column and adds nothing to it
    return (jnp.sum(jnp.where(experts[..., None] == lanes,
                              cots[0][..., None], 0), axis=-2),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def route_sigmoid_topk(x, router_w, bias, top_k: int, scaling: float = 1.0):
    """Bias-corrected sigmoid routing (``noaux_tc``): scores
    ``s = sigmoid(x W_r)`` in float32 at the highest matmul precision;
    the ``top_k`` experts of ``s + bias`` are chosen; their weights are
    ``s[chosen] / sum(s[chosen]) * scaling``, without the bias.
    Returns (experts [T, k] int32, weights [T, k] float32)."""
    s = jax.nn.sigmoid(_router_logits(x, router_w))
    _, experts = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    experts = keep("moe_route", experts)
    g = jnp.take_along_axis(s, experts, axis=-1)
    g = g / jnp.sum(g, axis=-1, keepdims=True) * scaling
    return experts.astype(jnp.int32), g


def route_softmax_topk(x, router_w, top_k: int):
    """Softmax routing: ``p = softmax(x W_r)`` over all the experts in
    float32 at the highest matmul precision; the ``top_k`` largest are
    chosen and their weights renormalised, ``p[chosen] / sum(p[chosen])``
    (``norm_topk_prob``).  No bias, no scaling.
    Returns (experts [T, k] int32, weights [T, k] float32)."""
    g, experts = _top_k(jax.nn.softmax(_router_logits(x, router_w), axis=-1),
                        top_k)
    return experts.astype(jnp.int32), g / jnp.sum(g, axis=-1, keepdims=True)


ROUTINGS = ("sigmoid", "softmax")


def _take_rows(a, idx):
    """Rows of ``a`` at ``idx``; an index past the end gives a zero row."""
    return jnp.take(a, idx, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def _dispatch(x, row_token, dest):
    """Token rows into the sorted buffer: ``xs[r] = x[row_token[r]]``
    (zeros where a row holds no pair).  Pair and row are one to one, so
    the gradient is a gather too: ``dx[t] = sum_j dxs[dest[t, j]]``."""
    return _take_rows(x, row_token)


def _dispatch_fwd(x, row_token, dest):
    return _take_rows(x, row_token), (dest, jnp.zeros((), x.dtype))


def _dispatch_bwd(res, dxs):
    dest, like = res
    dx = sum(_take_rows(dxs, dest[:, j]).astype(jnp.float32)
             for j in range(dest.shape[1]))
    return dx.astype(like.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, g, dest, row_token, row_pair):
    """Sorted rows back to tokens, weighted: ``out[t] = sum_j g[t, j]
    y[dest[t, j]]`` (a pair on an expert that is not held adds nothing).
    Both gradients are gathers."""
    return sum(g[:, j, None] * _take_rows(y, dest[:, j])
               for j in range(dest.shape[1]))


def _combine_fwd(y, g, dest, row_token, row_pair):
    return (_combine(y, g, dest, row_token, row_pair),
            (y, g, dest, row_token, row_pair))


def _combine_bwd(res, dout):
    y, g, dest, row_token, row_pair = res
    row_w = _take_rows(g.reshape(-1), row_pair)
    dy = (_take_rows(dout, row_token) * row_w[:, None]).astype(y.dtype)
    dg = jnp.stack([jnp.sum(_take_rows(y, dest[:, j]) * dout, axis=-1)
                    for j in range(dest.shape[1])], axis=1)
    return dy, dg.astype(g.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def dropless_rungs(t: int, top_k: int, count: int, n_routed: int,
                   tile_m: int) -> Tuple[int, ...]:
    """The lengths the sorted buffer of a dropless layer may run at,
    ascending, from the call's own shapes.  The last is the worst case a
    step can meet, ``padded_rows(t * top_k, count)`` (every choice of every
    token on a held expert).  Before it stand twice and four times what
    even routing sends to ``count`` of ``n_routed`` experts, each only
    where it is at most half the worst case: a rank that holds half or
    all of the experts has the worst case alone, and no branch."""
    from paddle_tpu.ops.grouped_matmul import padded_rows

    worst = padded_rows(t * top_k, count, tile_m)
    even = -(-t * top_k * count // n_routed)
    rungs = [padded_rows(times * even, count, tile_m) for times in (2, 4)]
    return tuple(r for r in rungs if 2 * r <= worst) + (worst,)


def _experts_at(rows: int, tile_m: int, ct, plan, x, g, w_gate, w_up,
                w_down):
    """The held experts over a sorted buffer of ``rows`` rows: dispatch,
    the gated feed-forward as three grouped products (operands of type
    ``ct``), combine.  The plan is made at the worst-case length; a
    shorter buffer reads its first ``rows`` rows and ``rows // tile_m``
    tiles, which hold every live row where ``n_active * tile_m <= rows``
    (``dest``'s "nowhere" stays past the end, where ``_take_rows`` gives
    zeros)."""
    from paddle_tpu.ops import grouped_matmul as gm

    dest, row_token, row_pair, tile_group, n_active = plan
    if rows < row_token.shape[0]:
        row_token, row_pair = row_token[:rows], row_pair[:rows]
        tile_group = tile_group[:rows // tile_m]
    xs = _dispatch(x.astype(ct), row_token, dest)
    h = gm.grouped_matmul(xs, w_gate, tile_group, n_active, tile_m)
    u = gm.grouped_matmul(xs, w_up, tile_group, n_active, tile_m)
    a = (jax.nn.silu(h) * u).astype(ct)
    y = gm.grouped_matmul(a, w_down, tile_group, n_active, tile_m)
    return _combine(y, g, dest, row_token, row_pair)


# One branch of ``_experts``, each way.  Jitted: the expert layers of a
# model call a rung at the same shapes, so its kernels are traced and
# lowered once a model and not once a layer (tracing is paid by every
# start, the warm ones too).  ``at = (rows, tile_m, ct, interpret)``; the
# last is in the key because the kernels read the mode where they are traced.
@functools.partial(jax.jit, static_argnums=(0,))
def _rung_forward(at, plan, *operands):
    return _experts_at(*at[:3], plan, *operands)


@functools.partial(jax.jit, static_argnums=(0,))
def _rung_backward(at, plan, operands, dout):
    return jax.vjp(functools.partial(_experts_at, *at[:3], plan),
                   *operands)[1](dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts(static, rung, plan, *operands):
    """:func:`_experts_at` at the rung ``rung`` of ``static = (rungs,
    tile_m, ct, interpret)``, chosen on the device.  No array of a
    buffer's length leaves a branch, forward or backward: the gradient's
    rule keeps the operands and the plan and branches again, and the taken
    branch runs its forward once more on the way (under ``train.remat``
    that IS the recomputed forward: the segment's own has nothing left to
    make)."""
    return jax.lax.switch(
        rung, [functools.partial(_rung_forward, (r,) + static[1:])
               for r in static[0]], plan, *operands)


def _experts_fwd(static, rung, plan, *operands):
    return _experts(static, rung, plan, *operands), (rung, plan, operands)


def _experts_bwd(static, res, dout):
    rung, plan, operands = res
    return (None, None) + jax.lax.switch(
        rung, [functools.partial(_rung_backward, (r,) + static[1:])
               for r in static[0]], plan, operands, dout)


_experts.defvjp(_experts_fwd, _experts_bwd)


def moe_dropless(x: jax.Array, p: Dict[str, jax.Array], *, top_k: int,
                 held: Tuple[int, int], routing: str = "sigmoid",
                 scaling: float = 1.0, valid: Optional[jax.Array] = None,
                 tile_m: int = 0, operand_dtype=None):
    """What one rank of an expert-parallel group computes of a dropless
    expert layer: it routes over ALL the experts (``p["router"]`` is
    [D, n_routed]) by the ``routing`` its caller names, ``"sigmoid"``
    (:func:`route_sigmoid_topk`, with ``p["bias"]`` the correction bias and
    ``scaling``) or ``"softmax"`` (:func:`route_softmax_topk`), holds the
    ``held = (first, count)`` of them whose gated feed-forward matrices it
    is given (``w_gate``, ``w_up`` [count, D, F]; ``w_down`` [count, F,
    D]), and returns its own experts' part of the result plus, where
    ``p`` has one, the shared expert (``shared_gate``, ``shared_up``,
    ``shared_down``), which every rank computes alike, times
    ``sigmoid(x shared_mix)`` where ``p`` has that [D, 1] gate.  A chosen
    expert that is not held adds nothing here; no token is dropped and
    there is no capacity.

    (token, choice) pairs on held experts are placed expert by expert
    into a buffer whose groups start at multiples of ``tile_m``
    (``ops/grouped_matmul.py``).  The plan is made for ``T * top_k +
    count * tile_m`` rows, the worst case a step can meet (every choice of
    every token on a held expert); the buffer itself, and every pass over
    it, forward and backward, has the length of the first of
    :func:`dropless_rungs` that holds the step's live tiles, chosen on
    the device, and the worst case only in a step that needs it (or
    where it is the one rung: a rank that holds half or all of the
    experts has no branch).  Only the tiles that hold rows are computed.
    ``valid`` [T] keeps padding rows of a packed buffer out.
    ``operand_dtype`` is the type the rows take for the products (the
    matrices are rounded to it), the shared expert's products too: the
    global policy's where it is left out (bfloat16 under
    ``FLAGS.use_bf16``); a caller that holds float32 matrices and wants no
    rounded copy of them names float32.

    x: [T, D].  Returns (y [T, D] float32, stats) with the step's
    ``rows_total`` (valid tokens x top_k), ``rows_held`` (pairs that
    landed on held experts), ``max_expert_rows`` (the fullest held
    expert), ``live_experts`` (held experts with a row) and ``live_tiles``
    (row tiles of ``tile_m`` the products compute: a held expert takes
    one even with no row), as device scalars, and ``rung_steps`` {rows of
    a rung: 1.0 where the step ran at it, else 0.0}."""
    from paddle_tpu.ops import grouped_matmul as gm
    from paddle_tpu.ops import math as pmath

    enforce_that(routing in ROUTINGS,
                 f"routing {routing!r} is not one of {ROUTINGS}",
                 context="moe_dropless")
    tile_m = tile_m or gm.TILE_M
    first, count = held
    t, _ = x.shape
    with jax.named_scope("moe.route"):
        if routing == "softmax":
            experts, g = route_softmax_topk(x, p["router"], top_k)
        else:
            experts, g = route_sigmoid_topk(x, p["router"], p["bias"],
                                            top_k, scaling)
        local = experts - first
        on = (local >= 0) & (local < count)
        if valid is not None:
            on = on & valid[:, None]
        local = jnp.where(on, local, count).reshape(-1)
        onehot = (local[:, None] == jnp.arange(count)[None, :]
                  ).astype(jnp.int32)                       # [T k, count]
        counts = jnp.sum(onehot, axis=0)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot,
                       axis=-1)
        rows = gm.padded_rows(t * top_k, count, tile_m)
        offsets, tile_group, n_active = gm.tile_plan(
            counts, rows // tile_m, tile_m)
        dest = jnp.where(on.reshape(-1),
                         offsets[jnp.minimum(local, count - 1)] + rank,
                         rows).astype(jnp.int32)            # rows = nowhere
        pair = jnp.arange(t * top_k, dtype=jnp.int32)
        row_pair = jnp.full((rows,), t * top_k, jnp.int32).at[dest].set(
            pair, mode="drop")
        row_token = jnp.where(row_pair < t * top_k, row_pair // top_k, t)
        dest = dest.reshape(t, top_k)
        # all that ``moe.experts`` reads of this scope: a recomputed
        # segment holds it (a few MB) and routes once a step
        g, dest, row_token, row_pair, tile_group, n_active = keep(
            "moe_route", g, dest, row_token, row_pair, tile_group, n_active)
    rungs = dropless_rungs(t, top_k, count, p["router"].shape[1], tile_m)
    with jax.named_scope("moe.experts"):
        ct = operand_dtype or pmath.compute_dtype(x)
        plan = (dest, row_token, row_pair, tile_group, n_active)
        operands = (x, g, p["w_gate"], p["w_up"], p["w_down"])
        # the first rung that holds the live tiles: those the rows pass
        rung = jnp.sum(n_active[0] * tile_m > jnp.asarray(
            rungs[:-1], jnp.int32)).astype(jnp.int32)
        if len(rungs) == 1:
            out = _experts_at(rungs[0], tile_m, ct, plan, *operands)
        else:
            out = _experts((rungs, tile_m, ct, gm.interpret_default()), rung,
                           plan, *operands)
    if "shared_gate" in p:
        with jax.named_scope("moe.shared"):
            if operand_dtype is None:
                mm = pmath.matmul
            else:
                # the caller's type for the shared expert's products too:
                # float32 matrices are multiplied where they lie
                def mm(a, b):
                    return jnp.matmul(a.astype(ct), b.astype(ct),
                                      preferred_element_type=jnp.float32)
            shared = mm(jax.nn.silu(mm(x, p["shared_gate"]))
                        * mm(x, p["shared_up"]), p["shared_down"])
            if "shared_mix" in p:
                shared = shared * jax.nn.sigmoid(mm(x, p["shared_mix"]))
            out = out + shared
    n_valid = t if valid is None else jnp.sum(valid)
    stats = {"rows_total": jnp.asarray(n_valid * top_k, jnp.float32),
             "rows_held": jnp.sum(counts).astype(jnp.float32),
             "max_expert_rows": jnp.max(counts).astype(jnp.float32),
             "live_experts": jnp.sum(counts > 0).astype(jnp.float32),
             "live_tiles": n_active[0].astype(jnp.float32),
             "rung_steps": {rows: (rung == i).astype(jnp.float32)
                            for i, rows in enumerate(rungs)}}
    return out, stats


def record_moe_stats(stats, registry=None, prefix: str = "moe") -> None:
    """Land one step's routing statistics OF THE CAPACITY PATH
    (``moe_ffn`` / ``moe_ffn_reference`` with ``return_stats``) on the obs
    metrics registry (host-side: call OUTSIDE jit, on concrete step
    outputs).  The dropless path drops nothing and needs no such call:
    ``layer.moe_dropless`` publishes ``moe_rows_total``,
    ``moe_rows_held_total`` and ``moe_max_expert_rows`` from inside the
    compiled train step (``Context.count``), and ``trainer.SGD`` adds them
    to the same registry where it reads the costs.

      - ``{prefix}_drop_rate`` gauge — fraction of (token, choice)
        dispatch slots past capacity this step;
      - ``{prefix}_expert_load_imbalance`` gauge — max expert load
        relative to uniform (1.0 == perfectly balanced routing);
      - ``{prefix}_dropped_tokens`` counter — cumulative drop mass.
    """
    import numpy as np

    from paddle_tpu.obs.registry import default_registry

    reg = registry if registry is not None else default_registry()
    drop = float(stats["drop_rate"])
    reg.gauge(f"{prefix}_drop_rate",
              "fraction of (token, choice) MoE dispatch slots dropped "
              "past expert capacity in the last recorded step").set(drop)
    frac = stats.get("expert_fraction")
    if frac is not None:
        f = np.asarray(frac, dtype=np.float64)
        if f.size:
            reg.gauge(f"{prefix}_expert_load_imbalance",
                      "max expert routing fraction relative to uniform "
                      "(1.0 = balanced)").set(float(f.max() * f.size))
    if drop > 0.0:
        reg.counter(f"{prefix}_dropped_tokens",
                    "cumulative dropped MoE dispatch mass").inc(drop)
