"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

The reference's model parallelism places whole layers on devices and streams
work through per-device compute threads (ParallelNeuralNetwork.h:15-70
dispatchByDeviceId; MultiGradientMachine.h:41-165 pipelines its ring copies
between trainer threads).  The TPU-native carry-over of that capability is a
collective-permute pipeline:

  - the model is S identical stages; each stage's parameters live ONLY on
    its device along the ``stage`` mesh axis (stacked leading dim, sharded),
  - microbatches enter at stage 0 and hop stage->stage+1 each tick via
    ``lax.ppermute`` over ICI,
  - one ``lax.scan`` runs M + S - 1 ticks (the GPipe fill+drain schedule);
    the last stage accumulates per-microbatch outputs,
  - everything is a plain shard_map program: ``jax.grad`` differentiates
    through scan + ppermute (ppermute's transpose is the reverse hop), so
    pipeline-parallel TRAINING needs no hand-written backward schedule.

First/last-stage hooks put the EMBED and the LOSS/HEAD on the boundary
stages: ``first_fn(first_params, mb)`` maps the raw microbatch feed into
the stage-0 activation, ``last_fn(last_params, y, mb)`` maps the last
stage's emission into the per-microbatch output that accumulates (a
loss, logits, ...).  Under SPMD every device computes both hooks each
tick and ``where``-masks the result — the same cheap-at-small-M choice
the replicated feed already makes.

This trades the 1F1B memory optimisation for compiler-visible simplicity —
the XLA analog of GPipe, not PipeDream; ``remat=True`` wraps the stage
body in ``jax.checkpoint`` and recovers most of the memory if needed.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


# the audited compiled-path site every pipeline_apply dispatch runs
# through; its contract (below) declares the closed-form collective
# budget `python -m paddle_tpu.analysis sharding` checks
PIPELINE_SITE = "parallel.pipeline"


@dataclass(frozen=True)
class PipelineConfig:
    """Trainer-facing pipeline-parallel configuration
    (``trainer.SGD(pipeline=PipelineConfig(...))``).

    - ``num_stages``: S.  0 derives it from the mesh's ``axis`` size
      (or, when the trainer builds the mesh, from the device count).
      The model's layer count must divide by S.
    - ``microbatches``: M per step; the batch must divide by M.  Bubble
      fraction is the GPipe closed form ``(S-1)/(M+S-1)`` — raise M to
      amortize the fill/drain bubble, at the cost of smaller
      per-microbatch matmuls.
    - ``n_layers`` / ``n_heads``: the transformer-zoo geometry the
      trainer partitions (``blk{i}_*`` params -> S stages of
      ``n_layers/S`` blocks; embed + loss/head ride the boundary-stage
      hooks).
    - ``remat``: ``jax.checkpoint`` on the stage body (GPipe remat).
    """

    num_stages: int = 0
    microbatches: int = 8
    axis: str = "stage"
    remat: bool = False
    n_layers: int = 0
    n_heads: int = 1


def pipeline_contract(mesh, axis: str, m: int, hop_shape, hop_dtype,
                      out_shape, out_dtype, n_extra_args: int = 0):
    """The REAL declared sharding contract for one pipeline geometry:
    stacked stage params shard their leading dim over ``axis``,
    microbatches and outputs replicate, and the schedule's collectives
    are priced in closed form (the arXiv 2112.09017 model the auditor
    uses — budget == estimate, so ANY extra collective trips the gate):

      - one ``ppermute`` hop of the per-shard activation ``y`` per scan
        tick: ``b_hop`` bytes each, ``ticks = M + S - 1`` ticks;
      - the final one-hot-masked psum replicating the last stage's
        [M, ...] accumulator: ``2 * M*b_out * (S-1)/S``.
    """
    import numpy as np

    from paddle_tpu.analysis.retrace import SiteContract
    from paddle_tpu.analysis.sharding import all_reduce_bytes

    s = int(mesh.shape[axis])
    ticks = m + s - 1
    b_hop = int(np.prod(hop_shape)) * jnp.dtype(hop_dtype).itemsize
    b_out = int(np.prod(out_shape)) * jnp.dtype(out_dtype).itemsize
    comm = float(ticks * b_hop) + all_reduce_bytes(m * b_out, s)
    return SiteContract(
        allow_collectives=True,
        mesh_axes=tuple((a, int(mesh.shape[a])) for a in mesh.axis_names),
        comm_bytes=comm,
        in_specs=((axis,),) + ((),) * (1 + n_extra_args),
        out_specs=((),))


def stack_stage_params(param_list: Sequence[Any], mesh: Mesh = None,
                       axis: str = "stage"):
    """Stack S per-stage pytrees into one pytree with leading dim S (the
    stage axis), placed so each stage's slice lives on its own device —
    the 'weights live only on their stage' layout."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *param_list)
    if mesh is not None:
        def _place(x):
            spec = P(axis, *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(mesh, spec))

        stacked = jax.tree.map(_place, stacked)
    return stacked


def _mb_slice_struct(microbatches):
    """Abstract one microbatch (leading M dim dropped) from the feed
    pytree; every leaf must carry the same leading M."""
    leaves = jax.tree.leaves(microbatches)
    m = int(leaves[0].shape[0])
    sliced = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), microbatches)
    return m, sliced


def _sds_key(x):
    return (tuple(x.shape), jnp.dtype(x.dtype).name)


def pipeline_apply(mesh: Mesh, stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stacked_params, microbatches,
                   axis: str = "stage",
                   first_fn: Optional[Callable] = None,
                   first_params=None,
                   last_fn: Optional[Callable] = None,
                   last_params=None,
                   remat: bool = False) -> jax.Array:
    """Run M microbatches through S pipeline stages; returns [M, ...] outputs.

    ``stacked_params``: pytree with leading dim S (see stack_stage_params).
    ``microbatches``: [M, mb, ...] array — or a pytree of such arrays
    when ``first_fn`` digests a structured feed — replicated (every
    stage sees the feed; only stage 0 reads it — the cheap choice at
    small M, and the scan/ppermute structure is identical either way).
    ``stage_fn(params, x) -> y`` with y.shape == x.shape (homogeneous
    stages — the classic collective-permute pipeline contract).

    Boundary hooks (both optional):
      - ``first_fn(first_params, mb) -> x``: the EMBED on the first
        stage — maps one microbatch feed into the stage-0 activation;
      - ``last_fn(last_params, y, mb) -> out``: the LOSS/HEAD on the
        last stage — maps the final emission (plus the feed, for
        targets) into the per-microbatch value to accumulate.
    ``remat=True`` wraps the stage body in ``jax.checkpoint``.
    """
    m, mb_sds = _mb_slice_struct(microbatches)
    stage_sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stacked_params)
    if first_fn is not None:
        x_sds = jax.eval_shape(first_fn, first_params, mb_sds)
    else:
        x_sds = jax.tree.leaves(mb_sds)[0]
    y_sds = jax.eval_shape(stage_fn, stage_sds, x_sds)
    if (y_sds.shape, y_sds.dtype) != (x_sds.shape, x_sds.dtype):
        raise ValueError(
            f"pipeline stage_fn must be shape-homogeneous: in "
            f"{x_sds.shape}:{x_sds.dtype} vs out {y_sds.shape}:{y_sds.dtype}")
    if last_fn is not None:
        out_sds = jax.eval_shape(last_fn, last_params, y_sds, mb_sds)
    else:
        out_sds = y_sds
    fn = _pipeline_jit(mesh, stage_fn, axis, m, first_fn, last_fn,
                       bool(remat), _sds_key(x_sds), _sds_key(out_sds))
    return fn(stacked_params,
              () if first_params is None else first_params,
              () if last_params is None else last_params,
              microbatches)


@functools.lru_cache(maxsize=64)
def _pipeline_jit(mesh: Mesh, stage_fn, axis: str, m: int, first_fn,
                  last_fn, remat: bool, x_key, out_key):
    """One audited jit per (mesh, stage_fn, axis, microbatch count,
    hooks, remat, activation/output geometry) — the zero.py identity
    idiom: a fresh wrapper per call would re-trace an identical program
    every call, which the retrace auditor would rightly flag, and an
    unnamed bare dispatch would leave the pipeline invisible to the
    sharding/xla gates.  The cache keys on the CALLER'S ``stage_fn``
    (and hook) identity: pass stable (module-level) callables to reuse
    compiles across calls — a fresh lambda per call re-traces per call
    (exactly the pre-cache behavior), and the bounded maxsize evicts
    dead entries so that pattern cannot pin meshes/executables forever.
    The geometry keys (activation/output shape+dtype) are exactly what
    the closed-form comm budget needs, so the REAL contract is computed
    at wrap time."""
    n_stages = mesh.shape[axis]
    ticks = m + n_stages - 1
    x_shape, x_dtype = x_key
    out_shape, out_dtype = out_key
    body_fn = jax.checkpoint(stage_fn) if remat else stage_fn

    def per_device(params_blk, first_p, last_p, mbs):
        # params_blk leaves: [1, ...] (this device's stage); drop the dim
        params = jax.tree.map(lambda x: x[0], params_blk)
        stage = lax.axis_index(axis)
        acc0 = jnp.zeros((m,) + tuple(out_shape), out_dtype)
        recv0 = jnp.zeros(tuple(x_shape), x_dtype)
        # shard_map tracks varying-manual-axes (VMA): the carry becomes
        # stage-varying after one tick, so it must start so
        acc0, recv0 = lax.pcast((acc0, recv0), (axis,), to="varying")

        def tick(carry, t):
            acc, recv = carry
            mb_idx = jnp.clip(t, 0, m - 1)
            mb = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, mb_idx,
                                                   keepdims=False), mbs)
            feed = first_fn(first_p, mb) if first_fn is not None \
                else jax.tree.leaves(mb)[0]
            x = jnp.where(stage == 0, feed, recv)
            y = body_fn(params, x)
            # hop to the next stage (no wraparound: stage 0's input is the
            # feed; ppermute fills missing receivers with zeros)
            nxt = lax.ppermute(y, axis,
                               [(i, i + 1) for i in range(n_stages - 1)])
            # last stage emits microbatch t-(S-1) at tick t — its hook
            # must see THAT microbatch's feed (targets), not tick t's
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            if last_fn is not None:
                mb_out = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, out_idx,
                                                       keepdims=False), mbs)
                emit = last_fn(last_p, y, mb_out)
            else:
                emit = y
            take = (stage == n_stages - 1) & (t >= n_stages - 1)
            cur = lax.dynamic_index_in_dim(acc, out_idx, keepdims=False)
            upd = jnp.where(take, emit, cur)
            acc = lax.dynamic_update_index_in_dim(acc, upd, out_idx, 0)
            return (acc, nxt), None

        (acc, _), _ = lax.scan(tick, (acc0, recv0), jnp.arange(ticks))
        # replicate the last stage's outputs to every device (psum of a
        # one-hot-masked buffer); its transpose distributes cotangents back
        acc = lax.psum(jnp.where(stage == n_stages - 1, acc,
                                 jnp.zeros_like(acc)), axis)
        return acc

    def run(stacked_params, first_params, last_params, microbatches):
        in_params_spec = jax.tree.map(lambda _: P(axis), stacked_params)
        repl = lambda tree: jax.tree.map(lambda _: P(), tree)  # noqa: E731
        return shard_map(per_device, mesh=mesh,
                         in_specs=(in_params_spec, repl(first_params),
                                   repl(last_params), repl(microbatches)),
                         out_specs=P())(stacked_params, first_params,
                                        last_params, microbatches)

    from paddle_tpu.analysis.retrace import audit_jit

    contract = pipeline_contract(mesh, axis, m, x_shape, x_dtype,
                                 out_shape, out_dtype, n_extra_args=2)
    return audit_jit(run, site=PIPELINE_SITE, xla_contract=contract)
