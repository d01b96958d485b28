"""Async-SGD analog: local SGD with periodic parameter averaging.

Reference analog: the pserver async path — ParameterServer2::asyncSGD
applies each trainer's gradients immediately without barriers
(ParameterServer2.cpp:457), trainers tolerate stale parameters, and
``async_lagged_grad_discard_ratio`` drops gradients that lag too far
behind (TrainerConfig.proto:132-134).

TPU-native reinterpretation (SURVEY.md §7 item 8): there is no parameter
server to absorb staleness on an ICI mesh — asynchrony becomes LOCAL
updates. Each data shard keeps its own parameter replica and steps
independently (zero cross-chip traffic); every ``sync_period`` steps the
replicas are averaged with one ``pmean`` (the WaitPassStart/synchronize
barrier collapses into a collective). The staleness-control knob
survives as ``lagged_grad_discard_ratio``: a shard whose gradient norm
exceeds ratio x the mesh-mean norm skips its local update that step
(outlier/straggler gradient rejection, the async discard analog).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.platform.enforce import enforce_that



def _tree_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in leaves))


class LocalSGD:
    """Local-update data parallelism with periodic averaging.

    Parameters are stacked per worker on a leading axis sharded over
    ``axis`` — each shard owns its replica. ``make_step(grad_fn)``
    compiles one mesh-wide step; ``replicate``/``average`` move between
    single and per-worker parameter layouts.
    """

    def __init__(self, mesh, sync_period: int = 4, axis: str = "data",
                 lagged_grad_discard_ratio: float = 0.0,
                 learning_rate: float = 0.01):
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self.sync_period = int(sync_period)
        self.discard_ratio = float(lagged_grad_discard_ratio)
        self.lr = float(learning_rate)

    # -- parameter layout --------------------------------------------------

    def replicate(self, params: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """params -> per-worker stacked replicas [n, ...], sharded."""
        def rep(x):
            stacked = jnp.broadcast_to(x[None], (self.n,) + x.shape)
            return jax.device_put(
                stacked, NamedSharding(self.mesh, P(self.axis)))
        return jax.tree.map(rep, params)

    def average(self, stacked: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return jax.tree.map(lambda x: jnp.mean(x, axis=0), stacked)

    # -- step --------------------------------------------------------------

    def make_step(self, grad_fn: Callable):
        """``grad_fn(params, feeds) -> (loss, grads)`` per shard.

        Returns jitted ``step(stacked_params, step_idx, feeds)`` ->
        (mean_loss, new_stacked_params). Feeds must have a leading batch
        dim divisible by the worker count (sharded over ``axis``)."""
        axis = self.axis
        period = self.sync_period
        ratio = self.discard_ratio
        lr = self.lr

        def local(params_stk, step_idx, feeds):
            # params_stk: [1, ...] this worker's replica
            params = jax.tree.map(lambda x: x[0], params_stk)
            loss, grads = grad_fn(params, feeds)
            if ratio > 0.0:
                gn = _tree_norm(grads)
                mean_gn = jax.lax.pmean(gn, axis)
                keep = gn <= ratio * mean_gn
                grads = jax.tree.map(
                    lambda g: jnp.where(keep, g, jnp.zeros_like(g)), grads)
            new_params = jax.tree.map(lambda p, g: p - lr * g, params,
                                      grads)
            do_sync = (step_idx + 1) % period == 0
            # lax.cond, not where-select: the pmean collective must only
            # EXECUTE on sync steps (every worker sees the same step_idx,
            # so the branch is uniform and cannot deadlock)
            new_params = jax.lax.cond(
                do_sync,
                lambda p: jax.tree.map(
                    lambda q: jax.lax.pmean(q, axis), p),
                lambda p: p,
                new_params)
            mean_loss = jax.lax.pmean(loss, axis)
            return jax.tree.map(lambda x: x[None], new_params), mean_loss

        fn = shard_map(local, mesh=self.mesh,
                       in_specs=(P(axis), P(), P(axis)),
                       out_specs=(P(axis), P()),
                       check_vma=False)
        return jax.jit(fn)
