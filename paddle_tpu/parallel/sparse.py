"""Sharded embedding tables + sparse-row updates — the "large model
distributed training" capability.

Reference analog (SURVEY.md §2.3): huge embedding tables living only on
pservers with per-batch row prefetch and sparse-row gradient pushes —
doc/design/cluster_train/large_model_dist_train.md:1-38,
SparseRemoteParameterUpdater (trainer/RemoteParameterUpdater.h:265),
SparseRowCpuMatrix (math/SparseRowMatrix.h), GET_PARAM_SPARSE RPC
(ParameterService.proto), sparse ports (Flags.cpp:70).

TPU-native design: the table is row-sharded over a mesh axis with
``NamedSharding(P(axis, None))``; lookups run under ``shard_map`` as
owner-computes + ``psum`` (each shard gathers the rows it owns, zeros
elsewhere — the GET_PARAM_SPARSE prefetch becomes one small id all-gather
plus one row-sum over ICI instead of parameter-server RPC). Gradients stay
in SelectedRows form (ids + rows) and optimizers update only touched rows
(the SparseRowMatrix capability), scatter-added shard-locally."""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.platform.enforce import enforce_that



# ---------------------------------------------------------------------------
# SelectedRows — the sparse gradient representation (selected_rows.h analog)
# ---------------------------------------------------------------------------


@dataclass
class SelectedRows:
    """A sparse slab of a [vocab, dim] tensor: ``rows[i]`` is the gradient
    for table row ``ids[i]``. Duplicate ids are allowed (scatter-add)."""

    ids: jax.Array      # [n] int32
    rows: jax.Array     # [n, dim]
    height: int         # vocab size

    def to_dense(self) -> jax.Array:
        out = jnp.zeros((self.height, self.rows.shape[-1]),
                        self.rows.dtype)
        return out.at[self.ids].add(self.rows)


jax.tree_util.register_pytree_node(
    SelectedRows,
    lambda s: ((s.ids, s.rows), s.height),
    lambda h, c: SelectedRows(c[0], c[1], h))


def embedding_grad(table: jax.Array, ids: jax.Array,
                   loss_fn: Callable[[jax.Array], jax.Array]
                   ) -> Tuple[jax.Array, SelectedRows]:
    """loss + SelectedRows gradient of an embedding lookup.

    ``loss_fn(rows)`` consumes the gathered rows [n, dim]. The table itself
    is never densely differentiated — the grad lives only on touched rows
    (the reference's sparse_update=True path)."""
    flat_ids = ids.reshape(-1).astype(jnp.int32)
    rows = jnp.take(table, flat_ids, axis=0)
    loss, d_rows = jax.value_and_grad(loss_fn)(rows)
    return loss, SelectedRows(flat_ids, d_rows, table.shape[0])


# ---------------------------------------------------------------------------
# sparse-row optimizers (SparseRowCpuMatrix sgdUpdate / adagrad analogs)
# ---------------------------------------------------------------------------


def sgd_update_rows(table: jax.Array, grad: SelectedRows,
                    lr: float) -> jax.Array:
    return table.at[grad.ids].add(-lr * grad.rows)


def adagrad_update_rows(table: jax.Array, accum: jax.Array,
                        grad: SelectedRows, lr: float,
                        epsilon: float = 1e-6
                        ) -> Tuple[jax.Array, jax.Array]:
    """Row-sparse Adagrad: O(n_rows * dim) work, no dense temporaries.

    Duplicate ids are pre-combined (segment-sum over the deduped slots)
    so the accumulator sees each touched row exactly once."""
    n = grad.ids.shape[0]
    uniq, inv = jnp.unique(grad.ids, size=n, fill_value=-1,
                           return_inverse=True)
    pad = uniq < 0
    safe = jnp.clip(uniq, 0, table.shape[0] - 1)
    combined = jax.ops.segment_sum(grad.rows, inv.reshape(-1),
                                   num_segments=n)
    combined = jnp.where(pad[:, None], 0.0, combined)
    acc_delta = jnp.square(combined)   # pad rows already zeroed above
    acc_rows = jnp.take(accum, safe, axis=0) + acc_delta
    step = lr * combined / (jnp.sqrt(acc_rows) + epsilon)
    tab_delta = jnp.where(pad[:, None], 0.0, -step)
    # pad slots are clipped to index 0; scatter-add with zeroed deltas is
    # well-defined under that collision (set would drop row 0's update)
    return (table.at[safe].add(tab_delta),
            accum.at[safe].add(acc_delta))


# ---------------------------------------------------------------------------
# mesh-sharded table + lookup
# ---------------------------------------------------------------------------


def shard_table(mesh, table, axis: str = "model"):
    """Place a [vocab, dim] table row-sharded over ``axis`` (the pserver
    block-partition analog; each shard owns vocab/n contiguous rows)."""
    return jax.device_put(table, NamedSharding(mesh, P(axis, None)))


def sharded_lookup(mesh, table: jax.Array, ids: jax.Array,
                   axis: str = "model",
                   batch_axis: Optional[str] = None) -> jax.Array:
    """Gather rows from a row-sharded table: owner-computes + psum.

    Each shard holds rows [lo, hi); it serves the ids it owns and
    contributes zeros for the rest; a single ``psum`` over the table axis
    assembles full rows on every participant. ``batch_axis`` optionally
    shards ``ids`` over the data axis too (each data-shard gets its own
    rows; the psum rides ICI)."""
    vocab = table.shape[0]
    n_shards = mesh.shape[axis]
    enforce_that(vocab % n_shards == 0,
                 f"vocab {vocab} must divide over {n_shards} '{axis}' shards",
                 context="sparse")
    per = vocab // n_shards

    id_spec = P(batch_axis) if batch_axis else P()

    def local(tab, idv):
        # tab: [per, dim] local rows; idv: local ids
        shard = jax.lax.axis_index(axis)
        lo = shard * per
        rel = idv.astype(jnp.int32) - lo
        mine = (rel >= 0) & (rel < per)
        rows = jnp.take(tab, jnp.clip(rel, 0, per - 1), axis=0)
        rows = jnp.where(mine[..., None], rows, 0.0)
        return jax.lax.psum(rows, axis)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis, None), id_spec),
                   out_specs=id_spec,
                   check_vma=False)
    return fn(table, ids)


def sharded_row_update(mesh, table: jax.Array, grad: SelectedRows,
                       lr: float, axis: str = "model") -> jax.Array:
    """Apply an SGD row update to a row-sharded table: every shard
    scatter-adds only the rows it owns (no gradient traffic for rows the
    shard doesn't hold — the sparse SendParameter analog)."""
    vocab = table.shape[0]
    n_shards = mesh.shape[axis]
    per = vocab // n_shards

    def local(tab, idv, rows):
        shard = jax.lax.axis_index(axis)
        lo = shard * per
        rel = idv.astype(jnp.int32) - lo
        mine = (rel >= 0) & (rel < per)
        contrib = jnp.where(mine[:, None], rows, 0.0)
        return tab.at[jnp.clip(rel, 0, per - 1)].add(-lr * contrib)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis, None), P(), P()),
                   out_specs=P(axis, None),
                   check_vma=False)
    return fn(table, grad.ids, grad.rows)


def alltoall_lookup(mesh, table: jax.Array, ids: jax.Array,
                    axis: str = "model") -> jax.Array:
    """Expert-parallel style lookup: ids are sharded over ``axis`` (each
    shard has its own query slice); rows come back via all_to_all-shaped
    traffic (here: all_gather of the per-shard queries + owner-computes +
    reduce_scatter). Bandwidth-optimal when queries are sharded."""
    vocab = table.shape[0]
    n_shards = mesh.shape[axis]
    per = vocab // n_shards
    enforce_that(ids.shape[0] % n_shards == 0,
                 "alltoall_lookup needs ids divisible over the axis",
                 context="sparse")

    def local(tab, idv):
        # idv: this shard's queries [b/n]. Gather everyone's queries,
        # serve owned rows, reduce_scatter the answers back.
        all_ids = jax.lax.all_gather(idv, axis, tiled=True)   # [b]
        shard = jax.lax.axis_index(axis)
        lo = shard * per
        rel = all_ids.astype(jnp.int32) - lo
        mine = (rel >= 0) & (rel < per)
        rows = jnp.take(tab, jnp.clip(rel, 0, per - 1), axis=0)
        rows = jnp.where(mine[..., None], rows, 0.0)
        return jax.lax.psum_scatter(rows, axis, scatter_dimension=0,
                                    tiled=True)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis, None), P(axis)),
                   out_specs=P(axis),
                   check_vma=False)
    return fn(table, ids)


# ---------------------------------------------------------------------------
# v2-API integration: a sparse updater for embedding parameters
# ---------------------------------------------------------------------------


class SparseEmbeddingUpdater:
    """Routes embedding parameters through row-sparse updates inside a
    training loop (the sparse_update=True ParamAttr path of the reference).

    ``apply(params, grads, lr, ids={...})`` updates marked params only on
    the rows named by that step's ids (SelectedRows + scatter-add —
    sharded when a mesh is given); unmarked params take the dense step.
    Without ids for a marked param it falls back to the dense update."""

    def __init__(self, mesh=None, sparse_params: Tuple[str, ...] = (),
                 axis: str = "model"):
        self.mesh = mesh
        self.sparse = set(sparse_params)
        self.axis = axis

    def apply(self, params: Dict[str, jax.Array],
              grads: Dict[str, jax.Array], lr: float,
              ids: Optional[Dict[str, jax.Array]] = None
              ) -> Dict[str, jax.Array]:
        ids = ids or {}
        out = {}
        for k, p in params.items():
            g = grads.get(k)
            if g is None:
                out[k] = p
            elif k in self.sparse and k in ids:
                row_ids = ids[k].reshape(-1).astype(jnp.int32)
                # jax.grad gives the scatter-summed dense grad; taking its
                # touched rows per occurrence would double-count duplicate
                # ids, so dedupe (pad slots masked to zero rows, not routed
                # to a real id)
                uniq = jnp.unique(row_ids, size=row_ids.shape[0],
                                  fill_value=-1)
                pad = uniq < 0
                safe = jnp.clip(uniq, 0, p.shape[0] - 1)
                rows = jnp.where(pad[:, None], 0.0,
                                 jnp.take(g, safe, axis=0))
                sel = SelectedRows(safe, rows, p.shape[0])
                if self.mesh is not None:
                    out[k] = sharded_row_update(self.mesh, p, sel, lr,
                                                self.axis)
                else:
                    out[k] = sgd_update_rows(p, sel, lr)
            else:
                out[k] = p - lr * g
        return out
