"""ZeRO-1 cross-replica sharded weight update (arXiv 2004.13336).

The replicated data-parallel path keeps the full optimizer state on every
replica and all-reduces gradients before the update.  "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training" replaces
that with: reduce-scatter the gradients, update a 1/N shard of every
parameter per replica, all-gather the updated weights — cutting
optimizer-state HBM by N x and swapping one all-reduce for the cheaper
reduce-scatter + all-gather pair over ICI.

Formulation here: each parameter is flattened, zero-padded to a multiple of
the ``data``-axis size, and viewed as a 1-D array sharded over that axis.
Inside the jitted train step the shard view is expressed with
``with_sharding_constraint`` — under GSPMD the grad constraint lowers the
preceding psum into a reduce-scatter and the replicated constraint on the
updated flat weights lowers into an all-gather, i.e. exactly the paper's
``psum_scatter`` / ``all_gather`` pair without hand-splitting the step into
a shard_map.  Optimizer slot state lives PERMANENTLY in the flat sharded
layout (allocated sharded at ``init_state``, never replicated), so every
existing optimizer's elementwise ``_update`` works through the shard view
unchanged — one wrapper, not N forks.

Precedence (mirrors :func:`paddle_tpu.parallel.api.param_sharding`): a
param with an explicit ``ParamAttr.sharding`` — or one the ``zero_axis``
largest-dim rule already shards — keeps its declared layout and passes
through untouched; static params pass through too (their state never
changes, so sharding it would buy nothing and cost a per-step gather).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

from paddle_tpu.platform.enforce import enforce_that

# state keys holding one entry per parameter name (the trees the plan
# re-lays-out); everything else in an optimizer state (step, sm scalars,
# avg_count) is layout-free and passes through untouched
_PARAM_KEYED = ("avg", "prune_masks")


@dataclass(frozen=True)
class ZeroEntry:
    """Per-parameter shard layout: ``shape`` flattens to ``size`` elements,
    zero-padded to ``padded`` (a multiple of the axis size) when sharded."""

    shape: Tuple[int, ...]
    size: int
    padded: int
    sharded: bool


class ZeroPlan:
    """Shard plan for ZeRO-1 optimizer-state sharding over one mesh axis.

    Traced-side (inside jit): :meth:`shard_tree` / :meth:`gather_tree`
    re-layout params+grads around the optimizer update.  Placement-side
    (outside jit): :meth:`place_flat` / :meth:`shard_state` /
    :meth:`gather_state` move host/checkpoint arrays into and out of the
    flat sharded layout.
    """

    def __init__(self, mesh, axis: str, entries: Dict[str, ZeroEntry]):
        self.mesh = mesh
        self.axis = axis
        self.entries = entries

    # -- shardings ---------------------------------------------------------

    def flat_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(self.axis))

    def replicated_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def is_sharded(self, name: str) -> bool:
        e = self.entries.get(name)
        return e is not None and e.sharded

    # -- traced-side views (used inside the jitted step) -------------------

    def shard_view(self, name: str, x):
        """Full tensor -> padded flat view constrained to 1/N per replica.
        On a gradient fresh out of a psum this is the reduce-scatter; on a
        replicated param it is a local slice."""
        e = self.entries.get(name)
        if e is None or not e.sharded:
            return x
        import jax.numpy as jnp

        flat = x.reshape(-1)
        if e.padded != e.size:
            flat = jnp.pad(flat, (0, e.padded - e.size))
        return _constrain(flat, self.flat_sharding())

    def gather_view(self, name: str, x):
        """Padded flat shard view -> full replicated tensor (the all-gather
        of the updated weights)."""
        e = self.entries.get(name)
        if e is None or not e.sharded:
            return x
        full = _constrain(x, self.replicated_sharding())
        return full[:e.size].reshape(e.shape)

    def shard_tree(self, tree: Dict[str, Any]) -> Dict[str, Any]:
        return {k: self.shard_view(k, v) for k, v in tree.items()}

    def gather_tree(self, tree: Dict[str, Any]) -> Dict[str, Any]:
        return {k: self.gather_view(k, v) for k, v in tree.items()}

    # -- placement-side (init / checkpoint resume) -------------------------

    def _host_full(self, v) -> np.ndarray:
        """Full host copy of ``v``.  A SHARDED device array (the
        gather-on-save path walking flat 1/N slot shards) goes through
        the compiled ``zero.replicate`` identity — one XLA all-gather
        then a single host read, instead of np.asarray's per-shard
        host copies — which also covers the multi-process case where
        np.asarray on non-addressable devices would raise.  Replicated
        or single-device arrays read straight through."""
        import jax

        sh = self.replicated_sharding()
        if isinstance(v, jax.Array) and \
                (not v.is_fully_addressable
                 or (not v.is_fully_replicated and _mesh_spanning(v, sh))):
            v = _identity_jit(sh, "zero.replicate",
                              in_spec=(self.axis,))(v)
            return np.asarray(v.addressable_data(0))
        return np.asarray(v)

    def place_flat(self, name: str, v):
        """Place a host/device array (full-shape OR already-flat) into the
        flat sharded layout on the mesh."""
        import jax

        e = self.entries[name]
        if not e.sharded:
            return v
        if isinstance(v, jax.Array) and tuple(v.shape) == (e.padded,):
            # already-flat device state being RE-placed (a resume, or
            # _place_on_mesh over live slots): one compiled reshard
            # identity instead of gathering to host and scattering back
            # per tensor — the re-place the sharding auditor flagged
            return _constrain(v, self.flat_sharding())
        host = self._host_full(v)
        if host.shape != (e.padded,):
            enforce_that(host.size == e.size,
                         f"zero shard of {name!r}: got {host.shape}, "
                         f"expected {e.shape} or flat ({e.padded},)",
                         context="zero")
            flat = host.reshape(-1)
            if e.padded != e.size:
                flat = np.concatenate(
                    [flat, np.zeros(e.padded - e.size, flat.dtype)])
            host = flat
        return _put_global(host, self.flat_sharding())

    def shard_state(self, state: Any) -> Any:
        """Re-lay-out an optimizer state (full-shape host arrays from a
        checkpoint, or an already-flat state being re-placed) into the flat
        sharded layout.  Non-param-keyed entries pass through."""
        if not isinstance(state, dict):
            return state
        out = dict(state)
        if "slots" in out:
            out["slots"] = {
                s: {k: (self.place_flat(k, v) if k in self.entries else v)
                    for k, v in d.items()}
                for s, d in out["slots"].items()}
        for key in _PARAM_KEYED:
            if key in out:
                out[key] = {
                    k: (self.place_flat(k, v) if k in self.entries else v)
                    for k, v in out[key].items()}
        return out

    def _unflatten(self, name: str, v):
        e = self.entries[name]
        if not e.sharded:
            return self._host_full(v)
        host = self._host_full(v)  # gathers shards on the host
        if host.shape == e.shape:
            return host  # already layout-independent (zero was off)
        enforce_that(host.shape == (e.padded,),
                     f"zero gather of {name!r}: got {host.shape}, "
                     f"expected ({e.padded},)", context="zero")
        return host[:e.size].reshape(e.shape)

    def gather_state(self, state: Any) -> Any:
        """Inverse of :meth:`shard_state`: flat shard views back to
        full-shape host arrays, so checkpoints stay layout-independent
        (a zero=1 save loads under zero=0 and vice versa)."""
        if not isinstance(state, dict):
            return state
        out = dict(state)
        if "slots" in out:
            out["slots"] = {
                s: {k: (self._unflatten(k, v) if k in self.entries else v)
                    for k, v in d.items()}
                for s, d in out["slots"].items()}
        for key in _PARAM_KEYED:
            if key in out:
                out[key] = {
                    k: (self._unflatten(k, v) if k in self.entries else v)
                    for k, v in out[key].items()}
        return out


def build_zero_plan(mesh, params: Dict[str, Any], specs=None,
                    axis: str = "data",
                    zero_axis: Optional[str] = None) -> ZeroPlan:
    """Build the per-tensor shard plan for ZeRO-1 over ``axis``.

    Reuses :func:`param_sharding` for the precedence rules: only params it
    leaves fully replicated (no explicit ``ParamAttr.sharding``, not taken
    by the ``zero_axis`` largest-dim rule) get the flat 1/N layout.
    Non-divisible sizes pad up to the axis size; scalars degenerate to one
    real element plus padding (still correct, trivially small).
    """
    from paddle_tpu.parallel.api import param_sharding

    enforce_that(axis in mesh.axis_names, f"no axis {axis!r} in mesh",
                 context="zero")
    n = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis])
    declared = param_sharding(mesh, params, specs=specs, zero_axis=zero_axis)
    entries = {}
    for name, v in params.items():
        attr = specs[name].attr if specs is not None and name in specs else None
        static = bool(attr is not None and attr.is_static)
        explicit = attr is not None and attr.sharding is not None
        # replicated = no dim actually carries a mesh axis (the zero_axis
        # largest-dim rule leaves non-divisible params at P(None,...), which
        # is logically replicated and still wants its slots ZeRO-sharded)
        replicated = not explicit and all(
            a is None for a in tuple(declared[name].spec))
        size = int(np.prod(np.shape(v))) if np.ndim(v) else 1
        sharded = replicated and not static and n > 1
        padded = -(-size // n) * n if sharded else size
        entries[name] = ZeroEntry(shape=tuple(np.shape(v)), size=size,
                                  padded=padded, sharded=sharded)
    return ZeroPlan(mesh, axis, entries)


def host_tree(tree):
    """Full host (numpy) copy of a pytree of arrays — the checkpoint
    snapshot path (``checkpoint.snapshot_checkpoint``).  Replicated and
    single-device arrays read straight through ``np.asarray``; a
    physically-sharded mesh-spanning array routes through the compiled
    ``zero.host_gather`` identity — one XLA all-gather then a single
    host read instead of per-shard host copies — which also covers the
    multi-process case where ``np.asarray`` on non-addressable devices
    would raise (the same contract as :meth:`ZeroPlan._host_full`, made
    plan-free so params/model-state snapshot through it too)."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P

    def leaf(v):
        if v is None:
            return None
        if isinstance(v, jax.Array) and \
                (not v.is_fully_addressable or not v.is_fully_replicated):
            mesh = getattr(v.sharding, "mesh", None)
            if mesh is not None:
                sh = NamedSharding(mesh, P())
                if _mesh_spanning(v, sh):
                    v = _identity_jit(sh, "zero.host_gather")(v)
                    return np.asarray(v.addressable_data(0))
        return np.asarray(v)

    return jax.tree.map(leaf, tree)


def opt_state_bytes_per_device(tree) -> int:
    """Exact per-device bytes of a (possibly sharded) state pytree — the
    acceptance metric for the N x optimizer-state reduction."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and getattr(leaf, "sharding", None) \
                is not None:
            shard = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard)) * leaf.dtype.itemsize
        else:
            total += np.asarray(leaf).nbytes
    return total


@functools.lru_cache(maxsize=None)
def _identity_jit(sharding, site: str, in_spec=None):
    """One compiled identity per (sharding, site, declared-input-spec) —
    per-call wrappers would re-trace an identical signature every call
    (a real retrace the audit sites would rightly flag)."""
    from paddle_tpu.analysis.retrace import SiteContract, audit_jit

    # collectives (the resharding all-gather/scatter the out_shardings
    # lower into) are the POINT of a placement site — the jaxpr auditor
    # reports them as INFO and the sharding auditor costs them against
    # the declared specs: out = the target sharding's spec; in = the
    # caller-declared source placement (None = unknown, costed 0)
    spec = getattr(sharding, "spec", ())
    return audit_jit(lambda a: a, site=site, out_shardings=sharding,
                     xla_contract=SiteContract(
                         allow_collectives=True,
                         in_specs=(in_spec,) if in_spec is not None
                         else None,
                         out_specs=(tuple(spec),),
                         mesh_axes=tuple(
                             (str(a), int(n)) for a, n in
                             dict(sharding.mesh.shape).items())
                         if getattr(sharding, "mesh", None) is not None
                         else ()))


def _mesh_spanning(v, sharding) -> bool:
    """True when the compiled identity may consume ``v`` directly: the
    array is either not fully addressable (multi-process — put_global
    could not even read it) or already lives on exactly the target
    mesh's devices.  A committed array on SOME OTHER device set (a
    single-device checkpoint staging buffer, a sub-mesh) would make the
    jit raise 'incompatible devices', so it takes the host path."""
    if not v.is_fully_addressable:
        return True
    return set(v.sharding.device_set) == set(sharding.mesh.devices.flat)


def _constrain(x, sharding):
    """Sharding constraint that works both under trace (the in-step
    reduce-scatter / all-gather) and eagerly (placement — the compiled
    reshard identity keeps mesh-resident device arrays on device and is
    multi-process safe; host values and off-mesh arrays go through
    put_global)."""
    import jax

    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    if isinstance(x, jax.Array) and _mesh_spanning(x, sharding):
        return _identity_jit(sharding, "zero.reshard")(x)
    return _put_global(x, sharding)


def _put_global(v, sharding):
    from paddle_tpu.parallel.api import put_global

    return put_global(v, sharding)
