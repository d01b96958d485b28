"""Shared helpers for pallas TPU kernels."""

from __future__ import annotations

import jax


def interpret_default() -> bool:
    """Run pallas kernels in interpret mode on CPU (tests, virtual CPU
    meshes); any other backend compiles them natively — and a kernel
    that fails to compile there raises, nothing falls back."""
    return jax.default_backend() == "cpu"


def per_device(fn, mesh):
    """``fn`` run whole on every device of ``mesh``, over replicated
    operands and with a replicated result.

    GSPMD cannot partition a Mosaic kernel — on the TPU a pallas call
    inside a jit that spans several devices fails to lower ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in
    a shard_map") — so the layers that call one go through here.
    Sequence feeds are replicated over the mesh (``SGD._shard_feeds``),
    which makes "every device runs the whole call" the placement the
    surrounding program already has.  No mesh, or one device: ``fn``
    itself."""
    if mesh is None or mesh.size == 1:
        return fn
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    return shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                     check_vma=False)
