"""Device & mesh discovery — the TPUPlace/DeviceContext analog.

Reference: paddle/platform/place.h (CPUPlace/GPUPlace) and paddle.init()
(python/paddle/v2/__init__.py:65-86) which parsed use_gpu/trainer_count into
gflags. On TPU the analog is: discover the chips JAX sees, build a
``jax.sharding.Mesh`` over them (ICI within a slice, DCN across slices), and
hold it as the process-global default mesh every parallel component uses.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from paddle_tpu.platform.compile_cache import enable_compile_cache
from paddle_tpu.platform.enforce import EnforceError, enforce_that
from paddle_tpu.platform.flags import FLAGS

_state = {
    "initialized": False,
    "mesh": None,
    "devices": None,
}


def _parse_mesh_flags() -> Tuple[Optional[Tuple[int, ...]], Tuple[str, ...]]:
    shape = None
    if FLAGS.mesh_shape:
        shape = tuple(int(x) for x in str(FLAGS.mesh_shape).split(",") if x)
    axes = tuple(a.strip() for a in str(FLAGS.mesh_axes).split(",") if a.strip())
    return shape, axes


def init(**kwargs) -> None:
    """Initialize the framework: set flags, discover devices, build the mesh.

    ``paddle.init(use_gpu=..., trainer_count=...)`` analog. Keyword args are
    flag overrides (see platform.flags); mesh construction reads ``mesh_shape``
    / ``mesh_axes``. Safe to call more than once — later calls rebuild the mesh.

    Multi-host: pass ``coordinator_address=`` (plus optional
    ``num_processes=``/``process_id=``) to join a multi-host job via
    JAX's coordination service — the etcd-registration analog
    (go/pserver/etcd_client.go:67-166); afterwards jax.devices() spans
    every host and meshes/collectives ride ICI within a slice and DCN
    across (see parallel.mesh.hybrid_mesh).
    """
    import jax  # deferred so flag 'platform' can take effect first

    coord = kwargs.pop("coordinator_address", None)
    nproc = kwargs.pop("num_processes", None)
    pid = kwargs.pop("process_id", None)
    enforce_that(coord is not None or (nproc is None and pid is None),
                 "num_processes/process_id need coordinator_address= — "
                 "refusing to silently run single-host", context="init")
    if coord is not None:
        prev = _state.get("distributed")
        enforce_that(prev is None or prev == coord,
                     f"jax.distributed already initialized against {prev}; "
                     f"cannot re-initialize against {coord}", context="init")
        if prev is None:
            dist_kw = {"coordinator_address": coord}
            if nproc is not None:
                dist_kw["num_processes"] = int(nproc)
            if pid is not None:
                dist_kw["process_id"] = int(pid)
            try:
                jax.distributed.initialize(**dist_kw)
            except RuntimeError as e:
                # most common cause: some paddle/jax API already touched
                # the backend (jax.devices() etc.) — surface the ordering
                # requirement instead of the deep-JAX error
                raise EnforceError(
                    "paddle.init(coordinator_address=...) must be the "
                    "FIRST paddle/jax call in the process (the JAX "
                    f"backend is already initialized): {e}",
                    context="init") from e
            _state["distributed"] = coord

    FLAGS.update(**kwargs)
    if FLAGS.platform:
        jax.config.update("jax_platforms", FLAGS.platform)
    enable_compile_cache()
    if FLAGS.check_nan:
        jax.config.update("jax_debug_nans", True)

    devices = jax.devices()
    _state["devices"] = devices

    shape, axes = _parse_mesh_flags()
    if shape is None:
        shape = (len(devices),)
    if len(axes) < len(shape):
        raise EnforceError(
            f"mesh_axes {axes} shorter than mesh_shape {shape}", context="init"
        )
    axes = axes[: len(shape)]
    n_needed = int(np.prod(shape))
    enforce_that(
        n_needed <= len(devices),
        f"mesh_shape {shape} needs {n_needed} devices, found {len(devices)}",
        context="init",
    )
    mesh_devices = np.asarray(devices[:n_needed]).reshape(shape)
    _state["mesh"] = jax.sharding.Mesh(mesh_devices, axes)
    _state["initialized"] = True


def is_initialized() -> bool:
    return _state["initialized"]


def _ensure_init() -> None:
    if not _state["initialized"]:
        init()


def default_mesh():
    """The process-global device mesh (builds a 1-D 'data' mesh on demand)."""
    _ensure_init()
    return _state["mesh"]


def set_default_mesh(mesh) -> None:
    _state["mesh"] = mesh
    _state["initialized"] = True


def device_count() -> int:
    _ensure_init()
    return len(_state["devices"])


def devices() -> Sequence:
    _ensure_init()
    return list(_state["devices"])


def platform_name() -> str:
    _ensure_init()
    return _state["devices"][0].platform
