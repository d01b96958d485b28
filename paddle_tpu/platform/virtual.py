"""Virtual-CPU platform forcing, shared by every driver-facing entry
point (__graft_entry__, tests/conftest.py).

The simulation trick: XLA's host platform splits into N virtual devices
when ``--xla_force_host_platform_device_count=N`` is set BEFORE the CPU
client is created — the in-process multi-node test strategy (reference:
pserver/test/test_ParameterServer2.cpp spins servers+clients in one
process). Two environment hazards make this fiddly:

- jax may already be imported with its config snapshotted, so the
  JAX_PLATFORMS env var alone is read too late — jax.config must be
  updated too;
- XLA_FLAGS may already carry a DIFFERENT device count, which must be
  replaced, not merely detected.
"""

from __future__ import annotations

import re
from typing import Dict, MutableMapping, Optional

_FLAG = "--xla_force_host_platform_device_count"


def set_device_count_flag(environ: MutableMapping[str, str],
                          n_devices: int) -> None:
    """Set (or REPLACE) the virtual-device-count flag in environ['XLA_FLAGS'].

    Presence-checking is not enough: a pre-existing `=1` from some other
    harness would silently win and the n-device mesh build would fail."""
    flags = environ.get("XLA_FLAGS", "")
    if _FLAG in flags:
        flags = re.sub(rf"{_FLAG}=\d+", f"{_FLAG}={n_devices}", flags)
    else:
        flags = f"{flags} {_FLAG}={n_devices}".strip()
    environ["XLA_FLAGS"] = flags


def virtual_cpu_env(base_env: Dict[str, str], n_devices: int,
                    extra_pythonpath: Optional[str] = None) -> Dict[str, str]:
    """Child-process env with an n-device CPU platform forced."""
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    set_device_count_flag(env, n_devices)
    parts = ([extra_pythonpath] if extra_pythonpath else []) \
        + env.get("PYTHONPATH", "").split(":")
    env["PYTHONPATH"] = ":".join(p for p in parts if p)
    return env


def force_cpu_inproc(n_devices: int) -> bool:
    """Force an n-device virtual CPU platform in THIS process.

    Returns True when the current process can run on the virtual CPU mesh;
    False when a non-CPU backend is already initialized (too late — the
    caller must re-exec in a clean subprocess, see virtual_cpu_env)."""
    import os

    set_device_count_flag(os.environ, n_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    from jax._src import xla_bridge

    if not bool(getattr(xla_bridge, "_backends", None)):
        # env alone is not enough: jax may be pre-imported with its
        # config already snapshotted — set it explicitly
        jax.config.update("jax_platforms", "cpu")
        return True
    try:
        return (jax.default_backend() == "cpu"
                and jax.device_count() >= n_devices)
    except Exception:
        return False
