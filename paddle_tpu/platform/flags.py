"""Global flag registry — the gflags analog.

Reference: paddle/utils/Flags.cpp:18-81 centralizes process flags (use_gpu,
trainer_count, ports, log_period, ...) and python/paddle/v2/__init__.py:65-86
surfaces them via ``paddle.init(**kwargs)`` + ``PADDLE_INIT_*`` env vars.

Here flags are a typed registry populated from defaults < environment
(``PADDLE_TPU_<NAME>``) < ``init(**kwargs)``. TPU-era flags replace the GPU/
pserver ones: mesh axis sizes instead of trainer_count/num_gradient_servers.

A flag is a setting of the PROCESS (seed, logging, mesh, precision and kernel
paths, tracing and the auditors).  What an engine, a fleet or a trainer is
built with is a keyword of its constructor, with its default there.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict

from paddle_tpu.platform.enforce import EnforceError

_ENV_PREFIX = "PADDLE_TPU_"


@dataclass
class _FlagSpec:
    name: str
    default: Any
    parser: Callable[[str], Any]
    help: str


def _parse_bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


class _Flags:
    """Typed global flags with attribute access (``FLAGS.log_period``)."""

    def __init__(self):
        object.__setattr__(self, "_specs", {})
        object.__setattr__(self, "_values", {})

    def define(self, name: str, default: Any, help: str = "", parser=None) -> None:
        if parser is None:
            if isinstance(default, bool):
                parser = _parse_bool
            elif isinstance(default, int):
                parser = int
            elif isinstance(default, float):
                parser = float
            else:
                parser = str
        self._specs[name] = _FlagSpec(name, default, parser, help)
        env = os.environ.get(_ENV_PREFIX + name.upper())
        self._values[name] = parser(env) if env is not None else default

    def set(self, name: str, value: Any) -> None:
        if name not in self._specs:
            raise EnforceError(f"unknown flag {name!r}", context="flags")
        self._values[name] = value

    def update(self, **kwargs) -> None:
        for k, v in kwargs.items():
            self.set(k, v)

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._values)

    def __getattr__(self, name: str) -> Any:
        values = object.__getattribute__(self, "_values")
        if name in values:
            return values[name]
        raise AttributeError(name)

    def __setattr__(self, name: str, value: Any) -> None:
        self.set(name, value)


FLAGS = _Flags()

# Core process flags (reference: paddle/utils/Flags.cpp:18-81, re-scoped for TPU).
FLAGS.define("seed", 0, "global RNG seed (0 = nondeterministic per-process)")
FLAGS.define("log_period", 100, "print batch stats every N batches")
FLAGS.define("show_parameter_stats_period", 0, "print per-parameter grad stats every N batches")
FLAGS.define("check_nan", False, "enable jax debug_nans (FE_INVALID tripwire analog)")
FLAGS.define("platform", "", "force a jax platform ('cpu'/'tpu'); empty = auto")
FLAGS.define("mesh_shape", "", "comma dims for the device mesh, e.g. '8' or '2,4'")
FLAGS.define("mesh_axes", "data", "comma axis names matching mesh_shape")
FLAGS.define("use_bf16", True, "compute matmuls/convs in bfloat16 on TPU")
FLAGS.define("use_pallas", True,
             "use the hand-written pallas fused LSTM/GRU cells of "
             "ops/rnn.py; off = their plain JAX/XLA scans with identical "
             "semantics (attention has no such switch)")
FLAGS.define("bf16_activations", True,
             "store inter-layer image activations in bfloat16 (halves HBM "
             "traffic between fused conv blocks; stats/losses stay f32). "
             "Only active when use_bf16 is also on.")
FLAGS.define("bf16_dense_activations", False,
             "store fc/embedding/attention outputs (the transformer "
             "residual stream) in bfloat16. Norm statistics and losses "
             "still reduce in f32. Off by default: flip for bandwidth-"
             "bound dense models. Only active when use_bf16 is also on.")
FLAGS.define("attn_pv_f32", False,
             "keep the flash-attention PV-matmul operands (softmax probs "
             "and V, plus the backward dS/P operands) in f32 instead of "
             "the tiles' native dtype. Removes the bf16 softmax-prob "
             "rounding for accuracy-sensitive runs at the cost of the "
             "slower f32 MXU path for those matmuls.")
FLAGS.define("obs_trace", False,
             "request-scoped span tracing (paddle_tpu.obs): when on, "
             "ServingEngine/FleetRouter construct a real Tracer on "
             "their injected clock and every request lifecycle edge "
             "(submit/route/admit/prefill chunk/decode tick/preempt/"
             "resubmit/terminal), fleet lease/fence/reap transition, "
             "and PagePool alloc/ref/free lands on one exportable "
             "timeline (python -m paddle_tpu.obs export -> Perfetto). "
             "Checked at CONSTRUCTION time (the audit_jit idiom): set "
             "it before building the engine/fleet being traced. Off = "
             "the shared NULL_TRACER, a true no-op — zero events, zero "
             "clock reads, zero extra compiles or host syncs on the "
             "decode tick.")
FLAGS.define("obs_keep_all", True,
             "flag-built tracers retain the FULL event list for export "
             "(the replay/debug default). A long-running service should "
             "set this off: only the bounded flight-recorder ring "
             "(obs_ring_size) is kept, so tracing memory cannot grow "
             "without bound; save()/export then cover the ring's most-"
             "recent window.")
FLAGS.define("obs_ring_size", 4096,
             "flight-recorder depth: the tracer keeps this many most-"
             "recent events in a bounded ring, dumped to a postmortem "
             "file whenever a conservation invariant (PAGE-LEAK/"
             "REF-LEAK/FLEET-LEAK) trips.", parser=int)
FLAGS.define("obs_dump_dir", "/tmp/paddle_tpu_obs",
             "directory for flight-recorder postmortem dumps; each dump "
             "prints a grep-able 'OBS-POSTMORTEM: <path>' line that "
             "tools_tier1.sh surfaces on any ladder exit >= 3.")
FLAGS.define("fluid_verify", "warn",
             "static program verification before Executor.run compiles "
             "a fluid Program: 'warn' (default) logs every diagnostic "
             "the paddle_tpu.analysis verifier finds, 'strict' raises "
             "on ERROR diagnostics (shape/dtype conflicts, "
             "def-before-use, dangling fetches, duplicate writers), "
             "'off' disables.  Runs once per compiled (program, "
             "feed-shape) specialization, so steady state pays nothing.")
FLAGS.define("jit_audit", False,
             "retrace auditing: when on, audit_jit-instrumented call "
             "sites (serving decode/prefill, trainer steps, inference, "
             "ZeRO placement, fluid executor) record abstract-signature "
             "-> compile events in paddle_tpu.analysis.retrace.auditor() "
             "and flag compiles after seal() — or recompiles of an "
             "already-compiled signature — as RETRACE diagnostics.  "
             "Checked at wrap time: set it BEFORE constructing the "
             "engine/trainer being audited.  Off = bare jax.jit, zero "
             "overhead.")
FLAGS.define("xla_audit_const_bytes", 65536,
             "const-capture threshold for the jaxpr auditor (python -m "
             "paddle_tpu.analysis xla): an array larger than this many "
             "bytes baked into an audited site's executable as a jaxpr "
             "const (instead of an argument) is an XLA-AUDIT error — "
             "consts are re-baked on every compile, duplicated per "
             "specialization, and invisible to donation. Per-site "
             "override: SiteContract(const_bytes=...).", parser=int)
FLAGS.define("xla_audit_big_arg_bytes", 1048576,
             "donation-candidate threshold for the jaxpr auditor: a "
             "non-donated argument larger than this many bytes whose "
             "avals all match unclaimed outputs is reported (WARNING) "
             "as a donation candidate — if the caller overwrites it "
             "with the result (the repo's step idiom), donating saves "
             "a full copy. Per-site override: "
             "SiteContract(big_arg_bytes=...).", parser=int)
FLAGS.define("conc_audit_max_schedules", 64,
             "per-drive schedule budget for the concurrency auditor's "
             "schedule-permutation explorer (python -m "
             "paddle_tpu.analysis concurrency): each chaos drive "
             "replays at most this many permuted intra-tick schedules "
             "(single-tick deltas first, then depth-2 combinations) "
             "against its canonical fingerprint. The default explores "
             "well past the >=50-interleavings-per-drive bar the audit "
             "documents; raise it for deeper soak runs, lower it only "
             "for quick smoke iterations.", parser=int)
FLAGS.define("shard_audit_virtual_devices", 8,
             "virtual CPU device count the sharding-audit CLI (python "
             "-m paddle_tpu.analysis sharding) forces before backend "
             "init, so its ZeRO placement drive runs on a real "
             "multi-device 'data' axis without TPU hardware (the "
             "tests/conftest.py trick). Only effective when the jax "
             "backend has not initialized yet; <=1 disables the "
             "forcing and the placement drive degrades to a loud "
             "'not audited' notice.", parser=int)
FLAGS.define("log_level", "INFO", "logging level")
