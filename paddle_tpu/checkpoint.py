"""Checkpoint/resume with optimizer state.

Reference analog: per-pass parameter dirs ``pass-%05d`` written by
ParamUtil::saveParameters (trainer/ParamUtil.h:77-96), resume via
--start_pass/--init_model_path (ParamUtil.h:108-111), and the Gen-cloud
optimizer-state-inclusive checkpoints with md5+meta written atomically
(go/pserver/service.go:76-152, OptimizerConfig.proto *OptimizerState).

Layout per pass::

    <dir>/pass-00007/
        params.tar      # weights (v2 Parameters tar format)
        state.pkl       # optimizer slots + model state (np arrays)
        meta.json       # pass id, md5 of both blobs, timestamp, cursor

Commit protocol (the Go pserver's tmp+rename path, made kill-precise):

1. ``params.tar`` is written to a tempfile and renamed into place;
2. ``state.pkl`` likewise;
3. ``meta.json`` — carrying the md5 of both blobs — is written LAST,
   again tmp+rename.

A checkpoint exists only once its meta commits: a kill at any earlier
point leaves a meta-less dir that every reader skips (the previous
checkpoint stays ``latest``), and a kill mid-prune or a torn blob is
caught by the md5 verify and rejected with a grep-able ``CKPT-CORRUPT``
line instead of crashing the resume.

The save is split in two halves so a background writer can own the slow
one (:class:`paddle_tpu.resilience.AsyncCheckpointer`):

- :func:`snapshot_checkpoint` — device -> host copy (the only part that
  must stall training; ZeRO shard plans gather through the compiled
  ``zero.replicate`` identity);
- :func:`write_checkpoint` — pure disk I/O over the host snapshot,
  thread-safe, honoring the commit protocol above.

``extra_meta`` may carry a ``cursor`` dict (pass id, step-in-pass,
global step, rng state, task-queue position) — the step-granular resume
contract ``trainer.SGD.train(resume=True)`` reads back.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from paddle_tpu.parameters import Parameters
from paddle_tpu.platform.enforce import EnforceError, enforce_that

_PASS_RE = re.compile(r"^pass-(\d{5})$")

# write_checkpoint announces these phases to its commit_hook, in order;
# a fault plan killing at "meta" simulates the classic torn save: both
# blobs durable, meta missing, previous checkpoint still latest
COMMIT_PHASES = ("params", "state", "meta", "done")


def _to_numpy_tree(tree):
    from paddle_tpu.parallel.zero import host_tree

    return host_tree(tree)


def _md5(path: str) -> str:
    h = hashlib.md5()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _atomic_write(path: str, writer) -> None:
    d = os.path.dirname(path)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            writer(f)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def pass_dir(root: str, pass_id: int) -> str:
    return os.path.join(root, f"pass-{pass_id:05d}")


# ---------------------------------------------------------------------------
# snapshot (device -> host) / write (host -> disk) split
# ---------------------------------------------------------------------------


@dataclass
class HostCheckpoint:
    """A fully host-resident checkpoint payload: everything
    :func:`write_checkpoint` needs, holding NO device buffers — safe to
    hand to a background writer thread while the training loop keeps
    donating its device state."""

    params: Dict[str, np.ndarray]
    opt_state: Any = None
    model_state: Any = None


def snapshot_checkpoint(parameters, opt_state: Any = None,
                        model_state: Any = None,
                        shard_plan: Any = None) -> HostCheckpoint:
    """Device -> host copy of the full training state (the only phase of
    an async save that stalls the train loop).  ``shard_plan`` (a
    ``parallel.zero.ZeroPlan``): ZeRO-1 flat slot shards gather back to
    full tensor shapes through the plan's compiled-identity path so the
    artifact stays layout-independent — a zero=1 save loads under
    zero=0 (or a different mesh size) and vice versa."""
    if shard_plan is not None and opt_state is not None:
        opt_state = shard_plan.gather_state(opt_state)
    params = parameters.as_dict() if hasattr(parameters, "as_dict") \
        else dict(parameters)
    return HostCheckpoint(params=_to_numpy_tree(params),
                          opt_state=_to_numpy_tree(opt_state),
                          model_state=_to_numpy_tree(model_state))


def write_checkpoint(root: str, pass_id: int, host: HostCheckpoint,
                     extra_meta: Optional[Dict] = None,
                     commit_hook: Optional[Callable[[str], None]] = None
                     ) -> str:
    """Write a host snapshot to ``pass_dir(root, pass_id)`` under the
    tmp+rename+md5 commit protocol (meta.json LAST — see module doc).
    Pure disk I/O: thread-safe against a training loop that keeps
    running, and re-entrant over a torn dir from an earlier kill (the
    same pass id simply overwrites the debris).

    ``commit_hook`` is called with each :data:`COMMIT_PHASES` name just
    BEFORE that phase's write ("done" fires after the meta commit) — the
    fault-injection seam ``TrainFaultPlan.save_hook`` uses to kill a
    save at a chosen point."""
    hook = commit_hook if commit_hook is not None else (lambda phase: None)
    d = pass_dir(root, pass_id)
    os.makedirs(d, exist_ok=True)
    params_path = os.path.join(d, "params.tar")
    state_path = os.path.join(d, "state.pkl")
    hook("params")
    _atomic_write(params_path, lambda f: _params_to_tar(host.params, f))
    hook("state")
    _atomic_write(state_path, lambda f: pickle.dump(
        {"opt_state": host.opt_state,
         "model_state": host.model_state}, f))
    meta = {"pass_id": pass_id,
            "params_md5": _md5(params_path),
            "state_md5": _md5(state_path),
            "timestamp": time.time()}
    meta.update(extra_meta or {})
    hook("meta")
    _atomic_write(os.path.join(d, "meta.json"),
                  lambda f: f.write(json.dumps(meta).encode()))
    hook("done")
    return d


def _params_to_tar(host_params: Dict[str, np.ndarray], f) -> None:
    """Write a host param dict in the v2 Parameters tar format (one
    writer: delegates to Parameters.to_tar so the on-disk shape cannot
    diverge between the sync and async save paths)."""
    p = Parameters()
    p._values.update(host_params)
    p.to_tar(f)


def save_checkpoint(root: str, pass_id: int, parameters: Parameters,
                    opt_state: Any = None, model_state: Any = None,
                    extra_meta: Optional[Dict] = None,
                    shard_plan: Any = None,
                    commit_hook: Optional[Callable[[str], None]] = None
                    ) -> str:
    """Synchronous save: snapshot + write in one call (the original
    entry point; the AsyncCheckpointer calls the two halves itself)."""
    return write_checkpoint(
        root, pass_id,
        snapshot_checkpoint(parameters, opt_state=opt_state,
                            model_state=model_state, shard_plan=shard_plan),
        extra_meta=extra_meta, commit_hook=commit_hook)


# ---------------------------------------------------------------------------
# verify / load / prune
# ---------------------------------------------------------------------------


def _pass_ids(root: str) -> List[int]:
    if not os.path.isdir(root):
        return []
    return sorted(int(m.group(1)) for name in os.listdir(root)
                  if (m := _PASS_RE.match(name)))


# committed checkpoint dirs are immutable (same-id rewrites go through
# tmp+rename, changing inode mtimes), so a successful verify is cached
# by the three files' stat signature — repeat prunes/loads over the
# same artifacts skip the full md5 read-back.  Only SUCCESS is cached:
# failures are cheap to recompute and may be fixed by an overwrite.
_VERIFY_OK_CACHE: Dict[str, Tuple] = {}


def _stat_sig(d: str) -> Optional[Tuple]:
    try:
        sig = []
        for name in ("meta.json", "params.tar", "state.pkl"):
            st = os.stat(os.path.join(d, name))
            sig.append((name, st.st_size, st.st_mtime_ns))
        return tuple(sig)
    except OSError:
        return None


def verify_pass_dir(root: str, pass_id: int) -> Optional[str]:
    """Integrity check of one checkpoint dir (the etcd-meta md5 check of
    the Go pserver, runnable without loading).  Returns None when the
    artifact is intact, else a short reason string: missing/corrupt
    meta.json (a kill before the meta commit), or a missing/torn blob
    (a torn prune, a partially-synced copy)."""
    d = pass_dir(root, pass_id)
    sig = _stat_sig(d)
    if sig is not None and _VERIFY_OK_CACHE.get(d) == sig:
        return None
    meta_path = os.path.join(d, "meta.json")
    if not os.path.exists(meta_path):
        return "missing meta.json"
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except (OSError, ValueError):
        return "corrupt meta.json"
    for blob, key in (("params.tar", "params_md5"),
                      ("state.pkl", "state_md5")):
        path = os.path.join(d, blob)
        if key not in meta:
            return f"meta.json missing {key}"
        if not os.path.exists(path):
            return f"missing {blob}"
        if _md5(path) != meta[key]:
            return f"md5 mismatch on {blob}"
    if sig is not None:
        if len(_VERIFY_OK_CACHE) > 256:
            _VERIFY_OK_CACHE.clear()
        _VERIFY_OK_CACHE[d] = sig
    return None


def _report_corrupt(d: str, reason: str) -> None:
    # grep-able, same contract as OBS-POSTMORTEM: the resilience checker
    # (python -m paddle_tpu.resilience check) counts these lines and
    # tools_tier1.sh turns its findings into ladder exit 10
    print(f"CKPT-CORRUPT: {d} ({reason})", flush=True)


def latest_pass(root: str) -> Optional[int]:
    if not os.path.isdir(root):
        return None
    best = None
    for name in os.listdir(root):
        m = _PASS_RE.match(name)
        if m and os.path.exists(os.path.join(root, name, "meta.json")):
            p = int(m.group(1))
            best = p if best is None else max(best, p)
    return best


def prune_checkpoints(root: str, keep: int = 2) -> None:
    """Delete old checkpoints, never the newest VERIFIED one: only dirs
    that pass :func:`verify_pass_dir` count toward ``keep``, so corrupt
    young dirs (a torn prune, a kill-during-save) cannot cause the only
    good artifact to be reaped.  Unverified dirs NEWER than the oldest
    kept verified checkpoint are left alone too (they may be saves in
    flight); older debris is swept.  With no verified dir at all the old
    id-order rule applies (nothing is provably better than anything
    else)."""
    import shutil

    ids = _pass_ids(root)
    if not ids:
        return
    if keep <= 0:
        victims = ids
    else:
        # newest-first with early stop: verification (an md5 read-back,
        # though cached for immutable committed dirs) runs only until
        # `keep` intact dirs are found — old dirs below the cut are
        # deleted without ever being hashed
        kept: List[int] = []
        for pid in reversed(ids):
            if verify_pass_dir(root, pid) is None:
                kept.append(pid)
                if len(kept) >= keep:
                    break
        if not kept:
            victims = ids[:-keep]
        else:
            cut = kept[-1]
            victims = [pid for pid in ids if pid < cut]
    for pid in victims:
        _VERIFY_OK_CACHE.pop(pass_dir(root, pid), None)
        shutil.rmtree(pass_dir(root, pid), ignore_errors=True)


def _read_checkpoint(d: str) -> Tuple[Parameters, Any, Any, Dict]:
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    with open(os.path.join(d, "params.tar"), "rb") as f:
        params = Parameters.from_tar(f)
    with open(os.path.join(d, "state.pkl"), "rb") as f:
        st = pickle.load(f)
    return params, st["opt_state"], st["model_state"], meta


def load_latest(root: str) -> Optional[Tuple[Parameters, Any, Any, Dict]]:
    """Newest INTACT checkpoint under ``root``, or None when no usable
    one exists.  Walks newest -> oldest: a dir whose meta never
    committed (kill-during-save) is skipped silently — that is the
    commit protocol working as designed — while a meta-bearing dir with
    missing/torn blobs is rejected with a ``CKPT-CORRUPT`` line and the
    walk falls back to the next-older artifact instead of crashing the
    resume."""
    for pid in reversed(_pass_ids(root)):
        reason = verify_pass_dir(root, pid)
        if reason is None:
            return _read_checkpoint(pass_dir(root, pid))
        if reason != "missing meta.json":
            _report_corrupt(pass_dir(root, pid), reason)
    return None


def load_checkpoint(root: str, pass_id: Optional[int] = None
                    ) -> Tuple[Parameters, Any, Any, Dict]:
    """Returns (parameters, opt_state, model_state, meta), md5-verified
    (the etcd-meta check of the Go pserver).  With ``pass_id=None`` the
    newest intact checkpoint wins — corrupt dirs are rejected with a
    ``CKPT-CORRUPT`` line and the next-older artifact is used.  An
    EXPLICIT ``pass_id`` that fails verification raises (the caller
    asked for that artifact specifically; silently substituting another
    would resume from the wrong state)."""
    if pass_id is None:
        got = load_latest(root)
        enforce_that(got is not None,
                     f"no intact checkpoints under {root}",
                     context="checkpoint")
        return got
    d = pass_dir(root, pass_id)
    reason = verify_pass_dir(root, pass_id)
    if reason is not None:
        _report_corrupt(d, reason)
        raise EnforceError(f"CKPT-CORRUPT: corrupt checkpoint {d} "
                           f"({reason})", context="checkpoint")
    return _read_checkpoint(d)
