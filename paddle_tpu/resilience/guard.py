"""Bad-step guards: the in-step policy that keeps one NaN gradient from
poisoning optimizer slots forever.

The check itself lives INSIDE the jitted train step (``trainer.SGD``
builds it when a :class:`BadStepGuard` is set): one fused f32
global-sq-norm reduction over all gradients decides ``good`` (finite,
and under ``max_norm`` when set), the optimizer update runs as usual,
and every params / slot / model-state leaf is selected back to its OLD
value on a bad step — so a skipped step is a true no-op on training
state while costing zero extra host syncs (the bad counters ride the
same lazy device-scalar contract as ``.cost``).

Policy ladder:

- ``"skip"`` — never apply a bad step; count it (the per-step floor
  every policy includes);
- ``"rollback"`` — additionally, ``rollback_after`` CONSECUTIVE bad
  steps raise :class:`~paddle_tpu.resilience.faults.BadStepRollback`
  after dumping a flight-recorder postmortem: persistent badness means
  the inputs or state are wrong and the run must restart from its last
  verified checkpoint (the resume supervisor does exactly that).  The
  consecutive counter is kept ON DEVICE and read back only every
  ``check_every`` steps (default: ``rollback_after``), so a persisting
  streak is caught within one window while healthy steps never sync.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["BadStepGuard", "screen_grads", "select_good", "guard_init",
           "guard_outputs"]


@dataclass(frozen=True)
class BadStepGuard:
    """Configuration for the in-step bad-step guard.

    - ``policy``: ``"skip"`` or ``"rollback"`` (the ladder above);
    - ``max_norm``: global grad-norm ceiling — a FINITE step whose norm
      exceeds it is also treated bad (0 = finiteness check only);
    - ``rollback_after``: K consecutive bad steps trigger the rollback
      (policy ``"rollback"`` only);
    - ``check_every``: host-readback cadence for the consecutive
      counter, in steps (0 = ``rollback_after``).
    """

    policy: str = "skip"
    max_norm: float = 0.0
    rollback_after: int = 3
    check_every: int = 0

    def __post_init__(self):
        if self.policy not in ("skip", "rollback"):
            raise ValueError(f"BadStepGuard.policy must be 'skip' or "
                             f"'rollback', got {self.policy!r}")
        if self.policy == "rollback" and self.rollback_after < 1:
            # 0 would make `consec >= rollback_after` true on a healthy
            # step: every cadence check rolls back a perfectly good run
            raise ValueError("BadStepGuard.rollback_after must be >= 1, "
                             f"got {self.rollback_after}")

    @property
    def cadence(self) -> int:
        return max(1, int(self.check_every or self.rollback_after))


def guard_init():
    """Fresh host-side guard-state pytree, passed as the train step's
    extra argument.  ``inject`` is re-stamped by the trainer from the
    fault plan every step (0.0 outside injection windows); the counters
    are replaced by the step's device outputs."""
    import numpy as np

    return {"inject": np.float32(0.0),
            "bad_consec": np.int32(0),
            "bad_total": np.int32(0)}


def screen_grads(grads, inject, max_norm: float):
    """Traced-side: poison + screen the gradient tree.

    Adds ``inject`` (a scalar; 0.0 = no-op, NaN/Inf = an injected bad
    step) to every gradient, then computes ONE fused f32 global
    sq-norm reduction and the ``good`` verdict: all-finite, and under
    ``max_norm`` when set.  Returns ``(grads, good, sq_norm)``; the
    reduction fuses into the surrounding jitted step — no host
    callback, no extra sync."""
    import functools

    import jax.numpy as jnp

    grads = {k: g + inject.astype(g.dtype) for k, g in grads.items()}
    sq = functools.reduce(
        jnp.add,
        [jnp.sum(jnp.square(g.astype(jnp.float32)))
         for g in grads.values()],
        jnp.zeros((), jnp.float32))
    good = jnp.isfinite(sq)
    if max_norm > 0.0:
        good = jnp.logical_and(good, sq <= jnp.float32(max_norm) ** 2)
    return grads, good, sq


def select_good(good, new_tree, old_tree):
    """Traced-side: per-leaf ``where(good, new, old)`` over matching
    pytrees — the skip-step select.  On a good step this is the
    identity on ``new``; on a bad one params/slots/model-state come out
    bit-identical to their pre-step values (pinned vs an uninterrupted
    control by ``resilience.chaos.seeded_chaos``)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda n, o: jnp.where(good, n, o),
                        new_tree, old_tree)


def guard_outputs(good, guard_state):
    """Traced-side: next guard counters — consecutive resets on a good
    step, total accumulates."""
    import jax.numpy as jnp

    consec = jnp.where(good, 0,
                       guard_state["bad_consec"] + 1).astype(jnp.int32)
    total = (guard_state["bad_total"]
             + jnp.where(good, 0, 1)).astype(jnp.int32)
    return {"bad_consec": consec, "bad_total": total}
