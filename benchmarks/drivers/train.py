"""Driver of the ``train`` kind: one ``trainer.SGD`` through ``SGD.train``.

One call of ``SGD.train`` carries the whole run, so the compiled step and
its state that the check drives are the ones the window times:

- pass 0: one step.  At its end the optimiser's first moment gives the
  first gradient as the optimiser got it (``m = (1 - beta1) g``).
- pass 1: two more steps.  At its end the parameters' change over the
  three steps is read, leaf by leaf.
- pass 2: ``warmup_steps`` steps, then the window: fresh batches until
  ``--seconds`` have passed.

Every argument of ``SGD.train`` and every flag stays at its default.  A
step's cost is read one step late (while the next one runs), as a handler
that logs costs would: a step ends when its cost reached the host.  After
the window the trainer is freed and the plain reference follows the same
three batches from the same weights; see ``check``.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from harness import cells, measure, weights
from harness.measure import say

CHECK_STEPS = 3


def updated(cell, leaves: weights.Leaves) -> weights.Leaves:
    """Those of the train group's leaves that the optimiser updates: all
    of them, less those the family's ``frozen`` names (a router's bias,
    say)."""
    fam = cell.family()
    frozen = set(fam.frozen(cell.config)) if hasattr(fam, "frozen") else ()
    return {k: v for k, v in leaves.items() if k not in frozen}


def build_trainer(cell, seed: int):
    """(trainer, the family's program, the family's leaves).  The weights
    are made here from the seed and put into the trainer's ``Parameters``
    under the family's name map, which has to cover the trainer's
    parameters and the family's leaves alike, each exactly once."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer, trainer

    cfg, opt = cell.config, cell.config["train"]["optimizer"]
    leaves = cell.family().leaves(cfg, "train")
    paddle.topology.reset_name_scope()
    prog = cell.family().train_program(cfg)
    cost, names = prog["cost"], prog["names"]
    params = paddle.Parameters.from_topology(paddle.topology.Topology(
        cost if isinstance(cost, list) else [cost]))
    missing = (set(params.names()) ^ set(names)) | \
        (set(names.values()) ^ set(leaves))
    if missing or len(set(names.values())) != len(names):
        raise cells.CellError(f"the trainer's parameters and the "
                              f"reference's leaves differ: {sorted(missing)}")
    made = weights.make(leaves, seed)
    for prog_name, ref_name in names.items():
        params[prog_name] = made[ref_name]
    del made
    sgd = trainer.SGD(cost=cost, parameters=params,
                      update_equation=optimizer.Adam(
                          learning_rate=opt["learning_rate"],
                          beta1=opt["beta1"], beta2=opt["beta2"],
                          epsilon=opt["epsilon"]))
    return sgd, prog, leaves


def warm_log_flush() -> None:
    """``SGD.train`` stacks the costs of every ``FLAGS.log_period`` steps
    into one transfer when it logs them; compile that helper now, so the
    first log line of the window does not."""
    import jax.numpy as jnp

    from paddle_tpu.platform.flags import FLAGS

    if FLAGS.log_period:
        cost = jnp.zeros((), jnp.float32)
        np.asarray(jnp.stack([cost] * int(FLAGS.log_period)))


def registry_change(before: Dict[str, float], after: Dict[str, float]
                    ) -> Dict[str, float]:
    """What the program published into ``paddle_tpu.obs``'s default
    registry between two snapshots, series by series (one that is new
    counts from 0)."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float))}


def to_host(tree):
    import jax

    return jax.tree.map(np.asarray, tree)


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The largest gap over the leaves between two sets of per-leaf
    readings (a norm each, or a sketch each), each gap against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    size = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    floor = float(np.median(list(size.values())))
    return max(float(np.linalg.norm(np.asarray(got[k]) - np.asarray(want[k])))
               / max(size[k], floor) for k in want)


def run(cell, args, devs, started: float, watch: measure.CompileWatch,
        broken=None):
    """Returns the record the result line and the per-layer readers are
    made from.  ``broken`` (tests only) wraps the compiled step."""
    import jax

    from paddle_tpu import event
    from paddle_tpu.obs import default_registry

    traffic = cell.traffic
    sgd, prog, leaves = build_trainer(cell, args.seed)
    names = prog["names"]
    moving = updated(cell, leaves)
    beta1 = cell.config["train"]["optimizer"]["beta1"]
    gen = cell.generator().make(traffic, cell.config, args.seed)
    spans = measure.Spans()
    tracing = measure.Tracing(cell.name) if args.trace else None
    warm = int(traffic["warmup_steps"])
    seen: Dict[str, object] = {"losses": [], "first_grad": None,
                               "change": None}
    steps: List[dict] = []          # window steps: tokens, done
    win = {"t0": None, "counters": None}
    pending: List = []              # [(event, tokens, phase)]
    def settle() -> None:
        ev, tokens, phase = pending.pop(0)
        with spans.span("train_step_wait"):
            cost = ev.cost                   # waits for that step
        if phase == "check":
            seen["losses"].append(cost)
        elif phase == "warm":                # the last one stands
            win["counters"] = default_registry().snapshot()
            win["t0"] = time.perf_counter()
        else:
            steps.append({"tokens": tokens, "done": time.perf_counter()})

    batches = iter(gen)
    state = {"pass": 0, "tokens": 0, "phase": "check"}

    def reader():
        p = state["pass"]
        state["pass"] += 1
        if p < 2:
            for _ in range(1 if p == 0 else CHECK_STEPS - 1):
                samples, state["tokens"] = next(batches)
                state["phase"] = "check"
                spans.open("feed")
                yield samples
            return
        for _ in range(warm):
            samples, state["tokens"] = next(batches)
            state["phase"] = "warm"
            spans.open("feed")
            yield samples
        while True:
            if win["t0"] is not None and \
                    time.perf_counter() >= win["t0"] + args.seconds:
                return
            with spans.span("reader"):
                samples, state["tokens"] = next(batches)
            state["phase"] = "window"
            spans.open("feed")
            yield samples

    trace_at = float(traffic.get("trace_after_s", 2.0))
    trace_for = float(traffic.get("trace_seconds", 3.0))

    def on_event(ev) -> None:
        if isinstance(ev, event.BeginIteration):
            spans.close("feed")
            spans.open("dispatch")
        elif isinstance(ev, event.EndIteration):
            spans.close("dispatch")
            pending.append((ev, state["tokens"], state["phase"]))
            if len(pending) > 1:
                settle()
            if tracing is not None and win["t0"] is not None:
                into = time.perf_counter() - win["t0"]
                if not tracing.on and tracing.t0 is None and into >= trace_at:
                    tracing.start()
                    spans.open("trace_window")
                elif tracing.on and \
                        time.perf_counter() - tracing.t0 >= trace_for:
                    spans.close("trace_window")
                    tracing.stop()
        elif isinstance(ev, event.EndPass):
            while pending:
                settle()
            if ev.pass_id == 0:
                slots = sgd.opt_state["slots"]
                read = weights.grad_readings(moving)
                got = jax.jit(lambda m, key: read(
                    {k: v / (1.0 - beta1) for k, v in m.items()}, key))(
                    {ref: slots["m"][prog] for prog, ref in names.items()
                     if ref in moving},
                    weights.sketch_key(args.seed))
                seen["first_grad"] = to_host(got)
            elif ev.pass_id == 1:
                now = sgd.parameters.as_dict()
                seen["change"] = weights.change_norms(
                    {ref: now[prog] for prog, ref in names.items()}, leaves,
                    args.seed)

    warm_log_flush()
    if broken is not None:
        broken(sgd)
    sgd.train(reader, num_passes=3, event_handler=on_event,
              feeding=prog["feeding"])
    if tracing is not None and tracing.on:
        spans.close("trace_window")
        tracing.stop()
    counters = registry_change(win["counters"], default_registry().snapshot())
    t0 = win["t0"]
    inside = [s for s in steps if s["done"] <= t0 + args.seconds]
    # the window ends with the last step that ended inside --seconds, so
    # that it holds whole steps only: the rate is those steps' tokens over
    # exactly their time, and does not jump by a step from run to run
    t1 = inside[-1]["done"] if inside else t0 + args.seconds
    record = {
        "kind": "train", "window": (t0, t1), "seconds": t1 - t0,
        "setup_s": t0 - started, "steps": inside, "spans": spans.rows,
        "tokens": sum(s["tokens"] for s in inside),
        "attempted": len(steps), "failed": 0,
        "compiles_in_window": watch.inside(t0, t1),
        "memory_peak_bytes": measure.memory_peak_bytes(devs),
        "tracing": tracing, "counters": {**counters, "steps": len(inside)},
        "layers_run": prog["layers"],
        "layouts": gen.layouts,
        "end_to_end": {
            "train_tokens_per_s": sum(s["tokens"] for s in inside)
            / (t1 - t0),
            "setup_s": t0 - started},
    }
    say(f"window: {len(inside)} steps of {len(steps)} ended inside "
        f"{args.seconds} s, the last after {t1 - t0:.4f} s; "
        f"{record['tokens']} real tokens")
    del sgd
    gc.collect()
    record["check"] = check(cell, args, seen, mode="f32")
    return record


def reference_readings(cell, args, mode: str) -> dict:
    """The plain reference over the first three batches of the seed, from
    the seed's weights: its losses, its first gradient's norms and its
    parameters' change, leaf by leaf."""
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    leaves = cell.family().leaves(cfg, "train")
    read = weights.grad_readings(updated(cell, leaves))
    step_fn = cell.family().reference_train_step(
        cell.reference(), cfg, mode=mode,
        optimizer=cfg["train"]["optimizer"],
        reduce_grads=lambda g, key: read(weights.flatten(g), key),
        block_rows=int(cell.traffic["check"]["block_rows"]),
        head_rows=int(cell.traffic["check"]["head_rows"]))
    w = weights.unflatten(weights.make(leaves, args.seed))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    m, v = zeros(w), zeros(w)
    cap = int(cell.traffic["tokens_per_step"])
    losses, first = [], None
    batches = iter(cell.generator().make(cell.traffic, cfg, args.seed))
    for step in range(CHECK_STEPS):
        samples, _ = next(batches)
        flat = [np.zeros(cap, np.int32) for _ in range(3)]
        seg = np.full(cap, len(samples), np.int32)
        at = 0
        for i, sample in enumerate(samples):
            n = len(sample[0])
            for dst, src in zip(flat, sample):
                dst[at:at + n] = src
            seg[at:at + n] = i
            at += n
        loss, reduced, w, m, v = step_fn(
            w, m, v, step, weights.sketch_key(args.seed), flat[0], flat[1],
            flat[2], seg,
            seg < len(samples), float(len(samples)))
        losses.append(float(loss))
        if step == 0:
            first = to_host(reduced)
    change = weights.change_norms(weights.flatten(w), leaves, args.seed)
    return {"losses": losses, "first_grad": first, "change": change}


def compare(got: dict, want: dict, limits: dict) -> dict:
    """Each number compared, beside its limit."""
    rows = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        rows[f"loss_step{i}_rel"] = (abs(a - b) / abs(b),
                                     limits["loss_rel"])
    rows["first_grad_norm_worst_leaf"] = (
        worst_leaf_gap(got["first_grad"]["norm"], want["first_grad"]["norm"]),
        limits["first_grad_norm_worst_leaf"])
    rows["first_grad_sketch_worst_leaf"] = (
        worst_leaf_gap(got["first_grad"]["sketch"],
                       want["first_grad"]["sketch"]),
        limits["first_grad_sketch_worst_leaf"])
    rows["param_change_norm_worst_leaf"] = (
        worst_leaf_gap(got["change"], want["change"]),
        limits["param_change_norm_worst_leaf"])
    return rows


def check(cell, args, seen: dict, mode: str) -> dict:
    t = time.perf_counter()
    want = reference_readings(cell, args, mode)
    rows = compare(seen, want, cell.limits)
    ok = len(seen["losses"]) == CHECK_STEPS
    for name, (value, limit) in rows.items():
        fine = bool(np.isfinite(value)) and value <= limit
        ok = ok and fine
        say(f"check {name}: {value:.6g} (limit {limit:g})"
            f"{'' if fine else '  <-- over'}")
    say(f"check: reference ({mode}) took {time.perf_counter() - t:.1f} s; "
        f"losses {seen['losses']} vs {want['losses']}")
    return {"correct": ok, "rows": {k: v[0] for k, v in rows.items()},
            "limits": {k: v[1] for k, v in rows.items()}, "reference": want}


def control(cell, args, devs, started, watch) -> dict:
    """The control of ``correct``: the reference in the precision below
    the stated one (float8 operands) put in the program's place and held to
    the same limits.  Needs no window and no trainer."""
    got = reference_readings(cell, args, "fp8")
    want = reference_readings(cell, args, "f32")
    rows = compare(got, want, cell.limits)
    return {"correct": all(v <= lim for v, lim in rows.values()),
            "rows": {k: v[0] for k, v in rows.items()}}
