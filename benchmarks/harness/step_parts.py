"""Where a train step's device time went: every device operation of a
traced step put down to one phase and one part, from the scopes the
program wrote (PR 39).

``op_scopes.op_names`` gives each instruction its ``op_name``; a fusion has
its root's.  That is not enough for a whole step: XLA fuses the optimizer's
update into the weight-gradient product it follows, and the fusion carries
the product's name.  So this reads, from the same HLO proto of the trace
file, each fusion's **members** too (the instructions of its called
computation, nested fusions' with them: opcode and ``op_name``).

What the program writes (``paddle_tpu``): ``topology.node_scope`` puts a
node's kind outside its name (``.../jvp(layer_norm)/jvp(blk0_ln1)/...``;
under a ``remat_scope`` ``.../jvp(remat_blk0)/layer_norm/blk0_ln1/...``),
``Optimizer.apply`` works under ``opt`` (``opt.clip``, ``opt.update``,
``opt.average``, ``opt.shard``, ``opt.gather``), the step's own glue under
``step.loss``, ``step.guard``, ``step.stats``.  The **part** of an
``op_name`` is the outermost scope the program wrote: the first segment
after ``jit(...)`` that is not JAX's own (``jit(...)``, ``checkpoint``,
``rematted_computation``) nor the remat group's (``remat_*``), and not the
last, which names the primitive; under ``opt`` it is the ``opt.*`` scope.
Its **phase** is what JAX wrote around it: ``rematted_computation``
recompute, else ``transpose(...)`` backward, else ``jvp(...)`` forward, else
other; under ``opt``, update.  An inner scope of a layer (``attn.proj``,
``gdn.scan``, ``moe.route``, ``head.xent``: a dotted name) is kept beside
the part for the log.

The rules, one class an operation:

- an operation any of whose members (or itself) lies under ``opt`` is of
  phase ``update`` and of that ``opt.*`` part.  Where another member is a
  ``dot`` or a ``convolution`` from outside ``opt`` (the weight-gradient
  product the update was fused behind), the operation is also **shared**
  with that member's class: its time counts once, under ``update``, and is
  listed again in the ``shared`` table under the pair;
- otherwise the operation's own ``op_name`` decides (the hero's, as XLA
  gives it), and members of another part are listed in ``shared`` in the
  same way; a fusion whose own ``op_name`` holds no scope (its root is a
  bitcast or a copy XLA made) takes its members' commonest class, of equals
  the first;
- an operation with no ``op_name``, or with none of the program's scopes in
  it or in a member, is ``unattributed`` (XLA's own ``copy``, ``copy-done``,
  ...): a class of its own, shown and not spread over the layers;
- time is never split inside one operation and never counted twice in a
  total.  A ``while`` and the operations of its body overlap in the trace:
  each instant goes to the operation that started last (the innermost), so
  the classes' times sum to the device's busy time (``trace.busy_seconds``)
  on chip 0, operations clipped to the window;
- a program without the new scopes (nothing under ``opt``: a commit before
  PR 39) is attributed all the same by :func:`attribute`, its instance
  names as parts, but :func:`of_run` and every reader built on it give
  ``None`` and do not raise.

What this cannot see: the time inside one fusion (a product and the update
fused behind it are one interval), and which of a fusion's members of two
kinds took it (the root's class has all of it; the ``shared`` table says
with whom).
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

from harness import op_scopes, program_spans as P, trace as T
from harness.measure import say
from harness.op_scopes import _fields, _sub, _varint

PHASES = ("forward", "recompute", "backward", "update", "other")
UNATTRIBUTED = "unattributed"
HEAVY = ("dot", "convolution")            # the MXU's opcodes

Class = Tuple[str, str]                   # (phase, part)

_WRAPPED = re.compile(r"^([A-Za-z_]+)\((.*)\)$")
_INNER = re.compile(r"^[a-z]+\.[a-z_]+$")
_JAX_OWN = ("checkpoint", "rematted_computation")


@dataclasses.dataclass
class Instr:
    opcode: str
    op_name: str
    # (opcode, op_name) of a fusion's members, nested fusions' with them
    members: List[Tuple[str, str]]


Program = Dict[str, Instr]                # by "%name", as the trace has it


def _ints(value) -> List[int]:
    """A repeated int64 field's value: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _module_program(module: bytes) -> Program:
    comps: Dict[int, List[Tuple[str, str, str, List[int]]]] = {}
    for comp in _sub(module, 3):                      # computations
        cid, rows = 0, []
        for f, v in _fields(comp):
            if f == 5 and isinstance(v, int):         # id
                cid = v
            elif f == 2:                              # an instruction
                name = opcode = op = ""
                called: List[int] = []
                for g, w in _fields(v):
                    if g == 1:
                        name = w.decode()
                    elif g == 2:
                        opcode = w.decode()
                    elif g == 7:                      # OpMetadata
                        op = next(_sub(w, 2), b"").decode()
                    elif g == 38:                     # called_computation_ids
                        called += _ints(w)
                rows.append((name, opcode, op, called))
        comps[cid] = rows

    def members(called: Sequence[int], seen: frozenset
                ) -> List[Tuple[str, str]]:
        out: List[Tuple[str, str]] = []
        for cid in called:
            if cid in seen:
                continue
            for _, opcode, op, inner in comps.get(cid, ()):
                if opcode == "fusion":
                    out += members(inner, seen | {cid})
                elif opcode != "parameter":
                    out.append((opcode, op))
        return out

    return {"%" + name: Instr(opcode, op,
                              members(called, frozenset())
                              if opcode == "fusion" else [])
            for rows in comps.values() for name, opcode, op, called in rows
            if name}


def program(path: str) -> Program:
    """The step's program of a trace file: the one with most named
    instructions, as ``op_scopes.op_names`` takes it; {} where the file
    keeps no HLO."""
    with open(path, "rb") as f:
        space = f.read()
    best: Program = {}
    named = -1
    for plane in _sub(space, 1):                      # XSpace.planes
        if next(_sub(plane, 2), b"").decode() != op_scopes.METADATA_PLANE:
            continue
        for entry in _sub(plane, 4):                  # event_metadata map
            for meta in _sub(entry, 2):
                for stat in _sub(meta, 5):
                    for proto in _sub(stat, 6):       # bytes_value: HloProto
                        for module in _sub(proto, 1):
                            prog = _module_program(module)
                            n = sum(1 for i in prog.values() if i.op_name)
                            if n > named:
                                best, named = prog, n
    return best


def classify(op_name: str) -> Optional[Tuple[str, str, str]]:
    """(phase, part, inner scope or "") of one ``op_name``; ``None`` where
    it is empty or holds none of the program's scopes."""
    path = op_name.split(";", 1)[0].split("/")        # "a;b": merged ops
    wrappers: List[str] = []
    scopes: List[str] = []
    for seg in path[1:-1]:          # jit(step) first, the primitive last
        own: List[str] = []
        m = _WRAPPED.match(seg)
        while m:
            own.append(m.group(1))
            seg = m.group(2)
            m = _WRAPPED.match(seg)
        wrappers += own
        if "jit" in own or "pjit" in own:
            continue
        if seg:
            scopes.append(seg)
    tops = [s for s in scopes
            if s not in _JAX_OWN and not s.startswith("remat_")]
    if not tops:
        return None
    part = tops[0]
    inner = next((s for s in reversed(tops[1:]) if _INNER.match(s)), "")
    if part == "opt":
        return "update", (inner if inner.startswith("opt.") else "opt"), ""
    if "rematted_computation" in scopes:
        phase = "recompute"
    elif "transpose" in wrappers:
        phase = "backward"
    elif "jvp" in wrappers:
        phase = "forward"
    else:
        phase = "other"
    return phase, part, inner


def has_scopes(prog: Program) -> bool:
    """Whether the program wrote the scopes this file goes by: something of
    it lies under ``opt`` (every train step updates)."""
    under = op_scopes.under("opt")
    return any(under(i.op_name) or any(under(op) for _, op in i.members)
               for i in prog.values())


def _label(c: Tuple[str, str, str]) -> str:
    return f"{c[0]} {c[1]}" + (f"/{c[2]}" if c[2] else "")


def class_of(instr: Optional[Instr]
             ) -> Tuple[Class, str, List[Tuple[str, str]]]:
    """((phase, part), inner scope, [(own label, other label)] of the
    ``shared`` table) of one device operation."""
    if instr is None:
        return ("other", UNATTRIBUTED), "", []
    own = classify(instr.op_name)
    inside = [(opcode, classify(op)) for opcode, op in instr.members]
    updates = [c for _, c in inside if c and c[0] == "update"]
    if own is not None and own[0] == "update":
        updates.insert(0, own)
    if updates:
        # the commonest opt.* part; of equals the first, the own one first
        mine = collections.Counter(updates).most_common(1)[0][0]
        others = {c for opcode, c in inside
                  if c and c[0] != "update" and opcode in HEAVY}
    else:
        scoped = [c for _, c in inside if c]
        if own is None and not scoped:
            return ("other", UNATTRIBUTED), "", []
        # a fusion whose root XLA made itself (a bitcast, a copy) has no
        # name of its own: its members' commonest class stands in
        mine = own or collections.Counter(scoped).most_common(1)[0][0]
        others = {c for c in scoped if c[1] != mine[1]}
    return mine[:2], mine[2], sorted((_label(mine), _label(o))
                                     for o in others)


def exclusive_seconds(ops: Sequence[T.Op], lo: float, hi: float
                      ) -> List[float]:
    """For each operation the seconds inside [lo, hi] in which it ran and
    no operation that started after it did: a ``while`` gets what its body
    left.  The values sum to the union of the intervals."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start, -ops[i].end))
    out = [0.0] * len(ops)
    stack: List[int] = []                 # open operations, innermost last
    at = lo

    def advance(to: float) -> None:
        nonlocal at
        to = min(max(to, lo), hi)
        while stack and to > at:
            top = stack[-1]
            end = min(ops[top].end, hi)
            if end <= at:
                stack.pop()
                continue
            step = min(end, to)
            out[top] += step - at
            at = step
            if end <= step:
                stack.pop()
        at = max(at, to)

    for i in order:
        if ops[i].end <= lo or ops[i].start >= hi:
            continue
        advance(ops[i].start)
        stack.append(i)
    advance(hi)
    return out


def attribute(tr: T.Trace, prog: Program) -> dict:
    """Chip 0's busy seconds inside the window by class.  ``seconds``
    {(phase, part): s}, ``inner`` {(phase, part, inner scope): s},
    ``shared`` {(own label, other label): s}, ``unattributed`` [(the HLO
    text's label, s)] largest first."""
    lo, hi = tr.window
    ops = tr.chips[0].ops
    seconds: Dict[Class, float] = {}
    inner: Dict[Tuple[str, str, str], float] = {}
    shared: Dict[Tuple[str, str], float] = {}
    loose: Dict[str, float] = {}
    known: Dict[str, tuple] = {}
    for o, s in zip(ops, exclusive_seconds(ops, lo, hi)):
        if not s:
            continue
        if o.name not in known:
            known[o.name] = class_of(prog.get(o.name))
        cls, scope, pairs = known[o.name]
        seconds[cls] = seconds.get(cls, 0.0) + s
        if scope:
            inner[cls + (scope,)] = inner.get(cls + (scope,), 0.0) + s
        for pair in pairs:
            shared[pair] = shared.get(pair, 0.0) + s
        if cls[1] == UNATTRIBUTED:
            loose[o.label] = loose.get(o.label, 0.0) + s
    return {"seconds": seconds, "inner": inner, "shared": shared,
            "unattributed": sorted(loose.items(), key=lambda kv: -kv[1])}


def of_run(run: dict) -> Optional[dict]:
    """The attribution of a train run's traced window in ms a step (read
    once, printed once, then kept on the record): ``ms``, ``inner``,
    ``shared``, ``unattributed`` as :func:`attribute` gives them, ``steps``
    and ``step_ms``.  ``None`` without a trace, without a step in it, or
    for a program without the scopes."""
    if "step_parts" not in run:
        run["step_parts"] = _of_run(run)
    return run["step_parts"]


def _of_run(run: dict) -> Optional[dict]:
    tr, tracing = run.get("trace"), run.get("tracing")
    if tr is None or tracing is None or not tr.chips \
            or run["kind"] != "train":
        return None
    steps = len(T.spans_named(tr, "dispatch"))
    path = tracing.file()
    if not steps or not path:
        return None
    prog = program(path)
    if not has_scopes(prog):
        return None
    got = attribute(tr, prog)
    per_step = lambda d: {k: 1e3 * v / steps for k, v in d.items()}  # noqa: E731
    parts = {"steps": steps, "ms": per_step(got["seconds"]),
             "inner": per_step(got["inner"]),
             "shared": per_step(got["shared"]),
             "unattributed": [(k, 1e3 * v / steps)
                              for k, v in got["unattributed"]]}
    parts["step_ms"] = sum(parts["ms"].values())
    for line in table(parts):
        say(line)
    return parts


def table(parts: dict, pairs: int = 12) -> List[str]:
    """The log's lines: phase x part, the ``shared`` pairs, the layers'
    inner scopes, the ten largest ``unattributed`` operations."""
    ms, whole = parts["ms"], parts["step_ms"] or 1.0
    rows = sorted({part for _, part in ms},
                  key=lambda p: -sum(ms.get((ph, p), 0.0) for ph in PHASES))
    out = [f"step parts: {parts['step_ms']:.2f} ms a step over "
           f"{parts['steps']} steps (ms a step; share of the step)",
           "  " + "part".ljust(24) + "".join(ph.rjust(10) for ph in PHASES)
           + "total".rjust(10) + "share".rjust(8)]
    for part in rows + ["all"]:
        cells = [sum(v for (ph, p), v in ms.items()
                     if ph == phase and part in (p, "all"))
                 for phase in PHASES]
        out.append("  " + part.ljust(24)
                   + "".join(f"{c:10.2f}" for c in cells)
                   + f"{sum(cells):10.2f}{100 * sum(cells) / whole:7.1f}%")
    ranked = sorted(parts["shared"].items(), key=lambda kv: -kv[1])
    out.append(f"  shared (counted once, under the first; the {pairs} "
               f"largest of {len(ranked)} pairs):")
    out += [f"    {a} + {b}: {v:.2f} ms" for (a, b), v in ranked[:pairs]]
    out.append("  inner scopes: " + ", ".join(
        f"{ph} {p}/{s} {v:.2f}" for (ph, p, s), v in
        sorted(parts["inner"].items(), key=lambda kv: -kv[1])))
    out.append("  unattributed, the ten largest: " + ", ".join(
        f"{k} {v:.3f} ms" for k, v in parts["unattributed"][:10]))
    return out


def ms_of(run: dict, phases: Sequence[str] = PHASES,
          parts: Optional[Sequence[str]] = None) -> Optional[float]:
    """ms a step of the classes of those phases and (where given) parts;
    ``None`` where :func:`of_run` is."""
    got = of_run(run)
    if got is None:
        return None
    return sum(v for (ph, p), v in got["ms"].items()
               if ph in phases and (parts is None or p in parts))


def span_ms_per_step(run: dict, name: str) -> Optional[float]:
    """The median length, in ms, of the program's host span ``pt:<name>``
    over the traced window of a train run; ``None`` where the program wrote
    none (a commit before PR 39)."""
    if run["kind"] != "train":
        return None
    return P.median_ms([b - a for a, b in P.named(P.of_run(run), name)])
