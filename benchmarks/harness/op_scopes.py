"""Which named scope each device operation of a trace came from.

The ``XLA Ops`` events of a TPU trace are named by their HLO text, which
does not carry ``jax.named_scope`` names.  The same ``.xplane.pb`` keeps,
in its ``/host:metadata`` plane, the optimised HLO module of every program
that ran (one ``Hlo Proto`` stat an event-metadata entry), and there each
instruction has its ``op_name``: the scope path JAX gave the operation it
was lowered from, ``jit(step)/.../gdn/gdn.scan/dot_general``; a fusion has
its root's.  This reads those names with a few lines of protobuf wire
format (field numbers of ``xplane.proto`` and ``hlo.proto``), so that a
reader can select a trace's operations by scope.
"""

from __future__ import annotations

import functools
import re
from typing import Callable, Dict, Iterator, Tuple

from harness import trace as T

METADATA_PLANE = "/host:metadata"


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        c = b[i]
        i += 1
        value |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return value, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; a length-delimited value is
    its bytes, a varint its number, fixed ones are skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _sub(b: bytes, number: int) -> Iterator[bytes]:
    return (v for f, v in _fields(b) if f == number
            and isinstance(v, (bytes, bytearray)))


@functools.lru_cache(maxsize=2)
def op_names(path: str) -> Dict[str, str]:
    """{instruction name as the trace has it ("%fusion.83"): op_name} of
    the largest program in the file (the step; a helper of a few scalars
    may reuse its instruction names); {} where the file keeps no HLO."""
    with open(path, "rb") as f:
        space = f.read()
    best: Dict[str, str] = {}
    for plane in _sub(space, 1):                      # XSpace.planes
        if next(_sub(plane, 2), b"").decode() != METADATA_PLANE:
            continue
        for entry in _sub(plane, 4):                  # event_metadata map
            for meta in _sub(entry, 2):               # its value
                for stat in _sub(meta, 5):            # XEventMetadata.stats
                    for proto in _sub(stat, 6):       # bytes_value: HloProto
                        for module in _sub(proto, 1):
                            names = _module_op_names(module)
                            if len(names) > len(best):
                                best = names
    return best


def _module_op_names(module: bytes) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for comp in _sub(module, 3):                      # computations
        for ins in _sub(comp, 2):                     # instructions
            name = next(_sub(ins, 1), b"").decode()
            for md in _sub(ins, 7):                   # OpMetadata
                op = next(_sub(md, 2), b"").decode()
                if name and op:
                    out["%" + name] = op
    return out


def under(scope: str) -> Callable[[str], bool]:
    """Whether an ``op_name`` lies under the named scope ``scope``, also
    where JAX wrapped the scope's name (``jvp(gdn)``, ``transpose(jvp(gdn
    ))``, ``checkpoint``, ``rematted_computation``)."""
    pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
    return lambda op_name: bool(pat.search(op_name))


def seconds_under(tr: T.Trace, names: Dict[str, str],
                  inside: Callable[[str], bool], kernels: str = "") -> float:
    """Seconds of chip 0, inside the window, in which an operation ran
    whose ``op_name`` satisfies ``inside`` (or, with ``kernels``, a Pallas
    kernel whose name starts with it).  A ``while`` and the operations of
    its body overlap in the trace, so this is the union of the matching
    intervals, not their sum."""
    lo, hi = tr.window
    hit = [(o.start, o.end) for o in tr.chips[0].ops
           if o.start >= lo and o.end <= hi
           and (inside(names.get(o.name, ""))
                or (kernels and T.is_kernel(o)
                    and o.name.startswith("%" + kernels)))]
    return T.clipped_seconds(T.merge(hit), lo, hi)
