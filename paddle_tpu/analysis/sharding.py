"""Static GSPMD sharding-propagation auditor with collective-cost budgets.

PR 9's jaxpr auditor (:mod:`paddle_tpu.analysis.xla`) can see the
collectives GSPMD *already inserted* — but only after the partitioner
has made its placement decisions, and it cannot say whether a declared
``NamedSharding`` plan is even consistent, where an implicit all-gather
will materialize, or what a resharding costs in bytes over the links.
This module answers those questions *statically*, before anything runs
on chips: it re-materializes each captured ``audit_jit`` signature
(the same :class:`~paddle_tpu.analysis.retrace.CapturedCall` plumbing
the xla auditor uses), seeds every input with the ``PartitionSpec``
declared in the site's :class:`SiteContract` (``in_specs`` /
``out_specs`` / ``mesh_axes`` — see retrace.py), and walks the jaxpr
with a GSPMD-style propagation model:

- **elementwise** ops preserve shardings (conflicting placements on one
  dim mean GSPMD must all-gather an operand);
- **dot_general** contracting over a dim sharded the same way on both
  operands produces *partial sums* — a pending ``psum`` that a
  downstream ``sharding_constraint`` over the same axis lowers into the
  cheaper reduce-scatter (exactly how ``parallel/zero.py`` gets its
  reduce-scatter/all-gather pair out of ``with_sharding_constraint``);
- **reshape/transpose/pad/slice** of a sharded dim either preserve the
  placement (prefix-product-preserving reshape, permutation) or force a
  resharding;
- **gather/scatter** (the paged-KV layout ops) are safe when the
  sharded dims are operand *batching* dims and a forced gather when the
  sharded dim is indexed or collapsed across shards;
- explicit collectives and ``sharding_constraint`` eqns are costed with
  the distributed-TPU model of arXiv 2112.09017: for an ``N``-way axis
  and a tensor of ``b`` bytes, all-gather and reduce-scatter move
  ``b*(N-1)/N`` bytes per device, an all-reduce (psum) moves
  ``2*b*(N-1)/N``, an all-to-all ``b*(N-1)/N`` and a ppermute ``b``.

Findings are :class:`Diagnostic`\\ s tagged ``SHARD-AUDIT`` naming
rule + site + eqn:

- **contract-mismatch** — inferred output placement differs from the
  declared ``out_specs``, or a declared spec names an axis the
  ``mesh_axes`` don't have;
- **implicit-all-gather** — a sharded operand is forced replicated
  (conflicting elementwise placements, one-side-sharded contraction,
  non-preserving reshape, sliced/indexed sharded dim), with the
  materialized bytes in the message;
- **accidental-replication** — an ``expect_sharded`` argument arrives
  replicated, or a weight-shaped const is baked replicated into a site
  whose contract shards anything (consts can never be sharded);
- **axis-collision** — the same mesh axis consumed twice in one
  contraction (two output dims, or a declared spec using one axis for
  two dims of one tensor);
- **comm-budget** — the estimated collective bytes per call exceed the
  ``comm_bytes`` budget declared next to the jit (the serving step
  declares 0: a single-replica decode tick must not pay interconnect;
  the TP serving PR flips that to a derived ``model``-axis budget).

``python -m paddle_tpu.analysis sharding`` drives the same sealed
serving + trainer steady states as the xla gate, plus the ZeRO
placement jits on a virtual-8 mesh, declares the (still trivial)
pipeline/MoE contracts so their uncaptured sites print a loud notice,
and exits 0 clean / 1 findings / 2 crash — ``tools_tier1.sh`` ladder
exit 9.

Model limits (documented, all conservative): unknown ops produce
unknown placements and unknown placements never produce findings —
conflicts are proofs, not guesses (the program_check philosophy);
``shard_map`` bodies are walked only for their explicit collectives
(per-shard byte semantics); ``while`` bodies count one trip and
``scan`` bodies multiply by the trip count; pending partial-sums pass
through linear ops only and are charged as a full psum at their first
non-linear consumer or at the outputs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

from paddle_tpu.analysis.diagnostics import Diagnostic, Severity
from paddle_tpu.analysis.retrace import (CapturedCall, SiteContract,
                                         SiteRecord, auditor, declare_site)
from paddle_tpu.analysis.xla import _aval_bytes, _sub_jaxprs

__all__ = ["audit_sharding_sites", "audit_record_sharding", "ShardReport",
           "RULE_NAMES", "normalize_spec", "apply_spec",
           "all_gather_bytes", "reduce_scatter_bytes", "all_reduce_bytes",
           "drive_zero_placement", "drive_serving_tp_steady_state",
           "drive_pipeline_moe_train_step",
           "replay_serving_tp", "ensure_virtual_devices",
           "run_sharding_audit"]

TAG = "SHARD-AUDIT"

RULE_NAMES = ("contract-mismatch", "implicit-all-gather",
              "accidental-replication", "axis-collision", "comm-budget")

_DEFAULT_CONTRACT = SiteContract()

_COLLECTIVES = {"psum": "ar", "psum_invariant": "ar", "all_reduce": "ar",
                "all_gather": "ag", "all_gather_invariant": "ag",
                "psum_scatter": "rs", "reduce_scatter": "rs",
                "all_to_all": "a2a", "ppermute": "pp", "pshuffle": "pp"}

# ops a pending partial-sum may pass through without materializing the
# psum (linear in the pending operand, or pure data movement)
_PENDING_PASS = {"add", "sub", "add_any", "neg", "mul", "div",
                 "reshape", "transpose", "convert_element_type",
                 "broadcast_in_dim", "pad", "slice", "concatenate",
                 "squeeze", "expand_dims", "rev", "copy", "reduce_sum",
                 "dot_general", "stop_gradient"}


# ---------------------------------------------------------------------------
# spec plumbing
# ---------------------------------------------------------------------------


class VSpec(NamedTuple):
    """Inferred placement of one jaxpr var: ``dims`` is a per-dim tuple
    of mesh-axis names (None = replicated on that dim) or None when the
    placement is unknown; ``pending`` carries the mesh axes over which
    the value is a *partial sum* awaiting a psum/reduce-scatter."""

    dims: Optional[Tuple[Optional[str], ...]]
    pending: frozenset = frozenset()


def _repl(ndim: int) -> VSpec:
    return VSpec(dims=(None,) * ndim)


_UNKNOWN = VSpec(dims=None)


def normalize_spec(spec) -> Optional[Tuple[Optional[str], ...]]:
    """PartitionSpec / tuple / None -> per-dim tuple of single axis
    names.  Multi-axis dim entries (``("x", "y")``) collapse to their
    first axis — the repo shards one axis per dim."""
    if spec is None:
        return None
    out: List[Optional[str]] = []
    for e in tuple(spec):
        if e is None:
            out.append(None)
        elif isinstance(e, str):
            out.append(e)
        elif isinstance(e, (tuple, list)) and e:
            out.append(str(e[0]))
        else:
            out.append(None)
    return tuple(out)


def _spec_for(specs: Optional[Tuple], i: int, n: int):
    """The declared spec for position ``i`` of ``n``: a length-1 specs
    tuple broadcasts to every position; missing positions are None."""
    if specs is None:
        return None
    if len(specs) == 1:
        return specs[0]
    return specs[i] if i < len(specs) else None


def apply_spec(spec, shape, axes: Dict[str, int]
               ) -> Tuple[VSpec, List[Tuple[str, str]]]:
    """Apply a declared spec to one array leaf; returns (VSpec,
    problems) where problems are (rule, message) pairs.  A spec applies
    only when the leaf has enough dims and every sharded dim divides by
    the axis size; otherwise the leaf is replicated (the documented
    broadcast-over-leaves semantics — optimizer scalars under a flat
    ZeRO spec must not error)."""
    probs: List[Tuple[str, str]] = []
    entries = normalize_spec(spec)
    if entries is None:
        return _UNKNOWN, probs
    nd = len(shape)
    dims: List[Optional[str]] = [None] * nd
    seen: Dict[str, int] = {}
    if len(entries) > nd:
        return _repl(nd), probs
    for d, ax in enumerate(entries):
        if ax is None:
            continue
        if ax in seen:
            probs.append((
                "axis-collision",
                f"declared spec {entries} uses mesh axis {ax!r} for two "
                f"dims ({seen[ax]} and {d}) of one tensor — an axis can "
                "shard at most one dim"))
            continue
        seen[ax] = d
        if axes and ax not in axes:
            probs.append((
                "contract-mismatch",
                f"declared spec names mesh axis {ax!r} but mesh_axes "
                f"declares only {sorted(axes)}"))
            continue
        n = axes.get(ax)
        if n is not None and (int(shape[d]) % int(n)) != 0:
            continue                    # leaf too small: replicated
        dims[d] = ax
    return VSpec(dims=tuple(dims)), probs


# ---------------------------------------------------------------------------
# collective cost model (arXiv 2112.09017 ring costs, bytes per device)
# ---------------------------------------------------------------------------


def _factor(n: Optional[int]) -> float:
    """(N-1)/N for a known axis size; 1.0 (the upper bound) unknown."""
    if n is None or n <= 1:
        return 1.0 if n is None else 0.0
    return (n - 1) / n


def all_gather_bytes(nbytes: float, n: Optional[int]) -> float:
    return nbytes * _factor(n)


def reduce_scatter_bytes(nbytes: float, n: Optional[int]) -> float:
    return nbytes * _factor(n)


def all_reduce_bytes(nbytes: float, n: Optional[int]) -> float:
    return 2.0 * nbytes * _factor(n)


def all_to_all_bytes(nbytes: float, n: Optional[int]) -> float:
    return nbytes * _factor(n)


# ---------------------------------------------------------------------------
# the propagation walk
# ---------------------------------------------------------------------------


def _diag(sev: Severity, rule: str, site: str, msg: str,
          where: str = "") -> Diagnostic:
    loc = f" eqn {where}" if where else ""
    return Diagnostic(sev, TAG, f"[{rule}] site {site!r}{loc}: {msg}",
                      vars=(site, rule))


@dataclass
class _Walk:
    """Mutable state shared across one signature's (recursive) walk."""

    site: str
    contract: SiteContract
    axes: Dict[str, int]
    diags: List[Diagnostic] = field(default_factory=list)
    comm: float = 0.0
    _charged: set = field(default_factory=set)   # (id(var), axis)

    def report(self, sev: Severity, rule: str, msg: str,
               where: str = "") -> None:
        self.diags.append(_diag(sev, rule, self.site, msg, where=where))

    def size(self, axis: str) -> Optional[int]:
        return self.axes.get(axis)

    def charge_pending(self, var, vs: VSpec, where: str) -> VSpec:
        """Materialize a var's pending partial-sums as full psums (a
        non-linear consumer, or the jaxpr outputs) — charged once per
        (var, axis)."""
        if not vs.pending:
            return vs
        b = _aval_bytes(getattr(var, "aval", None))
        for axis in vs.pending:
            key = (id(var), axis)
            if key not in self._charged:
                self._charged.add(key)
                self.comm += all_reduce_bytes(b, self.size(axis))
        return vs._replace(pending=frozenset())

    def gather(self, rule_msg: str, nbytes: float, axis: str,
               where: str) -> None:
        """One implicit-all-gather finding + its cost."""
        self.comm += all_gather_bytes(nbytes, self.size(axis))
        self.report(
            Severity.ERROR, "implicit-all-gather",
            f"{rule_msg} — GSPMD must materialize "
            f"~{all_gather_bytes(nbytes, self.size(axis)):.0f} bytes "
            f"over the {axis!r} links (all-gather of a "
            f"{int(nbytes)}-byte operand)", where=where)


def _shape(v) -> Tuple[int, ...]:
    return tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())


def _union_pending(ins: Sequence[VSpec]) -> frozenset:
    out: frozenset = frozenset()
    for vs in ins:
        out = out | vs.pending
    return out


def _prod(xs) -> int:
    n = 1
    for x in xs:
        n *= int(x)
    return n


def _eltwise(st: _Walk, eqn, ins: List[VSpec], path: str,
             linear: bool) -> List[VSpec]:
    """Default rule for shape-broadcasting ops: merge operand specs
    dim-by-dim (aligned from the right); conflicting placements force
    an implicit all-gather of the later operand."""
    out_shape = _shape(eqn.outvars[0])
    nd = len(out_shape)
    if linear:
        pend = _union_pending(ins)
    else:
        for v, vs in zip(eqn.invars, ins):
            st.charge_pending(v, vs, path)
        pend = frozenset()
    unknown = any(vs.dims is None and _prod(_shape(v)) > 1
                  for v, vs in zip(eqn.invars, ins))
    dims: List[Optional[str]] = [None] * nd
    axis_dim: Dict[str, int] = {}
    for oi, (v, vs) in enumerate(zip(eqn.invars, ins)):
        if vs.dims is None:
            continue
        ish = _shape(v)
        off = nd - len(ish)
        for d, ax in enumerate(vs.dims):
            if ax is None:
                continue
            od = d + off
            if od < 0 or ish[d] != out_shape[od] or out_shape[od] <= 1:
                continue
            prev_dim = axis_dim.get(ax)
            if dims[od] is None and prev_dim is None:
                dims[od] = ax
                axis_dim[ax] = od
            elif dims[od] == ax:
                continue
            else:
                # conflict: same dim different axes, or same axis on a
                # different dim — the later operand gets gathered
                if dims[od] is not None:
                    clash = (f"dim {od} of the result is already "
                             f"placed on axis {dims[od]!r}")
                else:
                    clash = (f"axis {ax!r} already shards dim "
                             f"{prev_dim} of the result")
                st.gather(
                    f"operand {oi} of {eqn.primitive.name} is sharded "
                    f"{ax!r}@dim{d} but {clash}",
                    _aval_bytes(v.aval), ax,
                    where=f"{path} ({eqn.primitive.name})")
    if unknown:
        return [VSpec(None, pend) for _ in eqn.outvars]
    return [VSpec(tuple(dims), pend)] + \
        [VSpec(tuple(dims)) for _ in eqn.outvars[1:]]


def _rule_dot_general(st: _Walk, eqn, ins: List[VSpec],
                      path: str) -> List[VSpec]:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs_v, rhs_v = eqn.invars[0], eqn.invars[1]
    lvs, rvs = ins[0], ins[1]
    where = f"{path} (dot_general)"
    # pending: dot is linear in each operand separately; both pending
    # would double-count a product of partials — materialize both then
    if lvs.pending and rvs.pending:
        lvs = st.charge_pending(lhs_v, lvs, path)
        rvs = st.charge_pending(rhs_v, rvs, path)
    pend = lvs.pending | rvs.pending
    if lvs.dims is None or rvs.dims is None:
        return [VSpec(None, pend)]
    lsh, rsh = _shape(lhs_v), _shape(rhs_v)
    ld, rd = list(lvs.dims), list(rvs.dims)
    # contraction dims: sharded-both-sides (same axis) => partial sums;
    # sharded one side (or differently) => that operand gets gathered
    for li, ri in zip(lc, rc):
        la, ra = ld[li], rd[ri]
        if la is not None and la == ra:
            pend = pend | {la}
        elif la is not None or ra is not None:
            if la is not None:
                st.gather(
                    f"contraction dim {li} of the lhs is sharded "
                    f"{la!r} but the rhs contraction dim is not",
                    _aval_bytes(lhs_v.aval), la, where=where)
                ld[li] = None
            if ra is not None:
                st.gather(
                    f"contraction dim {ri} of the rhs is sharded "
                    f"{ra!r} but the lhs contraction dim is not",
                    _aval_bytes(rhs_v.aval), ra, where=where)
                rd[ri] = None
    out_dims: List[Optional[str]] = []
    used: Dict[str, str] = {}

    def _take(ax: Optional[str], origin: str) -> Optional[str]:
        if ax is None:
            return None
        if ax in pend:
            st.report(
                Severity.ERROR, "axis-collision",
                f"mesh axis {ax!r} is consumed by the contraction "
                f"(partial sums) AND shards the {origin} — one axis "
                "cannot do both in one dot_general", where=where)
            return None
        if ax in used:
            st.report(
                Severity.ERROR, "axis-collision",
                f"mesh axis {ax!r} shards both the {used[ax]} and the "
                f"{origin} of one dot_general — the output would be "
                "sharded twice over one axis", where=where)
            return None
        used[ax] = origin
        return ax

    # batch dims: must agree; they lead the output
    for li, ri in zip(lb, rb):
        la, ra = ld[li], rd[ri]
        ax = la if la == ra else None
        if la != ra and (la is not None or ra is not None):
            bad_v, bad_ax = (rhs_v, ra) if ra is not None else (lhs_v, la)
            st.gather(
                f"batch dims of dot_general are sharded inconsistently "
                f"({la!r} vs {ra!r})", _aval_bytes(bad_v.aval),
                bad_ax, where=where)
            ax = None
        out_dims.append(_take(ax, "batch dims"))
    for i in range(len(lsh)):
        if i not in lc and i not in lb:
            out_dims.append(_take(ld[i], "lhs free dims"))
    for i in range(len(rsh)):
        if i not in rc and i not in rb:
            out_dims.append(_take(rd[i], "rhs free dims"))
    return [VSpec(tuple(out_dims), pend)]


def _reshape_groups(ish: Tuple[int, ...], osh: Tuple[int, ...]):
    """Contiguous factor groups of a reshape: ``[(in_dims, out_dims)]``
    pairs with equal products, two-pointer walk.  None when the shapes
    don't decompose (zero-sized dims etc.) — callers fall back to the
    conservative gather."""
    groups: List[Tuple[List[int], List[int]]] = []
    i = j = 0
    ni, nj = len(ish), len(osh)
    while i < ni or j < nj:
        if i < ni and int(ish[i]) == 1 and (j >= nj or int(osh[j]) != 1):
            groups.append(([i], []))        # dangling size-1 in dim
            i += 1
            continue
        if j < nj and int(osh[j]) == 1 and (i >= ni or int(ish[i]) != 1):
            groups.append(([], [j]))        # dangling size-1 out dim
            j += 1
            continue
        if i >= ni or j >= nj:
            return None
        pi, pj = int(ish[i]), int(osh[j])
        di, dj = [i], [j]
        i += 1
        j += 1
        while pi != pj:
            if pi < pj:
                if i >= ni:
                    return None
                pi *= int(ish[i])
                di.append(i)
                i += 1
            else:
                if j >= nj:
                    return None
                pj *= int(osh[j])
                dj.append(j)
                j += 1
        if pi <= 0:
            return None
        groups.append((di, dj))
    return groups


def _rule_reshape(st: _Walk, eqn, ins: List[VSpec],
                  path: str) -> List[VSpec]:
    """GSPMD-compatible reshape propagation: a sharded dim survives when
    it is the MAJOR (first >1) dim of its contiguous factor group and
    the group's major output dim holds a whole number of shards — the
    shard boundary stays contiguous, so merging ``[H, D] -> [H*D]`` or
    splitting ``[E] -> [H, D]`` keeps a head-sharded placement (the
    megatron Q/K/V reshapes).  A sharded dim that is minor in its group,
    or whose target major dim doesn't divide by the axis size, still
    forces the all-gather."""
    vs = ins[0]
    if vs.dims is None:
        return [VSpec(None, vs.pending)]
    in_shape = _shape(eqn.invars[0])
    out_shape = _shape(eqn.outvars[0])
    out_dims: List[Optional[str]] = [None] * len(out_shape)
    groups = _reshape_groups(tuple(in_shape), tuple(out_shape))

    def lose(d: int, ax: str) -> None:
        st.gather(
            f"reshape {tuple(in_shape)} -> {tuple(out_shape)} "
            f"splits/merges the {ax!r}-sharded dim {d}",
            _aval_bytes(eqn.invars[0].aval), ax,
            where=f"{path} (reshape)")

    if groups is None:
        for d, ax in enumerate(vs.dims):
            if ax is not None:
                lose(d, ax)
        return [VSpec(tuple(out_dims), vs.pending)]
    for di, dj in groups:
        major_in = next((d for d in di if int(in_shape[d]) > 1),
                        di[0] if di else None)
        major_out = next((d for d in dj if int(out_shape[d]) > 1),
                         dj[0] if dj else None)
        for d in di:
            ax = vs.dims[d]
            if ax is None:
                continue
            n = st.size(ax)
            if d == major_in and major_out is not None and \
                    (n is None or int(out_shape[major_out]) % int(n) == 0):
                out_dims[major_out] = ax
            else:
                lose(d, ax)
    return [VSpec(tuple(out_dims), vs.pending)]


def _rule_transpose(st: _Walk, eqn, ins: List[VSpec],
                    path: str) -> List[VSpec]:
    vs = ins[0]
    if vs.dims is None:
        return [VSpec(None, vs.pending)]
    perm = eqn.params["permutation"]
    return [VSpec(tuple(vs.dims[p] for p in perm), vs.pending)]


def _rule_broadcast(st: _Walk, eqn, ins: List[VSpec],
                    path: str) -> List[VSpec]:
    vs = ins[0]
    out_shape = _shape(eqn.outvars[0])
    if vs.dims is None:
        return [VSpec(None, vs.pending)]
    in_shape = _shape(eqn.invars[0])
    bdims = eqn.params["broadcast_dimensions"]
    out_dims: List[Optional[str]] = [None] * len(out_shape)
    for i, od in enumerate(bdims):
        if vs.dims[i] is not None \
                and int(in_shape[i]) == int(out_shape[od]):
            out_dims[od] = vs.dims[i]
    return [VSpec(tuple(out_dims), vs.pending)]


def _rule_pad(st: _Walk, eqn, ins: List[VSpec], path: str) -> List[VSpec]:
    vs = ins[0]
    if vs.dims is None:
        return [VSpec(None, vs.pending)]
    cfg = eqn.params["padding_config"]
    out_dims = list(vs.dims)
    for d, (lo, hi, interior) in enumerate(cfg):
        if out_dims[d] is not None and (lo or hi or interior):
            st.gather(
                f"pad widens the {out_dims[d]!r}-sharded dim {d}",
                _aval_bytes(eqn.invars[0].aval), out_dims[d],
                where=f"{path} (pad)")
            out_dims[d] = None
    return [VSpec(tuple(out_dims), vs.pending)]


def _rule_slice(st: _Walk, eqn, ins: List[VSpec],
                path: str) -> List[VSpec]:
    vs = ins[0]
    if vs.dims is None:
        return [VSpec(None, vs.pending)]
    in_shape = _shape(eqn.invars[0])
    out_shape = _shape(eqn.outvars[0])
    out_dims = list(vs.dims)
    for d in range(len(in_shape)):
        if out_dims[d] is not None \
                and int(out_shape[d]) != int(in_shape[d]):
            st.gather(
                f"{eqn.primitive.name} cuts the {out_dims[d]!r}-sharded "
                f"dim {d} ({in_shape[d]} -> {out_shape[d]})",
                _aval_bytes(eqn.invars[0].aval), out_dims[d],
                where=f"{path} ({eqn.primitive.name})")
            out_dims[d] = None
    return [VSpec(tuple(out_dims), vs.pending)]


def _rule_squeeze(st: _Walk, eqn, ins: List[VSpec],
                  path: str) -> List[VSpec]:
    vs = ins[0]
    if vs.dims is None:
        return [VSpec(None, vs.pending)]
    drop = set(eqn.params["dimensions"])
    return [VSpec(tuple(ax for d, ax in enumerate(vs.dims)
                        if d not in drop), vs.pending)]


def _rule_concat(st: _Walk, eqn, ins: List[VSpec],
                 path: str) -> List[VSpec]:
    cdim = eqn.params["dimension"]
    for oi, (v, vs) in enumerate(zip(eqn.invars, ins)):
        if vs.dims is not None and len(vs.dims) > cdim \
                and vs.dims[cdim] is not None:
            st.gather(
                f"operand {oi} of concatenate is sharded "
                f"{vs.dims[cdim]!r} on the concat dim {cdim}",
                _aval_bytes(v.aval), vs.dims[cdim],
                where=f"{path} (concatenate)")
            ins[oi] = VSpec(tuple(None if d == cdim else ax
                                  for d, ax in enumerate(vs.dims)),
                            vs.pending)
    out = _eltwise_nonbroadcast_merge(st, eqn, ins, path, skip_dim=cdim)
    return out


def _eltwise_nonbroadcast_merge(st: _Walk, eqn, ins, path,
                                skip_dim: int) -> List[VSpec]:
    out_shape = _shape(eqn.outvars[0])
    nd = len(out_shape)
    dims: List[Optional[str]] = [None] * nd
    unknown = False
    for v, vs in zip(eqn.invars, ins):
        if vs.dims is None:
            unknown = True
            continue
        for d, ax in enumerate(vs.dims):
            if ax is None or d == skip_dim or d >= nd:
                continue
            if dims[d] is None:
                dims[d] = ax
            elif dims[d] != ax:
                st.gather(
                    f"concatenate operands disagree on dim {d} "
                    f"({dims[d]!r} vs {ax!r})", _aval_bytes(v.aval), ax,
                    where=f"{path} (concatenate)")
    pend = _union_pending(ins)
    return [VSpec(None if unknown else tuple(dims), pend)]


def _rule_reduce(st: _Walk, eqn, ins: List[VSpec],
                 path: str) -> List[VSpec]:
    vs = ins[0]
    axes = eqn.params.get("axes", ())
    name = eqn.primitive.name
    linear = name in ("reduce_sum",)
    if not linear:
        vs = st.charge_pending(eqn.invars[0], vs, path)
    if vs.dims is None:
        return [VSpec(None, vs.pending) for _ in eqn.outvars]
    pend = vs.pending
    out_dims = []
    for d, ax in enumerate(vs.dims):
        if d in axes:
            if ax is not None:
                # reducing over a sharded dim leaves per-device partial
                # results: a pending cross-replica reduce
                pend = pend | {ax}
        else:
            out_dims.append(ax)
    return [VSpec(tuple(out_dims), pend) for _ in eqn.outvars]


def _rule_gather(st: _Walk, eqn, ins: List[VSpec],
                 path: str) -> List[VSpec]:
    vs = ins[0]
    if vs.dims is None:
        return [_UNKNOWN]
    dn = eqn.params["dimension_numbers"]
    in_shape = _shape(eqn.invars[0])
    slice_sizes = tuple(eqn.params.get("slice_sizes", ()) or ())
    batching = set(getattr(dn, "operand_batching_dims", ()) or ())
    indexed = set(dn.start_index_map) | set(dn.collapsed_slice_dims)
    for d, ax in enumerate(vs.dims):
        if ax is None or d in batching:
            continue
        if d in indexed:
            st.gather(
                f"gather indexes the {ax!r}-sharded operand dim {d} "
                "(not a batching dim): every shard needs every other "
                "shard's rows", _aval_bytes(eqn.invars[0].aval), ax,
                where=f"{path} (gather)")
    out_shape = _shape(eqn.outvars[0])
    out_dims: List[Optional[str]] = [None] * len(out_shape)
    # batching dims lead the output and keep their placement
    for i, d in enumerate(sorted(batching)):
        if i < len(out_dims) and vs.dims[d] is not None:
            out_dims[i] = vs.dims[d]
    # window (offset) dims pass the operand placement through when the
    # slice keeps the WHOLE dim — the paged-KV reads (k_pages[table]:
    # page/head/head_dim are full-window dims) stay head-sharded, which
    # is what lets the walk prove the TP decode path reduce-not-gather.
    # A partial slice of a sharded dim is a real re-layout: gather it.
    window = [d for d in range(len(in_shape))
              if d not in dn.collapsed_slice_dims and d not in batching]
    offset = tuple(dn.offset_dims)
    for od, d in zip(offset, window):
        ax = vs.dims[d]
        if ax is None or d in indexed:
            # indexed dims were already reported (and charged) above —
            # an indexed-but-uncollapsed dim is also a window dim, and
            # double-charging it would inflate the comm estimate 2x
            continue
        full = (d < len(slice_sizes)
                and int(slice_sizes[d]) == int(in_shape[d]))
        if full and od < len(out_dims) and out_dims[od] is None:
            out_dims[od] = ax
        elif not full:
            st.gather(
                f"gather slices the {ax!r}-sharded operand dim {d} "
                f"({in_shape[d]} -> "
                f"{slice_sizes[d] if d < len(slice_sizes) else '?'})",
                _aval_bytes(eqn.invars[0].aval), ax,
                where=f"{path} (gather)")
    return [VSpec(tuple(out_dims), vs.pending)]


def _rule_scatter(st: _Walk, eqn, ins: List[VSpec],
                  path: str) -> List[VSpec]:
    vs = ins[0]
    if vs.dims is None:
        return [_UNKNOWN]
    dn = eqn.params["dimension_numbers"]
    batching = set(getattr(dn, "operand_batching_dims", ()) or ())
    touched = set(dn.scatter_dims_to_operand_dims) \
        | set(dn.inserted_window_dims)
    for d, ax in enumerate(vs.dims):
        if ax is None or d in batching:
            continue
        if d in touched:
            st.gather(
                f"{eqn.primitive.name} writes across the {ax!r}-sharded "
                f"operand dim {d} (not a batching dim)",
                _aval_bytes(eqn.invars[0].aval), ax,
                where=f"{path} ({eqn.primitive.name})")
    # scatter preserves the operand's shape and placement
    return [VSpec(vs.dims, _union_pending(ins))]


def _sharding_spec_of(sharding) -> Tuple[Optional[Tuple], Dict[str, int]]:
    """(normalized dims, axis sizes) of a NamedSharding-like object;
    (None, {}) when the sharding type is opaque (GSPMD bytes)."""
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is None or mesh is None:
        return None, {}
    try:
        sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:
        sizes = {}
    return normalize_spec(tuple(spec)), sizes


def _apply_constraint(st: _Walk, var, vs: VSpec, tgt_dims, sizes,
                      where: str) -> VSpec:
    """Cost the transition ``vs`` -> ``tgt_dims`` (a sharding
    constraint or an enforced out_sharding): pending partial-sums over
    an axis the target shards become reduce-scatters (the ZeRO trick),
    other pendings full psums; a sharded axis the target drops is an
    all-gather; replicated -> sharded is a free local slice."""
    for a, n in sizes.items():
        st.axes.setdefault(a, n)
    b = _aval_bytes(getattr(var, "aval", None))
    nd = len(_shape(var))
    tgt = list(tgt_dims) + [None] * (nd - len(tgt_dims)) \
        if tgt_dims is not None else None
    if tgt is None:
        return st.charge_pending(var, vs, where)
    tgt_axes = {a for a in tgt if a is not None}
    for axis in vs.pending:
        key = (id(var), axis)
        if key in st._charged:
            continue
        st._charged.add(key)
        if axis in tgt_axes:
            st.comm += reduce_scatter_bytes(b, st.size(axis))
        else:
            st.comm += all_reduce_bytes(b, st.size(axis))
    if vs.dims is not None:
        src_axes = {a for a in vs.dims if a is not None}
        for axis in src_axes - tgt_axes:
            st.comm += all_gather_bytes(b, st.size(axis))
        for axis in src_axes & tgt_axes:
            if vs.dims.index(axis) != tgt.index(axis):
                # moved to a different dim: an all-to-all-ish reshard
                st.comm += all_to_all_bytes(b, st.size(axis))
    return VSpec(tuple(tgt))


def _rule_constraint(st: _Walk, eqn, ins: List[VSpec],
                     path: str) -> List[VSpec]:
    tgt_dims, sizes = _sharding_spec_of(eqn.params.get("sharding"))
    return [_apply_constraint(st, eqn.invars[0], ins[0], tgt_dims, sizes,
                              f"{path} (sharding_constraint)")]


def _rule_collective(st: _Walk, eqn, ins: List[VSpec],
                     path: str) -> List[VSpec]:
    kind = _COLLECTIVES[eqn.primitive.name]
    axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(axes, (str, int)):
        axes = (axes,)
    for v in eqn.invars:
        b = _aval_bytes(getattr(v, "aval", None))
        for ax in axes:
            n = st.size(str(ax))
            if kind == "ar":
                st.comm += all_reduce_bytes(b, n)
            elif kind == "ag":
                # cost on the gathered OUTPUT bytes
                ob = sum(_aval_bytes(o.aval) for o in eqn.outvars)
                st.comm += all_gather_bytes(ob, n)
            elif kind == "rs":
                st.comm += reduce_scatter_bytes(b, n)
            elif kind == "a2a":
                st.comm += all_to_all_bytes(b, n)
            else:                                      # ppermute
                st.comm += float(b)
    return [_UNKNOWN for _ in eqn.outvars]


_EQN_RULES: Dict[str, Callable] = {
    "dot_general": _rule_dot_general,
    "reshape": _rule_reshape,
    "transpose": _rule_transpose,
    "broadcast_in_dim": _rule_broadcast,
    "pad": _rule_pad,
    "slice": _rule_slice,
    "dynamic_slice": _rule_slice,
    "squeeze": _rule_squeeze,
    "concatenate": _rule_concat,
    "gather": _rule_gather,
    "scatter": _rule_scatter,
    "scatter-add": _rule_scatter,
    "scatter_add": _rule_scatter,
    "sharding_constraint": _rule_constraint,
}
for _name in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
              "reduce_and", "reduce_or", "argmax", "argmin"):
    _EQN_RULES[_name] = _rule_reduce


# ---------------------------------------------------------------------------
# recursive jaxpr walk
# ---------------------------------------------------------------------------


def _as_closed(obj):
    """Jaxpr-or-ClosedJaxpr -> (jaxpr, consts)."""
    jaxpr = getattr(obj, "jaxpr", obj)
    consts = getattr(obj, "consts", ())
    return jaxpr, consts


def _walk_jaxpr(st: _Walk, obj, in_specs: Sequence[VSpec],
                path: str = "") -> List[VSpec]:
    """Propagate VSpecs through one (possibly nested) jaxpr; returns
    the outvars' VSpecs.  ``in_specs`` aligns positionally with the
    jaxpr's invars (missing/short -> unknown)."""
    import jax
    from jax.extend import core as jex_core

    jaxpr, _consts = _as_closed(obj)
    env: Dict[int, VSpec] = {}
    for cv in jaxpr.constvars:
        # jaxpr consts are baked into the executable: replicated by
        # construction on every device
        env[id(cv)] = _repl(len(_shape(cv)))
    for i, v in enumerate(jaxpr.invars):
        vs = in_specs[i] if i < len(in_specs) else _UNKNOWN
        env[id(v)] = vs if vs is not None else _UNKNOWN

    def read(v) -> VSpec:
        if isinstance(v, jex_core.Literal):
            return _repl(len(_shape(v)))
        return env.get(id(v), _UNKNOWN)

    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}{i}"
        ins = [read(v) for v in eqn.invars]
        outs = _run_eqn(st, eqn, ins, here)
        for o, vs in zip(eqn.outvars, outs):
            env[id(o)] = vs if vs is not None else _UNKNOWN
    return [read(v) for v in jaxpr.outvars]


def _align_last(ins: List[VSpec], n: int) -> List[VSpec]:
    """Align outer operand specs onto ``n`` inner invars the way the
    drift rule does: the LAST n operands map positionally (jit and
    custom_* calls pass consts first)."""
    if n <= len(ins):
        return ins[-n:]
    return [_UNKNOWN] * (n - len(ins)) + ins


def _run_eqn(st: _Walk, eqn, ins: List[VSpec], path: str) -> List[VSpec]:
    name = eqn.primitive.name
    rule = _EQN_RULES.get(name)
    if rule is not None:
        return rule(st, eqn, ins, path)
    if name in _COLLECTIVES:
        return _rule_collective(st, eqn, ins, path)
    if name == "jit" or name == "closed_call" or name == "remat" \
            or name == "checkpoint" or name == "custom_jvp_call" \
            or name == "custom_vjp_call" or name == "custom_vjp_call_jaxpr":
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr") \
            or eqn.params.get("fun_jaxpr")
        if inner is None:
            return [_UNKNOWN for _ in eqn.outvars]
        n_in = len(_as_closed(inner)[0].invars)
        outs = _walk_jaxpr(st, inner, _align_last(ins, n_in),
                           path=f"{path}.")
        # primal outputs lead; anything extra (residuals) stays unknown
        return (outs + [_UNKNOWN] * len(eqn.outvars))[:len(eqn.outvars)]
    if name == "cond":
        branches = eqn.params.get("branches", ())
        merged: Optional[List[VSpec]] = None
        best_comm = 0.0
        for br in branches:
            sub = _Walk(site=st.site, contract=st.contract,
                        axes=dict(st.axes))
            n_in = len(_as_closed(br)[0].invars)
            outs = _walk_jaxpr(sub, br, _align_last(ins[1:], n_in),
                               path=f"{path}.")
            st.diags.extend(sub.diags)
            best_comm = max(best_comm, sub.comm)
            if merged is None:
                merged = list(outs)
            else:
                merged = [a if (a.dims is not None and a.dims == b.dims)
                          else VSpec(None, a.pending | b.pending)
                          for a, b in zip(merged, outs)]
        st.comm += best_comm
        outs = merged or []
        return (outs + [_UNKNOWN] * len(eqn.outvars))[:len(eqn.outvars)]
    if name == "while":
        body = eqn.params.get("body_jaxpr")
        if body is not None:
            n_in = len(_as_closed(body)[0].invars)
            _walk_jaxpr(st, body, _align_last(ins, n_in),
                        path=f"{path}.")           # one trip, like xla
        return [_UNKNOWN for _ in eqn.outvars]
    if name == "scan":
        inner = eqn.params.get("jaxpr")
        if inner is None:
            return [_UNKNOWN for _ in eqn.outvars]
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        length = max(1, int(eqn.params.get("length", 1)))
        n_in = len(_as_closed(inner)[0].invars)
        seed = list(ins[:nc + ncar])               # xs slices: unknown
        seed += [_UNKNOWN] * (n_in - len(seed))
        sub = _Walk(site=st.site, contract=st.contract,
                    axes=dict(st.axes))
        outs = _walk_jaxpr(sub, inner, seed[:n_in], path=f"{path}.")
        st.diags.extend(sub.diags)
        st.comm += sub.comm * length               # per-trip collectives
        carries = outs[:ncar]                      # stacked ys: unknown
        res = carries + [_UNKNOWN] * (len(eqn.outvars) - ncar)
        return res[:len(eqn.outvars)]
    if name == "shard_map":
        inner = eqn.params.get("jaxpr")
        if inner is not None:
            sub = _Walk(site=st.site, contract=st.contract,
                        axes=dict(st.axes))
            mesh = eqn.params.get("mesh")
            if mesh is not None:
                try:
                    for a, n in dict(mesh.shape).items():
                        sub.axes.setdefault(str(a), int(n))
                except Exception:
                    pass
            n_in = len(_as_closed(inner)[0].invars)
            # manual region: per-shard shapes, named specs don't apply —
            # walk only to collect the explicit collectives' bytes
            _walk_jaxpr(sub, inner, [_UNKNOWN] * n_in, path=f"{path}.")
            st.comm += sub.comm
        return [_UNKNOWN for _ in eqn.outvars]
    subs = _sub_jaxprs(eqn)
    if subs:
        # unrecognized higher-order op: collect collective costs from
        # the inside, propagate nothing
        for s in subs:
            sub = _Walk(site=st.site, contract=st.contract,
                        axes=dict(st.axes))
            _walk_jaxpr(sub, s, [_UNKNOWN] * len(_as_closed(s)[0].invars),
                        path=f"{path}.")
            st.diags.extend(sub.diags)
            st.comm += sub.comm
        return [_UNKNOWN for _ in eqn.outvars]
    # default: elementwise when the shapes broadcast; unknown otherwise
    out_shape = _shape(eqn.outvars[0]) if eqn.outvars else ()
    if eqn.invars and all(_broadcasts(_shape(v), out_shape)
                          for v in eqn.invars):
        linear = name in _PENDING_PASS
        return _eltwise(st, eqn, ins, path, linear=linear)
    if not eqn.invars:
        return [_repl(len(_shape(o))) for o in eqn.outvars]
    for v, vs in zip(eqn.invars, ins):
        st.charge_pending(v, vs, path)
    return [_UNKNOWN for _ in eqn.outvars]


def _broadcasts(ish: Tuple[int, ...], osh: Tuple[int, ...]) -> bool:
    if len(ish) > len(osh):
        return False
    for i, o in zip(reversed(ish), reversed(osh)):
        if int(i) != 1 and int(i) != int(o):
            return False
    return True


# ---------------------------------------------------------------------------
# per-capture audit
# ---------------------------------------------------------------------------


def _leaf_path_key(path) -> str:
    """Pytree key path -> a stable lookup string: dict keys / sequence
    indices / attr names joined by '/'.  A flat ``{name: array}`` param
    dict yields exactly ``name``."""
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def _resolve_leaf_specs(arg, spec):
    """Pairs of (per-leaf spec, leaf) for one positional arg.  A plain
    spec broadcasts over every leaf (the documented semantics); a DICT
    spec maps pytree key paths to per-leaf specs — the TP serving step
    declares its params this way, one megatron placement per weight —
    with unmatched leaves left None (undeclared, never a finding)."""
    import jax

    if not isinstance(spec, dict):
        return [(spec, leaf) for leaf in jax.tree.leaves(arg)]
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(arg)[0]:
        key = _leaf_path_key(path)
        s = spec.get(key)
        if s is None and "/" in key:
            s = spec.get(key.rsplit("/", 1)[-1])
        out.append((s, leaf))
    return out


def _leaf_specs_for_call(st: _Walk, cap: CapturedCall,
                         contract: SiteContract) -> List[VSpec]:
    """Per-invar seed VSpecs: each positional arg's declared spec
    (broadcast rule; dict specs resolve per leaf by pytree key) applied
    to every one of its array leaves, in the same flatten order
    ``make_jaxpr`` uses; kwargs leaves are unknown.  Contract problems
    (bad axis, duplicate axis, replicated expect_sharded arg) are
    reported here."""
    axes = st.axes
    seeds: List[VSpec] = []
    n_args = len(cap.args)
    for i, arg in enumerate(cap.args):
        spec = _spec_for(contract.in_specs, i, n_args)
        any_sharded = False
        has_leaf = False
        for leaf_spec, leaf in _resolve_leaf_specs(arg, spec):
            if hasattr(leaf, "shape"):
                has_leaf = True
                vs, probs = apply_spec(leaf_spec, tuple(leaf.shape), axes)
                for rule, msg in probs:
                    st.report(Severity.ERROR, rule,
                              f"arg {i}: {msg}")
                if vs.dims is not None \
                        and any(a is not None for a in vs.dims):
                    any_sharded = True
                seeds.append(vs)
            else:
                seeds.append(_UNKNOWN)
        if i in contract.expect_sharded and has_leaf and not any_sharded:
            st.report(
                Severity.ERROR, "accidental-replication",
                f"arg {i} is declared expect_sharded but its effective "
                "input spec carries no mesh axis — the plan's sharding "
                "never reached this argument (every device holds a full "
                "replica)")
    import jax

    for leaf in jax.tree.leaves(cap.kwargs):
        seeds.append(_UNKNOWN)
    return seeds


def _declares_sharding(contract: SiteContract) -> bool:
    for specs in (contract.in_specs, contract.out_specs):
        if not specs:
            continue
        for s in specs:
            entries = s.values() if isinstance(s, dict) else (s,)
            for e in entries:
                ns = normalize_spec(e)
                if ns and any(a is not None for a in ns):
                    return True
    return False


def _out_sharding_targets(st: _Walk, cap: CapturedCall, n_out: int):
    """Per-output (dims, sizes) enforced by the jit's requested
    ``out_shardings`` kwarg (the zero placement identities), or None."""
    import jax

    osh = cap.jit_kwargs.get("out_shardings")
    if osh is None:
        return None
    leaves = jax.tree.leaves(osh, is_leaf=lambda x: hasattr(x, "spec")
                             or isinstance(x, (tuple,)) and not x)
    if not leaves:
        return None
    out = []
    for i in range(n_out):
        leaf = leaves[i] if i < len(leaves) else leaves[-1] \
            if len(leaves) == 1 else None
        if leaf is None:
            out.append((None, {}))
        else:
            out.append(_sharding_spec_of(leaf))
    return out


def _audit_capture(site: str, cap: CapturedCall, contract: SiteContract,
                   closed) -> Tuple[List[Diagnostic], float]:
    """Run the propagation walk over ONE materialized signature;
    returns (diagnostics, estimated collective bytes per call)."""
    from paddle_tpu.platform.flags import FLAGS

    st = _Walk(site=site, contract=contract,
               axes={a: int(n) for a, n in contract.mesh_axes})
    seeds = _leaf_specs_for_call(st, cap, contract)
    if len(seeds) != len(closed.jaxpr.invars):
        # flatten-order mismatch (exotic pytree): audit without seeds —
        # unknowns never produce findings, so this degrades safely
        seeds = [_UNKNOWN] * len(closed.jaxpr.invars)
    # weight-shaped consts are replicated by construction: in a site
    # whose contract shards anything, that IS the accidental replication
    if _declares_sharding(contract):
        limit = contract.big_arg_bytes if contract.big_arg_bytes \
            is not None else int(FLAGS.xla_audit_big_arg_bytes)
        for cv, c in zip(closed.jaxpr.constvars, closed.consts):
            nbytes = getattr(c, "nbytes", 0) or 0
            if nbytes > limit:
                st.report(
                    Severity.ERROR, "accidental-replication",
                    f"{tuple(getattr(c, 'shape', ()))} "
                    f"{getattr(c, 'dtype', '?')} ({nbytes} bytes) is a "
                    "jaxpr const — consts replicate onto every device, "
                    "so a sharded site pays a full copy per chip; pass "
                    "it as an argument with a declared spec",
                    where="consts")
    outs = _walk_jaxpr(st, closed, seeds)
    # the jit's own out_shardings are an enforced final resharding
    # (the zero placement identities' all-gather lives here)
    targets = _out_sharding_targets(st, cap, len(closed.jaxpr.outvars))
    if targets is not None:
        outs = [_apply_constraint(st, v, vs, dims, sizes, "out")
                for v, vs, (dims, sizes)
                in zip(closed.jaxpr.outvars, outs, targets)]
    # leftover partial sums cross the jit boundary: GSPMD inserts the
    # all-reduce before returning (the data-parallel grad psum)
    outs = [st.charge_pending(v, vs, "out")
            for v, vs in zip(closed.jaxpr.outvars, outs)]
    n_out = len(outs)
    for i, (v, vs) in enumerate(zip(closed.jaxpr.outvars, outs)):
        declared = normalize_spec(_spec_for(contract.out_specs, i, n_out))
        if declared is None or vs.dims is None:
            continue
        nd = len(_shape(v))
        want = (tuple(declared) + (None,) * nd)[:nd]
        if tuple(vs.dims) != want:
            st.report(
                Severity.ERROR, "contract-mismatch",
                f"output {i} is inferred {_fmt_dims(vs.dims)} but the "
                f"contract declares {_fmt_dims(want)} — the site's "
                "declared plan and the compiled program disagree")
    if contract.comm_bytes is not None and st.comm > contract.comm_bytes:
        st.report(
            Severity.ERROR, "comm-budget",
            f"estimated {st.comm:.0f} collective bytes per call exceed "
            f"the declared comm_bytes budget {contract.comm_bytes:.0f} "
            "— an unplanned resharding/collective entered the compiled "
            "step")
    elif st.comm > 0:
        if contract.comm_bytes is not None:
            st.report(
                Severity.INFO, "comm-budget",
                f"estimated {st.comm:.0f} collective bytes per call "
                f"(within the declared {contract.comm_bytes:.0f}-byte "
                "budget)")
        else:
            st.report(
                Severity.INFO, "comm-budget",
                f"estimated {st.comm:.0f} collective bytes per call "
                "(unbudgeted; declare SiteContract(comm_bytes=...) to "
                "gate)")
    return st.diags, st.comm


def _fmt_dims(dims) -> str:
    return "P(" + ", ".join(str(a) for a in dims) + ")"


# ---------------------------------------------------------------------------
# site / auditor surface
# ---------------------------------------------------------------------------


@dataclass
class ShardReport:
    """Sharding-audit result for one site across its signatures."""

    site: str
    signatures: int = 0
    comm_bytes: float = 0.0             # max over signatures, per call
    diagnostics: List[Diagnostic] = field(default_factory=list)

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics
                if d.severity is Severity.ERROR]


def audit_record_sharding(name: str, rec: SiteRecord,
                          rules: Optional[Sequence[str]] = None
                          ) -> ShardReport:
    """Audit every captured signature of one site through its OWN
    captured contract (xla.py's per-capture fallback chain); dedupe by
    message across signatures; stamp the comm estimate onto the record
    so ``auditor().publish`` lands it as ``comm_bytes_total{site=}``."""
    from paddle_tpu.analysis.xla import materialize_jaxpr

    rep = ShardReport(site=name)
    seen: set = set()
    for _sig, cap in list(rec.captured.items()):
        contract = cap.contract or rec.contract or _DEFAULT_CONTRACT
        closed = materialize_jaxpr(cap)
        diags, comm = _audit_capture(name, cap, contract, closed)
        rep.signatures += 1
        rep.comm_bytes = max(rep.comm_bytes, comm)
        for d in diags:
            if rules is not None and d.vars[1] not in rules:
                continue
            if d.message not in seen:
                seen.add(d.message)
                rep.diagnostics.append(d)
    rec.comm_bytes = rep.comm_bytes
    return rep


def audit_sharding_sites(aud=None, sites: Optional[Sequence[str]] = None,
                         rules: Optional[Sequence[str]] = None
                         ) -> Dict[str, ShardReport]:
    """Audit every captured ``audit_jit`` site; {site: ShardReport}.
    Sites with no captures are skipped here — the driver prints the
    loud 'declared but not audited' notice for the contract-bearing
    ones, so a stub plan cannot silently pass."""
    aud = aud if aud is not None else auditor()
    out: Dict[str, ShardReport] = {}
    for name, rec in sorted(aud.sites.items()):
        if sites is not None and name not in sites:
            continue
        if not rec.captured:
            continue
        out[name] = audit_record_sharding(name, rec, rules=rules)
    return out


# ---------------------------------------------------------------------------
# drives (CLI + clean-run test pins share them)
# ---------------------------------------------------------------------------


def ensure_virtual_devices(n: int) -> int:
    """Force ``n`` virtual CPU devices for a CLI run (same trick as
    tests/conftest.py) — must run before the first backend
    initialization; returns the actual device count either way."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            flags + f" --xla_force_host_platform_device_count={int(n)}"
    import jax

    return len(jax.devices())


def drive_zero_placement(n_devices: Optional[int] = None):
    """Exercise the ZeRO placement jits (``zero.reshard`` /
    ``zero.replicate``) on a data mesh: place a host optimizer state
    into the flat sharded layout, RE-place the already-flat device
    state (the compiled reshard), and gather it back layout-independent
    (the compiled all-gather the checkpoint save pays).  Requires
    ``FLAGS.jit_audit`` on before the call.  Returns the plan (or None
    when only one device is available — nothing shards)."""
    import jax
    import numpy as np

    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.zero import build_zero_plan

    devs = jax.devices()
    n = int(n_devices or min(8, len(devs)))
    if n < 2:
        return None
    mesh = make_mesh((n,), ("data",), devs[:n])
    params = {"w": np.zeros((8, 8), np.float32),
              "b": np.zeros((9,), np.float32)}       # padding case
    plan = build_zero_plan(mesh, params)
    state = {"slots": {"momentum": {
        k: np.ones_like(v) for k, v in params.items()}}}
    placed = plan.shard_state(state)                 # host -> flat shards
    replaced = plan.shard_state(placed)              # zero.reshard site
    gathered = plan.gather_state(replaced)           # zero.replicate site
    for k, v in params.items():
        np.testing.assert_allclose(
            np.asarray(gathered["slots"]["momentum"][k]),
            np.ones_like(v))
    return plan


def drive_serving_tp_steady_state(tp: int = 2, kv_dtype: str = "int8"):
    """The tensor-parallel serving steady state the gate audits IN
    ADDITION to the replicated one: a ``model``-axis mesh of ``tp``
    chips, int8 pool, GQA heads — warmup covers every (decode, prefill)
    pair bucket the replay uses, a full-cover cache hit exercises the
    sharded COW fork and a fault-poisoned request the sharded scrub, so
    ``serving.step``/``fork_page``/``zero_pages`` all capture TP
    signatures under the flipped model-axis contracts.  The model
    geometry deliberately differs from the replicated drive's (H4/KVH2
    vs H2) so the two engines' signatures never collide at the shared
    sites.  Requires ``FLAGS.jit_audit`` on before the call; returns
    the engine (None when fewer than ``tp`` devices exist — the CLI's
    virtual-8 guarantee makes that a test-environment case only)."""
    import jax
    import numpy as np

    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.serving import DecoderLM, ServingEngine
    from paddle_tpu.serving.faults import FaultPlan

    devs = jax.devices()
    if len(devs) < tp:
        return None
    mesh = make_mesh((tp,), ("model",), devs[:tp])
    model = DecoderLM(vocab_size=50, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=8, max_positions=128)
    params = model.init_params(jax.random.PRNGKey(1))
    faults = FaultPlan()
    eng = ServingEngine(model, params, eos_id=1, page_size=4,
                        num_pages=64, max_pages_per_seq=12, max_slots=4,
                        buckets=(4, 8, 16), prefill_chunk=8,
                        kv_dtype=kv_dtype, faults=faults, mesh=mesh)
    rng = np.random.RandomState(1)
    shared = rng.randint(2, 50, size=8).tolist()   # two FULL pages
    eng.submit(shared, max_tokens=6)
    eng.run(max_ticks=200)
    eng.submit(shared, max_tokens=6)               # full-cover hit: fork
    eng.run(max_ticks=200)
    eng.submit(rng.randint(2, 50, size=4).tolist(), max_tokens=12)
    eng.step()
    eng.submit(rng.randint(2, 50, size=20).tolist(), max_tokens=8)
    eng.run(max_ticks=300)
    # poisoned decode: the sharded FAILED scrub (serving.zero_pages)
    bad = eng.submit(rng.randint(2, 50, size=5).tolist(), max_tokens=6)
    eng.step()
    faults.poison_nan(bad)
    eng.run(max_ticks=200)
    return eng


def replay_serving_tp(eng) -> None:
    """The sealed steady-state replay for the TP engine — fresh traffic
    over the same pair buckets, so 'TP adds no compile dimension' is
    checked by the same RETRACE fold-in as the replicated replay."""
    import numpy as np

    rng = np.random.RandomState(9)
    eng.submit(rng.randint(2, 50, size=4).tolist(), max_tokens=10)
    eng.step()
    eng.submit(rng.randint(2, 50, size=17).tolist(), max_tokens=6)
    eng.run(max_ticks=300)


def drive_page_migration(eng):
    """Exercise ``serving.import_pages``: export one RUNNING request's
    page chain from ``eng`` and splice it straight back in
    (migrate.import_chain), so the donated import scatter captures
    under the KV contract instead of standing as a declared-but-dead
    site.  Returns the imported rid (or None if the engine never made
    the request migratable — a scheduler-pressure case the caller
    surfaces as a coverage notice)."""
    import numpy as np

    from paddle_tpu.serving.migrate import export_chain, import_chain

    rng = np.random.RandomState(11)
    rid = eng.submit(rng.randint(2, 50, size=9).tolist(), max_tokens=8)
    for _ in range(60):
        if rid in eng.migratable_rids():
            break
        eng.step()
    else:
        eng.cancel(rid)
        return None
    blob = export_chain(eng, rid)
    rid2 = import_chain(eng, blob)
    eng.cancel(rid)
    if rid2 is not None:
        eng.cancel(rid2)
    return rid2


def drive_pipeline_moe_train_step(stages: int = 4, microbatches: int = 4):
    """Drive a REAL pipeline-parallel train step plus an expert-parallel
    MoE forward/backward so ``parallel.pipeline`` and ``parallel.moe``
    capture under their closed-form contracts (budget == estimate — any
    extra collective trips the gate):

    - a 4-layer transformer LM on a ``(data=2, stage=4)`` mesh through
      ``trainer.SGD(pipeline=PipelineConfig(...), zero=1)`` — one
      guardable jitted step running the GPipe fill+drain schedule with
      ZeRO-sharded boundary-param optimizer state (the 4D composition);
    - a top-2-routed ``moe_ffn`` with drop-rate stats (fwd+grad) and a
      top-1 forward on an 8-way ``expert`` mesh.

    Requires ``FLAGS.jit_audit`` on before the call.  Returns the
    trainer (None when fewer than ``2 * stages`` devices exist — the
    CLI's virtual-8 guarantee makes that a test-environment case)."""
    import jax
    import numpy as np

    devs = jax.devices()
    if len(devs) < 2 * stages:
        return None
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as popt
    from paddle_tpu import trainer as ptrainer
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel.pipeline import PipelineConfig

    vocab, d, n_layers, n_heads, t = 64, 32, 4, 2, 16
    paddle.topology.reset_name_scope()
    tokens, pos, target, logits, cost = transformer.build(
        vocab_size=vocab, d_model=d, n_layers=n_layers, n_heads=n_heads,
        max_len=t)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=0)
    sgd = ptrainer.SGD(cost=cost, parameters=params,
                       update_equation=popt.Adam(learning_rate=1e-3),
                       pipeline=PipelineConfig(num_stages=stages,
                                               microbatches=microbatches,
                                               n_layers=n_layers,
                                               n_heads=n_heads),
                       zero=1)
    step = sgd._build_step()
    rng = np.random.RandomState(3)
    samples = []
    for _ in range(2 * microbatches):
        toks = rng.randint(0, vocab, size=t)
        samples.append((toks.tolist(), list(range(t)),
                        np.roll(toks, -1).tolist()))
    feeder = sgd._make_feeder({"tokens": 0, "pos": 1, "target": 2})
    feeds = sgd._shard_feeds(feeder.feed(samples))
    step(sgd.parameters.as_dict(), sgd.opt_state, sgd.model_state,
         jax.random.PRNGKey(0), feeds)

    from paddle_tpu.parallel import moe as pmoe
    from paddle_tpu.parallel.mesh import make_mesh

    n = min(8, len(devs))
    mesh = make_mesh((n,), ("expert",), devs[:n])
    mp = pmoe.init_moe_params(jax.random.PRNGKey(5), d_model=16,
                              hidden=32, num_experts=n)
    x = jax.random.normal(jax.random.PRNGKey(6), (8 * n, 16))

    def moe_loss(p, xx):
        y, aux, stats = pmoe.moe_ffn(mesh, xx, p, top_k=2,
                                     return_stats=True)
        return (y * y).mean() + 0.01 * aux, stats

    (_, stats), _ = jax.value_and_grad(moe_loss, has_aux=True)(mp, x)
    pmoe.record_moe_stats(stats)        # the metrics-registry seam
    # top-1 wrap key too — distinct token count, so the two dispatch
    # geometries stay distinct signatures at the shared site (the
    # RETRACE fold would flag same-signature recompiles)
    pmoe.moe_ffn(mesh, x[:4 * n], mp, top_k=1)
    return sgd


def run_sharding_audit(printer: Callable[[str], None] = print,
                       rules: Optional[Sequence[str]] = None
                       ) -> Tuple[Dict[str, ShardReport],
                                  List[Diagnostic]]:
    """The acceptance run: flip ``FLAGS.jit_audit`` on, drive the same
    serving + trainer steady states as the xla gate PLUS the ZeRO
    placement jits, the pipeline-parallel train step and the
    expert-parallel MoE dispatch (closed-form contracts, budget ==
    estimate), seal, and replay a steady-state serving burst — then run
    the sharding rules over every captured site.  Returns (reports,
    all_diagnostics); RETRACE diagnostics from the sealed replay fold
    in, same contract as the xla gate."""
    from paddle_tpu.analysis.xla import (drive_serving_steady_state,
                                         drive_trainer_step)
    from paddle_tpu.platform.flags import FLAGS

    old = FLAGS.jit_audit
    FLAGS.jit_audit = True
    aud = auditor()
    aud.reset()
    try:
        eng = drive_serving_steady_state(seal=False)
        drive_trainer_step()
        plan = drive_zero_placement()
        # the tensor-parallel steady state rides the same gate: its
        # model-axis contracts (megatron param specs, sharded pool,
        # closed-form psum budget) audit next to the replicated
        # baseline, so an implicit all-gather or comm-budget regression
        # on the TP decode hot path fails tier-1 through the SAME
        # ladder exit as any other sharding finding
        tp_eng = drive_serving_tp_steady_state()
        pipe_sgd = drive_pipeline_moe_train_step()
        migrated = drive_page_migration(eng)
        aud.seal()
        import numpy as np

        rng = np.random.RandomState(7)
        eng.submit(rng.randint(2, 50, size=4).tolist(), max_tokens=12)
        eng.step()
        eng.submit(rng.randint(2, 50, size=17).tolist(), max_tokens=8)
        eng.run(max_ticks=300)
        if tp_eng is not None:
            # sealed TP replay: TP must not add a compile dimension
            replay_serving_tp(tp_eng)
        reports = audit_sharding_sites(aud, rules=rules)
    finally:
        FLAGS.jit_audit = old
    diags: List[Diagnostic] = []
    for name, rep in reports.items():
        printer(f"== {name}: {rep.signatures} signature(s), "
                f"est {rep.comm_bytes:.0f} collective bytes/call")
        for d in rep.diagnostics:
            printer(f"  {d}")
        diags.extend(rep.diagnostics)
    if plan is None:
        printer("== zero placement: <2 devices, nothing shards — the "
                "ZeRO reduce-scatter/all-gather pair was NOT audited "
                "(run with virtual devices to cover it)")
    if tp_eng is None:
        printer("== serving tp: <2 devices — the tensor-parallel "
                "serving contracts were NOT audited (run with virtual "
                "devices to cover them)")
    if pipe_sgd is None:
        printer("== pipeline/moe: <8 devices — the pipeline-parallel "
                "train step and expert-parallel MoE contracts were NOT "
                "audited (run with virtual devices to cover them)")
    if migrated is None:
        printer("== page migration: the export/import splice never ran "
                "(request not migratable) — serving.import_pages was "
                "NOT audited this run")
    # a contract-bearing site the drives never compiled is a coverage
    # hole, not a pass — the pipeline/MoE stubs land here by design
    for name, rec in sorted(aud.sites.items()):
        if rec.contract is not None and not rec.captured:
            printer(f"== {name}: declared a sharding contract but "
                    "captured no signatures this run — its plan was "
                    "NOT audited (stub or dead site)")
    retraces = list(aud.diagnostics)
    for d in retraces:
        printer(f"  {d}")
    diags.extend(retraces)
    return reports, diags
