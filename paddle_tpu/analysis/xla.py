"""Jaxpr-level compiled-path auditor: does what gets COMPILED match
what the site declared?

PR 5's verifier checks the *fluid program* layer and the retrace
auditor counts *how often* we compile — this module inspects *what*
gets compiled: the ``ClosedJaxpr`` behind every named ``audit_jit``
site (``serving.step``, the trainer steps, the ZeRO placement jits).
That is where a silently dropped ``donate_argnums``, a bf16→f32
promotion, or an accidentally const-captured weight array costs real
HBM and MFU while every number still comes out right.

Each site's :class:`~paddle_tpu.analysis.retrace.SiteRecord` (under
``FLAGS.jit_audit``) captures one abstract ``(args, kwargs)`` per
compiled signature plus the *requested* jit kwargs and the
:class:`~paddle_tpu.analysis.retrace.SiteContract` declared next to
the jit call.  The auditor re-materializes each signature's jaxpr via
``jax.make_jaxpr`` and runs a rule registry over it:

- **donation-contract** — every argnum the contract declares donatable
  must appear in the requested ``donate_argnums`` (requested, not
  backend-effective: CPU tier-1 runs still verify the TPU contract)
  and be alias-eligible (some output aval matches each donated leaf);
  any large non-donated argument whose avals all match outputs is a
  donation candidate (the caller overwrites it, so XLA pays a copy).
- **dtype-promotion-drift** — the walk seeds every input with its
  declared dtype and flags narrow operands (bf16/f16/int8) silently
  promoted into f32 matmuls/reductions; ``contract.allow_upcast``
  sanctions the intentional paths (int8 dequant, f32 loss/norm
  reductions under use_bf16, ``attn_pv_f32``).
- **host-transfer** — ``pure_callback``/``io_callback``/
  ``debug_callback``/infeed/outfeed eqns: ERROR inside ``per_tick``
  serving sites (one host sync per tick is the documented budget and
  it happens OUTSIDE the compiled step), INFO elsewhere.
- **const-capture** — arrays above a byte threshold baked into the
  executable as jaxpr consts instead of arguments: re-baked on every
  compile, duplicated per specialization, and invisible to donation.
- **collective-placement** — ``psum``/``all_gather``/... eqns: ERROR
  in single-replica ``per_tick`` sites, INFO where the contract says
  collectives are the point (ZeRO, sharded train steps).
- **budget** — an abstract live-set/FLOP estimate per signature
  (:func:`estimate_jaxpr`), checked against the ``peak_bytes`` /
  ``flops`` budgets declared next to the ``audit_jit`` call.

Findings are structured :class:`Diagnostic`\\ s whose code is the
grep-able ``XLA-AUDIT`` tag and whose message names the rule, site and
eqn.  ``python -m paddle_tpu.analysis xla`` drives a sealed mixed
serving steady-state run (int8 KV, prefix cache on) plus one trainer
step, audits every captured site, and exits 1 on findings / 2 on a
crash — ``tools_tier1.sh`` turns that into ladder exit 8.

Estimator semantics (documented approximations, all upper-bound
flavored): peak bytes is a linear live-variable scan that ignores
donation aliasing and rematerialization; nested jaxprs (pjit / scan /
cond / shard_map) contribute ``max(inner peak, outer live)``; scan
FLOPs multiply by the trip count, while_loops count one trip; conv
FLOPs use the dense upper bound.  Budgets are guardrails against
asymptotic surprises (an O(B·S²) broadcast, a duplicated pool), not
cycle-accurate predictions — declare them with slack.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from paddle_tpu.analysis.diagnostics import Diagnostic, Severity
from paddle_tpu.analysis.retrace import (CapturedCall, SiteContract,
                                         SiteRecord, auditor)

__all__ = ["audit_sites", "audit_record", "estimate_jaxpr", "SiteReport",
           "RULES", "drive_serving_steady_state",
           "drive_serving_spec_steady_state", "drive_trainer_step",
           "run_compiled_path_audit"]

TAG = "XLA-AUDIT"

_DEFAULT_CONTRACT = SiteContract()

_NARROW = {"bfloat16", "float16", "int8", "uint8"}
_DRIFT_SINKS = {"dot_general", "conv_general_dilated", "reduce_sum",
                "reduce_prod"}
_CALLBACKS = {"pure_callback", "io_callback", "debug_callback", "callback",
              "infeed", "outfeed"}
_COLLECTIVES = {"psum", "psum_invariant", "all_gather", "all_gather_invariant",
                "all_to_all", "ppermute", "pshuffle", "psum_scatter",
                "reduce_scatter", "all_reduce"}


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    import numpy as np

    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:           # symbolic dim: count as 1
            pass
    return n * np.dtype(dtype).itemsize


def _aval_key(aval) -> Tuple:
    return (tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "?")))


def _sub_jaxprs(eqn) -> List:
    """Closed sub-jaxprs of an eqn (pjit, scan, while, cond branches,
    custom_* calls, shard_map) as (ClosedJaxpr-or-Jaxpr) values."""
    import jax
    from jax.extend import core as jex_core

    out = []

    def add(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            out.append(v)
        elif isinstance(v, jex_core.Jaxpr):
            out.append(jex_core.ClosedJaxpr(v, ()))

    for v in eqn.params.values():
        add(v)
        if isinstance(v, (list, tuple)):
            for x in v:
                add(x)
    return out


def _iter_eqns(closed, path: str = ""):
    """Yield (eqn, path) depth-first across nested jaxprs; ``path`` is
    the dotted eqn index ("3.1" = eqn 1 inside eqn 3's sub-jaxpr)."""
    for i, eqn in enumerate(closed.jaxpr.eqns):
        here = f"{path}{i}"
        yield eqn, here
        for sub in _sub_jaxprs(eqn):
            yield from _iter_eqns(sub, path=f"{here}.")


def materialize_jaxpr(cap: CapturedCall):
    """Re-trace one captured signature through the raw callable that
    ACTUALLY traced it (each capture carries its own closure — two
    engines sharing a site name wrap different closures).
    ``make_jaxpr`` traces the raw fn (NOT the counting wrapper), so
    materialization never pollutes the compile counts; static jit
    kwargs (out_shardings, donation) do not change the traced
    program."""
    import jax

    return jax.make_jaxpr(cap.fn)(*cap.args, **cap.kwargs)


# ---------------------------------------------------------------------------
# live-set / FLOP estimator
# ---------------------------------------------------------------------------


def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = 1
    for d in lb:
        batch *= int(lhs[d])
    contract = 1
    for d in lc:
        contract *= int(lhs[d])
    m = 1
    for i, d in enumerate(lhs):
        if i not in lc and i not in lb:
            m *= int(d)
    n = 1
    for i, d in enumerate(rhs):
        if i not in rc and i not in _rb:
            n *= int(d)
    return 2.0 * batch * m * n * contract


def _elems(aval) -> float:
    n = 1
    for d in getattr(aval, "shape", ()):
        try:
            n *= int(d)
        except TypeError:
            pass
    return float(n)


def estimate_jaxpr(closed) -> Tuple[int, float]:
    """(peak_live_bytes, total_flops) of one ClosedJaxpr — a linear
    abstract walk: every var costs ``prod(shape) * itemsize`` from its
    definition to its last use (donation aliasing ignored, so the
    estimate upper-bounds a donating executable); FLOPs are exact for
    ``dot_general``, input-sized for reductions, output-sized for
    everything elementwise, dense-upper-bound for conv, and nested
    jaxprs fold in as described in the module doc."""
    import jax
    from jax.extend import core as jex_core

    jaxpr = closed.jaxpr
    last_use: Dict[int, int] = {}
    n_eqns = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                last_use[id(v)] = i
    for v in jaxpr.outvars:
        if not isinstance(v, jex_core.Literal):
            last_use[id(v)] = n_eqns

    live: Dict[int, int] = {}
    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        live[id(v)] = _aval_bytes(v.aval)
    cur = sum(live.values())
    peak = cur
    flops = 0.0
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        subs = _sub_jaxprs(eqn)
        if subs:
            inner = [estimate_jaxpr(s) for s in subs]
            inner_peak = max(p for p, _ in inner)
            inner_flops = sum(f for _, f in inner)
            if name == "scan":
                inner_flops *= max(1, int(eqn.params.get("length", 1)))
            elif name == "cond":
                inner_flops = max(f for _, f in inner)
            flops += inner_flops
            peak = max(peak, cur + inner_peak)
        elif name == "dot_general":
            flops += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            # dense upper bound: every output element pays the whole
            # kernel (2 * out * rhs_elems / out_channels would need the
            # dimension_numbers dance; the bound is what budgets want)
            flops += 2.0 * _elems(eqn.outvars[0].aval) \
                * _elems(eqn.invars[1].aval)
        elif name.startswith("reduce_") or name in ("argmax", "argmin"):
            flops += _elems(eqn.invars[0].aval)
        else:
            flops += sum(_elems(o.aval) for o in eqn.outvars)
        for o in eqn.outvars:
            b = _aval_bytes(o.aval)
            live[id(o)] = b
            cur += b
        peak = max(peak, cur)
        dying = {id(v) for v in eqn.invars
                 if not isinstance(v, jex_core.Literal)}
        for vid in dying:
            if last_use.get(vid) == i and vid in live:
                cur -= live.pop(vid)
    return int(peak), flops


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


def _diag(sev: Severity, rule: str, site: str, msg: str,
          where: str = "") -> Diagnostic:
    loc = f" eqn {where}" if where else ""
    return Diagnostic(sev, TAG, f"[{rule}] site {site!r}{loc}: {msg}",
                      vars=(site, rule))


def _flat_avals(x) -> List[Tuple]:
    """Aval keys of every array leaf of one argument pytree."""
    import jax

    out = []
    for leaf in jax.tree.leaves(x):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            out.append(_aval_key(leaf))
    return out


def _leaf_bytes(x) -> int:
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(x):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            n = 1
            for d in leaf.shape:
                n *= int(d)
            total += n * np.dtype(leaf.dtype).itemsize
    return total


def _big_arg_threshold(contract: SiteContract) -> int:
    if contract.big_arg_bytes is not None:
        return int(contract.big_arg_bytes)
    from paddle_tpu.platform.flags import FLAGS

    return int(FLAGS.xla_audit_big_arg_bytes)


def _const_threshold(contract: SiteContract) -> int:
    if contract.const_bytes is not None:
        return int(contract.const_bytes)
    from paddle_tpu.platform.flags import FLAGS

    return int(FLAGS.xla_audit_const_bytes)


def _rule_donation(site, closed, call, jit_kwargs, contract,
                   est) -> List[Diagnostic]:
    args, _kwargs = call
    donate = jit_kwargs.get("donate_argnums", ()) or ()
    if isinstance(donate, int):
        donate = (donate,)
    donate = set(int(d) for d in donate)
    out: List[Diagnostic] = []
    # multiset of output avals, consumed as donated/candidate args match
    remaining: Dict[Tuple, int] = {}
    for aval in closed.out_avals:
        k = _aval_key(aval)
        remaining[k] = remaining.get(k, 0) + 1

    def consume(keys) -> bool:
        taken = []
        for k in keys:
            if remaining.get(k, 0) <= 0:
                for t in taken:
                    remaining[t] += 1
                return False
            remaining[k] -= 1
            taken.append(k)
        return True

    for argnum in contract.donate:
        if argnum >= len(args):
            continue
        if argnum not in donate:
            out.append(_diag(
                Severity.ERROR, "donation-contract", site,
                f"arg {argnum} is declared donatable by the site "
                f"contract but absent from the requested donate_argnums="
                f"{tuple(sorted(donate))} — the compiled step copies it "
                "instead of updating in place (peak HBM doubles the "
                "documented cost)"))
            continue
        if not consume(_flat_avals(args[argnum])):
            out.append(_diag(
                Severity.WARNING, "donation-contract", site,
                f"arg {argnum} is donated but not alias-eligible: no "
                "unclaimed output aval matches every donated leaf, so "
                "XLA silently drops the donation"))
    big = _big_arg_threshold(contract)
    for i, a in enumerate(args):
        if i in donate or i in contract.donate:
            continue
        keys = _flat_avals(a)
        if not keys or _leaf_bytes(a) < big:
            continue
        if consume(keys):
            out.append(_diag(
                Severity.WARNING, "donation-contract", site,
                f"arg {i} ({_leaf_bytes(a)} bytes) aval-matches the "
                "outputs but is not donated — if the caller overwrites "
                "it with the result (the repo's step idiom), donating "
                "saves a full copy"))
    return out


def _rule_dtype_drift(site, closed, call, jit_kwargs, contract,
                      est) -> List[Diagnostic]:
    import jax
    from jax.extend import core as jex_core

    allow = set(contract.allow_upcast)
    out: List[Diagnostic] = []
    seen: set = set()                      # (origin, prim): dedupe spam

    def walk(sub, origin: Dict[int, str], path: str):
        for i, eqn in enumerate(sub.jaxpr.eqns):
            here = f"{path}{i}"
            name = eqn.primitive.name
            # origin per POSITION over the FULL invar list (Literals
            # slot in as None) — sub-jaxpr invars align positionally
            # with eqn.invars, so filtering literals first would shift
            # every origin onto the wrong inner operand
            in_orig = [None if isinstance(v, jex_core.Literal)
                       else origin.get(id(v)) for v in eqn.invars]
            if name == "convert_element_type":
                v0 = eqn.invars[0]
                src_dt = str(v0.aval.dtype) if hasattr(v0, "aval") else "?"
                src = in_orig[0] or src_dt
                dst_dt = str(eqn.outvars[0].aval.dtype)
                if dst_dt == "float32" and src in _NARROW \
                        and src not in allow:
                    origin[id(eqn.outvars[0])] = src
                continue
            if name in _DRIFT_SINKS:
                for o in in_orig:
                    if o and (o, name) not in seen:
                        seen.add((o, name))
                        out.append(_diag(
                            Severity.ERROR, "dtype-promotion-drift", site,
                            f"{o} operand silently upcast to f32 feeds "
                            f"{name} — the narrow dtype's memory/MXU "
                            "saving is spent without being declared; "
                            "allowlist an intentional path via "
                            f"SiteContract(allow_upcast=({o!r},))",
                            where=f"{here} ({name})"))
                continue
            subs = _sub_jaxprs(eqn)
            if subs:
                for s in subs:
                    inner: Dict[int, str] = {}
                    ivars = s.jaxpr.invars
                    for v, o in zip(ivars, in_orig[-len(ivars):]):
                        if o:
                            inner[id(v)] = o
                    walk(s, inner, path=f"{here}.")
                continue
            # elementwise/structural f32 ops carry the origin forward
            # (the dequant mul, gathers, reshapes) so the sink check
            # sees through them
            carried = next((o for o in in_orig if o), None)
            if carried:
                for o in eqn.outvars:
                    if str(getattr(o.aval, "dtype", "")) == "float32":
                        origin[id(o)] = carried

    seed: Dict[int, str] = {}
    for v in closed.jaxpr.invars:
        dt = str(getattr(v.aval, "dtype", ""))
        if dt in _NARROW and dt not in allow:
            seed[id(v)] = dt
    walk(closed, seed, "")
    return out


def _rule_host_transfer(site, closed, call, jit_kwargs, contract,
                        est) -> List[Diagnostic]:
    sev = Severity.ERROR if contract.per_tick else Severity.INFO
    out = []
    for eqn, path in _iter_eqns(closed):
        name = eqn.primitive.name
        if name in _CALLBACKS or "callback" in name:
            out.append(_diag(
                sev, "host-transfer", site,
                f"{name} crosses the host boundary inside the compiled "
                "step" + (" — a per-tick serving site budgets exactly "
                          "one host sync per tick, OUTSIDE the jit"
                          if contract.per_tick else ""),
                where=f"{path} ({name})"))
    return out


def _rule_const_capture(site, closed, call, jit_kwargs, contract,
                        est) -> List[Diagnostic]:
    import numpy as np

    limit = _const_threshold(contract)
    out = []

    def check(sub, path):
        for cv, c in zip(sub.jaxpr.constvars, sub.consts):
            nbytes = getattr(c, "nbytes", None)
            if nbytes is None:
                try:
                    nbytes = np.asarray(c).nbytes
                except Exception:
                    continue
            if nbytes > limit:
                shape = tuple(getattr(c, "shape", ()))
                dtype = getattr(c, "dtype", "?")
                out.append(_diag(
                    Severity.ERROR, "const-capture", site,
                    f"{shape} {dtype} ({nbytes} bytes) captured as a "
                    "jaxpr const instead of an argument — baked into "
                    "the executable, re-baked on every compile, and "
                    "invisible to donation; pass it through the call",
                    where=path or "consts"))
        for i, eqn in enumerate(sub.jaxpr.eqns):
            for s in _sub_jaxprs(eqn):
                check(s, f"{path}{i}." if path else f"{i}.")

    check(closed, "")
    return out


def _rule_collectives(site, closed, call, jit_kwargs, contract,
                      est) -> List[Diagnostic]:
    out = []
    for eqn, path in _iter_eqns(closed):
        name = eqn.primitive.name
        if name in _COLLECTIVES:
            if contract.per_tick and not contract.allow_collectives:
                out.append(_diag(
                    Severity.ERROR, "collective-placement", site,
                    f"{name} inside a single-replica per-tick site — a "
                    "decode step must not pay interconnect latency per "
                    "token", where=f"{path} ({name})"))
            else:
                out.append(_diag(
                    Severity.INFO, "collective-placement", site,
                    f"{name} (declared intentional for this site)",
                    where=f"{path} ({name})"))
        elif name == "sharding_constraint" and contract.per_tick:
            out.append(_diag(
                Severity.INFO, "collective-placement", site,
                "GSPMD sharding constraint — a resharding point the "
                "partitioner may lower into a collective",
                where=f"{path} ({name})"))
    return out


def _rule_budget(site, closed, call, jit_kwargs, contract,
                 est) -> List[Diagnostic]:
    peak, flops = est
    out = []
    if contract.peak_bytes is not None and peak > contract.peak_bytes:
        out.append(_diag(
            Severity.ERROR, "budget", site,
            f"estimated peak live set {peak} bytes exceeds the declared "
            f"budget {int(contract.peak_bytes)} — an unplanned "
            "allocation (duplicated pool, O(B*S^2) broadcast) grew the "
            "compiled footprint"))
    if contract.flops is not None and flops > contract.flops:
        out.append(_diag(
            Severity.ERROR, "budget", site,
            f"estimated {flops:.3g} FLOPs exceed the declared budget "
            f"{contract.flops:.3g} — the compiled step does "
            "asymptotically more math than the site declared"))
    return out


RULES: Dict[str, Callable] = {
    "donation-contract": _rule_donation,
    "dtype-promotion-drift": _rule_dtype_drift,
    "host-transfer": _rule_host_transfer,
    "const-capture": _rule_const_capture,
    "collective-placement": _rule_collectives,
    "budget": _rule_budget,
}


# ---------------------------------------------------------------------------
# per-site driver
# ---------------------------------------------------------------------------


@dataclass
class SiteReport:
    """Audit result for one site across every captured signature."""

    site: str
    signatures: int = 0
    peak_bytes: int = 0                 # max over signatures
    flops: float = 0.0                  # max over signatures
    eqns: int = 0                       # max over signatures
    diagnostics: List[Diagnostic] = field(default_factory=list)

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics
                if d.severity is Severity.ERROR]


def audit_record(name: str, rec: SiteRecord,
                 rules: Optional[Sequence[str]] = None) -> SiteReport:
    """Audit every captured signature of one site — each through its
    OWN captured closure/kwargs/contract (falling back to the record's
    latest, then the defaults).  Diagnostics are deduplicated across
    signatures by message (two prefill buckets of the same program
    produce the same finding once)."""
    rep = SiteReport(site=name)
    seen_msgs: set = set()
    for sig, cap in list(rec.captured.items()):
        contract = cap.contract or rec.contract or _DEFAULT_CONTRACT
        closed = materialize_jaxpr(cap)
        est = estimate_jaxpr(closed)
        rep.signatures += 1
        rep.peak_bytes = max(rep.peak_bytes, est[0])
        rep.flops = max(rep.flops, est[1])
        rep.eqns = max(rep.eqns, len(closed.jaxpr.eqns))
        call = (cap.args, cap.kwargs)
        for rname, rule in RULES.items():
            if rules is not None and rname not in rules:
                continue
            for d in rule(name, closed, call, cap.jit_kwargs, contract,
                          est):
                if d.message not in seen_msgs:
                    seen_msgs.add(d.message)
                    rep.diagnostics.append(d)
    return rep


def audit_sites(aud=None, sites: Optional[Sequence[str]] = None,
                rules: Optional[Sequence[str]] = None
                ) -> Dict[str, SiteReport]:
    """Audit every site the (global) retrace auditor captured; returns
    {site: SiteReport}.  Sites with no captures (never called under
    ``FLAGS.jit_audit``) are skipped — there is nothing to audit."""
    aud = aud if aud is not None else auditor()
    out: Dict[str, SiteReport] = {}
    for name, rec in sorted(aud.sites.items()):
        if sites is not None and name not in sites:
            continue
        if not rec.captured:
            continue
        out[name] = audit_record(name, rec, rules=rules)
    return out


# ---------------------------------------------------------------------------
# the driven acceptance run (CLI + clean-run test pins share it)
# ---------------------------------------------------------------------------


def drive_serving_steady_state(kv_dtype: str = "int8", seal: bool = True):
    """Build a small engine and run the canonical mixed steady state
    (int8 KV + prefix cache by default): short decode, a chunked long
    prefill riding the same ticks, a full-cover cache hit exercising
    the COW fork site, and one fault-plan-poisoned request whose FAILED
    scrub exercises the zero_pages site — then seal and replay the same
    pattern so the retrace contract is checked too.  Requires
    ``FLAGS.jit_audit`` on BEFORE the call (audit_jit's wrap-time
    gate).  Returns the engine.
    """
    import jax
    import numpy as np

    from paddle_tpu.serving import DecoderLM, ServingEngine
    from paddle_tpu.serving.faults import FaultPlan

    model = DecoderLM(vocab_size=50, num_layers=2, num_heads=2,
                      head_dim=8, max_positions=128)
    params = model.init_params(jax.random.PRNGKey(0))
    faults = FaultPlan()
    eng = ServingEngine(model, params, eos_id=1, page_size=4,
                        num_pages=64, max_pages_per_seq=12, max_slots=4,
                        buckets=(4, 8, 16), prefill_chunk=8,
                        kv_dtype=kv_dtype, faults=faults)
    rng = np.random.RandomState(0)
    shared = rng.randint(2, 50, size=8).tolist()   # two FULL pages

    def mixed_burst(long_len: int):
        eng.submit(rng.randint(2, 50, size=4).tolist(), max_tokens=12)
        eng.step()
        eng.submit(rng.randint(2, 50, size=long_len).tolist(),
                   max_tokens=8)
        eng.run(max_ticks=300)

    # warmup: every pair bucket + the COW fork compile
    eng.submit(shared, max_tokens=6)
    eng.run(max_ticks=200)
    eng.submit(shared, max_tokens=6)               # full-cover hit: fork
    eng.run(max_ticks=200)
    mixed_burst(20)
    # one poisoned decode: the NaN row fails ONLY that request, whose
    # uncached pages get the device scrub — serving.zero_pages must
    # compile (and so be audited) too, or its donation contract would
    # sit forever untested behind a fault path tier-1 never walks
    bad = eng.submit(rng.randint(2, 50, size=5).tolist(), max_tokens=6)
    eng.step()
    faults.poison_nan(bad)
    eng.run(max_ticks=200)
    if seal:
        auditor().seal()
        # steady state: the same arrival pattern must not compile again
        eng.submit(shared, max_tokens=6)
        eng.run(max_ticks=200)
        mixed_burst(17)
    return eng


def drive_serving_spec_steady_state(seal: bool = True):
    """The speculative-decoding steady state (round 18): an n-gram
    speculating engine (``spec_mode='ngram'``) runs a repetitive trace
    so the widened ``serving.step`` — each slot contributing ``k+1``
    verify rows — compiles, accepts, rejects and rolls back for real,
    then (sealed) replays the same shape: the audit proves speculation
    adds the ``k`` dimension to the (bucket, k1) jit ladder and nothing
    else, under the SAME step contract.  Requires ``FLAGS.jit_audit``
    on before the call.  Returns the engine."""
    import jax
    import numpy as np

    from paddle_tpu.serving import DecoderLM, ServingEngine

    model = DecoderLM(vocab_size=50, num_layers=2, num_heads=2,
                      head_dim=8, max_positions=128)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, eos_id=1, page_size=4,
                        num_pages=64, max_pages_per_seq=12, max_slots=4,
                        buckets=(4, 8, 16), prefill_chunk=8,
                        spec_mode="ngram", spec_k=3)
    rng = np.random.RandomState(0)
    phrase = rng.randint(2, 50, size=4).tolist()

    def burst():
        # repetitive prompts: the n-gram proposer finds real matches,
        # so accept AND reject/rollback paths both execute
        eng.submit(phrase * 3, max_tokens=10)
        eng.step()
        eng.submit(rng.randint(2, 50, size=6).tolist(), max_tokens=8)
        eng.run(max_ticks=300)

    burst()
    if seal:
        auditor().seal()
        burst()                       # steady state: no new compiles
    return eng


def drive_trainer_step(batches: int = 2, batch_size: int = 16):
    """One tiny fc-classifier training pass (the ``trainer.train_step``
    site, donation contract (0, 1, 2)) plus one test pass (the
    ``trainer.test_step`` site).  The trainer runs GUARDED
    (resilience.BadStepGuard, skip policy) so the audited jaxpr is the
    production fault-tolerant step: the fused bad-step reduction and the
    skip selects must stay inside the ONE compiled program — no host
    callback, no extra compile, no donation regression.  Requires
    ``FLAGS.jit_audit`` on before the call.  Returns the SGD trainer."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import layer, optimizer, trainer as trainer_mod
    from paddle_tpu.resilience.guard import BadStepGuard

    x = layer.data(name="x", type=paddle.data_type.dense_vector(8))
    y = layer.data(name="y", type=paddle.data_type.integer_value(3))
    h = layer.fc(x, size=16, act="relu")
    logits = layer.fc(h, size=3)
    cost = layer.classification_cost(input=logits, label=y)
    params = paddle.Parameters.from_topology(
        paddle.topology.Topology([cost]), seed=0)
    sgd = trainer_mod.SGD(cost=cost, parameters=params,
                          update_equation=optimizer.Momentum(
                              momentum=0.9, learning_rate=0.05),
                          guard=BadStepGuard(policy="skip"))
    rng = np.random.RandomState(0)
    data = [(rng.randn(8).astype(np.float32) * 0.1, int(rng.randint(0, 3)))
            for _ in range(batches * batch_size)]
    reader = paddle.batch(lambda: iter(data), batch_size)
    sgd.train(reader, num_passes=1)
    sgd.test(reader)                       # trainer.test_step compiles
    return sgd


def run_compiled_path_audit(printer: Callable[[str], None] = print,
                            rules: Optional[Sequence[str]] = None
                            ) -> Tuple[Dict[str, SiteReport],
                                       List[Diagnostic]]:
    """The acceptance run: flip ``FLAGS.jit_audit`` on, drive the
    sealed serving steady state plus one trainer pass, audit every
    captured site (``rules`` restricts the registry; RETRACE
    diagnostics from the sealed replay are folded in regardless).
    Returns (reports, all_diagnostics)."""
    from paddle_tpu.platform.flags import FLAGS

    old = FLAGS.jit_audit
    FLAGS.jit_audit = True
    aud = auditor()
    aud.reset()
    try:
        eng = drive_serving_steady_state(seal=False)
        # the widened speculative step (k+1 verify rows per slot) rides
        # the same serving.step contract — audit it in the gate too
        drive_serving_spec_steady_state(seal=False)
        drive_trainer_step()
        aud.seal()
        # sealed steady-state replay (fresh traffic, same buckets)
        import numpy as np

        rng = np.random.RandomState(7)
        eng.submit(rng.randint(2, 50, size=4).tolist(), max_tokens=12)
        eng.step()
        eng.submit(rng.randint(2, 50, size=17).tolist(), max_tokens=8)
        eng.run(max_ticks=300)
        reports = audit_sites(aud, rules=rules)
    finally:
        FLAGS.jit_audit = old
    diags: List[Diagnostic] = []
    for name, rep in reports.items():
        printer(f"== {name}: {rep.signatures} signature(s), "
                f"{rep.eqns} eqns, est peak {rep.peak_bytes} B, "
                f"est {rep.flops:.3g} FLOPs")
        for d in rep.diagnostics:
            printer(f"  {d}")
        diags.extend(rep.diagnostics)
    # a contract-bearing site the drive never compiled is a coverage
    # hole, not a pass — say so, loudly enough to notice in the log
    for name, rec in sorted(aud.sites.items()):
        if rec.contract is not None and not rec.captured:
            printer(f"== {name}: declared a SiteContract but captured "
                    "no signatures this run — its contract was NOT "
                    "audited")
    retraces = list(aud.diagnostics)
    for d in retraces:
        printer(f"  {d}")
    diags.extend(retraces)
    return reports, diags
