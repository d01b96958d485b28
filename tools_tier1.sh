#!/usr/bin/env bash
# Tier-1 verify wrapper: runs the command the driver runs after every PR
# (six xdist workers, 1,470 s, the count from the junit file where there
# is one) and prints DOTS_PASSED, then the ladder's gates.
#
#   ./tools_tier1.sh            # exit code = pytest's; DOTS_PASSED=N
set -o pipefail
cd "$(dirname "$0")"
rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist load --junitxml=/tmp/_t1.xml \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null)
# flight-recorder surfacing (paddle_tpu.obs): when a conservation
# invariant trips with tracing on, the engine/fleet dumps the recent
# event ring to a postmortem file and stamps its path into the log —
# print those paths next to ANY ladder exit >= 3 so the leak report
# arrives with the event history that produced it
print_postmortems() {
    grep -ao 'OBS-POSTMORTEM: .*' /tmp/_t1.log | sort -u
}
# the serving page-leak invariant checker stamps PAGE-LEAK into any
# failure it raises: a leak anywhere in the suite is a loud, distinct
# failure (exit 3), not one more red test to skim past
if grep -aq 'PAGE-LEAK' /tmp/_t1.log; then
    echo 'PAGE-LEAK: serving free-list conservation violated (see log above)'
    print_postmortems
    exit 3
fi
# same contract for the refcount invariant: a page reference that no
# running/queued request (or fault-plan pressure window) accounts for —
# prefix sharing, COW forks, preemption-unref or eviction went unbalanced
if grep -aq 'REF-LEAK' /tmp/_t1.log; then
    echo 'REF-LEAK: serving page-refcount conservation violated (see log above)'
    print_postmortems
    exit 4
fi
# int8 KV quantization parity (round 12): the parity harness
# (serving/decode_attention.py check_quant_drift, exercised by the
# ragged suite) stamps QUANT-DRIFT into any failure where the int8
# roundtrip exceeds its logit-error bound — a quantization regression
# is a loud, distinct failure (exit 7 extends the ladder), not one
# more red test to skim past
if grep -aq 'QUANT-DRIFT' /tmp/_t1.log; then
    echo 'QUANT-DRIFT: int8 KV parity exceeded its logit-error bound (see log above)'
    print_postmortems
    exit 7
fi
# repo-invariant linter (paddle_tpu.analysis.lint): wall-clock in
# serving/master, unseeded global RNG, per-tick host syncs, mutable
# defaults, import-time FLAGS reads.  Findings print a LINT-FAIL tag;
# exit 5 keeps the loud-failure ladder (PAGE-LEAK=3, REF-LEAK=4).
# The linter's own exit status is checked too: a crash (import error,
# unknown rule) must fail the gate loudly, not fall through as green.
# branch on the linter's OWN exit status, not a grep of the shared log:
# a failing pytest whose captured output happens to contain the literal
# tag must not masquerade as a lint failure
env JAX_PLATFORMS=cpu python -m paddle_tpu.analysis lint 2>&1 | tee -a /tmp/_t1.log
lint_rc=${PIPESTATUS[0]}
if [ "$lint_rc" -eq 1 ]; then
    echo 'LINT-FAIL: repo-invariant lint findings (see log above)'
    print_postmortems
    exit 5
elif [ "$lint_rc" -ne 0 ]; then
    echo "LINT-FAIL: linter itself exited $lint_rc without running to completion"
    print_postmortems
    exit 5
fi
# fleet conservation gate (paddle_tpu.serving.fleet): replays a seeded
# replica-kill chaos trace and checks every fleet rid reached exactly
# one terminal status, nothing completed twice, and no replica pool —
# dead ones included — leaked a page or a ref.  Exit 6 extends the
# ladder (PAGE-LEAK=3, REF-LEAK=4, LINT-FAIL=5); same contract as the
# lint step: branch on the checker's OWN exit status (findings=1,
# crash=2), never on a grep of the shared log.  Run via -c, not -m:
# runpy would execute a second copy of fleet.py next to the one the
# serving package already imported (RuntimeWarning + duplicate classes)
env JAX_PLATFORMS=cpu python -c 'import sys; from paddle_tpu.serving.fleet import main; sys.exit(main(["check"]))' 2>&1 | tee -a /tmp/_t1.log
fleet_rc=${PIPESTATUS[0]}
if [ "$fleet_rc" -eq 1 ]; then
    echo 'FLEET-LEAK: serving-fleet conservation violated (see log above)'
    print_postmortems
    exit 6
elif [ "$fleet_rc" -ne 0 ]; then
    echo "FLEET-LEAK: fleet checker itself exited $fleet_rc without running to completion"
    print_postmortems
    exit 6
fi
# jaxpr compiled-path audit (paddle_tpu.analysis.xla): drives a sealed
# mixed serving steady state (int8 KV, prefix cache on) plus one train
# step under FLAGS.jit_audit, then rule-checks every captured site's
# ClosedJaxpr — donation contracts, dtype promotion drift, host
# callbacks, const-captured weights, collective placement, per-site
# memory/FLOP budgets.  Exit 8 extends the ladder (3/4/5/6/7); same
# contract as the lint/fleet gates: branch on the auditor's OWN exit
# status (findings=1, crash=2), never on a grep of the shared log.
env JAX_PLATFORMS=cpu python -m paddle_tpu.analysis xla 2>&1 | tee -a /tmp/_t1.log
xla_rc=${PIPESTATUS[0]}
if [ "$xla_rc" -eq 1 ]; then
    echo 'XLA-AUDIT: compiled-path contract violated (see log above)'
    print_postmortems
    exit 8
elif [ "$xla_rc" -ne 0 ]; then
    echo "XLA-AUDIT: jaxpr auditor itself exited $xla_rc without running to completion"
    print_postmortems
    exit 8
fi
# static sharding-propagation audit (paddle_tpu.analysis.sharding):
# drives the same sealed serving+trainer steady states as the xla gate
# plus the ZeRO placement jits on a virtual-8 mesh, then checks every
# captured site's declared PartitionSpec contract — contract mismatch,
# implicit all-gathers, accidental replication, axis collisions, and
# the per-tick collective-bytes budget.  Exit 9 extends the ladder
# (3/4/5/6/7/8); same contract as the lint/fleet/xla gates: branch on
# the auditor's OWN exit status (findings=1, crash=2), never on a grep
# of the shared log.
env JAX_PLATFORMS=cpu python -m paddle_tpu.analysis sharding 2>&1 | tee -a /tmp/_t1.log
shard_rc=${PIPESTATUS[0]}
if [ "$shard_rc" -eq 1 ]; then
    echo 'SHARD-AUDIT: sharding-propagation contract violated (see log above)'
    print_postmortems
    exit 9
elif [ "$shard_rc" -ne 0 ]; then
    echo "SHARD-AUDIT: sharding auditor itself exited $shard_rc without running to completion"
    print_postmortems
    exit 9
fi
# checkpoint/resume chaos gate (paddle_tpu.resilience): replays the
# seeded kill+NaN+slow+torn-save training chaos plan under the resume
# supervisor and checks every invariant — final params bit-identical to
# the uninterrupted control, every death resumed from a verified
# checkpoint, injected non-finite steps skipped with optimizer slots
# untouched, zero CKPT-CORRUPT on surviving artifacts, and a kill
# between blob write and meta commit leaving the previous checkpoint
# loadable.  Exit 10 extends the ladder (3/4/5/6/7/8/9); same contract
# as the lint/fleet/xla/shard gates: branch on the checker's OWN exit
# status (findings=1, crash=2), never on a grep of the shared log —
# tests intentionally corrupt checkpoints and print CKPT-CORRUPT lines.
env JAX_PLATFORMS=cpu python -m paddle_tpu.resilience check 2>&1 | tee -a /tmp/_t1.log
resil_rc=${PIPESTATUS[0]}
if [ "$resil_rc" -eq 1 ]; then
    echo 'CKPT-CORRUPT: training checkpoint/resume chaos invariants violated (see log above)'
    print_postmortems
    exit 10
elif [ "$resil_rc" -ne 0 ]; then
    echo "CKPT-CORRUPT: resilience checker itself exited $resil_rc without running to completion"
    print_postmortems
    exit 10
fi
# page-migration conservation gate (paddle_tpu.serving.migrate): replays
# a seeded disaggregated 2-prefill/2-decode fleet with live chain
# handoffs, an injected blob drop (fallback re-prefill), a decode-replica
# kill (prefix re-adoption) and cross-replica prefix seeds, then checks
# the migration ledger balances (started == applied + fallbacks +
# aborted), no transfer is left pending after drain, every replica's O(1)
# prefill-backlog probe matches a from-scratch recompute, and both pools
# conserve pages/refs.  Exit 11 extends the ladder (3/4/5/6/7/8/9/10);
# same contract as the lint/fleet/xla/shard/resilience gates: branch on
# the checker's OWN exit status (findings=1, crash=2), never on a grep of
# the shared log — migration tests intentionally print MIGRATE-LEAK
# lines.  Run via -c, not -m: runpy would execute a second copy of
# migrate.py next to the one the serving package already imported.
env JAX_PLATFORMS=cpu python -c 'import sys; from paddle_tpu.serving.migrate import main; sys.exit(main(["check"]))' 2>&1 | tee -a /tmp/_t1.log
mig_rc=${PIPESTATUS[0]}
if [ "$mig_rc" -eq 1 ]; then
    echo 'MIGRATE-LEAK: page-migration conservation violated (see log above)'
    print_postmortems
    exit 11
elif [ "$mig_rc" -ne 0 ]; then
    echo "MIGRATE-LEAK: migration checker itself exited $mig_rc without running to completion"
    print_postmortems
    exit 11
fi
# multi-tenant control-plane gate (paddle_tpu.serving.control): replays
# a seeded tenant-storm + autoscale + replica-kill trace (WFQ on, SLO
# classes + quotas live, the autoscaler growing then shrinking the
# fleet across the swing) and checks the admission ledger partitions
# per tenant (submitted == admitted + quota_deferred + shed), no
# non-storming tenant missed a deadline, the storming tenant's quota
# bucket actually deferred work, the WFQ drained empty, every token
# stream stayed exactly-once through every scaling event, and every
# replica — killed and drained ones included — conserved pages/refs.
# Exit 12 extends the ladder (3/4/5/6/7/8/9/10/11); same contract as
# the other gates: branch on the checker's OWN exit status (findings=1,
# crash=2), never on a grep of the shared log.  Run via -c, not -m:
# runpy would execute a second copy of control.py next to the one the
# serving package already imported.
env JAX_PLATFORMS=cpu python -c 'import sys; from paddle_tpu.serving.control import main; sys.exit(main(["check"]))' 2>&1 | tee -a /tmp/_t1.log
ctl_rc=${PIPESTATUS[0]}
if [ "$ctl_rc" -eq 1 ]; then
    echo 'CONTROL-LEAK: multi-tenant control-plane invariants violated (see log above)'
    print_postmortems
    exit 12
elif [ "$ctl_rc" -ne 0 ]; then
    echo "CONTROL-LEAK: control checker itself exited $ctl_rc without running to completion"
    print_postmortems
    exit 12
fi
# hierarchical KV-cache gate (paddle_tpu.serving.kv_cache): replays a
# seeded host-tier trace — a clean spill/swap-in round trip must be
# token-identical to a cold prefill, an injected torn spill AND a
# seeded bit-flip must both be caught by the per-page checksum at
# swap-in (degrading to a miss, never a wrong-KV hit), and a
# kill + restart_replica warm restart must re-adopt verified host
# pages with zero duplicate completions — then checks the three-state
# page ledger (device/host/dropped) balances on every engine.  Exit 13
# extends the ladder (3..12); same contract as the other gates: branch
# on the checker's OWN exit status (findings=1, crash=2), never on a
# grep of the shared log.  Run via -c, not -m: runpy would execute a
# second copy of kv_cache.py next to the one the serving package
# already imported.
env JAX_PLATFORMS=cpu python -c 'import sys; from paddle_tpu.serving.kv_cache import main; sys.exit(main(["check"]))' 2>&1 | tee -a /tmp/_t1.log
kv_rc=${PIPESTATUS[0]}
if [ "$kv_rc" -eq 1 ]; then
    echo 'HOSTTIER-LEAK: hierarchical KV-cache invariants violated (see log above)'
    print_postmortems
    exit 13
elif [ "$kv_rc" -ne 0 ]; then
    echo "HOSTTIER-LEAK: kv-cache checker itself exited $kv_rc without running to completion"
    print_postmortems
    exit 13
fi
# concurrency-auditor gate (paddle_tpu.analysis.concurrency): the
# guarded_by lock-discipline checker over every annotated threaded
# module, the declared lifecycle state machines checked statically
# (assignment-site extraction) and dynamically (transition recorder
# during the chaos drives), and the schedule-permutation model checker
# replaying each seeded chaos drive under permuted intra-tick schedules
# — any terminal-fingerprint divergence is a reproducible interleaving
# bug and dumps an OBS-POSTMORTEM for its minimal schedule prefix.
# Exit 14 extends the ladder (3..13); same contract as the other
# gates: branch on the auditor's OWN exit status (findings=1,
# crash=2), never on a grep of the shared log — the conc tests
# intentionally print CONC-AUDIT/PROTO-AUDIT/SCHED-AUDIT lines.
env JAX_PLATFORMS=cpu python -m paddle_tpu.analysis concurrency 2>&1 | tee -a /tmp/_t1.log
conc_rc=${PIPESTATUS[0]}
if [ "$conc_rc" -eq 1 ]; then
    echo 'CONC-AUDIT: concurrency invariants violated (see log above)'
    print_postmortems
    exit 14
elif [ "$conc_rc" -ne 0 ]; then
    echo "CONC-AUDIT: concurrency auditor itself exited $conc_rc without running to completion"
    print_postmortems
    exit 14
fi
exit $rc
