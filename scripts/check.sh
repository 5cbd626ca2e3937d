#!/usr/bin/env bash
# One-entry-point repo check: byte-compile, graftlint (repo-invariant
# static analysis), ruff, then the chaos stages and the tier-1 pytest
# command from ROADMAP.md.
#
#   scripts/check.sh            # full: compile + lint + chaos + tier-1
#   scripts/check.sh --fast     # compile + lint only (skip pytest)
#
# Exits non-zero on the first failing stage.  The cheap static stages
# run FIRST so a drifted knob table or an unguarded dispatch fails in
# seconds, not after a 10-minute test tier.
#
# Stage toggles: LINT=0 skips graftlint, RUFF=0 skips ruff (ruff also
# skips with a warning when the binary is absent — this container
# doesn't ship it and nothing may be pip-installed), CHAOS=0 etc. per
# stage below.  LOCKTRACE=1 is applied to the fleet/scale smokes (the
# runtime lock-order detector, docs/static-analysis.md).

set -o pipefail
cd "$(dirname "$0")/.."

echo "== compileall =="
python -m compileall -q mlmicroservicetemplate_tpu tests tools || exit 1

# graftlint: the repo-specific invariants no generic linter knows —
# dispatch-guard coverage, write-ahead ordering, clock injection, knob
# drift, metric drift, exception discipline (tools/graftlint/,
# docs/static-analysis.md).  Nonzero exit on any unwaived finding.
if [ "${LINT:-1}" != "0" ]; then
    echo "== graftlint =="
    python -m tools.graftlint --json mlmicroservicetemplate_tpu/ || exit 1
else
    echo "== graftlint skipped (LINT=0) =="
fi

# ruff: REQUIRED since r18 when the binary is present (the generic
# rule families graftlint doesn't cover — unused imports, mutable
# defaults, f-string misuse; [tool.ruff] in pyproject.toml).  RUFF=0
# skips explicitly; a container without ruff warns and skips.
if [ "${RUFF:-1}" != "0" ]; then
    echo "== ruff =="
    if command -v ruff >/dev/null 2>&1; then
        ruff check mlmicroservicetemplate_tpu tests tools || exit 1
    elif python -c "import ruff" >/dev/null 2>&1; then
        python -m ruff check mlmicroservicetemplate_tpu tests tools || exit 1
    else
        echo "ruff binary absent; skipping (nothing may be pip-installed here)"
    fi
else
    echo "== ruff skipped (RUFF=0) =="
fi

if [ "$1" = "--fast" ]; then
    echo "== tier-1 tests skipped (--fast) =="
    exit 0
fi

# Chaos tier: the fault-injection/recovery suite (kept OUT of tier-1 by
# the conftest's chaos->slow propagation) plus a 3-point FAULT_SPEC
# smoke matrix — one transient, one fatal, one watchdog-cut hang — each
# run against the supervised loop expecting token-identical completion.
# CHAOS=0 skips the stage.
if [ "${CHAOS:-1}" != "0" ]; then
    echo "== chaos suite (fault injection + crash recovery) =="
    timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
        -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
    echo "== FAULT_SPEC smoke matrix =="
    for spec in "chunk:transient@2" "chunk:fatal@2" "chunk:hang(30)@2"; do
        echo "-- FAULT_SMOKE_SPEC=$spec"
        timeout -k 10 240 env JAX_PLATFORMS=cpu FAULT_SMOKE_SPEC="$spec" \
            python -m pytest tests/test_faults.py::test_fault_spec_smoke -q \
            -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
    done
else
    echo "== chaos suite skipped (CHAOS=0) =="
fi

# Chunked-prefill smoke: 3-point PREFILL_CHUNK matrix, each run under
# a prefill_chunk-site FAULT_SPEC injection through the supervised
# loop, expecting token-identical completion and a drained block pool
# (chaos tier, so it stays out of tier-1).  PREFILL_SMOKE=0 skips.
if [ "${PREFILL_SMOKE:-1}" != "0" ]; then
    echo "== chunked-prefill smoke matrix =="
    for chunk in 8 16 32; do
        echo "-- PREFILL_SMOKE_CHUNK=$chunk (prefill_chunk:fatal@2)"
        timeout -k 10 240 env JAX_PLATFORMS=cpu PREFILL_SMOKE_CHUNK="$chunk" \
            PREFILL_SMOKE_SPEC="prefill_chunk:fatal@2" \
            python -m pytest tests/test_prefill_chunked.py::test_prefill_chunk_smoke \
            -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
    done
else
    echo "== chunked-prefill smoke skipped (PREFILL_SMOKE=0) =="
fi

# Fleet-failover smoke: R=2 replicas, a replica-scoped fatal schedule
# (r0:chunk:fatal@2) that exhausts replica 0's restart window with
# chunks in flight (paged, int8), asserting ZERO streams
# lost — every stream completes token-identically on the survivor and
# the dead replica's block ledger drains to zero (chaos tier, so it
# stays out of tier-1).  FLEET_SMOKE=0 skips.
if [ "${FLEET_SMOKE:-1}" != "0" ]; then
    echo "== fleet-failover smoke (R=2, r0:chunk:fatal@2, LOCKTRACE=1) =="
    timeout -k 10 240 env JAX_PLATFORMS=cpu LOCKTRACE=1 \
        FLEET_SMOKE_SPEC="${FLEET_SMOKE_SPEC:-r0:chunk:fatal@2}" \
        python -m pytest \
        tests/test_fleet.py::test_fleet_failover_chaos_paged_int8 \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== fleet-failover smoke skipped (FLEET_SMOKE=0) =="
fi

# Autoscale smoke: an elastic fleet starts at R=1 (paged, int8),
# batch-class load drives the governor's queue trigger until it
# scales up via donor-param broadcast, a replica-scoped fatal
# (r1:chunk:fatal) kills the new replica mid-decode, and the governor
# must replace it after FLEET_EVICT_S with ZERO streams lost (every
# stream token-identical) and every pool ledger drained (chaos tier,
# so it stays out of tier-1).  SCALE_SMOKE=0 skips.
if [ "${SCALE_SMOKE:-1}" != "0" ]; then
    echo "== autoscale smoke (elastic [1..3] + r1:chunk:fatal, LOCKTRACE=1) =="
    timeout -k 10 300 env JAX_PLATFORMS=cpu LOCKTRACE=1 \
        SCALE_SMOKE_SPEC="${SCALE_SMOKE_SPEC:-r1:chunk:fatal@4}" \
        python -m pytest \
        tests/test_scaling.py::test_scale_smoke_load_up_kill_replace \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== autoscale smoke skipped (SCALE_SMOKE=0) =="
fi

# Staged-prep smoke (r19, docs/compilation.md): an rN:-scoped fatal
# on replica 1's double-buffered host-prep upload (site `prep`) must
# fail its streams over token-identically onto the survivor with both
# pool ledgers drained — the chaos pin that the staged prep's
# grants never outlive a dead replica (chaos tier, so it stays out of
# tier-1).  PREP_SMOKE=0 skips.
if [ "${PREP_SMOKE:-1}" != "0" ]; then
    echo "== staged-prep smoke (r1:prep:fatal@1 failover, LOCKTRACE=1) =="
    timeout -k 10 300 env JAX_PLATFORMS=cpu LOCKTRACE=1 \
        python -m pytest \
        tests/test_compile_cache.py::test_prep_kill_fails_over_token_identically \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== staged-prep smoke skipped (PREP_SMOKE=0) =="
fi

# Tiered-KV smoke: the host-RAM swap path under a fatal chunk fault
# with a tiny KV_HOST_BUDGET_MB — recovery must resume every stream
# token-identically from the HOST copy, with zero re-prefill chunks
# (pinned via the loop's prefill-window counter), and both tier
# ledgers must drain to zero (chaos tier, so it stays out of tier-1).
# TIER_SMOKE=0 skips.
if [ "${TIER_SMOKE:-1}" != "0" ]; then
    echo "== tiered-KV smoke (chunk:fatal@2 + KV_HOST_BUDGET_MB) =="
    timeout -k 10 240 env JAX_PLATFORMS=cpu \
        TIER_SMOKE_SPEC="${TIER_SMOKE_SPEC:-chunk:fatal@2}" \
        TIER_SMOKE_HOST_MB="${TIER_SMOKE_HOST_MB:-0.5}" \
        python -m pytest tests/test_kv_tier.py::test_tier_smoke \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== tiered-KV smoke skipped (TIER_SMOKE=0) =="
fi

# Crash smoke: a REAL serving process with JOURNAL_DIR is SIGKILLed
# mid-stream, restarted on the same journal, and the reconnect
# (GET /v1/streams/{request_id}) must drain a token-identical body —
# zero lost streams, zero duplicated tokens (chaos tier, so it stays
# out of tier-1).  CRASH_SMOKE=0 skips; CRASH_SMOKE_FSYNC overrides
# the journal fsync policy under test (default always).
if [ "${CRASH_SMOKE:-1}" != "0" ]; then
    echo "== crash smoke (SIGKILL mid-stream + journal replay) =="
    timeout -k 10 420 env JAX_PLATFORMS=cpu \
        CRASH_SMOKE_FSYNC="${CRASH_SMOKE_FSYNC:-always}" \
        python -m pytest tests/test_durability.py::test_crash_smoke \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== crash smoke skipped (CRASH_SMOKE=0) =="
fi

# Job smoke: a REAL serving process with JOBS_ENABLED=1 takes a
# multi-line /v1/batches job, is SIGKILLed mid-job, restarts on the
# same JOURNAL_DIR, and must complete the job with exactly-once
# per-line results (no duplicates, no gaps; every line identical to
# the interactive completion) while the stream journal drains to zero
# incomplete streams (chaos tier, so it stays out of tier-1).
# JOB_SMOKE=0 skips.
if [ "${JOB_SMOKE:-1}" != "0" ]; then
    echo "== job smoke (SIGKILL mid-job + store replay) =="
    timeout -k 10 420 env JAX_PLATFORMS=cpu \
        python -m pytest tests/test_jobs.py::test_job_crash_smoke \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== job smoke skipped (JOB_SMOKE=0) =="
fi

# Observability smoke: the full HTTP service under TRACE=1 with a
# transient fault injected, then /debug/trace (schema-valid Perfetto
# JSON with every stage span) and /debug/engine (flight recorder with
# the retry event) are validated.  OBS_SMOKE=0 skips.
if [ "${OBS_SMOKE:-1}" != "0" ]; then
    echo "== observability smoke (TRACE=1 + chunk:transient@2) =="
    timeout -k 10 240 env JAX_PLATFORMS=cpu \
        OBS_SMOKE_SPEC="${OBS_SMOKE_SPEC:-chunk:transient@2}" \
        python -m pytest tests/test_tracing.py::test_observability_smoke \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== observability smoke skipped (OBS_SMOKE=0) =="
fi

# Multi-tenant smoke (docs/multi-tenancy.md): two tenants (3:1
# weights, one on a LoRA adapter, max_concurrency=2) over an R=2
# fleet with a replica-0 fatal chunk fault mid-decode.  Quota sheds
# must stay 429-classed with Retry-After through the chaos, BOTH
# tenants must keep completing on the survivor, and every ledger —
# tenant occupancy, adapter-pool refs, both replicas' paged-KV block
# pools — must drain to zero (chaos tier, so it stays out of
# tier-1).  TENANT_SMOKE=0 skips.
if [ "${TENANT_SMOKE:-1}" != "0" ]; then
    echo "== multi-tenant smoke (quota 429 + r0:chunk:fatal@2, LOCKTRACE=1) =="
    timeout -k 10 240 env JAX_PLATFORMS=cpu LOCKTRACE=1 \
        TENANT_SMOKE_SPEC="${TENANT_SMOKE_SPEC:-r0:chunk:fatal@2}" \
        python -m pytest tests/test_tenancy.py::test_tenant_smoke_chaos \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== multi-tenant smoke skipped (TENANT_SMOKE=0) =="
fi

# Tensor-parallel smoke (docs/tensor-parallel.md): a TP=2 paged
# engine over the 8 virtual host devices under a fatal chunk fault —
# recovery must complete every stream token-identically to an
# unfaulted TP=1 run and drain the sharded pool's single ledger to
# zero (chaos tier, so it stays out of tier-1).  TP_SMOKE=0 skips.
if [ "${TP_SMOKE:-1}" != "0" ]; then
    echo "== tensor-parallel smoke (TP=2 + chunk:fatal@2, LOCKTRACE=1) =="
    timeout -k 10 240 env JAX_PLATFORMS=cpu LOCKTRACE=1 \
        TP_SMOKE_SPEC="${TP_SMOKE_SPEC:-chunk:fatal@2}" \
        python -m pytest tests/test_tp_serving.py::test_tp_smoke_chaos \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== tensor-parallel smoke skipped (TP_SMOKE=0) =="
fi

# Multi-chip smoke (docs/fault-tolerance.md device-loss rung): an
# elastic fleet of TP groups (2,2,1) over the 8 virtual host devices;
# device_lost fires into shard 1 of replica 0 mid-decode.  The whole
# TP group must evacuate with ZERO streams lost (every stream
# token-identical to a solo run, including TP=2 -> TP=1 adoption on a
# narrower survivor), the lost device must be retired from the carve
# pool, the governor must respawn on remaining healthy devices, every
# pool ledger must drain to zero, and a same-placement respawn of the
# sibling group must record ZERO serve-time XLA compiles (chaos tier,
# so it stays out of tier-1).  MULTICHIP_SMOKE=0 skips.
if [ "${MULTICHIP_SMOKE:-1}" != "0" ]; then
    echo "== multi-chip smoke (TP groups 2,2,1 + r0:chunk:device_lost(1)@4, LOCKTRACE=1) =="
    timeout -k 10 300 env JAX_PLATFORMS=cpu LOCKTRACE=1 \
        MULTICHIP_SMOKE_SPEC="${MULTICHIP_SMOKE_SPEC:-r0:chunk:device_lost(1)@4}" \
        python -m pytest \
        tests/test_multichip.py::test_multichip_smoke_device_loss \
        -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit 1
else
    echo "== multi-chip smoke skipped (MULTICHIP_SMOKE=0) =="
fi

echo "== tier-1 tests (as the driver runs them: six xdist workers) =="
# Every worker imports every test file, so a suite that passes with
# -p no:xdist has not been shown to pass here: nothing may load libtpu
# or decide which tests exist at import (tests/test_chip_compile.py).
rm -f /tmp/_t1.log
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit $rc
