#!/usr/bin/env bash
# The tier-1 gate: the ONE statement of what "the tests pass" means.
# The first leg is the command the driver runs after every PR (it is
# recorded, with its exit code and count, in /root/TESTS_LAST_RUN.json
# and under `tests` on each line of PERF_LEDGER.jsonl): the whole of
# tests/ but the `slow` marks, on six pytest-xdist workers that each
# take whole files (--dist loadfile), inside 1,470 s.  The driver's
# own line also sets ALLOW_MULTIPLE_LIBTPU_LOAD=1; this script does
# not, because no test of that leg loads the TPU library (the one file
# that does, tests/test_tpu_lowering.py, is `slow` and runs below in a
# process of its own).  The legs after it are what the driver's line
# leaves out.
#
# Usage: tools/run_tier1.sh [--chaos] [--trace] [--lint] [extra pytest args...]
#        --chaos additionally runs the fault-injection suite (chaos
#        harness + PS fault tolerance + crash-mid-save) as a further
#        pass with its fixed, deterministic seeds
#        --trace additionally runs the whole suite with PADDLE_TRACE=1
#        PADDLE_METRICS=1 AND the flight recorder in full mode
#        (PADDLE_FLIGHT=1 — ISSUE 7: dump triggers armed, bundles into
#        the same temp dir) — proving always-on telemetry neither
#        breaks determinism nor leaks sink/bundle files into the repo
#        --lint runs GraftLint (ISSUE 6): the AST concurrency/tracing
#        linter over the repo module set AND the jaxpr self-audit of
#        the step programs, gated on tools/lint_baseline.json — any
#        finding not in the baseline exits nonzero
#
# ISSUE 13 (Pallas kernel tier): tests/test_pallas_kernels.py is the
# interpret-mode kernel parity suite — every ops/pallas/ kernel vs its
# XLA reference at the documented tolerance — and rides the first leg
# (its registry fixture clears mode overrides so order cannot leak).
# The trace pass below additionally proves the kernel-dispatch
# counters surface on /metrics (the suite's
# test_dispatch_counters_on_metrics_endpoint runs with telemetry live)
# without leaking any sink files into the repo.
set -u -o pipefail
cd "$(dirname "$0")/.."

CHAOS=0
TRACE=0
LINT=0
while :; do
    case "${1:-}" in
        --chaos) CHAOS=1; shift ;;
        --trace) TRACE=1; shift ;;
        --lint)  LINT=1;  shift ;;
        *) break ;;
    esac
done

PYARGS=(-q -m 'not slow' --continue-on-collection-errors
        -p no:cacheprovider -p xdist -n 6 --dist loadfile
        -p no:randomly "$@")

echo "== tier-1: the driver's command (6 workers, whole files, 1,470 s)"
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ "${PYARGS[@]}"
rc1=$?

rc3=0
if [ "$CHAOS" -eq 1 ]; then
    # the chaos suite is deterministic (seeded FaultPlans, no
    # probabilistic sleeps) — a red run here reproduces as-is.
    # test_train_guard.py is the NUMERIC chaos suite (PR 4): NaN/Inf
    # injection into grads/batches/activations, skip/rewind/blame.
    # test_elastic.py is the MEMBERSHIP chaos suite (ISSUE 9):
    # SIGKILL-every-K workers under the elastic launcher, lease
    # eviction, join/leave reforms — all proven bit-equal to the
    # fault-free run.
    # test_read_replica.py / test_geo.py / test_coordinator_ha.py /
    # test_serving_ps.py are the ONLINE SERVING TIER suite (ISSUE 10):
    # primary SIGKILL under live read traffic, lossy/delayed replica
    # and geo links, coordinator failover — all seeded + deterministic.
    # test_prefix_cache.py / test_spec_decode.py / test_kv_int8.py are
    # the INFERENCE GATEWAY suite (ISSUE 11): pool-exhaustion eviction
    # + re-admission under prefix sharing, speculation, and int8 KV —
    # all replay paths bit-checked live (check_replay).
    # test_fleet_observatory.py is the FLEET OBSERVATORY suite (ISSUE
    # 12): multi-process aggregator scrape/merge, straggler + stale
    # flagging, SLO burn-rate breaches dumping flight bundles, and the
    # per-request trace lanes — the whole e2e runs subprocess PS
    # servers and an artificially delayed replica.
    # test_online_loop.py / test_feature_lifecycle.py /
    # test_geo_conflict.py are the ONLINE LEARNING LOOP suite (ISSUE
    # 14): streaming trainer kill/resume exactly-once (cursor-derived
    # idempotency stamps + primary SIGKILL + lossy geo link, shadow-
    # table accounting), TTL eviction replicated down the mutation
    # stream, and the bidirectional conflict policies (additive /
    # last-writer-wins) converging to their fixed points.
    # test_elastic_device.py is the DEVICE-NATIVE ELASTIC ENGINE suite
    # (ISSUE 17): compiled-SPMD reduce world-invariance, streamed
    # checkpoint byte-equality vs the concat format, ranged N->M
    # restores, the O(max shard) host-staging bound, and reform-hook
    # recompiles; test_crash_mid_save.py also gained the SIGKILL-mid-
    # streamed-save torn-step test.
    # test_gateway.py is the INFERENCE FEDERATION suite (ISSUE 18):
    # prefix-affinity routing, replica SIGKILL mid-decode (subprocess,
    # seeded gw_kill plan) with every stream finishing token-identical
    # to the fault-free run, KV-migration drain mid-traffic,
    # flaky-link (gw_flaky) cut/delay survival, and deadline-ordered
    # shedding at the router.
    echo "== tier-1 chaos pass: fault injection suite"
    env JAX_PLATFORMS=cpu python -m pytest \
        tests/test_chaos_harness.py tests/test_ps_fault_tolerance.py \
        tests/test_crash_mid_save.py tests/test_train_guard.py \
        tests/test_elastic.py tests/test_read_replica.py \
        tests/test_geo.py tests/test_coordinator_ha.py \
        tests/test_serving_ps.py tests/test_prefix_cache.py \
        tests/test_spec_decode.py tests/test_kv_int8.py \
        tests/test_fleet_observatory.py tests/test_online_loop.py \
        tests/test_feature_lifecycle.py tests/test_geo_conflict.py \
        tests/test_elastic_device.py tests/test_gateway.py \
        "${PYARGS[@]}"
    rc3=$?
fi

rc4=0
if [ "$TRACE" -eq 1 ]; then
    # telemetry-on pass (ISSUE 5): same suite, tracing + metrics live.
    # Red here means telemetry perturbs training math or test state;
    # stray sink files outside the temp dir mean a test wrote its sink
    # into the repo (a leak the default-off contract forbids).
    echo "== tier-1 trace pass: PADDLE_TRACE=1 PADDLE_METRICS=1" \
         "PADDLE_FLIGHT=1"
    TRACE_DIR=$(mktemp -d -t tier1_trace.XXXXXX)
    env JAX_PLATFORMS=cpu PADDLE_TRACE=1 PADDLE_METRICS=1 \
        PADDLE_FLIGHT=1 PADDLE_TRACE_DIR="$TRACE_DIR" \
        python -m pytest tests/ "${PYARGS[@]}"
    rc4=$?
    # a green run must leak NEITHER trace sinks NOR flight bundles /
    # faulthandler sidecars NOR aggregator state files into the repo
    # (tests that trigger dumps / fleet snapshots point
    # PADDLE_TRACE_DIR / state_file at their own tmp dirs)
    LEAKED=$(find . -maxdepth 2 \( -name 'trace-*.jsonl' -o -name \
        'flight-*.jsonl' -o -name 'faulthandler-*.txt' -o -name \
        'fleet-*.jsonl' \) -not -path \
        './paddle_trace/*' 2>/dev/null; [ -d paddle_trace ] && echo \
        paddle_trace)
    if [ -n "$LEAKED" ]; then
        echo "== trace pass leaked sink/bundle files into the repo:"
        echo "$LEAKED"
        rc4=1
    fi
    rm -rf "$TRACE_DIR"
fi

# Auto-sharding planner smoke (ISSUE 15): every run proves the planner
# still returns a non-empty ranked plan list whose top-k all LOWER via
# compile_abstract + XLA memory analysis (the CLI re-execs itself under
# an 8-device virtual CPU mesh).  Cheap (~30 s) and catches both a
# broken SpecLayout derivation and a verify-path regression.
echo "== tier-1 planner smoke: tools/plan.py --verify"
env JAX_PLATFORMS=cpu python tools/plan.py --model proxy_fsdp \
    --chips 8 --verify --top-k 2 --json > /dev/null
rc6=$?

# TPU lowering check (ISSUE 21): AOT-compile every registered Pallas
# kernel and a small decoder DistributedTrainStep (1 chip, fsdp4,
# tp2 x fsdp2, pp2 x tp2) for a v5e:2x2 TOPOLOGY through the real
# dispatch sites — needs libtpu, not a chip.  Catches what no
# interpret-mode test can: a kernel Mosaic will not lower, a kernel call
# the SPMD partitioner will not take.  Marked slow (kept out of the
# first leg: one process at a time may load libtpu); ~30 s here.
echo "== tier-1 TPU lowering check: tests/test_tpu_lowering.py"
env JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_lowering.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc8=$?

rc5=0
if [ "$LINT" -eq 1 ]; then
    # GraftLint gate: pillar 2 (lock-order + tracing-hazard AST lint
    # over the configured module set) and pillar 1 (jaxpr self-audit
    # of the mlp/lenet/llama_tiny step programs), both checked against
    # the committed baseline — a NEW finding fails CI.  Amend with
    #   python tools/graft_lint.py --write-baseline --reason "..."
    # only for findings that are genuinely justified.
    echo "== tier-1 lint pass: GraftLint (AST + jaxpr self-audit)"
    env JAX_PLATFORMS=cpu python tools/graft_lint.py --audit \
        --baseline tools/lint_baseline.json
    rc5=$?
fi

echo "== tier-1: tests rc=$rc1, chaos rc=$rc3, trace rc=$rc4," \
     "lint rc=$rc5, plan rc=$rc6, tpu_lowering rc=$rc8"
if [ "$rc1" -ne 0 ] || [ "$rc3" -ne 0 ] || [ "$rc4" -ne 0 ] \
        || [ "$rc5" -ne 0 ] || [ "$rc6" -ne 0 ] || [ "$rc8" -ne 0 ]; then
    echo "== tier-1 FAILED (any leg being red fails the gate)"
    exit 1
fi
echo "== tier-1 OK"
