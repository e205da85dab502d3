#!/usr/bin/env bash
# One-command reproduction of every number the round docs report
# (VERDICT r4 missing #7 — the reference ships paddle_build.sh +
# tools/test_runner.py; this is the paddle_tpu equivalent).
#
# Stages (each timed, JSON summary at the end):
#   analyze python -m paddle_tpu.analysis (static analysis, CPU, seconds)
#   fast    pytest -m fast           (~3 min sanity lane)
#   suite   pytest tests/            (full suite)
#   audit   tools/api_parity_audit.py (implemented/shimmed/missing counts)
#   dryrun  __graft_entry__.dryrun_multichip(8) on a virtual CPU mesh
#   perf-smoke tools/perf_smoke.py   (fused run_steps vs per-step, CPU, seconds)
#   serving-smoke tools/serving_smoke.py (closed compile set + KV-decode identity)
#   tune-smoke tools/tune_smoke.py  (plan + serving measured search, warm replay, K701)
#   scenario-smoke tools/scenario_smoke.py (autoscaling loop under traffic chaos + disagg)
#   moe-smoke tools/moe_smoke.py (expert-sharded decode: closed set + balanced routing)
#   chaos-smoke tools/chaos_smoke.py (SIGKILL-resume bit identity + circuit recovery)
#   obs-smoke tools/obs_smoke.py   (metrics scrape + JSONL sink + serving spans)
#   router-smoke tools/router_smoke.py (replica kill -> zero-loss failover + rolling swap)
#   gen-smoke tools/gen_smoke.py (continuous batching: token identity, zero recompiles, probes)
#   tenancy-smoke tools/tenancy_smoke.py (multi-LoRA tenants: mixed-vs-serial bit identity, hot-add zero recompiles, noisy-neighbor cap)
#   quant-smoke tools/quant_smoke.py (int8/fp8 serving: margin-accounted tokens, equal-HBM slots, quantized rolling swap)
#   slo-smoke tools/slo_smoke.py (request tracing end-to-end + SLO burn-rate alert)
#   elastic-smoke tools/elastic_smoke.py (NaN rollback + exact resume + collective watchdog)
#   pod-smoke tools/pod_smoke.py (N-process gang: sharded bit identity, SIGKILL -> gang restore, wedge watchdog, router failover, F803)
#
# Usage:  tools/run_gates.sh [--skip analyze|fast|suite|audit|dryrun|perf-smoke|serving-smoke|tune-smoke|scenario-smoke|moe-smoke|chaos-smoke|obs-smoke|router-smoke|gen-smoke|tenancy-smoke|quant-smoke|slo-smoke|elastic-smoke|pod-smoke]...
#         tools/run_gates.sh --only suite
# Exit code: 0 iff every stage that ran passed.
set -u
cd "$(dirname "$0")/.."

SKIP=""
ONLY=""
while [ $# -gt 0 ]; do
  case "$1" in
    --skip) SKIP="$SKIP $2"; shift 2 ;;
    --only) ONLY="$2"; shift 2 ;;
    *) echo "unknown arg $1" >&2; exit 2 ;;
  esac
done

SUMMARY="$(mktemp)"
echo "{" > "$SUMMARY"
FAILED=0
FIRST=1

want() {  # does stage $1 run?
  if [ -n "$ONLY" ]; then [ "$ONLY" = "$1" ]; return; fi
  case " $SKIP " in *" $1 "*) return 1 ;; esac
  return 0
}

record() {  # stage status seconds detail
  [ $FIRST -eq 0 ] && echo "," >> "$SUMMARY"
  FIRST=0
  # JSON-encode the detail (backslashes/quotes/control chars in log tails)
  local detail_json
  detail_json=$(printf '%s' "$4" | python -c \
    'import json,sys; print(json.dumps(sys.stdin.read()[:160]))')
  printf '  "%s": {"status": "%s", "seconds": %s, "detail": %s}' \
    "$1" "$2" "$3" "$detail_json" >> "$SUMMARY"
  [ "$2" = "pass" ] || [ "$2" = "skipped" ] || FAILED=1
}

run_stage() {  # name cmd...
  local name="$1"; shift
  if ! want "$name"; then
    echo "== $name: skipped"
    record "$name" skipped 0 ""
    return
  fi
  echo "== $name: $*"
  local t0 t1 log status detail
  log="$(mktemp "/tmp/gate_${name}_XXXX.log")"
  t0=$(date +%s)
  if "$@" >"$log" 2>&1; then status=pass; else status=FAIL; fi
  t1=$(date +%s)
  tail -5 "$log"
  detail=$(tail -1 "$log")
  record "$name" "$status" $((t1 - t0)) "$detail"
  if [ "$status" = "FAIL" ]; then
    echo "== $name: FAIL ($((t1 - t0))s) — full log kept at $log"
  else
    echo "== $name: $status ($((t1 - t0))s)"
    rm -f "$log"
  fi
}

# static analysis first: cheapest gate, no device work (JAX_PLATFORMS=cpu)
run_stage analyze env JAX_PLATFORMS=cpu python -m paddle_tpu.analysis --strict \
  paddle_tpu.models.bert paddle_tpu.models.gpt \
  paddle_tpu.vision.models.resnet paddle_tpu.vision.models.vgg \
  paddle_tpu.vision.models.lenet paddle_tpu.vision.models.mobilenetv1 \
  paddle_tpu.vision.models.mobilenetv2
# concurrency lint over the framework's OWN source: lock-order inversions,
# locks held across blocking calls, unguarded cross-thread writes, bare
# Condition.waits (C10xx).  Error severity (a lock-order cycle) fails the
# gate; the fixture zoo in tests/test_concurrency_analysis.py proves the
# rules FIRE, this sweep proves the tree is clean
run_stage analyze-concurrency env JAX_PLATFORMS=cpu \
  python -m paddle_tpu.analysis --concurrency paddle_tpu/

run_stage fast   python -m pytest tests/ -m fast -q
run_stage suite  python -m pytest tests/ -q
run_stage audit  python tools/api_parity_audit.py
run_stage dryrun python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
# fused multi-step path exercised on every gate run (CPU: dispatch-count
# and numerical-equivalence property, not a throughput claim)
run_stage perf-smoke env JAX_PLATFORMS=cpu python tools/perf_smoke.py
# serving: closed compile set + exact padded/unpadded answers + KV-decode
# token identity (CPU correctness gate, not a throughput claim)
run_stage serving-smoke env JAX_PLATFORMS=cpu python tools/serving_smoke.py
# measured search: sharding-plan candidates timed as real
# fused train steps + serving dials timed against the deterministic bench
# trace, winners persisted (schema v2); a second process replays both from
# disk with zero searches, K701 silent on hits and firing on an injected
# post-warm search
run_stage tune-smoke env JAX_PLATFORMS=cpu python tools/tune_smoke.py
# autoscaling loop: seeded traffic chaos (flash crowd / diurnal / heavy tail /
# poison) drives SloEngine -> ReplicaPool; fleet scales up AND down in bounds
# with zero lost requests, S605 silent, closed per-engine compile sets; then
# the prefill-heavy burst replayed colo vs prefill/decode-disaggregated:
# decode-class p99 strictly better, tokens bit-identical
run_stage scenario-smoke env JAX_PLATFORMS=cpu python tools/scenario_smoke.py
# expert-sharded decode: 4-expert top-2 GPT behind the continuous engine
# with per-step routing inside the jitted step -> closed compile set, zero
# post-warmup XLA compiles, tokens bit-identical to eager greedy under
# ample capacity, every expert live (no dead experts / overflow), S606
# silent; the 0-expert build must publish no moe keys at all
run_stage moe-smoke env JAX_PLATFORMS=cpu python tools/moe_smoke.py
# resilience: injected checkpoint-write fault + SIGKILL -> bit-identical
# resume; injected serving fault -> circuit opens, sheds, recovers —
# all under the runtime lock sanitizer (zero C1004/C1005 asserted)
run_stage chaos-smoke env JAX_PLATFORMS=cpu FLAGS_lock_sanitizer=1 \
  python tools/chaos_smoke.py
# observability: live Prometheus scrape with advancing step counters,
# JSONL snapshot sink, and serving spans in the chrome trace
run_stage obs-smoke env JAX_PLATFORMS=cpu python tools/obs_smoke.py
# serving control plane: 1-of-3 replicas hard-failed mid-traffic -> every
# accepted request completes via failover, half-open re-admission after the
# cooldown, rolling swap_weights under load (zero rejects, zero recompiles)
# — all under the runtime lock sanitizer (zero C1004/C1005 asserted)
run_stage router-smoke env JAX_PLATFORMS=cpu FLAGS_lock_sanitizer=1 \
  python tools/router_smoke.py
# continuous batching decode plane: 1 long + many short requests -> tokens
# identical to uncached greedy, zero lost requests, zero post-warmup XLA
# recompiles, router probes stay green; page-sharing gate: a pool of two
# whole windows holds more than two resident slots with CoW shared-prefix
# reuse + speculative decoding, tokens bit-identical to uncached greedy,
# closed compile set (buckets + 3)
run_stage gen-smoke env JAX_PLATFORMS=cpu python tools/gen_smoke.py
# multi-tenant serving: mixed multi-LoRA traffic bit-identical to per-tenant
# serial baselines, adapter hot-add mid-traffic with zero post-warmup XLA
# compiles, noisy-neighbor flooder capped at its token budget with victim
# p99 within bound, S607 silent on the healthy run
run_stage tenancy-smoke env JAX_PLATFORMS=cpu python tools/tenancy_smoke.py
# quantized serving: int8/fp8 engines may flip near-tie tokens only (margin
# accounting vs fp32), an int8-KV pool holds strictly more resident slots
# at equal measured bytes with tokens/s no worse, and a quantized rolling
# swap across a router compiles nothing
run_stage quant-smoke env JAX_PLATFORMS=cpu python tools/quant_smoke.py
# request tracing + SLO: full router->slot span tree in the merged chrome
# export with zero post-warmup compiles, injected decode latency -> burn-rate
# alert + M903 + scale-up signal through the router hook, off means off
run_stage slo-smoke env JAX_PLATFORMS=cpu python tools/slo_smoke.py
# elastic training: injected NaN -> exactly one rollback + finite finish,
# SIGKILL mid-epoch -> bit-identical resume (shuffle order, RNG, params),
# wedged collective -> watchdog raises within the deadline, F802 on a
# rollback loop, disabled supervisor is a plain loop
run_stage elastic-smoke env JAX_PLATFORMS=cpu python tools/elastic_smoke.py
# pod-scale multi-host: N real processes through distributed.launch —
# sharded-data training bit-identical to single-process, SIGKILLed host ->
# gang restore from the agreed checkpoint with bit-identical finals,
# wedged collective -> TransientDeviceError on every live rank within the
# deadline, Router fronting cross-process engines loses zero accepted
# requests across a host kill, F803 on a restore storm (per-process
# metrics JSONL merged via exporters.merge_jsonl)
run_stage pod-smoke env JAX_PLATFORMS=cpu python tools/pod_smoke.py

echo "}" >> "$SUMMARY"
echo
echo "=== gate summary ==="
cat "$SUMMARY"
cp "$SUMMARY" GATES.json
echo
echo "written to GATES.json"
exit $FAILED
