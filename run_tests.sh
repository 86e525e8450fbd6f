#!/usr/bin/env bash
# Run the test suite on a pure-CPU 8-virtual-device JAX: tests must not
# depend on, or claim, an accelerator.
#
#   ./run_tests.sh            full suite (extra pytest args pass through)
#   ./run_tests.sh --obs      observability group only: tracer/export/
#                             monitoring-endpoint tests plus a smoke run
#                             of scripts/trace_report.py over the
#                             checked-in sample dump, so the JSONL
#                             export schema cannot silently drift.
#   ./run_tests.sh --sched    scheduling group only: admission-control
#                             queue discipline, overload/shed/drain
#                             serving surfaces, and the engine-level
#                             queued-request race tests
#                             (docs/SCHEDULING.md).
#   ./run_tests.sh --kv       KV host-offload group: pool LRU/TTL/budget
#                             discipline, park→restore round-trip
#                             equivalence on the CPU engine,
#                             restore-vs-cancel/-deadline races, parked
#                             KV across engine.restart(), KV_* config
#                             validation, plus a trace_report smoke
#                             checking the kv_offload/kv_restore phase
#                             percentiles (docs/KVCACHE.md).
#   ./run_tests.sh --kvq      quantized-KV group (KV_QUANT=int8):
#                             quantize/dequant numerics, model parity
#                             vs the bf16 cache, engine greedy
#                             equivalence + park→restore under
#                             quantization, honest int8+scales host
#                             byte accounting (~2x sessions per
#                             budget), and the compat-matrix
#                             validation (docs/KVCACHE.md "Quantized
#                             tier").
#   ./run_tests.sh --paged    paged-KV group (KV_LAYOUT=paged):
#                             block-allocator discipline (refcount
#                             aliasing, copy-on-write, leak
#                             invariant), paged-vs-dense greedy token
#                             parity (bf16 + int8, incl. the trained
#                             tinychat checkpoint), out-of-blocks
#                             admission sheds with retry_after,
#                             park→restore→release zero-leak, the
#                             kv.block_alloc chaos drill, and the
#                             failpoint lint (docs/KVCACHE.md "Paged
#                             tier").
#   ./run_tests.sh --radix    radix prefix-cache group
#                             (KV_RADIX_ENABLED=true): chain-digest /
#                             insert / match / split units, refcount-
#                             aware LRU+FIFO eviction with exact
#                             accounting, the allocator pressure seam,
#                             cross-session automatic admission with
#                             greedy parity (incl. the trained
#                             tinychat multi-turn O(delta) prefill),
#                             crash-restart tree rebuild, and the two
#                             radix chaos drills (docs/KVCACHE.md
#                             "Automatic prefix cache").
#   ./run_tests.sh --slo      SLO/watchdog group: burn-rate windows,
#                             goodput, the fake-clock stall watchdog,
#                             /slo + /events endpoints, the strict
#                             Prometheus validator, plus smoke runs of
#                             scripts/check_prometheus.py and the
#                             trace_report --slo CI gate.
#   ./run_tests.sh --router   fleet-router group: replica registry /
#                             probe health transitions, affinity +
#                             weighted placement, failover races
#                             (cancel-during-failover, drain-vs-new-
#                             session, death mid-prefill vs mid-decode,
#                             affinity across park/restore), the WS
#                             `resumed` integration, /fleet endpoints,
#                             and the remote-client pre-first-token
#                             retry discipline (docs/ROUTER.md).
#   ./run_tests.sh --fleet    fleet session-fabric group: the
#                             failpoint coverage lint (router seams
#                             included), cross-replica KV migration
#                             (wire form, drain-migrate byte
#                             accounting, failover pull, chaos drills
#                             for failed/corrupt/hung transfers and
#                             probe partitions), prefix-aware
#                             placement, the elastic scaler, the
#                             rolling-restart drill, the /kv/parked
#                             HTTP channel, and the real-engine
#                             drain -> migrate -> restore regression
#                             (docs/ROUTER.md).
#   ./run_tests.sh --disagg   disaggregated prefill/decode group
#                             (docs/ROUTER.md "Disaggregated prefill/
#                             decode"): the failpoint + router-span
#                             lints (the router.handoff seam must be
#                             chaos-injected and trace-asserted), role
#                             parsing/placement/tier stats, the full
#                             prefill->handoff->decode lifecycle on
#                             real engines with greedy token parity vs
#                             the mixed control, priced fallback to
#                             mixed placement, per-tier elastic
#                             scaling, prefill-death and hung-handoff
#                             chaos, radix donation of imported
#                             blocks, DISAGG_*/FLEET_ROLES config
#                             validation, and a no-engine pricing
#                             smoke.
#   ./run_tests.sh --structured  structured-decoding group: the
#                             schema→regex→DFA→token-FSM compiler
#                             (tokenizer-boundary cases incl.
#                             multi-byte UTF-8 and ByteLevel-BPE
#                             tokens spanning FSM edges), the device
#                             union arena, engine-level constrained
#                             generation (greedy determinism,
#                             adversarial schema battery on the
#                             trained tinychat checkpoint,
#                             jump-forward equivalence, cancel races,
#                             zero-cost-when-off), the /v1
#                             response_format + tool_choice and WS
#                             `structured` surfaces, and the hermes
#                             split-tag streaming parser
#                             (docs/STRUCTURED.md).
#   ./run_tests.sh --chaos    fault-injection/chaos group: the
#                             failpoint registry (spec grammar, p/
#                             count/after/match, zero-overhead-off),
#                             injected crash/hang/error/corrupt drills
#                             through engine, KV offload, remote, WS
#                             serving, SPMD and the structured
#                             compiler asserting the exactly-once-
#                             terminal + no-hang invariants, the
#                             supervisor restart-storm guard, the
#                             SPMD follower-kill liveness test, and
#                             the scripts/check_failpoints.py
#                             coverage lint (docs/RESILIENCE.md).
#   ./run_tests.sh --int4     int4 weight tier group (WEIGHT_QUANT=
#                             int4, docs/QUANTIZATION.md): pack/unpack
#                             roundtrip + group sweep, the fused XLA
#                             and Pallas matmul paths, model logit
#                             bounds, the AWQ calibration search,
#                             engine serving (incl. the int4 x
#                             int8-KV x paged composition and the
#                             trained-tinychat factory acceptance),
#                             sharding rules, perf-ledger weight
#                             bytes, the compat matrix, and a
#                             scripts/quantize_checkpoint.py
#                             --data-free smoke into a temp cache.
#   ./run_tests.sh --roofline roofline/decode-kernel group (docs/
#                             ROOFLINE.md): the compat-matrix lint
#                             (scripts/check_compat.py — doc tables vs
#                             live Config rejections), interpret-mode
#                             Pallas kernel parity (bf16 + fused int8
#                             dequant, single- and multi-token q,
#                             dense + paged), fused-dequant greedy
#                             parity and kernel routing at the engine
#                             seam, spec-verify and structured-FSM
#                             composition through the kernels, and a
#                             two-cell BENCH_MODE=roofline sweep smoke
#                             on the byte-tokenizer test model.
#   ./run_tests.sh --journey  fleet-tracing/token-journey group
#                             (docs/OBSERVABILITY.md "Fleet tracing
#                             and the token journey"): the router-span
#                             coverage lint (scripts/
#                             check_router_spans.py), traceparent
#                             propagation + cross-replica trace
#                             stitching (mid-stream failover, /kv/
#                             parked migration), the JourneyRecorder
#                             telescoping-hop unit tests, the WS
#                             journey opt-in surface, /fleet/metrics
#                             label-merged exposition through the
#                             strict Prometheus validator, the fleet
#                             flight recorder, plus a trace_report
#                             --journey reconciliation-gate smoke.
#   ./run_tests.sh --perf     perf-attribution/flight-recorder group:
#                             the step ledger (wall-time decomposition,
#                             padding waste, MFU, compile ledger),
#                             GET /perf + perf_* gauge exposition,
#                             fake-clock flight-bundle triggers, the
#                             profiler endpoints, and a trace_report
#                             --perf smoke (docs/OBSERVABILITY.md).
#   ./run_tests.sh --profiler continuous-profiler/program-attribution
#                             group (docs/OBSERVABILITY.md "Continuous
#                             profiler and program attribution"): the
#                             host stack sampler (role/cause
#                             classification, bounded stack table,
#                             gc.callbacks pauses, crash_thread-while-
#                             sampling no-deadlock), the per-program
#                             device-time ledger reconciliation
#                             property (sum == device_busy_s, bitwise),
#                             host_gap_causes closure, /debug/profile,
#                             flight-bundle profile sections with
#                             per-section fault isolation, strict
#                             Prometheus validity of perf_program_* /
#                             perf_host_gap_* mid-profile, PROF_*
#                             config validation, plus smoke runs of
#                             scripts/bench_compare.py (the
#                             BENCH_r*.json regression gate) and the
#                             trace_report --perf program table.
set -euo pipefail
cd "$(dirname "$0")"

PYENV=(env JAX_PLATFORMS=cpu
       XLA_FLAGS="--xla_force_host_platform_device_count=8")

if [[ "${1:-}" == "--obs" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_observability.py \
        tests/test_utils.py "tests/test_engine.py::TestEngineTracing" "$@"
    echo "--- trace_report smoke (tests/data/sample_trace.jsonl) ---"
    out="$("${PYENV[@]}" python scripts/trace_report.py \
        tests/data/sample_trace.jsonl)"
    echo "$out"
    # The report must recognise the core request phases by name.
    for phase in queue_wait prefill decode_step ws_send; do
        grep -q "$phase" <<<"$out" \
            || { echo "trace_report smoke: missing phase $phase" >&2; exit 1; }
    done
    exit 0
fi

if [[ "${1:-}" == "--sched" ]]; then
    shift
    exec "${PYENV[@]}" python -m pytest tests/test_scheduling.py \
        "tests/test_engine.py::TestSchedulerRaces" "$@"
fi

if [[ "${1:-}" == "--kv" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_kvcache.py "$@"
    echo "--- trace_report kv phase smoke ---"
    tmp="$(mktemp)"
    trap 'rm -f "$tmp"' EXIT
    cat > "$tmp" <<'EOF'
{"request_id": "r1", "session_id": "s1", "span": "queue_wait", "ts": 1.0, "dur_ms": 5.0, "attrs": {}}
{"request_id": "r1", "session_id": "s1", "span": "kv_restore", "ts": 1.01, "dur_ms": 2.5, "attrs": {"tokens": 512}}
{"request_id": "r1", "session_id": "s1", "span": "prefill", "ts": 1.02, "dur_ms": 4.0, "attrs": {}}
{"request_id": null, "session_id": "", "span": "kv_offload", "ts": 1.05, "dur_ms": 3.5, "attrs": {"tokens": 512}}
EOF
    out="$("${PYENV[@]}" python scripts/trace_report.py "$tmp")"
    echo "$out"
    for phase in kv_restore kv_offload; do
        grep -q "$phase" <<<"$out" \
            || { echo "trace_report kv smoke: missing $phase" >&2; exit 1; }
    done
    exit 0
fi

if [[ "${1:-}" == "--kvq" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_kv_quant.py "$@"
    echo "--- trace_report --perf kv-bandwidth smoke ---"
    tmp="$(mktemp)"
    trap 'rm -f "$tmp"' EXIT
    cat > "$tmp" <<'EOF'
{"request_id": null, "session_id": "", "span": "engine_step", "ts": 100.0, "dur_ms": 1000.0, "attrs": {"steps": 8, "batch": 2, "slots": 4, "occupancy": 0.5, "tokens": 16, "rows": 32, "kv_len": 512, "flops": 1e9, "kv_bytes": 2e9}}
{"request_id": null, "session_id": "", "span": "engine_prefill", "ts": 101.1, "dur_ms": 100.0, "attrs": {"bucket": 64, "tokens": 40, "rows": 64}}
EOF
    out="$("${PYENV[@]}" python scripts/trace_report.py --perf "$tmp")"
    echo "$out"
    grep -q "KV read" <<<"$out" \
        || { echo "trace_report --perf smoke: missing KV read GB/s" >&2; exit 1; }
    exit 0
fi

if [[ "${1:-}" == "--paged" ]]; then
    shift
    # Paged block-table KV tier (KV_LAYOUT=paged, docs/KVCACHE.md
    # "Paged tier"): allocator/config units + the slow engine suites
    # (paged-vs-dense token parity incl. int8 and the trained
    # checkpoint, aliasing, admission sheds, park/restore zero-leak)
    # + the block-pool chaos drill, with the failpoint lint first so
    # the catalog/test cross-check cannot drift.
    "${PYENV[@]}" python scripts/check_failpoints.py
    "${PYENV[@]}" python -m pytest tests/test_paged_kv.py \
        "tests/test_chaos.py::TestKVChaos::test_block_alloc_exhaustion_sheds_with_exact_accounting" \
        "$@"
    exit 0
fi

if [[ "${1:-}" == "--radix" ]]; then
    shift
    # Radix automatic prefix cache over the block pool (ISSUE 17,
    # docs/KVCACHE.md "Automatic prefix cache"): tree units + the
    # slow engine suites (cross-session hits with zero registration,
    # O(delta) multi-turn prefill on trained weights, pressure
    # eviction) + the chaos drills proving the failpoint fires before
    # eviction and refcounted blocks are never reclaimed. Failpoint
    # lint first, same bar as --paged.
    "${PYENV[@]}" python scripts/check_failpoints.py
    "${PYENV[@]}" python -m pytest tests/test_radix_kv.py \
        "tests/test_chaos.py::TestKVChaos::test_block_alloc_failpoint_fires_before_radix_eviction" \
        "tests/test_chaos.py::TestKVChaos::test_radix_pressure_never_evicts_refcounted_blocks" \
        "$@"
    echo "--- BENCH_MODE=radix smoke (2 agents x 3 turns, test model,"
    echo "    radix off vs on; one JSON line on stdout) ---"
    out="$("${PYENV[@]}" env BENCH_MODE=radix BENCH_MODEL=test-tiny \
        BENCH_RX_AGENTS=2 BENCH_RX_TURNS=3 BENCH_RX_MAX_TOKENS=8 \
        BENCH_QUANTIZE=none python bench.py)"
    echo "$out"
    for want in followup_ttft_p50_speedup hit_rate bytes_saved; do
        grep -q "$want" <<<"$out" \
            || { echo "radix bench smoke: missing '$want'" >&2; exit 1; }
    done
    exit 0
fi

if [[ "${1:-}" == "--slo" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_slo.py "$@"
    echo "--- trace_report --slo gate (tests/data/sample_trace.jsonl) ---"
    "${PYENV[@]}" python scripts/trace_report.py --slo \
        tests/data/sample_trace.jsonl
    echo "--- check_prometheus smoke (registry self-render) ---"
    "${PYENV[@]}" python - <<'EOF'
from fasttalk_tpu.utils.metrics import get_metrics
import importlib.util
spec = importlib.util.spec_from_file_location(
    "check_prometheus", "scripts/check_prometheus.py")
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
m = get_metrics()
m.counter("smoke_total", "smoke").inc()
m.histogram("smoke_ms", "smoke").observe(3.0)
problems = mod.validate(m.prometheus())
assert not problems, problems
print("exposition format OK")
EOF
    exit 0
fi

if [[ "${1:-}" == "--router" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_router.py \
        "tests/test_remote_engines.py::TestConnectRetry" "$@"
    echo "--- client.py reconnect-backoff smoke (no server: importable"
    echo "    + backoff path unit-exercised inline) ---"
    "${PYENV[@]}" python - <<'EOF'
import asyncio
import importlib.util

spec = importlib.util.spec_from_file_location("ft_client", "client.py")
client = importlib.util.module_from_spec(spec)
spec.loader.exec_module(client)

# The backoff classifier must honour retry_after frames...
try:
    client._maybe_backoff({"error": {"code": "rate_limit_error",
                                     "message": "shed",
                                     "retry_after": 2.5}})
    raise SystemExit("expected Backoff")
except client.Backoff as b:
    assert b.retry_after == 2.5
# ...and pass through non-capacity errors.
client._maybe_backoff({"error": {"code": "model_error",
                                 "message": "boom"}})
print("client backoff classifier OK")
EOF
    exit 0
fi

if [[ "${1:-}" == "--fleet" ]]; then
    shift
    echo "--- check_failpoints lint (router seams; docs/RESILIENCE.md) ---"
    "${PYENV[@]}" python scripts/check_failpoints.py
    "${PYENV[@]}" python -m pytest tests/test_fleet_fabric.py "$@"
    echo "--- migration channel smoke (serialize -> transfer -> import"
    echo "    between two real pools, in-process) ---"
    "${PYENV[@]}" python - <<'EOF'
import numpy as np
from fasttalk_tpu.kvcache.hostpool import HostKVPool, ParkedKV
from fasttalk_tpu.router.migrate import (deserialize_parked,
                                         serialize_parked)

k = np.random.default_rng(0).standard_normal((2, 64, 2, 4)).astype(
    np.float32)
entry = ParkedKV(session_id="smoke", tokens=list(range(64)), kept=64,
                 bucket=64, k=k, v=k.copy(),
                 nbytes=2 * int(k.nbytes))
wire = serialize_parked(entry)
out = deserialize_parked(wire)
np.testing.assert_array_equal(out.k, entry.k)
dst = HostKVPool(budget_mb=4.0)
assert dst.put(out)
assert dst.stats()["bytes"] == entry.nbytes
print(f"migration smoke OK: {len(wire)} wire bytes, "
      f"{entry.nbytes} pool bytes accounted exactly")
EOF
    exit 0
fi

if [[ "${1:-}" == "--disagg" ]]; then
    shift
    # Disaggregated prefill/decode serving (ISSUE 19, docs/ROUTER.md
    # "Disaggregated prefill/decode"): role vocabulary + placement,
    # the full prefill->handoff->decode lifecycle on real engines with
    # token parity vs the mixed control, pricing fallback, per-tier
    # elastic scaling, both-sides chaos, and radix donation on import.
    # Both lints first: the handoff failpoint must be chaos-injected
    # and its span asserted by the fleet-trace suite.
    "${PYENV[@]}" python scripts/check_failpoints.py
    "${PYENV[@]}" python scripts/check_router_spans.py
    "${PYENV[@]}" python -m pytest tests/test_disagg.py "$@"
    echo "--- disagg pricing smoke (role parse + handoff threshold +"
    echo "    wire-cost EMA, no engines) ---"
    "${PYENV[@]}" python - <<'EOF'
from fasttalk_tpu.kvcache.policy import RestorePolicy
from fasttalk_tpu.router.disagg import DisaggController, parse_roles

assert parse_roles("", 2) == ["mixed", "mixed"]
assert parse_roles("prefill,decode", 2) == ["prefill", "decode"]
pol = RestorePolicy(min_tokens=8)
ctl = DisaggController(pol, prefill_min_tokens=64)
pol.note_prefill(4096, 2.0)          # slow prefill ...
pol.note_migrate(64 * 1024 * 1024, 0.01)  # ... fast wire
assert ctl.wants_handoff(512), "long prompt must take the handoff"
assert not ctl.wants_handoff(8), "short prompt stays decode-local"
ctl.note_handoff(kept_tokens=512, nbytes=512 * 8192)
assert ctl.bytes_per_token() == 8192.0
slow = DisaggController(RestorePolicy(min_tokens=8),
                        prefill_min_tokens=64)
slow.kv_policy.note_migrate(1000, 10.0)   # ~100 B/s wire
assert not slow.wants_handoff(512), \
    "a priced-out wire must fall back to mixed placement"
print("disagg pricing smoke OK: threshold + EMA pricing + learned "
      f"bytes/token {ctl.bytes_per_token():.0f}")
EOF
    exit 0
fi

if [[ "${1:-}" == "--structured" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_structured.py "$@"
    echo "--- FSM compiler smoke (schema -> regex -> DFA -> token FSM"
    echo "    over the byte tokenizer; docs/STRUCTURED.md) ---"
    "${PYENV[@]}" python - <<'EOF'
import json
from fasttalk_tpu.engine.tokenizer import ByteTokenizer
from fasttalk_tpu.structured import FSMCompiler

comp = FSMCompiler(ByteTokenizer())
fsm = comp.compile({"kind": "json_schema", "schema": {
    "type": "object", "properties": {
        "city": {"type": "string", "maxLength": 12},
        "units": {"enum": ["C", "F"]}}}})
chain, _ = fsm.forced_chain(fsm.start)
assert bytes(chain).startswith(b'{"city":"'), bytes(chain)
print(f"token FSM: {fsm.n_states} states, {fsm.n_classes} classes, "
      f"forced prefix {bytes(chain)!r}")
comp.shutdown()
EOF
    exit 0
fi

if [[ "${1:-}" == "--chaos" ]]; then
    shift
    echo "--- check_failpoints lint (catalog <-> call sites <-> chaos"
    echo "    tests; docs/RESILIENCE.md) ---"
    "${PYENV[@]}" python scripts/check_failpoints.py
    "${PYENV[@]}" python -m pytest tests/test_chaos.py "$@"
    exit 0
fi

if [[ "${1:-}" == "--int4" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_int4_quant.py "$@"
    if [[ -f fasttalk_tpu/assets/tinychat/model.safetensors ]]; then
        echo "--- quantize_checkpoint.py smoke (data-free, temp cache) ---"
        tmpdir="$(mktemp -d)"
        trap 'rm -rf "$tmpdir"' EXIT
        cp -r fasttalk_tpu/assets/tinychat "$tmpdir/tinychat"
        "${PYENV[@]}" python scripts/quantize_checkpoint.py \
            --model tinychat --model-path "$tmpdir" --data-free \
            --group 128
        manifest="$(find "$tmpdir/.prepared" -name quantize_manifest.json)"
        [[ -n "$manifest" ]] \
            || { echo "int4 smoke: no quantize_manifest.json" >&2; exit 1; }
        grep -q '"mode": "data-free"' "$manifest" \
            || { echo "int4 smoke: manifest mode wrong" >&2; exit 1; }
        echo "manifest OK: $manifest"
    else
        echo "--- quantize_checkpoint.py smoke skipped (no tinychat" \
             "checkpoint; run scripts/train_tinychat.py first) ---"
    fi
    exit 0
fi

if [[ "${1:-}" == "--roofline" ]]; then
    shift
    echo "--- check_compat lint (doc compat tables <-> live Config"
    echo "    rejections; docs/ROOFLINE.md) ---"
    "${PYENV[@]}" python scripts/check_compat.py
    "${PYENV[@]}" python -m pytest tests/test_pallas_attention.py \
        "tests/test_kv_quant.py::TestCompatMatrix" \
        "tests/test_kv_quant.py::TestTrainedTinyAcceptance::test_greedy_parity_pallas_fused_dequant" \
        "tests/test_spec_decode.py::test_pallas_attention_composes_with_spec" \
        "tests/test_structured.py::TestStructuredWithPallas" \
        "$@"
    echo "--- BENCH_MODE=roofline sweep smoke (2 cells, XLA vs fused"
    echo "    Pallas, test model; one JSON line on stdout) ---"
    out="$("${PYENV[@]}" env BENCH_MODE=roofline BENCH_MODEL=test-tiny \
        BENCH_RF_CONFIGS=none:dense:xla,int8:dense:pallas \
        BENCH_RF_STEPS=8 BENCH_RF_SLOTS=2 BENCH_RF_MAX_TOKENS=8 \
        python bench.py)"
    echo "$out"
    for want in xla_dense pallas_dense frac_of_ceiling; do
        grep -q "$want" <<<"$out" \
            || { echo "roofline smoke: missing '$want'" >&2; exit 1; }
    done
    exit 0
fi

if [[ "${1:-}" == "--journey" ]]; then
    shift
    echo "--- check_router_spans lint (failpoint seams <-> router"
    echo "    spans <-> fleet-trace tests; docs/OBSERVABILITY.md) ---"
    "${PYENV[@]}" python scripts/check_router_spans.py
    "${PYENV[@]}" python -m pytest tests/test_fleet_trace.py "$@"
    echo "--- trace_report --journey reconciliation gate smoke ---"
    tmp="$(mktemp)"
    trap 'rm -f "$tmp"' EXIT
    cat > "$tmp" <<'EOF'
{"request_id": "s1:aa", "session_id": "s1", "span": "token_journey", "ts": 10.0, "dur_ms": 120.0, "attrs": {"frames": 3, "wall_ms": 120.0, "hops_sum_ms": 119.0, "reconciliation": 0.9917, "hops_ms": {"engine": 80.0, "device_fetch": 10.0, "detok_emit": 9.0, "loop_dequeue": 10.0, "ws_write": 10.0}, "frames_ms": {"engine": [60.0, 10.0, 10.0], "device_fetch": [4.0, 3.0, 3.0], "detok_emit": [3.0, 3.0, 3.0], "loop_dequeue": [4.0, 3.0, 3.0], "ws_write": [4.0, 3.0, 3.0]}}}
EOF
    out="$("${PYENV[@]}" python scripts/trace_report.py --journey "$tmp")"
    echo "$out"
    for want in engine ws_write "all journeys reconcile"; do
        grep -q "$want" <<<"$out" \
            || { echo "trace_report --journey smoke: missing '$want'" >&2; exit 1; }
    done
    # ...and the gate must actually FAIL on a hop sum that does not
    # telescope to the wall clock.
    sed 's/"hops_sum_ms": 119.0/"hops_sum_ms": 60.0/' "$tmp" > "$tmp.bad"
    if "${PYENV[@]}" python scripts/trace_report.py --journey \
            "$tmp.bad" >/dev/null 2>&1; then
        echo "trace_report --journey smoke: gate passed a broken sum" >&2
        rm -f "$tmp.bad"
        exit 1
    fi
    rm -f "$tmp.bad"
    echo "reconciliation gate rejects broken decomposition OK"
    exit 0
fi

if [[ "${1:-}" == "--perf" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_perf.py \
        "tests/test_observability.py::TestProfilerEndpoints" "$@"
    echo "--- trace_report --perf smoke ---"
    tmp="$(mktemp)"
    trap 'rm -f "$tmp"' EXIT
    cat > "$tmp" <<'EOF'
{"request_id": null, "session_id": "", "span": "engine_step", "ts": 100.0, "dur_ms": 1000.0, "attrs": {"steps": 8, "batch": 2, "slots": 4, "occupancy": 0.5, "tokens": 16, "rows": 32, "kv_len": 512, "flops": 1e9}}
{"request_id": null, "session_id": "", "span": "engine_prefill", "ts": 101.1, "dur_ms": 100.0, "attrs": {"bucket": 64, "tokens": 40, "rows": 64}}
EOF
    out="$("${PYENV[@]}" python scripts/trace_report.py --perf "$tmp")"
    echo "$out"
    for want in "perf attribution" "padding waste" "device busy"; do
        grep -q "$want" <<<"$out" \
            || { echo "trace_report --perf smoke: missing '$want'" >&2; exit 1; }
    done
    exit 0
fi

if [[ "${1:-}" == "--profiler" ]]; then
    shift
    "${PYENV[@]}" python -m pytest tests/test_profiler.py \
        tests/test_perf.py "$@"
    echo "--- bench_compare regression-gate smoke (committed"
    echo "    BENCH_r*.json trajectory; exit non-zero on regression) ---"
    "${PYENV[@]}" python scripts/bench_compare.py --smoke
    echo "--- trace_report --perf program-attribution smoke ---"
    tmp="$(mktemp)"
    trap 'rm -f "$tmp"' EXIT
    cat > "$tmp" <<'EOF'
{"request_id": null, "session_id": "", "span": "engine_step", "ts": 100.0, "dur_ms": 10.0, "attrs": {"occupancy": 0.5, "tokens": 16, "rows": 32, "program": "decode kv_len=512 steps=8"}}
{"request_id": "r1", "session_id": "s1", "span": "detok_emit", "ts": 100.011, "dur_ms": 3.0, "attrs": {}}
{"request_id": null, "session_id": "", "span": "engine_prefill", "ts": 100.016, "dur_ms": 20.0, "attrs": {"tokens": 40, "rows": 64, "program": "prefill chunk=512"}}
{"request_id": null, "session_id": "", "span": "engine_op", "ts": 100.04, "dur_ms": 5.0, "attrs": {"kind": "kv_restore", "program": "kv_restore bucket=1024"}}
EOF
    out="$("${PYENV[@]}" python scripts/trace_report.py --perf "$tmp")"
    echo "$out"
    for want in "per-program device time" "host-gap causes" \
            "decode kv_len=512 steps=8" "kv_restore bucket=1024" detok; do
        grep -q "$want" <<<"$out" \
            || { echo "trace_report program smoke: missing '$want'" >&2; exit 1; }
    done
    exit 0
fi

exec "${PYENV[@]}" python -m pytest tests/ "$@"
