#!/usr/bin/env bash
# One-command CI entrypoint — the repo's counterpart of the reference's
# build/test matrix (ref: tests/docker_extension_builds/run.sh,
# .jenkins/build.sh).  A fresh clone proves itself green with:
#
#     tools/ci.sh
#
# Steps, failing fast on the first red one:
#   1. default test tier   — CPU backend, 8 virtual devices, slow tier
#                            skipped (APEX_TPU_FULL=1 upgrades to the
#                            full tier, the builder's verify flow)
#   2. 8-device dryrun     — the multichip legs (GPT 3D DP x TP x PP,
#                            ResNet DP, SP/MoE/ZeRO) on a virtual mesh
#   3. monitor smoke       — a tiny standalone_gpt train run writes a
#                            JSONL event log through apex_tpu.monitor
#                            and tools/monitor_summary.py renders it,
#                            so the telemetry path is exercised on
#                            every CI run, not only under a TPU bench
#   4. kill->resume smoke  — the resilience acceptance path end to end:
#                            a checkpointed standalone_gpt run is
#                            SIGTERM'd at step 4 (--fault sigterm@4),
#                            must exit 0 with a CLEAN_EXIT.json marker,
#                            then the same command resumes to step 8;
#                            the shared JSONL must carry the
#                            preempt_exit and run_resumed events
#   5. pipeline kernels    — the fused-pipeline Pallas sweeps run in
#                            interpret mode on CPU (tiny tree, 3
#                            steps) and must match the per-stage path,
#                            so kernel regressions are caught without
#                            a TPU (ops/fused_pipeline.self_check)
#   6. static analysis     — the self-hosted trace-safety lint +
#                            kernel-parity audit must report zero
#                            unsuppressed findings, the generated
#                            doc tables (env flags, APX rules) must
#                            match their registries, and the sanitizer
#                            smoke must prove the GPT step compiles
#                            exactly once after warmup
#                            (docs/api/analysis.md)
#   7. compiled-graph audit — python -m apex_tpu.analysis --check-hlo
#                            lowers every registered entry point on
#                            CPU (8 host-platform devices, so the
#                            multichip entries' collective census is
#                            covered) and checks donation, dtype
#                            promotion, the collective census, host
#                            transfers, and peak live memory against
#                            tools/hlo_baseline.json
#   8. trace smoke          — a 3-step standalone_gpt run with
#                            --trace must emit the canonical wall-time
#                            waterfall (data_load/dispatch/
#                            device_compute/telemetry_drain/ckpt_io +
#                            other residual, parts summing to wall_ms)
#                            and a parseable Chrome trace artifact;
#                            then the same run in deferred-telemetry
#                            mode (--telemetry-drain-every 1) must
#                            pass --sanitize with the device->host
#                            transfer guard armed — zero per-step
#                            host transfers, metrics drained through
#                            the device ring (docs/api/
#                            observability.md)
#   9. scan-driver smoke     — the ISSUE-8 batched-step driver: a
#                            2-window x K=3 standalone_gpt run under
#                            --sanitize must prove ONE compile for
#                            all 6 steps (AOT window + recompile
#                            budget 0) with zero per-step host
#                            transfers, exactly ceil(N/K)=2 telemetry
#                            drains and the full 6-loss series in the
#                            log, and K-sized waterfall windows
#                            (tools/trace_check.py --scan-k); then the
#                            AOT + persistent-compile-cache leg: the
#                            registry warmup runs twice against one
#                            APEX_TPU_COMPILE_CACHE_DIR and the second
#                            process must warm-start from the cache
#                            (--expect-cache-hits)
#  10. serving smoke         — the ISSUE-9 continuous-batching stack:
#                            a sanitized `--serve` run (mixed-length
#                            requests, prefill via the flash fwd
#                            kernel, decode via the paged flash-decode
#                            kernel) must AOT-compile exactly one
#                            program per (batch, pages) ladder bucket
#                            and hold a post-warmup recompile budget
#                            of ZERO while sustaining tokens/s > 0,
#                            with the ISSUE-11 telemetry on: every
#                            submitted rid's lifecycle chain complete
#                            (N submitted => N terminal events, TTFT
#                            present for every non-preempted rid,
#                            queued+prefill+decode summing to each
#                            rid's wall), serve_tick engine gauges in
#                            the log, and the per-request Chrome
#                            lanes validated by tools/trace_check.py
#                            --serve; then a SIGTERM mid-serve must
#                            drain clean — admissions stop, every
#                            cache block returns to the pool,
#                            in-flight AND queued requests end in
#                            terminal preempted events whose chains
#                            still check out (docs/api/serving.md);
#                            finally the ISSUE-12 fast path: the same
#                            trace with --speculate-k 2 --prefix-share
#                            under --sanitize must keep the
#                            zero-recompile contract (draft/verify/
#                            CoW programs all in warmup), report
#                            acceptance_rate > 0 and shared blocks,
#                            and emit a tokens digest IDENTICAL to
#                            the plain leg's (speculative greedy ==
#                            greedy, token for token); then the
#                            ISSUE-13 resilience legs: a supervised
#                            `--fault crash@3` serve must restart
#                            once, journal-replay every non-terminal
#                            request WARM (prefix_hit_tokens > 0),
#                            keep N submitted => N terminal across
#                            the crash, and reproduce the
#                            uninterrupted run's tokens digest; and a
#                            `--fault stall@2` serve under a short
#                            watchdog timeout must fire the
#                            snapshot-then-drain escalation exactly
#                            once (one engine_snapshot, clean drain,
#                            chains complete)
#  11. SPMD sharding audit   — python -m apex_tpu.analysis
#                            --check-sharding compiles every
#                            plan-carrying multichip entry point under
#                            its MeshPlan's mesh (8 host-platform
#                            devices) and checks declared-vs-propagated
#                            shardings, reshard chains, collective
#                            budgets, overlap preconditions, and
#                            per-device memory against
#                            tools/sharding_baseline.json (APX701-705),
#                            failing on stale sharding_findings.txt
#                            suppressions; plus the committed
#                            MULTICHIP_TOPOLOGY.json must match the
#                            canonical MeshPlan constructors
#                            (docs/api/analysis.md)
#  12. fleet serving smoke   — the ISSUE-14 multi-replica stack: a
#                            sanitized 2-replica `--serve-fleet` run
#                            with one mid-serve rolling weight swap
#                            must lose ZERO requests (every submitted
#                            rid terminal fleet-wide, trace_check
#                            --serve over the per-replica logs) and
#                            compile NOTHING after warmup (the swap
#                            keeps the AOT ladder — sanitize proves
#                            it); a disaggregated leg must hand
#                            prefill KV off warm (handoffs > 0,
#                            prefix_hit_tokens > 0 on the decode
#                            replica); and a `--fault crash@2`
#                            replica with a journal must recover by
#                            replay (restarts>=1, replayed>0) while
#                            the fleet still completes every request
#                            (docs/api/serving.md#fleet-serving)
#  13. host-concurrency audit — the ISSUE-15 APX8xx family:
#                            python -m apex_tpu.analysis
#                            --check-concurrency audits lock
#                            discipline (guard inference over
#                            `with self._lock:` regions),
#                            lock-acquisition-order cycles aggregated
#                            across modules, flag-only signal
#                            handlers, blocking-under-lock, and
#                            thread-target jit dispatch outside a
#                            device pin against the committed EMPTY
#                            tools/concurrency_baseline.txt (stale
#                            entries fail); then the deterministic-
#                            schedule stress leg: 5 seeds x the
#                            2-replica threaded fleet under permuted
#                            tick interleavings must produce the
#                            IDENTICAL terminal digest with zero lost
#                            requests and zero uncaught background-
#                            thread exceptions
#                            (docs/api/analysis.md)
#  14. Q8 quantized serving  — the ISSUE-16 int8 weight-only tier:
#                            ops/quant_matmul.self_check() runs the
#                            interpret-mode parity sweep (GEMV +
#                            tiled paths vs the jnp twin, the
#                            all-zero-channel round-trip), then a
#                            sanitized `--serve --policy Q8` smoke
#                            must decode through int8 weights with
#                            the SAME AOT bucket ladder — one compile
#                            per bucket, zero post-warmup recompiles,
#                            tokens/s > 0 (docs/api/serving.md
#                            #weight-quantization)
#  15. live metrics plane   — the ISSUE-17 exporter end to end: a
#                            live probe scrapes /metrics off a
#                            serving fleet (per-replica labeled
#                            counters + fleet gauges), a SIGTERM
#                            drain flips /healthz 200 -> 503 before
#                            teardown, and a forced TTFT breach emits
#                            exactly one slo_burn episode traced back
#                            to its objective definition
#  16. process-isolated fleet — the ISSUE-18 control plane: a
#                            2-process supervised fleet run twice,
#                            uninterrupted and with replica r0
#                            SIGKILL'd mid-serve (kill9@2); the
#                            kill -9 leg must restart (restarts>=1),
#                            journal-replay into the fresh process
#                            (replayed>=1), lose ZERO requests, and
#                            reproduce the uninterrupted run's fleet
#                            digest token for token; trace_check
#                            --serve over supervisor + child logs
#                            proves every spawned (replica,
#                            incarnation) reaped exactly once; then a
#                            1-replica floor under a 10-request burst
#                            must autoscale up on the backlog trend
#                            with the autoscale event trace rendered
#                            by monitor_summary
#                            (docs/api/resilience.md
#                            #distributed-control-plane)
#  17. expert-parallel serving — the ISSUE-19 MoE decode fast path:
#                            ops/moe_routing.self_check() runs the
#                            fused routing kernel's interpret-mode
#                            parity sweep, then a sanitized
#                            `--serve --ep 2 --moe-experts 4` smoke
#                            must decode through the 4-expert Switch
#                            MoE sharded over 2 host devices — fused
#                            top-1 routing, the capacity-chunked
#                            overlapped all_to_all exchange and one
#                            masked psum per layer — with the SAME
#                            AOT bucket ladder (one compile per
#                            bucket, zero post-warmup recompiles)
#                            and tokens/s > 0 (docs/api/serving.md
#                            #expert-parallel-decode)
#  18. wire-protocol audit  — `--check-protocol` (APX901-905):
#                            serving/ + resilience/ audited against
#                            the ProtocolSpec registry in
#                            serving/control_plane.py — deadline
#                            discipline, op/header-field drift
#                            matched across the parent post/wait
#                            paths and the child dispatch table,
#                            socket/subprocess/tempdir lifecycle,
#                            retry-safety — with the linter's
#                            baseline semantics against the
#                            committed-EMPTY
#                            tools/protocol_baseline.txt (stale
#                            entries fail; docs/api/analysis.md
#                            #wire-protocol)
set -euo pipefail
cd "$(dirname "${BASH_SOURCE[0]}")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "[ci] 1/18 default test tier"
python -m pytest tests/ -q -m 'not slow' -p no:cacheprovider

echo "[ci] 2/18 8-device multichip dryrun"
python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

echo "[ci] 3/18 monitor smoke"
MONITOR_SMOKE_JSONL="$(mktemp -t apex_tpu_monitor_smoke.XXXXXX.jsonl)"
python -m apex_tpu.testing.standalone_gpt --steps 3 \
    --jsonl "$MONITOR_SMOKE_JSONL"
python tools/monitor_summary.py "$MONITOR_SMOKE_JSONL"
rm -f "$MONITOR_SMOKE_JSONL"

echo "[ci] 4/18 kill->resume smoke"
RESIL_DIR="$(mktemp -d -t apex_tpu_resilience.XXXXXX)"
RESIL_JSONL="$RESIL_DIR/events.jsonl"
# leg 1: preempted at step 4 — must exit 0 via the graceful path
python -m apex_tpu.testing.standalone_gpt --steps 8 \
    --ckpt-dir "$RESIL_DIR/ck" --jsonl "$RESIL_JSONL" --fault sigterm@4
test -f "$RESIL_DIR/ck/CLEAN_EXIT.json" \
    || { echo "[ci] FAIL: no CLEAN_EXIT.json after SIGTERM"; exit 1; }
# leg 2: same command resumes from the final checkpoint to step 8
python -m apex_tpu.testing.standalone_gpt --steps 8 \
    --ckpt-dir "$RESIL_DIR/ck" --jsonl "$RESIL_JSONL" \
    | grep -q "steps_done=8" \
    || { echo "[ci] FAIL: resume did not reach step 8"; exit 1; }
grep -q '"name":"preempt_exit"' "$RESIL_JSONL" \
    && grep -q '"name":"run_resumed"' "$RESIL_JSONL" \
    || { echo "[ci] FAIL: resilience events missing from JSONL"; \
         exit 1; }
python tools/monitor_summary.py "$RESIL_JSONL"
rm -rf "$RESIL_DIR"

echo "[ci] 5/18 fused-pipeline kernel parity (Pallas interpret mode)"
python -c "from apex_tpu.ops import fused_pipeline; \
fused_pipeline.self_check()"

echo "[ci] 6/18 static analysis (self-hosted lint + docs drift + sanitizer)"
python -m apex_tpu.analysis --check
python -m apex_tpu.analysis --check-docs
python -m apex_tpu.analysis --smoke

echo "[ci] 7/18 compiled-graph audit (--check-hlo)"
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.analysis --check-hlo

echo "[ci] 8/18 trace smoke (waterfall + chrome + deferred telemetry)"
TRACE_DIR="$(mktemp -d -t apex_tpu_trace.XXXXXX)"
# leg 1: traced run — canonical spans, waterfall rows summing to
# wall_ms, and a parseable Chrome artifact
python -m apex_tpu.testing.standalone_gpt --steps 3 \
    --jsonl "$TRACE_DIR/run.jsonl" --trace "$TRACE_DIR"
python tools/trace_check.py "$TRACE_DIR/run.jsonl" \
    --chrome "$TRACE_DIR/trace.chrome.json"
python tools/monitor_summary.py "$TRACE_DIR/run.jsonl" \
    --chrome "$TRACE_DIR/rebuilt.chrome.json"
# leg 2: deferred telemetry must survive the sanitizer with the
# device->host transfer guard armed (zero per-step host transfers)
# while still draining the full loss series into the log
python -m apex_tpu.testing.standalone_gpt --steps 3 \
    --jsonl "$TRACE_DIR/deferred.jsonl" --telemetry-drain-every 1 \
    --sanitize
grep -q '"name":"loss"' "$TRACE_DIR/deferred.jsonl" \
    || { echo "[ci] FAIL: deferred run drained no loss metrics"; \
         exit 1; }
rm -rf "$TRACE_DIR"

echo "[ci] 9/18 scan-driver smoke (K-batched steps + AOT compile cache)"
SCAN_DIR="$(mktemp -d -t apex_tpu_scan.XXXXXX)"
# leg 1: 6 steps as 2 windows of K=3 under the sanitizer — one compile
# after warmup, d->h transfer guard armed (scan mode is deferred-
# telemetry by construction), waterfall rows are K-step windows
python -m apex_tpu.testing.standalone_gpt --steps 6 --scan-steps 3 \
    --jsonl "$SCAN_DIR/scan.jsonl" --trace "$SCAN_DIR" --sanitize \
    | grep -q "steps_done=6" \
    || { echo "[ci] FAIL: scan driver did not reach step 6"; exit 1; }
python tools/trace_check.py "$SCAN_DIR/scan.jsonl" --scan-k 3 --steps 6 \
    --chrome "$SCAN_DIR/trace.chrome.json"
[ "$(grep -c '"kind":"metric","name":"loss"' "$SCAN_DIR/scan.jsonl")" = 6 ] \
    || { echo "[ci] FAIL: scan run did not drain all 6 losses"; exit 1; }
[ "$(grep -c '"kind":"telemetry","name":"telemetry_drain"' "$SCAN_DIR/scan.jsonl")" = 2 ] \
    || { echo "[ci] FAIL: expected ceil(6/3)=2 telemetry drains"; exit 1; }
# leg 2: AOT + persistent compile cache — the second process must
# warm-start every compile from the first one's cache entries
APEX_TPU_COMPILE_CACHE_DIR="$SCAN_DIR/cc" \
    python -m apex_tpu.testing.entry_points --aot --entry fused_pipeline_step
APEX_TPU_COMPILE_CACHE_DIR="$SCAN_DIR/cc" \
    python -m apex_tpu.testing.entry_points --aot --entry fused_pipeline_step \
    --expect-cache-hits
rm -rf "$SCAN_DIR"

echo "[ci] 10/18 serving smoke (continuous batching + clean drain)"
SERVE_DIR="$(mktemp -d -t apex_tpu_serve.XXXXXX)"
# leg 1: sanitized serve — a pinned 2x1 ladder AOT-compiles in warmup
# (2 decode buckets + 1 prefill = 3 programs) and the whole run holds
# a post-warmup recompile budget of 0: one compile per bucket, ever
SERVE_OUT="$(APEX_TPU_SERVE_BATCH_BUCKETS=2,4 \
    APEX_TPU_SERVE_PAGE_BUCKETS=2 \
    python -m apex_tpu.testing.standalone_gpt --serve --requests 5 \
    --new-tokens 4 --jsonl "$SERVE_DIR/serve.jsonl" --sanitize \
    --trace "$SERVE_DIR/tr")"
echo "$SERVE_OUT"
echo "$SERVE_OUT" | grep -q "requests=5 " \
    || { echo "[ci] FAIL: serve did not finish all 5 requests"; exit 1; }
echo "$SERVE_OUT" | grep -q "compiles=3 " \
    || { echo "[ci] FAIL: expected one compile per bucket (2 decode + 1 prefill)"; exit 1; }
echo "$SERVE_OUT" | grep -Eq "tokens_s=[1-9]" \
    || { echo "[ci] FAIL: serve reported zero tokens/s"; exit 1; }
echo "$SERVE_OUT" | grep -Eq "ttft_p50_ms=[0-9]" \
    || { echo "[ci] FAIL: no TTFT percentiles in the serve summary"; exit 1; }
# ISSUE-11 lifecycle completeness: 5 submitted => 5 terminal events,
# TTFT on every non-preempted rid, parts summing to each rid's wall,
# engine gauges present, and the per-request Chrome lanes parse —
# all checked by trace_check --serve against the same JSONL
[ "$(grep -c '"name":"request_submitted"' "$SERVE_DIR/serve.jsonl")" = 5 ] \
    || { echo "[ci] FAIL: expected 5 request_submitted events"; exit 1; }
[ "$(grep -c '"name":"request_done"' "$SERVE_DIR/serve.jsonl")" = 5 ] \
    || { echo "[ci] FAIL: expected 5 terminal request_done events"; exit 1; }
grep -q '"kind":"serve_tick"' "$SERVE_DIR/serve.jsonl" \
    || { echo "[ci] FAIL: no serve_tick engine gauges in the JSONL"; exit 1; }
python tools/trace_check.py "$SERVE_DIR/serve.jsonl" --serve \
    --chrome "$SERVE_DIR/tr/serve.chrome.json"
python tools/monitor_summary.py "$SERVE_DIR/serve.jsonl"
SERVE_OUT_LEG1="$SERVE_OUT"   # leg 3 compares output digests
# leg 2: SIGTERM mid-serve (flag-only handler, --fault sigterm@2) —
# the engine stops admitting, frees every block, marks in-flight
# requests preempted and still returns a full summary; preempted
# requests carry complete lifecycle chains (trace_check --serve)
SERVE_OUT="$(python -m apex_tpu.testing.standalone_gpt --serve \
    --requests 4 --new-tokens 32 --jsonl "$SERVE_DIR/drain.jsonl" \
    --fault sigterm@2)"
echo "$SERVE_OUT"
echo "$SERVE_OUT" | grep -q "drained=1" \
    || { echo "[ci] FAIL: SIGTERM serve did not drain"; exit 1; }
echo "$SERVE_OUT" | grep -Eq "preempted=[1-9]" \
    || { echo "[ci] FAIL: no requests marked preempted"; exit 1; }
grep -q '"name":"serve_preempt"' "$SERVE_DIR/drain.jsonl" \
    || { echo "[ci] FAIL: no serve_preempt event in the JSONL"; exit 1; }
python tools/trace_check.py "$SERVE_DIR/drain.jsonl" --serve
# leg 3 (ISSUE-12): the decode fast path — speculative decoding +
# copy-on-write prefix sharing under --sanitize.  The same trace as
# leg 1 must (a) hold the zero-recompile ladder contract with the
# draft/verify/CoW programs in the warmup set, (b) record a positive
# acceptance rate (self-draft: exactly 1.0), and (c) emit
# token-for-token identical output to the plain engine — proven by
# comparing the SERVE_DONE tokens digests across the two legs.
PLAIN_DIGEST="$(echo "$SERVE_OUT_LEG1" | grep -o 'digest=[0-9a-f]*')"
SERVE_OUT="$(APEX_TPU_SERVE_BATCH_BUCKETS=2,4 \
    APEX_TPU_SERVE_PAGE_BUCKETS=2 \
    python -m apex_tpu.testing.standalone_gpt --serve --requests 5 \
    --new-tokens 4 --jsonl "$SERVE_DIR/spec.jsonl" --sanitize \
    --speculate-k 2 --prefix-share)"
echo "$SERVE_OUT"
echo "$SERVE_OUT" | grep -q "requests=5 " \
    || { echo "[ci] FAIL: spec serve did not finish all 5 requests"; exit 1; }
echo "$SERVE_OUT" | grep -Eq "spec_accept_rate=(1\.0|0\.[0-9]*[1-9])" \
    || { echo "[ci] FAIL: speculative serve reported zero acceptance"; exit 1; }
echo "$SERVE_OUT" | grep -Eq "shared_blocks_hw=[1-9]" \
    || { echo "[ci] FAIL: prefix sharing registered no shared blocks"; exit 1; }
SPEC_DIGEST="$(echo "$SERVE_OUT" | grep -o 'digest=[0-9a-f]*')"
[ -n "$PLAIN_DIGEST" ] && [ "$SPEC_DIGEST" = "$PLAIN_DIGEST" ] \
    || { echo "[ci] FAIL: speculative output digest $SPEC_DIGEST != plain $PLAIN_DIGEST"; exit 1; }
python tools/trace_check.py "$SERVE_DIR/spec.jsonl" --serve
# leg 4 (ISSUE-13): supervised crash recovery — the engine loop dies
# at tick 3 (--fault crash@3), the supervisor restarts it with the
# PR-3 bounded-backoff semantics, and the journal replay re-enters
# every non-terminal request WARM (the crashed requests' prompt pages
# survive the crash in the prefix index's idle LRU).  Asserted: one
# restart, a positive replay count, warm readmission
# (prefix_hit_tokens > 0), every submitted request terminal exactly
# once (trace_check --serve across the crash), and a tokens digest
# IDENTICAL to the same trace served uninterrupted (greedy decode is
# deterministic — recovery must not change a single token).
REF_OUT="$(python -m apex_tpu.testing.standalone_gpt --serve \
    --requests 5 --new-tokens 6)"
REF_DIGEST="$(echo "$REF_OUT" | grep -o 'digest=[0-9a-f]*')"
SERVE_OUT="$(python -m apex_tpu.testing.standalone_gpt --serve \
    --requests 5 --new-tokens 6 --prefix-share --supervise \
    --journal "$SERVE_DIR/crash.journal.jsonl" \
    --jsonl "$SERVE_DIR/crash.jsonl" --fault crash@3)"
echo "$SERVE_OUT"
echo "$SERVE_OUT" | grep -q "restarts=1" \
    || { echo "[ci] FAIL: supervised serve did not restart once"; exit 1; }
echo "$SERVE_OUT" | grep -Eq "replayed=[1-9]" \
    || { echo "[ci] FAIL: journal replay re-entered no requests"; exit 1; }
echo "$SERVE_OUT" | grep -Eq "prefix_hit_tokens=[1-9]" \
    || { echo "[ci] FAIL: replay readmission did not hit warm"; exit 1; }
[ "$(grep -c '"name":"request_submitted"' "$SERVE_DIR/crash.jsonl")" = 5 ] \
    || { echo "[ci] FAIL: crash leg expected 5 submits (no double-submit on replay)"; exit 1; }
[ "$(grep -c '"name":"request_done"' "$SERVE_DIR/crash.jsonl")" = 5 ] \
    || { echo "[ci] FAIL: crash leg expected exactly 5 terminal events"; exit 1; }
CRASH_DIGEST="$(echo "$SERVE_OUT" | grep -o 'digest=[0-9a-f]*')"
[ -n "$REF_DIGEST" ] && [ "$CRASH_DIGEST" = "$REF_DIGEST" ] \
    || { echo "[ci] FAIL: recovered digest $CRASH_DIGEST != uninterrupted $REF_DIGEST"; exit 1; }
python tools/trace_check.py "$SERVE_DIR/crash.jsonl" --serve
# leg 5 (ISSUE-13): watchdog stall -> snapshot-then-drain — the
# injected 1.5 s stall at tick 2 outlasts the 0.5 s watchdog timeout;
# the serve escalation policy must dump exactly ONE engine_snapshot
# (reason escalation:stall) and drain cleanly instead of ignoring the
# wedged decode: every request terminal preempted, chains complete.
SERVE_OUT="$(python -m apex_tpu.testing.standalone_gpt --serve \
    --requests 4 --new-tokens 24 --jsonl "$SERVE_DIR/stall.jsonl" \
    --fault stall@2:1.5 --stall-timeout 0.5)"
echo "$SERVE_OUT"
echo "$SERVE_OUT" | grep -q "drained=1" \
    || { echo "[ci] FAIL: stalled serve did not drain"; exit 1; }
[ "$(grep -c '"name":"engine_snapshot"' "$SERVE_DIR/stall.jsonl")" = 1 ] \
    || { echo "[ci] FAIL: expected exactly one escalation snapshot"; exit 1; }
grep -q '"reason":"escalation:stall"' "$SERVE_DIR/stall.jsonl" \
    || { echo "[ci] FAIL: snapshot not attributed to the stall escalation"; exit 1; }
grep -q '"name":"escalation_drain"' "$SERVE_DIR/stall.jsonl" \
    || { echo "[ci] FAIL: no escalation_drain event"; exit 1; }
python tools/trace_check.py "$SERVE_DIR/stall.jsonl" --serve
rm -rf "$SERVE_DIR"

echo "[ci] 11/18 SPMD sharding audit (--check-sharding) + topology drift"
# Compile every plan-carrying multichip entry under its mesh on the
# same 8-device host-platform trick the multichip tests use; fails on
# APX701-703 findings, per-device-memory drift vs the committed
# tools/sharding_baseline.json, and stale sharding_findings.txt
# suppressions (the linter-baseline semantics).  Then prove the
# committed MULTICHIP_TOPOLOGY.json still matches the canonical
# MeshPlan constructors — a topology change must be a reviewed diff.
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.analysis --check-sharding
python __graft_entry__.py --plans 8

echo "[ci] 12/18 fleet serving smoke (multi-replica + swap + disagg + crash replay)"
FLEET_DIR="$(mktemp -d -t apex_tpu_fleet.XXXXXX)"
# leg 1: sanitized 2-replica fleet with ONE rolling weight swap
# mid-serve — zero lost requests fleet-wide, zero compiles after
# warmup (the swap keeps every AOT-compiled ladder bucket), and the
# merged per-replica logs prove N submitted => N terminal
FLEET_OUT="$(XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.testing.standalone_gpt --serve-fleet \
    --replicas 2 --requests 8 --new-tokens 4 --swap --sanitize \
    --jsonl-dir "$FLEET_DIR/swap")"
echo "$FLEET_OUT"
echo "$FLEET_OUT" | grep -q "swaps=2" \
    || { echo "[ci] FAIL: rolling swap did not cover both replicas"; exit 1; }
echo "$FLEET_OUT" | grep -q "lost=0" \
    || { echo "[ci] FAIL: rolling swap lost requests"; exit 1; }
echo "$FLEET_OUT" | grep -q "done=8" \
    || { echo "[ci] FAIL: fleet did not finish all 8 requests"; exit 1; }
python tools/trace_check.py "$FLEET_DIR"/swap/serve-r0.jsonl \
    "$FLEET_DIR"/swap/serve-r1.jsonl --serve
python tools/monitor_summary.py "$FLEET_DIR"/swap/serve-r0.jsonl \
    "$FLEET_DIR"/swap/serve-r1.jsonl
# leg 2: disaggregated prefill/decode — a prefill-role replica runs
# the prompts and streams finished KV blocks into the decode
# replica's pool; every decode-side admission must land WARM
FLEET_OUT="$(XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.testing.standalone_gpt --serve-fleet \
    --replicas 1 --disaggregate --requests 5 --new-tokens 4 \
    --jsonl-dir "$FLEET_DIR/disagg")"
echo "$FLEET_OUT"
echo "$FLEET_OUT" | grep -Eq "handoffs=[1-9]" \
    || { echo "[ci] FAIL: no KV handoffs in the disaggregated leg"; exit 1; }
echo "$FLEET_OUT" | grep -Eq "prefix_hit_tokens=[1-9]" \
    || { echo "[ci] FAIL: disaggregated admissions did not land warm"; exit 1; }
echo "$FLEET_OUT" | grep -q "lost=0" \
    || { echo "[ci] FAIL: disaggregated leg lost requests"; exit 1; }
python tools/trace_check.py "$FLEET_DIR"/disagg/serve-*.jsonl --serve
# leg 3: replica crash + journal replay — replica r0 crashes at tick
# 2, recovers in place (crash_reset + replay of every non-terminal
# rid), and the fleet still completes every submitted request
FLEET_OUT="$(XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.testing.standalone_gpt --serve-fleet \
    --replicas 2 --requests 8 --new-tokens 6 --fault crash@2 \
    --journal-dir "$FLEET_DIR/journals" \
    --jsonl-dir "$FLEET_DIR/crash")"
echo "$FLEET_OUT"
echo "$FLEET_OUT" | grep -Eq "restarts=[1-9]" \
    || { echo "[ci] FAIL: crashed replica did not restart"; exit 1; }
echo "$FLEET_OUT" | grep -Eq "replayed=[1-9]" \
    || { echo "[ci] FAIL: journal replay re-entered no requests"; exit 1; }
echo "$FLEET_OUT" | grep -q "lost=0" \
    || { echo "[ci] FAIL: crash leg lost requests"; exit 1; }
echo "$FLEET_OUT" | grep -q "done=8" \
    || { echo "[ci] FAIL: crash leg did not finish all 8 requests"; exit 1; }
python tools/trace_check.py "$FLEET_DIR"/crash/serve-*.jsonl --serve
rm -rf "$FLEET_DIR"

echo "[ci] 13/18 host-concurrency audit (--check-concurrency) + schedule stress"
# static half: APX801-805 over the whole package against the
# committed EMPTY baseline (a stale entry fails like the linter's)
python -m apex_tpu.analysis --check-concurrency
# dynamic half: the same request trace under 5 permuted thread
# interleavings — identical terminal digest, zero lost requests,
# zero uncaught background-thread exceptions
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.analysis.schedule --seeds 5 --replicas 2 \
    --requests 6 --new-tokens 4

echo "[ci] 14/18 Q8 quantized serving smoke (int8 weight-only decode)"
# kernel half: the quant matmul's interpret-mode parity sweep — GEMV
# and tiled paths vs the jnp twin, plus the zero-channel round-trip
python -c "from apex_tpu.ops import quant_matmul; \
quant_matmul.self_check()"
# serve half: a sanitized --policy Q8 serve — weights quantized to
# per-channel int8 before the engine builds, the same pinned ladder
# AOT-compiles (1 decode bucket + 1 prefill = 2 programs), and the
# post-warmup recompile budget stays ZERO with tokens flowing
Q8_OUT="$(APEX_TPU_SERVE_BATCH_BUCKETS=2 \
    APEX_TPU_SERVE_PAGE_BUCKETS=2 \
    python -m apex_tpu.testing.standalone_gpt --serve --requests 3 \
    --new-tokens 3 --policy Q8 --sanitize)"
echo "$Q8_OUT"
echo "$Q8_OUT" | grep -q "requests=3 " \
    || { echo "[ci] FAIL: Q8 serve did not finish all 3 requests"; exit 1; }
echo "$Q8_OUT" | grep -q "compiles=2 " \
    || { echo "[ci] FAIL: Q8 serve broke the one-compile-per-bucket ladder"; exit 1; }
echo "$Q8_OUT" | grep -Eq "tokens_s=[1-9]" \
    || { echo "[ci] FAIL: Q8 serve reported zero tokens/s"; exit 1; }

echo "[ci] 15/18 live metrics plane (exporter + /healthz flip + SLO burn)"
METRICS_DIR="$(mktemp -d -t apex_tpu_metrics.XXXXXX)"
METRICS_PORT=$((19300 + RANDOM % 500))
# leg 1: sanitized 2-replica fleet with the exporter attached — the
# probe (started first, stdlib urllib only) scrapes /metrics while
# the fleet serves; the last exposition document must carry the
# per-replica labeled tokens counter AND the fleet queue-depth gauge
python tools/metrics_probe.py --port "$METRICS_PORT" \
    --out "$METRICS_DIR/fleet" --timeout 600 &
PROBE_PID=$!
FLEET_OUT="$(XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.testing.standalone_gpt --serve-fleet \
    --replicas 2 --requests 8 --new-tokens 4 --sanitize \
    --jsonl-dir "$METRICS_DIR/fleet-logs" \
    --metrics-port "$METRICS_PORT" --metrics-linger 1)"
echo "$FLEET_OUT"
wait "$PROBE_PID" \
    || { echo "[ci] FAIL: metrics probe never scraped the fleet"; exit 1; }
grep -Eq 'apex_tpu_serve_tokens_total\{replica="r0"\} [1-9]' \
    "$METRICS_DIR/fleet/metrics.last" \
    || { echo "[ci] FAIL: no per-replica labeled tokens counter in /metrics"; exit 1; }
grep -q '^apex_tpu_fleet_queue_depth ' \
    "$METRICS_DIR/fleet/metrics.last" \
    || { echo "[ci] FAIL: no fleet queue-depth gauge in /metrics"; exit 1; }
python tools/trace_check.py "$METRICS_DIR"/fleet-logs/serve-*.jsonl --serve
# leg 2: /healthz drain flip — a SIGTERM-drained serve must publish
# the drain before teardown; the probe's status-change log must show
# the operator-visible 200 -> 503 transition
python tools/metrics_probe.py --port "$METRICS_PORT" \
    --out "$METRICS_DIR/drain" --timeout 600 &
PROBE_PID=$!
SERVE_OUT="$(python -m apex_tpu.testing.standalone_gpt --serve \
    --requests 6 --new-tokens 8 --fault sigterm@2 \
    --metrics-port "$METRICS_PORT" --metrics-linger 1)"
echo "$SERVE_OUT"
wait "$PROBE_PID" \
    || { echo "[ci] FAIL: metrics probe never scraped the drain leg"; exit 1; }
grep -q '^200 ' "$METRICS_DIR/drain/healthz.log" \
    || { echo "[ci] FAIL: /healthz never reported healthy"; exit 1; }
grep -q '^503 .*"draining": true' "$METRICS_DIR/drain/healthz.log" \
    || { echo "[ci] FAIL: /healthz did not flip to 503 on the drain"; exit 1; }
# leg 3: forced SLO breach — an absurd TTFT objective trips the
# multi-window burn tracker: exactly ONE slo_burn episode through
# the alarm machinery, surfaced in SERVE_DONE, trace-checked back to
# its objective definition, and rendered by monitor_summary
SLO_OUT="$(APEX_TPU_SLO_TTFT_P99_MS=0.001 \
    python -m apex_tpu.testing.standalone_gpt --serve --requests 6 \
    --new-tokens 6 --jsonl "$METRICS_DIR/slo.jsonl")"
echo "$SLO_OUT"
echo "$SLO_OUT" | grep -q "slo_burns=1" \
    || { echo "[ci] FAIL: forced SLO breach did not emit exactly one burn episode"; exit 1; }
[ "$(grep -c '"name":"slo_burn"' "$METRICS_DIR/slo.jsonl")" = 1 ] \
    || { echo "[ci] FAIL: expected exactly one slo_burn alarm in the JSONL"; exit 1; }
grep -q '"name":"slo_objectives"' "$METRICS_DIR/slo.jsonl" \
    || { echo "[ci] FAIL: no slo_objectives definition event"; exit 1; }
python tools/trace_check.py "$METRICS_DIR/slo.jsonl" --serve
python tools/monitor_summary.py "$METRICS_DIR/slo.jsonl" \
    | grep "SLO: 1 burn episode" \
    || { echo "[ci] FAIL: monitor_summary did not render the SLO section"; exit 1; }
rm -rf "$METRICS_DIR"

echo "[ci] 16/18 process-isolated fleet (kill -9 drill + journal replay + autoscale trace)"
CP_DIR="$(mktemp -d -t apex_tpu_cp.XXXXXX)"
# leg 1: the uninterrupted 2-process reference — every replica is a
# supervised subprocess behind the socket control plane; its digest
# is the bar the kill-9 leg must reproduce token-identically
REF_OUT="$(XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.testing.standalone_gpt --serve-fleet --procs \
    --replicas 2 --requests 4 --new-tokens 3 --fleet-hidden 16 \
    --fleet-layers 1 --decode-reference \
    --journal-dir "$CP_DIR/ref-journals")"
echo "$REF_OUT"
echo "$REF_OUT" | grep -q "lost=0" \
    || { echo "[ci] FAIL: reference process fleet lost requests"; exit 1; }
echo "$REF_OUT" | grep -q "done=4 " \
    || { echo "[ci] FAIL: reference process fleet did not finish all 4 requests"; exit 1; }
REF_DIGEST="$(echo "$REF_OUT" | grep -Eo 'digest=[0-9a-f]+' | head -1)"
# leg 2: the kill -9 drill — replica r0's engine process is
# SIGKILL'd at its 2nd decode step (no handler can run), the
# supervisor reaps it, respawns with replay, and the fresh process
# re-enters every non-terminal rid from the on-disk journal; the
# fleet digest must equal the uninterrupted run's — exactly-once
# across a hard process death
KILL_OUT="$(XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.testing.standalone_gpt --serve-fleet --procs \
    --replicas 2 --requests 4 --new-tokens 3 --fleet-hidden 16 \
    --fleet-layers 1 --decode-reference --fault kill9@2 \
    --journal-dir "$CP_DIR/kill-journals" \
    --jsonl-dir "$CP_DIR/kill-logs")"
echo "$KILL_OUT"
echo "$KILL_OUT" | grep -Eq "restarts=[1-9]" \
    || { echo "[ci] FAIL: kill -9'd replica did not restart"; exit 1; }
echo "$KILL_OUT" | grep -Eq "replayed=[1-9]" \
    || { echo "[ci] FAIL: journal replay re-entered no requests after kill -9"; exit 1; }
echo "$KILL_OUT" | grep -q "lost=0" \
    || { echo "[ci] FAIL: kill -9 leg lost requests"; exit 1; }
echo "$KILL_OUT" | grep -q "done=4 " \
    || { echo "[ci] FAIL: kill -9 leg did not finish all 4 requests"; exit 1; }
echo "$KILL_OUT" | grep -q "$REF_DIGEST" \
    || { echo "[ci] FAIL: kill -9 digest differs from the uninterrupted run"; exit 1; }
# the supervisor + per-replica child logs must pass the distributed
# lifecycle checks: every spawned (replica, incarnation) reaped
# exactly once, N submitted => N terminal fleet-wide across the crash
python tools/trace_check.py "$CP_DIR"/kill-logs/*.jsonl --serve
# leg 3: autoscale — a 1-replica floor under a 10-request burst must
# scale up on the backlog trend and render the autoscale event trace
# in monitor_summary (drain-then-reap scale-down is exercised by the
# fleet teardown path and asserted via the spawn/reap pairing above)
SCALE_OUT="$(XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.testing.standalone_gpt --serve-fleet --procs \
    --replicas 1 --autoscale 1:2 --requests 10 --new-tokens 3 \
    --fleet-hidden 16 --fleet-layers 1 --decode-reference \
    --jsonl-dir "$CP_DIR/scale-logs")"
echo "$SCALE_OUT"
echo "$SCALE_OUT" | grep -Eq "autoscale_ups=[1-9]" \
    || { echo "[ci] FAIL: autoscale never scaled up under the burst"; exit 1; }
echo "$SCALE_OUT" | grep -q "lost=0" \
    || { echo "[ci] FAIL: autoscale leg lost requests"; exit 1; }
python tools/monitor_summary.py "$CP_DIR"/scale-logs/*.jsonl \
    | grep -q "autoscale trace" \
    || { echo "[ci] FAIL: monitor_summary did not render the autoscale trace"; exit 1; }
rm -rf "$CP_DIR"

echo "[ci] 17/18 expert-parallel serving smoke (MoE decode fast path)"
# kernel half: the fused routing kernel's interpret-mode parity sweep
# — Pallas top-k route/dispatch vs the jnp twin, keep/slot bit-exact
python -c "from apex_tpu.ops import moe_routing; \
moe_routing.self_check()"
# serve half: a sanitized --ep 2 serve over 2 host devices — the MLPs
# expand to a 4-expert Switch MoE, expert stacks shard, the pinned
# ladder AOT-compiles (1 decode bucket + 1 prefill = 2 programs), and
# the post-warmup recompile budget stays ZERO with tokens flowing
# through the overlapped exchange
EP_OUT="$(APEX_TPU_SERVE_BATCH_BUCKETS=2 \
    APEX_TPU_SERVE_PAGE_BUCKETS=2 \
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    python -m apex_tpu.testing.standalone_gpt --serve --requests 3 \
    --new-tokens 3 --ep 2 --moe-experts 4 --sanitize)"
echo "$EP_OUT"
echo "$EP_OUT" | grep -q "requests=3 " \
    || { echo "[ci] FAIL: EP serve did not finish all 3 requests"; exit 1; }
echo "$EP_OUT" | grep -q "compiles=2 " \
    || { echo "[ci] FAIL: EP serve broke the one-compile-per-bucket ladder"; exit 1; }
echo "$EP_OUT" | grep -Eq "tokens_s=[1-9]" \
    || { echo "[ci] FAIL: EP serve reported zero tokens/s"; exit 1; }

echo "[ci] 18/18 wire-protocol audit (--check-protocol)"
# the APX9xx family: serving/ + resilience/ audited against the
# declared ProtocolSpec registry — the baseline is committed EMPTY
# (every finding at introduction was fixed), so any output here is a
# new drift between the parent and child sides of the control plane
python -m apex_tpu.analysis --check-protocol

echo "[ci] all green"
