#!/usr/bin/env bash
# ci_gate.sh — the single pre-merge entry point (README "CI gate").
#
# Runs the repo's whole verification ladder in order, cheapest first,
# with a DISTINCT exit code per stage so a red CI run names its stage
# without log spelunking:
#
#   stage 1  full audit   `python -m tools.lint`            exit 10
#            (static SGL rules + conclint thread-model gate + proclint
#             process-mesh/RPC-protocol gate + HLO structure gate +
#             cost gate over
#             the EIGHT flagship programs — train_step, train_step_dp2,
#             train_step_dp2_int8 (the int8-ring wire-bytes win,
#             COST005-gated vs the f32 DP baseline), prefill_chunk,
#             decode, verify (the speculative verify-k round),
#             handoff_gather (the disagg tier's KV handoff source), and
#             decode_int8 (the int8-KV-arena decode) —
#             one shared lowering, tools/lint/{rules,hlo,cost}.py)
#   stage 2  records      `python -m tools.lint --records`  exit 11
#            (telemetry/record store validation incl. the extended
#             hlo_audit cost numerics, the wire-byte pair on
#             train_run/bench records, and flight_ref dump targets)
#   stage 3  obsq smoke   `python -m tools.obsq slo --check` exit 12
#            (the trace query layer reproduces a committed serve_load
#             fixture's TTFT p50/p99 + tokens/s from raw trace events —
#             guards the event schema obsq and the autotuner consume)
#   stage 4  disagg smoke `python -m tools.loadgen --disagg-smoke`
#            exit 13 (a tiny 1:1 prefill/decode tier serves 8 requests
#             with greedy streams asserted IDENTICAL to a single-engine
#             ServeEngine run — the KV handoff path end to end)
#   stage 5  spec smoke   `python -m tools.loadgen --spec-smoke`
#            exit 14 (self-speculation verify-k streams asserted
#             IDENTICAL to generate() and a plain engine, accept rate
#             asserted 1.0 — the speculative decode path end to end)
#   stage 6  spill smoke  `python -m tools.loadgen --spill-smoke`
#            exit 16 (a shrunk arena under churn spills shared-prefix
#             blocks to host RAM, a re-hit restores them, and both
#             streams are asserted IDENTICAL to generate() — the KV
#             spill/prefetch tier end to end, spill + restore counters
#             asserted nonzero)
#   stage 7  mp smoke     `python -m tools.loadgen --mp-smoke`
#            exit 17 (a 2-PROCESS 1:1 tier — each worker a ServeEngine
#             in its own OS process behind the serve.net framed RPC —
#             serves 6 requests with greedy streams asserted IDENTICAL
#             to a single in-process engine, with at least one KV
#             handoff over the digest-checked wire codec — process
#             spawn, the wire transport, and donated-scatter injection
#             end to end)
#   stage 8  chaos smoke  `python -m tools.chaosd --smoke`   exit 18
#            (a fixed-seed self-healing campaign against a 2-process
#             1:1 tier: one worker SIGKILLed and one SIGSTOPped
#             mid-stream — both deaths detected (crash AND hang),
#             every stream completes bitwise vs the single-engine
#             reference, both replacements respawned and adopted, and
#             no orphan worker process survives the run)
#   stage 9  autotune     `python -m tools.autotune smoke` + the
#            table-resolved consumers, exit 15
#            (committed best.json + autotune_sweep records validate —
#             incl. the stale-schema_version guard — then a real
#             2-point sweep -> fit -> table round-trip in a temp
#             store, then tools/loadgen.py and bench.py --serve run
#             END TO END with table-resolved arena knobs, no store
#             writes)
#   stage 10 tier-1 tests  the ROADMAP.md tier-1 command     exit 20
#
# Exit 0 = every stage green.  Intentional compiled-program changes are
# re-baselined first via `python -m tools.lint --hlo --update-baselines`
# (review the printed metric diff in the PR).
set -u -o pipefail
cd "$(dirname "$0")/.."

echo "== ci_gate stage 1/10: full audit (static + HLO structure + cost) =="
JAX_PLATFORMS=cpu python -m tools.lint || exit 10

echo "== ci_gate stage 2/10: record validation =="
JAX_PLATFORMS=cpu python -m tools.lint --records || exit 11

echo "== ci_gate stage 3/10: obsq SLO smoke (trace-derived vs committed fixture) =="
JAX_PLATFORMS=cpu python -m tools.obsq slo --check \
    --records tests/data/obsq/records.jsonl \
    --events tests/data/obsq/events.jsonl || exit 12

echo "== ci_gate stage 4/10: disagg smoke (1:1 tier streams == single engine) =="
JAX_PLATFORMS=cpu python -m tools.loadgen --disagg-smoke || exit 13

echo "== ci_gate stage 5/10: spec smoke (self-speculation streams == generate()) =="
JAX_PLATFORMS=cpu python -m tools.loadgen --spec-smoke || exit 14

echo "== ci_gate stage 6/10: spill smoke (spill/restore streams == generate()) =="
JAX_PLATFORMS=cpu python -m tools.loadgen --spill-smoke || exit 16

echo "== ci_gate stage 7/10: mp smoke (2-process tier streams == single engine) =="
JAX_PLATFORMS=cpu python -m tools.loadgen --mp-smoke || exit 17

echo "== ci_gate stage 8/10: chaos smoke (1 kill + 1 hang, streams bitwise, respawn) =="
JAX_PLATFORMS=cpu python -m tools.chaosd --smoke || exit 18

echo "== ci_gate stage 9/10: autotune smoke (sweep -> fit -> table -> consumers) =="
JAX_PLATFORMS=cpu python -m tools.autotune smoke || exit 15
JAX_PLATFORMS=cpu python -m tools.loadgen --requests 6 --rate 50 \
    --no-record || exit 15
JAX_PLATFORMS=cpu python bench.py --serve --no-record || exit 15

echo "== ci_gate stage 10/10: tier-1 test suite (ROADMAP.md budget) =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"
[ "$rc" -eq 0 ] || exit 20

echo "== ci_gate: all stages green =="
