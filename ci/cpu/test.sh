#!/usr/bin/env bash
# CPU test run (analog of ci/cpu/*): the fast SWAR kernel-parity shard
# first (packed-vs-int32 on small shapes, CPU mesh — a packed-path
# regression fails tier-1 before anything slow runs), then the full
# suite on the 8-virtual-device mesh, then the CPU-path CLI golden
# byte-diff.
set -e
cd "$(dirname "$0")/../.."
# ONE consolidated graftlint gate (fail-fast, cheapest): the linter's
# fixture-based self-tests, then a single repo-wide run with all 21
# rules — tracer leaks, unguarded SWAR entry points, swallowed
# exceptions, rogue env flags, host syncs, span discipline, the
# round-15 concurrency/durability pack (lock-discipline,
# blocking-under-lock, atomic-write-discipline, thread-lifecycle,
# scope-discipline), the round-18 compile-surface pack
# (jit-shape-hazard, dtype-drift, jit-in-loop, warmup-coverage,
# host-transfer-in-jit) and the round-22 contract pack
# (metric-registry, span-registry, fault-site-registry,
# schema-coherence, state-transition against racon_tpu/contracts.py).
# Zero unsuppressed findings is a hard gate; the machine-readable
# findings land in a CI artifact file so rule regressions are diffable
# across runs, and --timings echoes the per-rule cost so a budget
# regression names its rule in the log (budget: < 30 s on this repo).
lint_t0=$SECONDS
python -m tools.analysis --selftest
python -m tools.analysis --quiet --timings \
  --json /tmp/graftlint_findings.json \
  racon_tpu tests tools
echo "graftlint gate (selftest + repo-wide, 21 rules): $((SECONDS - lint_t0))s (budget 30s; artifact /tmp/graftlint_findings.json)"
# the README env-flags table (racon_tpu/flags.py) and the README lint
# rule table (tools/analysis --rules-md) are generated and must not
# drift
python -m racon_tpu.flags --check-readme README.md
python -m tools.analysis --check-readme README.md
python -m pytest tests/test_ops_swar.py -q
# runtime-sanitizer shard: the SWAR parity suite re-runs with shadow
# execution + canaries armed (every chunk sampled), plus the seeded
# fault/stall tests proving both sanitizer halves fire
RACON_TPU_SANITIZE=1 RACON_TPU_SANITIZE_SAMPLE=1 \
  python -m pytest tests/test_ops_swar.py tests/test_sanitize.py \
  tests/test_graftlint.py -q
# columnar host-init shard (fail-fast, same pattern as the SWAR shard):
# vectorized-vs-legacy window/layer parity, the native breaking-points
# decoder, and the pipelined run() — including the num_threads=1
# sequential-fallback smoke — before anything slow runs
python -m pytest tests/test_columnar_init.py tests/test_window.py -q
# first-party overlapper shard (fail-fast, round 20; the consolidated
# graftlint gate above covers racon_tpu/ops/overlap_seed.py +
# chain.py): minimizer/chain kernel-vs-numpy-oracle parity, the slice-
# boundary dedup, freq-cap accounting, the warm-up cache claim, and
# the --overlaps auto determinism contract —
# byte-identical across thread counts, --shards 2, and gz/FASTQ/FASTA
# input variants — plus the planner/rampler no-overlaps-file cases
python -m pytest tests/test_overlapper.py -q
# ragged-packing shard (fail-fast, round 10): the {padded,ragged} x
# {scatter,matmul} byte-identity grid — and the same grid again under
# the runtime sanitizer, so the int32 shadow path proves itself on the
# packed ragged layout (lint coverage now rides in the consolidated
# top-of-file gate)
python -m pytest tests/test_ragged.py -q
RACON_TPU_SANITIZE=1 RACON_TPU_SANITIZE_SAMPLE=1 \
  python -m pytest tests/test_ragged.py -q
# alignment-occupancy shard (fail-fast, round 17): the {bucketed,
# ragged} x {fixed-band, ladder} byte-identity grid for the ALIGNER —
# ragged pair packing (_AlignStream), the adaptive band ladder with
# escalation re-batching, stream-feed invariance, OOM reduce_capacity
# re-dispatch parity, the align warm-up cache claim and the
# align.dispatch stall ladder walk — then again under the sanitizer so
# the int32 shadow leg proves the SWAR-packed walk kernel
python -m pytest tests/test_align_stream.py -q
RACON_TPU_SANITIZE=1 RACON_TPU_SANITIZE_SAMPLE=1 \
  python -m pytest tests/test_align_stream.py -q
# streaming shard-run smoke (fail-fast): the invariance suite —
# including the 2-shard/3-shard byte-identity checks and the
# SIGKILL-then---resume round trip — before anything slow runs
python -m pytest tests/test_exec.py -q
# fault-tolerance shard (fail-fast, round 12): lease claim/expiry/
# reclaim races, per-class ladder transitions (backoff /
# OOM-backpressure re-dispatch parity / stall escalation /
# quarantine), part CRC verification + re-queue, run-report faults
# schema, and the 2-worker chaos soak (seeded SIGKILL + injected
# faults, byte-identical merge)
python -m pytest tests/test_faults.py -q
# concurrency shard (round 15): the exec/serve chaos soaks re-run with
# the sanitizer armed — the named locks become WitnessedLocks, the
# lock-order witness records the acquisition graph across every chip-
# worker/lease-keeper/socket-handler thread (and the soaks' SIGKILLed
# subprocesses), and any cycle reports at exit.  Round 16 added the
# serve kill/restart soak, so the witness also covers the journal
# (serve.journal) and supervision locks.  Round 23 adds the fleet
# chaos pair — the preemption drain and the kill-a-host migration
# soak — so the witness also covers the gateway's fleet.state lock
# against its placer/collector/beacon threads.
RACON_TPU_SANITIZE=1 python -m pytest tests/test_faults.py \
  tests/test_serve.py tests/test_serve_recovery.py \
  tests/test_fleet.py -q \
  -k "chaos or racing or concurrent"
# multi-chip execution shard (fail-fast, round 13): the topology/
# planner/chip-scheduler suite — get_mesh prefix selection,
# distributed_init idempotence, device-aware planning (LPT over chips
# + mesh marking), the 8-fake-device single-invocation byte-identity
# run with per-device report rows, the persistent-compile-cache round
# trip and the ragged stream-geometry warm-up — plus the existing mesh
# parity suite
python -m pytest tests/test_topology.py tests/test_parallel.py -q
# resident-service shard (fail-fast, round 14): protocol round-trip,
# three concurrent jobs byte-identical to their one-shot CLI runs,
# admission rejects-with-reason, the per-job fault ladder with server
# survival, job-scoped metrics disjointness (the clear_run fix) and
# the warm-path compile-amortization claim on the device engine
python -m pytest tests/test_serve.py -q
# fleet-serving shard (fail-fast, round 23): the multi-tenant gateway
# — newline-JSON protocol parity with serve (submit grows
# tenant/priority), weighted-fair stride scheduling with per-tenant
# budgets, lease-backed placement across registered hosts, durable
# journal accept-before-ack + restart recovery from spool, the
# fleet.place/gateway.accept fault sites, priority preemption that
# DRAINS the victim (never kills), and the kill-a-host migration soak
# with byte-identity against the one-shot CLI
python -m pytest tests/test_fleet.py -q
# crash-safe serving shard (fail-fast, round 16): the kill-server
# chaos soak (SIGKILL mid-batch under RACON_TPU_FAULTS=server.kill,
# restart from the same --serve-dir — byte-identical results, zero
# duplicate polishing, v5 recovery counts), restart recovery from
# spool/queue, idempotent double-submit, journal compaction size
# bound + torn-tail replay, spool-corruption re-queue, slot-death
# supervision/quarantine, the drain protocol and the retrying client
python -m pytest tests/test_serve_recovery.py -q
# observability shard (fail-fast, round 11): trace schema,
# RACON_TPU_TRACE byte-identity, disabled-span overhead guard,
# run-report schema validation for CLI and exec runs
python -m pytest tests/test_obs.py -q
# compile-surface runtime shard (fail-fast, round 18): forced-retrace
# attribution names the compiling (function, shape signature, phase),
# the absorbed serve compile_s listener's scoped semantics, the
# schema-v7 `compiles` section and the seal/violation bookkeeping
# (the sanitized serve warm-path acceptance test itself rides at the
# end of the resident-service shard — it must trace AFTER that
# shard's cold-retrace asserts)
python -m pytest tests/test_compile_surface.py -q
# contracts shard (fail-fast, round 22): the registry selfcheck, the
# lifecycle state machines, the v11 validator round-trip over all
# three report kinds from a real polish (zero validator-defaulted
# keys among exercised sections), the sanitize exit audit and the
# analyzer's --rules-md/--changed-only surfaces
python -m pytest tests/test_contracts.py -q
# catch-all (every file without a dedicated shard above) runs with the
# tier-1 slow filter: @pytest.mark.slow tests only execute in the
# per-file shards that name them, never silently in the budget run
python -m pytest tests/ -x -q -m "not slow" --ignore=tests/test_ops_swar.py \
  --ignore=tests/test_columnar_init.py --ignore=tests/test_window.py \
  --ignore=tests/test_exec.py --ignore=tests/test_ragged.py \
  --ignore=tests/test_align_stream.py \
  --ignore=tests/test_obs.py --ignore=tests/test_faults.py \
  --ignore=tests/test_serve.py --ignore=tests/test_serve_recovery.py \
  --ignore=tests/test_topology.py --ignore=tests/test_parallel.py \
  --ignore=tests/test_compile_surface.py --ignore=tests/test_overlapper.py \
  --ignore=tests/test_contracts.py --ignore=tests/test_fleet.py
# native core under ASan/UBSan (bp thread-pool decoder + streaming gzip
# parser); self-skips when the toolchain lacks the ASan runtime
bash ci/checks/native_sanitize.sh
DATA=/root/reference/test/data
# golden byte-diff WITH tracing on: --trace must not perturb a single
# output byte, and the emitted run_report.json must validate against
# its schema (the trace itself is sanity-checked for JSON-ness)
python -m racon_tpu -t 8 --trace /tmp/ci_cpu_trace.json \
  --run-report /tmp/ci_cpu_report.json \
  "$DATA/sample_reads.fastq.gz" "$DATA/sample_overlaps.paf.gz" \
  "$DATA/sample_layout.fasta.gz" > /tmp/ci_cpu_out.fasta
cmp /tmp/ci_cpu_out.fasta tests/data/golden_lambda_fastq_paf.fasta
python -m racon_tpu.obs --check /tmp/ci_cpu_report.json
python -c "import json; json.load(open('/tmp/ci_cpu_trace.json'))"
echo "cpu golden (traced): OK"
