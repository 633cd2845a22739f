#!/usr/bin/env bash
# Run the bench.py measurement set on the attached TPU chip; each line
# of bench output is one JSON record. Not run on the current
# installation (CHANGES.md, PR 21); ROADMAP D1 shrinks this to the one
# benchmark command.
#
# Usage: bash scripts/record_baselines.sh [outfile]
set -uo pipefail
cd "$(dirname "$0")/.."
OUT="${1:-/tmp/baselines_$(date +%s).jsonl}"

run() {
  local label="$1"; shift
  echo "== $label: $*" | tee -a "$OUT.log"
  if timeout 1800 "$@" >> "$OUT" 2>> "$OUT.log"; then
    tail -1 "$OUT"
  else
    # JSON-shaped marker: $OUT stays line-parseable AND failed runs
    # (possibly with partial records above) are flagged in-band
    # leading newline: a SIGTERM'd bench can leave $OUT mid-line
    printf '\n{"failed": "%s", "log": "%s"}\n' "$label" "$OUT.log" | tee -a "$OUT" >&2
  fi
}

# driver-identical default (0.69B proxy, full remat) + the dots A/B
run proxy-full  python bench.py
run proxy-dots  env BENCH_REMAT=dots python bench.py

# BASELINE.json configs at full family dims on one chip
run qlora8b        env BENCH_MODE=qlora8b python bench.py
run mistral7b-lora env BENCH_MODE=mistral7b-lora python bench.py
run gemma2-4k      env BENCH_MODE=gemma2-4k python bench.py
run seq4k          env BENCH_MODE=seq4k python bench.py
run moe            env BENCH_MODE=moe python bench.py
run qwen2-lora     env BENCH_MODE=qwen2-lora python bench.py
run decode         env BENCH_MODE=decode python bench.py

# continuous-batching serving A/B (serve/engine.py): engine across
# MAX_BATCH slots vs serial batch-1 greedy over the same request set,
# + p50/p99 per-token latency, batch occupancy, decode StepCostReport.
# The same run records the multi-tenant arm (mixed batched-LoRA batch
# vs per-adapter serial engines — bitwise, recompile-free, >=1.3x
# asserted, pool hit/miss/evict counters) and the speculative arm
# (self-draft SPEC_K=4 vs plain — bitwise, iteration reduction +
# acceptance rate)
run serve          env BENCH_MODE=serve python bench.py

# overlap execution path A/B (train/overlap.py, plan knob OVERLAP):
# OVERLAP=off vs =manual through the real make_train_step — the record
# asserts bitwise-identical loss streams and carries each arm's
# scheduled-HLO overlap evidence (overlap_frac / exposed collective
# bytes), the half of the claim that survives a dead backend
run overlap        env BENCH_MODE=overlap python bench.py

# DCN gradient-sync A/B (parallel/hierarchical.py, plan knobs
# DCN_SYNC/DCN_COMPRESS) on the emulated 2-slice hybrid mesh (re-execs
# onto the canonical 8-fake-device CPU mesh): flat vs hier cross-slice
# reduction — the record asserts bitwise-identical loss streams and
# carries each arm's ici_bytes/dcn_bytes/overlap_frac; value = the
# DCN traffic shrink factor (~= ici_size)
run dcn            env BENCH_MODE=dcn python bench.py

# autotune default-vs-tuned A/B (autotune/, re-execs onto the canonical
# 8-fake-device CPU mesh): cost-model search over the tiny_fsdp8 base
# plan; the record carries the winner diff, per-arm StepCostReport +
# exposed bytes + plan fingerprints, and both arms' real loss streams
# (tuned trajectory asserted valid against the default's shape);
# value = modeled step-time improvement
run autotune       env BENCH_MODE=autotune python bench.py

# fault-tolerance drill: time-to-recover (injected kill -> first
# post-resume step) + checkpoint-save latency under SIGTERM (must fit
# the preemption grace window); the record splits recompile time from
# restore+fast-forward time
run recovery       env BENCH_MODE=recovery python bench.py

# compile-once layer (perf/): cold build vs warm persistent-cache build
# vs deserialized AOT executable, + the compile-level StepCostReport
run compile        env BENCH_MODE=compile python bench.py

# elastic-training drill (canonical 8-fake-device CPU mesh, re-execs
# itself there): injected pool shrink 8->4->8, mesh re-formed and the
# checkpoint resumed RESHARDED at each change; the record carries the
# goodput ledger, time-to-first-step-after-shrink, and the per-attempt
# shrink/grow classification + plan fingerprints. OBS_DIR routes the
# run's full telemetry (per-rank events, metric exports, the bench
# record itself) into one dir...
OBS_ELASTIC_DIR="$(mktemp -d /tmp/obs_elastic.XXXXXX)"
run elastic        env BENCH_MODE=elastic OBS_DIR="$OBS_ELASTIC_DIR" python bench.py

# ...which `obs report` (gke_ray_train_tpu/obs) merges into ONE
# reconciled per-run artifact: per-attempt timeline (both reshards),
# goodput ledger terms summing to attempt wall-clock exactly, the
# causal trace's per-attempt critical path (span/ledger reconciled,
# rc=3 on drift), anomaly/capture inventory, and the bench record —
# report.json stays beside the events, the summary line lands in $OUT
run obs-report     python -m gke_ray_train_tpu.obs report "$OBS_ELASTIC_DIR"

# the elastic drill's post-run self-check: `obs diff` compares the
# fresh report against the checked-in regression ledger
# (tests/regressions/elastic_cpu8.json) under two-sided tolerances —
# goodput composition, counts, serve latency, critical-path shares —
# and the verdict is its own artifact line (rc=4 prints the offending
# term delta). After an INTENTIONAL goodput change, re-record with
# REGRESSION_UPDATE=1 (or `obs diff ... --update`) and review the JSON
# diff like code.
run obs-diff       python -m gke_ray_train_tpu.obs diff "$OBS_ELASTIC_DIR" \
    tests/regressions/elastic_cpu8.json

# close the loop (ISSUE 16): fold the elastic drill's observed
# telemetry back into the autotune registry. `ingest` matches each
# bench/goodput record to a registry arm by plan fingerprint under the
# surface/chip/backend refusal gates (a CPU run can NEVER
# calibrate a TPU entry; rc=3 just means nothing matched this dir —
# not a failure on a fresh registry), then `calibrate` re-fits the
# per-chip correction factors from everything observed so far. A
# drift trip here (rc=5) marks the entry STALE — the overlay refuses
# it until re-tuned, so treat it like a failed budget check.
run autotune-ingest    python -m gke_ray_train_tpu.autotune ingest \
    "$OBS_ELASTIC_DIR" --dir tuned_plans
run autotune-calibrate python -m gke_ray_train_tpu.autotune calibrate \
    --dir tuned_plans

# compile-cost budgets (tests/budgets/*.json) are recorded on the
# canonical 8-fake-device CPU mesh, NOT on the attached chip — the CLI
# re-execs itself there; `check` is what tier-1 runs. `--all` sweeps
# EVERY checked-in preset (train + hybrid + serve) in one invocation —
# never enumerate presets by hand here. Only re-record (`record --all`)
# after an INTENTIONAL cost change, and review the JSON diff like code.
run budget-check   python -m gke_ray_train_tpu.perf.budget check --all

# shardlint (gke_ray_train_tpu/analysis): the AST pass over the repo
# plus the trace-level analyzers on the canonical CPU mesh — no
# unbudgeted reshard collectives, donation held, one compile per fn
run shardlint      python -m gke_ray_train_tpu.analysis lint
run shardlint-check python -m gke_ray_train_tpu.analysis check

# plancheck (analysis/plancheck.py): static ExecutionPlan verification
# over the shipped configs — topology feasibility, model-dim
# divisibility, the checkpoint-portability matrix, budget fingerprint
# + KNOWN_KEYS consistency. No backend needed (safe on a dead chip).
run plancheck      python -m gke_ray_train_tpu.analysis plancheck

# kernelcheck (analysis/kernelcheck.py): static kernel rules
# (KER001-006) + differential sweeps of every registered kernel vs its
# oracle against the pinned tolerance ledger (tests/tolerances/). The
# sweeps re-exec onto the canonical 8-fake-device CPU mesh (safe on a
# dead chip); only re-record the ledger (TOLERANCE_UPDATE=1) after an
# INTENTIONAL numerics change, and review the JSON diff like code.
run kernelcheck    python -m gke_ray_train_tpu.analysis kernelcheck

# flash-kernel block-size A/B (queued since r4): 3x3 sweep around the
# defaults on the seq4k shape where the kernel dominates (up to 8 extra
# bench runs; the default q=256/kv=1024 cell IS the `seq4k` record
# above and is skipped here)
for q in 128 256 512; do
  for kv in 512 1024 2048; do
    [ "$q" = 256 ] && [ "$kv" = 1024 ] && continue
    run "flash-q${q}-kv${kv}" env BENCH_MODE=seq4k \
        FLASH_BLOCK_Q="$q" FLASH_BLOCK_KV="$kv" python bench.py
  done
done

# flagship entry through its own meter (steady-state vs incl-stalls
# since r5) — full job: train + eval + ckpt + merge + export
run flagship env FINE_TUNE_CONFIG=ray-jobs/fine_tune_config_offline_8b.json \
    python ray-jobs/fine_tune_llama_ray.py

echo "records in $OUT"
