#!/usr/bin/env bash
# CI entrypoint (the reference's pipeline.yaml Style + UnitTests analog):
#   lint (syntax/compile check) -> native build -> unit tests on a virtual
#   8-device CPU mesh (the local[*] analog, SURVEY.md §4).
set -euo pipefail
cd "$(dirname "$0")"

echo "== lint: compileall =="
python -m compileall -q synapseml_tpu tests tools bench.py chip_smoke.py __graft_entry__.py

echo "== lint: AST audit (undefined names / unused imports / import cycles) =="
python tools/lint.py

echo "== native build =="
make -C synapseml_tpu/native

echo "== docs site (tools/docgen, website analog) =="
python tools/docgen/docgen.py > /dev/null

echo "== helm chart render check (tools/helm analog) =="
python tools/helm/render.py > /dev/null
python tools/helm/render.py --set workers.replicas=4 --release ci-check > /dev/null

echo "== wheel publish dry-run =="
rm -rf build/ci_wheel && pip wheel --no-deps --no-build-isolation -q \
    -w build/ci_wheel . 2> /dev/null || python setup.py -q bdist_wheel -d build/ci_wheel
python - << 'EOF'
# twine-check analog: the wheel must carry METADATA, the package, and the
# native library; a publish would ship exactly this file
import glob, sys, zipfile
whl = glob.glob("build/ci_wheel/*.whl")
assert whl, "no wheel produced"
names = zipfile.ZipFile(whl[0]).namelist()
assert any(n.endswith("METADATA") for n in names), "wheel missing METADATA"
assert any(n.startswith("synapseml_tpu/") for n in names), "package missing"
assert any(n.endswith(".so") for n in names), "native lib missing from wheel"
print(f"wheel ok: {whl[0]} ({len(names)} files)")
EOF

echo "== static analysis (trace-safety / recompile / determinism / locks / lock-order / thread-shared / blocking-under-lock / blocking-io / collectives / sharding / donation / resource-discipline / precision-loss / quant-overflow / nonfinite-escape / dtype-drift / codegen-drift) =="
# parallel analyzers + incremental cache: repeat runs on an unchanged tree
# are near-free; the budget asserts the cache/pool plumbing stays effective
# (generous enough for a cold cache on a loaded CI box)
_sa_t0=$(date +%s)
JAX_PLATFORMS=cpu python tools/analysis/run.py --jobs 4 --cache
_sa_dt=$(( $(date +%s) - _sa_t0 ))
echo "static analysis wall time: ${_sa_dt}s"
if [ "${_sa_dt}" -gt 120 ]; then
    echo "static analysis exceeded its 120s budget (${_sa_dt}s) — the" \
         "incremental cache or analyzer perf has regressed" >&2
    exit 1
fi

echo "== unit tests (8-device CPU mesh) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -m pytest tests/ -x -q -m 'not slow'

echo "== lock-order witness (non-blocking: observed vs predicted acquisition orders) =="
# re-run a threaded subset with every project lock instrumented, then diff
# the observed acquisition-order graph against the static lock-order graph
# (docs/static-analysis.md "Runtime lock-order witness"). Report-only for
# now — the static analyzers above are the hard gate; an observed cycle or
# an observed-but-unpredicted edge prints here for triage without failing
# the build.
_lw_report="$(mktemp -t lockwitness.XXXXXX.json)"
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    SYNAPSEML_TPU_LOCK_WITNESS="${_lw_report}" \
    python -m pytest -x -q tests/test_fabric.py tests/test_io.py \
    -m 'not slow' || echo "lockwitness: instrumented subset failed (non-blocking)"
JAX_PLATFORMS=cpu python -m synapseml_tpu.testing.lockwitness \
    "${_lw_report}" || echo "lockwitness: diff reported issues (non-blocking)"
rm -f "${_lw_report}"

echo "== dtype witness (observed wire/accumulator dtypes vs static dtype-flow prediction) =="
# re-run the gbdt-wire + dl-seq subset with the product _witness_observe
# probes live, then diff the observed per-site dtype sets against the
# static dtype-flow prediction (docs/static-analysis.md "Runtime dtype
# witness"). Report-only for recall gaps (unpredicted/foreign sites print
# for triage); an OBSERVED contract violation — a probe with expect= that
# saw a different dtype at runtime — fails the build (exit 1 from the CLI).
_dw_report="$(mktemp -t dtypewitness.XXXXXX.json)"
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    SYNAPSEML_TPU_DTYPE_WITNESS="${_dw_report}" \
    python -m pytest -x -q tests/test_distributed_gbdt_collectives.py \
    tests/test_ring_attention.py -m 'not slow' \
    || echo "dtypewitness: instrumented subset failed (non-blocking)"
JAX_PLATFORMS=cpu python -m synapseml_tpu.testing.dtypewitness \
    "${_dw_report}"
rm -f "${_dw_report}"

echo "== preemption-recovery chaos suite (kill -> resume == uninterrupted) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_checkpoint_recovery.py -x -q

echo "== checkpoint overhead guardrail (save/restore must stay cheap) =="
JAX_PLATFORMS=cpu python bench.py --only bench_checkpoint_overhead

echo "== serving perf guard (bucketed runner: zero steady-state recompiles) =="
JAX_PLATFORMS=cpu python -m pytest tests/test_inference_runtime.py -x -q
JAX_PLATFORMS=cpu python - << 'EOF'
# end-to-end contract check: a warmed BucketedRunner-backed server must not
# compile after warmup no matter what batch sizes arrive (the per-shape
# recompile regression this PR removes; docs/serving-perf.md)
import numpy as np
from synapseml_tpu.core.inference import BucketedRunner

runner = BucketedRunner(lambda x: x * 2.0 + 1.0, max_batch_size=64,
                        name="ci.guard")
runner.warmup(np.zeros((1, 8), np.float32))
warm = runner.stats()
assert warm["total_compiles"] == len(warm["buckets"]), warm
rng = np.random.default_rng(0)
for n in rng.integers(1, 200, size=50):
    runner(rng.normal(size=(int(n), 8)).astype(np.float32))
after = runner.stats()
steady = after["total_compiles"] - after["warmup_compiles"]
assert steady == 0, f"{steady} steady-state compiles: {after}"
print(f"serving perf guard ok: buckets={after['buckets']} "
      f"compiles={after['total_compiles']} (all warmup) "
      f"hits={after['total_hits']}")
EOF

echo "== fabric chaos (kill-mid-swap + heartbeat partition; invariant: accepted requests never dropped) =="
JAX_PLATFORMS=cpu python -m pytest -x -q \
    "tests/test_fabric.py::TestHotSwap" \
    "tests/test_fabric.py::TestGatewayMembership::test_heartbeat_join_evict_on_silence_then_rejoin" \
    "tests/test_fabric.py::TestFabricInvariant"

echo "== federation guard (no single point of failure: kill any one gateway) =="
# the federated-fabric invariant battery: zero 5xx for accepted requests
# across a single-gateway kill mid-route / mid-lease / mid-broadcast,
# exactly one gate-approved version fabric-wide after surviving-peer 2PC
# recovery, and orphaned workers re-homing within one heartbeat interval
JAX_PLATFORMS=cpu python -m pytest -x -q \
    "tests/test_federation.py::TestGatewayKillInvariant" \
    "tests/test_federation.py::TestBroadcastRecovery" \
    "tests/test_federation.py::TestWorkerFailover"
JAX_PLATFORMS=cpu python - << 'EOF'
# federated req/s must scale >= 0.9x linear per gateway-doubling after
# core-normalization (on an N-core host a doubling adds at most
# min(2K,N)/min(K,N) real parallelism; on 1 core the bar degenerates to
# "federation tax <= 10% per doubling"), with the control plane converging
# at every width; per-gateway convergence time rides along for trending
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_fabric_federation"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
print(f"federated req/s per width: {rec['gateway_reqs_per_s']} "
      f"(per-doubling {rec['scaling_per_doubling']}, convergence "
      f"{rec['convergence_time_s']} s, {rec['cores']} cores)")
assert rec["guard"]["scaling_ge_0p9x_linear_core_normalized"], \
    f"federation tax broke 0.9x-linear core-normalized scaling: {rec}"
EOF

echo "== online learning chaos (invariant: accepted requests always answered by a gate-approved, never-regressed policy) =="
JAX_PLATFORMS=cpu python -m pytest -x -q \
    "tests/test_online.py::TestChaosInvariant"

echo "== distributed gbdt guard (quantized wire + auto router) =="
JAX_PLATFORMS=cpu python - << 'EOF'
# the routed learner must never lose to a hand-picked flag: auto's measured
# throughput stays within 5% of the best manual arm on every dataset shape,
# and on the wide shape auto must beat the same-run data-parallel f32
# baseline (the r05 configuration re-measured on THIS host — absolute rates
# don't transfer across hardware) by >= 1.5x (docs/distributed-gbdt.md);
# per-tree collective bytes ride along in the bench record for trending
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_distributed_gbdt_auto"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
per_ds = {name: ds["auto_vs_best_manual"]
          for name, ds in rec["datasets"].items()}
print(f"auto/best-manual per dataset: {per_ds} "
      f"(wide auto {rec['distributed_row_iters_per_s']} r-i/s, "
      f"{rec['speedup_vs_data_parallel_f32']}x same-run data-parallel f32)")
assert rec["guard"]["auto_within_5pct_of_best_manual"], \
    f"auto routed onto a >5%-slower learner: {per_ds}"
assert rec["guard"]["wide_auto_ge_1p5x_data_parallel_f32"], \
    (f"wide auto {rec['distributed_row_iters_per_s']} r-i/s < 1.5x the "
     f"same-run data-parallel f32 baseline "
     f"{rec['data_parallel_f32_row_iters_per_s']} r-i/s")
EOF

echo "== dl scaling guard (ZeRO sharding + pipeline parallelism) =="
# correctness first: fixed-seed parity (zero & pipeline match the replicated
# loss trajectory — both schedules), kill->resume through sharded checkpoints
# bit-for-bit (incl. the overlap schedule), resharding across mesh shapes —
# all on the 8-CPU-device forked mesh; then the elastic-pipeline battery
# (hang-in-hop -> PeerLostError naming the hop, kill -> shrunken stage
# groups resume from per-shard checkpoints)
JAX_PLATFORMS=cpu python -m pytest -x -q tests/test_dl_sharded.py
JAX_PLATFORMS=cpu python -m pytest -x -q tests/test_elastic.py -k TestPipelineElastic
JAX_PLATFORMS=cpu python - << 'EOF'
# then the memory/throughput claim (docs/dl-scaling.md): ZeRO's per-device
# live state (params + optimizer moments, from each leaf's sharding) must be
# <= 0.6x replicated, at a step time within 1.15x, on both the resnet and
# bert-style staged configs
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_dl_sharded"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
per_model = {name: {"bytes": m["zero_bytes_ratio"],
                    "step": m["zero_step_ratio"]}
             for name, m in rec["models"].items()}
print(f"zero/replicated ratios per model: {per_model}")
assert rec["guard"]["zero_bytes_le_0p6x_replicated"], \
    f"ZeRO state bytes exceed 0.6x replicated: {per_model}"
assert rec["guard"]["zero_step_within_1p15x_replicated"], \
    f"ZeRO step time exceeds 1.15x replicated: {per_model}"
EOF
JAX_PLATFORMS=cpu python - << 'EOF'
# overlap schedule guard (docs/dl-scaling.md "Overlap schedule"): the
# double-buffered/no-remat schedule must beat fill-drain >=1.05x on the
# staged-bert pipeline config (median of interleaved paired trials) while
# both schedules hold <=1e-5 loss parity with the replicated trainer
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_dl_overlap_pipeline"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
print(f"overlap vs fill_drain: {rec['value']}x "
      f"(trials {rec['trial_speedups']}), "
      f"parity {rec['loss_parity_vs_replicated']:.2e}")
assert rec["guard"]["overlap_ge_1p05x_fill_drain"], \
    f"overlap schedule under 1.05x fill-drain: {rec['trial_speedups']}"
assert rec["guard"]["schedule_parity_le_1em5_vs_replicated"], \
    f"schedule loss parity above 1e-5: {rec['loss_parity_vs_replicated']}"
EOF

echo "== seq scaling guard (ring/ulysses sequence parallelism) =="
# correctness first: ring/ulysses parity vs the reference (causal, uneven
# heads, padding, gradients) and the scoped trainer routing, on the
# 8-CPU-device forked mesh
JAX_PLATFORMS=cpu python -m pytest -x -q tests/test_ring_attention.py
JAX_PLATFORMS=cpu python - << 'EOF'
# then the scaling claims (docs/dl-scaling.md "Sequence parallelism"):
# seq x 4 training must match the unsharded loss trajectory to <= 1e-5
# (scope-only routing, identical param tree), the sharded operands'
# per-host activation bytes must be <= 0.3x unsharded, and the seq-32k
# config whose full score matrix exceeds the single-shard host budget
# must run seq-sharded to a finite result
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_dl_seq"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
print(f"seq x 4 parity {rec['value']:.2e}; "
      f"activation bytes {rec['activation_bytes_ratio']}x; "
      f"8k ring/ulysses delta {rec['parity_8k_ring_vs_ulysses']:.2e}; "
      f"32k sharded forward finite={rec['seq32k']['finite']}")
assert rec["guard"]["seq_parity_le_1em5_vs_unsharded"], \
    f"seq-sharded loss parity above 1e-5: {rec['arms']}"
assert rec["guard"]["activation_bytes_le_0p3x"], \
    f"per-host activation bytes above 0.3x: {rec['activation_bytes_ratio']}"
assert rec["guard"]["seq32k_over_budget_sharded_ok"], \
    f"seq-32k over-budget arm failed: {rec['seq32k']}"
EOF

echo "== out-of-core guard (streamed gbdt: parity, chaos, throughput) =="
# correctness first: sketch/resident/sparse parity, chunk-stream chaos,
# kill->resume bit-for-bit, the dl tail-drop regression (tests/test_oocore.py)
JAX_PLATFORMS=cpu python -m pytest -x -q tests/test_oocore.py
JAX_PLATFORMS=cpu python - << 'EOF'
# then the throughput claim (docs/out-of-core.md): training through the
# chunk pump with SYNAPSEML_TPU_STREAM_MEM_BUDGET pinned to a TENTH of the
# quantized stream (a simulated 10x-undersized device) must hold >= 0.7x
# the classic resident trainer's row-iterations/s at the same depthwise
# policy, and the in-flight chunk state must genuinely be >= 10x smaller
# than the stream it trains on
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_oocore_gbdt"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
print(f"streamed@10x {rec['value']} r-i/s = "
      f"{rec['streamed_vs_resident_10x']}x resident "
      f"({rec['resident_row_iters_per_s']} r-i/s); "
      f"oversize ratio {rec['oversize_ratio']}x; "
      f"streamed@1x ratio {rec['streamed_vs_resident_1x']}x")
assert rec["guard"]["oversize_ratio_ge_10"], \
    f"budget cap did not produce a >=10x-oversized stream: {rec}"
assert rec["guard"]["streamed_10x_ge_0p7x_resident"], \
    (f"streamed@10x {rec['value']} r-i/s is "
     f"{rec['streamed_vs_resident_10x']}x resident "
     f"{rec['resident_row_iters_per_s']} r-i/s — below the 0.7x floor")
EOF
python - << 'EOF'
# mesh arm (docs/out-of-core.md "Mesh data plane"): the SAME 10x-undersized
# budget streamed through a data-axis mesh — chunk source sharded across
# workers, per-chunk frontier partials psum'd once per growth step through
# the wire ladder — must hold >= 0.8x the mesh-RESIDENT rate, i.e.
# streaming may tax the fabric-parallel path at most 20%. bench.py pins
# the virtual 8-device CPU mesh for this workload itself.
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_oocore_gbdt_mesh"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
print(f"mesh-streamed@10x {rec['value']} r-i/s = "
      f"{rec['mesh_streamed_vs_resident_10x']}x mesh-resident "
      f"({rec['mesh_resident_row_iters_per_s']} r-i/s, "
      f"data axis x{rec['workers']}); "
      f"oversize ratio {rec['oversize_ratio']}x")
assert rec["guard"]["oversize_ratio_ge_10"], \
    f"mesh budget cap did not produce a >=10x-oversized stream: {rec}"
assert rec["guard"]["mesh_streamed_10x_ge_0p8x_mesh_resident"], \
    (f"mesh-streamed@10x {rec['value']} r-i/s is "
     f"{rec['mesh_streamed_vs_resident_10x']}x mesh-resident "
     f"({rec['mesh_resident_row_iters_per_s']} r-i/s) — below the 0.8x "
     f"floor")
EOF

echo "== auto-config guard (perfmodel.choose >= 0.95x best hand-tuned arm) =="
# runs AFTER the bench-backed guards above so this very CI run's training
# rows (gbdt router/wire, dl sharding/schedule, seq attention, chunk
# geometry) are in the journal; adds its own bucket-growth micro A/B, then
# asserts the learned
# model never picks a >5%-slower config than the best hand-tuned arm on any
# recorded family (docs/perf-model.md "Confidence / fallback rule")
JAX_PLATFORMS=cpu python tools/autoconfig_guard.py

echo "== elastic training guard (kill/hang a rank -> detect, agree, reshard, resume) =="
# the chaos battery behind docs/resilience.md "Elastic training": watchdog
# stall detection (stale peer vs slow straggler vs wedged collective),
# digest-verified consensus restart over survivors, gbdt + dl-zero
# shrink/regrow resume (no committed step ever lost; bit-for-bit on an
# unchanged mesh), and the respawn-or-shrink TrainingSupervisor — runs the
# file unfiltered so the slow multi-process leg stays covered here
JAX_PLATFORMS=cpu python -m pytest -x -q tests/test_elastic.py

echo "== automl elastic guard (preemptible successive-halving on the gang) =="
# the chaos battery behind docs/automl.md: seeded crash/hang/NaN/slowdown
# per candidate, kill->resume to the IDENTICAL best model, hung candidates
# reaped within budget, duplicate candidates computed once, fingerprint
# refusal on changed data, and the spool-worker gang (kill_rank -> respawn
# + re-spool) — runs the file unfiltered so the subprocess gang leg stays
# covered here
JAX_PLATFORMS=cpu python -m pytest -x -q tests/test_automl_elastic.py
JAX_PLATFORMS=cpu python - << 'EOF'
# halving economics (ISSUE 17 acceptance): the bracket's winner must stay
# within 2% of the exhaustive-CV best while spending <= 40% of its fold-fit
# time, the full resilience stack (checkpoints + budget reaper) must cost
# <= 1.5x the bare bracket, and the elastic arm must journal structured
# "automl_rung" perfmodel rows per rung
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_automl_elastic"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
print(f"halving fit time {rec['value']}x exhaustive "
      f"(regret {rec['best_regret']}, elastic overhead "
      f"{rec['elastic_overhead_x']}x, rows/rung {rec['perf_rows_per_rung']})")
assert rec["guard"]["halving_best_within_2pct"], \
    f"halving winner regressed >2% vs exhaustive: {rec}"
assert rec["guard"]["halving_fit_time_le_40pct"], \
    f"halving spent >40% of exhaustive fold-fit time: {rec}"
assert rec["guard"]["elastic_overhead_le_1p5x"], \
    f"resilience stack costs >1.5x the bare bracket: {rec}"
assert rec["guard"]["rung_rows_journaled"], \
    f"elastic arm journaled too few automl_rung perf rows: {rec}"
EOF

echo "== multi-tenant guard (per-tenant QoS isolation + atomic broadcast) =="
# the chaos battery behind docs/resilience.md "Multi-tenant fleet": runs the
# file UNFILTERED so the slow noisy-neighbor leg (3 tenants x 2 workers,
# flood + NaN-storm one tenant, the others' p99/availability hold) stays
# covered here alongside the QoS primitives, swap-race, pinning,
# shared-cache accounting, and kill-mid-broadcast convergence
JAX_PLATFORMS=cpu python -m pytest -x -q tests/test_multitenant.py
JAX_PLATFORMS=cpu python - << 'EOF'
# consolidation price (ISSUE 12 acceptance): K=3 model families sharing one
# 2-worker fleet must hold >= 0.8x the aggregate req/s of 3 dedicated
# single-model fleets on the same worker count; per-tenant p99 rides along
import json, subprocess, sys
out = subprocess.run([sys.executable, "bench.py", "--only",
                      "bench_multitenant"],
                     capture_output=True, text=True, check=True).stdout
rec = json.loads(out.strip().splitlines()[-1])
print(f"shared/dedicated {rec['value']}x ({rec['unit']})")
assert rec["value"] >= 0.8, \
    f"shared fleet below 0.8x dedicated aggregate: {rec}"
EOF

echo "CI OK"
