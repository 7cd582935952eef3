#!/usr/bin/env bash
# CI gate: tier-1 tests + the fast benchmark sweep (BENCH_gaunt.json).
#
#   scripts/ci.sh [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "=== tier-1 tests (conformance + resident-sharded + chain-kernel files deferred to their own tiers) ==="
python -m pytest -x -q \
  --ignore=tests/test_equivariance.py --ignore=tests/test_engine_transforms.py \
  --ignore=tests/test_resident_batched.py --ignore=tests/test_chain_kernel.py "$@"

echo "=== conformance tier: equivariance + transform/batched-plan parity (f32; bf16 has its own tier) ==="
python -m pytest -q tests/test_equivariance.py tests/test_engine_transforms.py \
  -k "not bfloat16"

echo "=== resident x sharded tier: MaceGaunt shard_data+fourier_resident on 2 devices ==="
# the unification gate: counter-proven no-fallback residency under
# donate/shard_spec, and the sharded resident MaceGaunt matching the
# unsharded legacy path numerically (subprocess tests set the XLA 2-device
# flag) — a silent fallback or divergence fails CI here
python -m pytest -q tests/test_resident_batched.py

echo "=== Pallas interpret tier: fused pairwise + n-way chain kernels (interpret=True) ==="
# every Pallas Gaunt kernel exercised off-TPU through the interpreter in one
# named gate: the pairwise collocation kernel (selected from test_kernels —
# a few seconds of dedicated re-run keeps this tier self-contained) and the
# n-way chain kernel with its grid-blocked accumulation, grad, vmap,
# residency, f64 and sharded paths — one pallas_call per chain, counter-proven
python -m pytest -q tests/test_chain_kernel.py -k "not bfloat16"
python -m pytest -q tests/test_kernels.py -k "gaunt_fused and not bfloat16"

echo "=== bf16 interpret tier: bfloat16 storage / f32 accumulation (conformance + chain kernels) ==="
# every bfloat16-parameterized case in one named gate: rotation-equivariance
# conformance at the documented bf16 tolerances (DESIGN.md §3.6), the n-way
# chain kernel vs the f32 tree oracle, and the pairwise kernel's dtype sweep
# — all through the Pallas interpreter off-TPU, storage bf16 / accumulation f32
python -m pytest -q tests/test_equivariance.py tests/test_chain_kernel.py \
  tests/test_kernels.py -k "bfloat16"

echo "=== batched-bench smoke (batched vs looped dispatch) ==="
python -m benchmarks.run --fast --only engine_batched --json ''

echo "=== serve tier: load-generator smoke (low QPS, tiny model, bucketed pools) ==="
# the serving scale-out gate (DESIGN.md §10): the open-loop load generator
# drives the bucketed scheduler/pool/pipelining stack end-to-end at low QPS
# — a deadlock, lost request, or scheduler regression hangs or fails here
# before the full bench (which re-runs serve into BENCH_gaunt.json) starts
python - <<'EOF'
from benchmarks.bench_serve import run_serve
recs = run_serve(fast=True, n_req=12, qps_list=(15.0,))
by = {r["name"]: r for r in recs}
assert by["serve_qps15"]["completed"] == 12, by
assert by["serve_qps15"]["rejected"] == 0, by
print("serve smoke OK")
EOF

echo "=== chaos smoke: fault-injected closed loop (raises + NaNs + timeouts) ==="
# the fault-tolerance gate (DESIGN.md §11): a seeded FaultPlan fails steps
# mid-drain and the run must still lose ZERO requests — every one completed
# or structurally rejected — with results identical to fault-free for the
# completions; a recovery regression (lost request, poisoned bucket-mate,
# retry that isn't idempotent) fails here before the full chaos bench runs
python - <<'EOF'
from benchmarks.bench_serve import run_serve_chaos
recs = run_serve_chaos(fast=True, n_req=12, rates=(0.2,))
for r in recs:
    assert r["lost"] == 0, r
    assert r["results_match"], r
chaos = [r for r in recs if r["name"].startswith("serve_chaos_rate")]
assert chaos and all(r["step_failures"] > 0 for r in chaos), recs
fo = [r for r in recs if r["name"] == "serve_chaos_failover"]
assert fo and fo[0]["failovers"] >= 1, recs
print("chaos smoke OK")
EOF

echo "=== fast benchmarks (--backend auto -> BENCH_gaunt.json) ==="
python -m benchmarks.run --fast --backend auto --json BENCH_gaunt.json

echo "=== BENCH_gaunt.json summary ==="
python - <<'EOF'
import json
d = json.load(open("BENCH_gaunt.json"))
recs = d["records"]
print(f"{len(recs)} records; engine picks:")
for r in recs:
    if r["name"].startswith("engine_chain_kernel"):
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  -> {r.get('backend')} "
              f"(tree {r.get('tree_us')} us, x{r.get('speedup_vs_tree')})")
    elif r["name"].startswith("engine_calibration"):
        print(f"  {r['name']:36s} factor={r.get('factor')} "
              f"(default {r.get('default_factor')})")
    elif r["name"].startswith("engine_grid_gate"):
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  grid gate "
              f"x{r.get('speedup_vs_sh_gate')} vs SH gate, err={r.get('err')}, "
              f"auto->{r.get('auto_policy')}")
    elif r["name"].startswith("engine_mixed_precision"):
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  bf16 "
              f"x{r.get('speedup_vs_f32')} vs f32, err={r.get('err')}, "
              f"auto->{r.get('auto_dtype')}")
    elif r["name"].startswith("engine_autotune_cache"):
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  warm "
              f"(cold {r.get('cold_us')} us, x{r.get('speedup_vs_cold')}, "
              f"warm timing runs {r.get('warm_timing_runs')})")
    elif r["name"] == "serve_bucketed_vs_single":
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  bucketed "
              f"x{r.get('speedup_vs_single')} vs single max_atoms "
              f"({r.get('throughput_rps')} rps, padding eff "
              f"{r.get('padding_efficiency')} vs "
              f"{r.get('single_padding_efficiency')})")
    elif r["name"].startswith("serve_qps"):
        print(f"  {r['name']:36s} {r['us']:>10.1f} us p50  "
              f"(p99 {r.get('p99_us')} us, {r.get('throughput_rps')} rps, "
              f"padding eff {r.get('padding_efficiency')})")
    elif r["name"].startswith("serve_chaos_rate"):
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  lost={r.get('lost')} "
              f"match={r.get('results_match')} "
              f"failures={r.get('step_failures')} retries={r.get('retries')} "
              f"recovery p99 {r.get('recovery_p99_ms')} ms, "
              f"x{r.get('degradation_vs_baseline')} of fault-free")
    elif r["name"] == "serve_chaos_failover":
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  lost={r.get('lost')} "
              f"match={r.get('results_match')} "
              f"failovers={r.get('failovers')} "
              f"requeued={r.get('requeued_on_failover')}")
    elif r["name"].startswith(("engine_batched", "engine_chain")):
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  "
              f"(looped {r.get('looped_us')} us, x{r.get('speedup_vs_looped')})")
    elif r["name"].startswith("engine_"):
        print(f"  {r['name']:36s} {r['us']:>10.1f} us  -> {r.get('backend')}")
EOF

echo "=== warm-cache guard: calibrate CLI populates, second sweep runs 0 timings ==="
# two passes over a throwaway cache file: the first measures the (fast)
# workload grid and persists it, the second must answer every selection from
# the file — --verify-warm exits non-zero if even one timing run happened,
# which is exactly the cold-start cliff the persistent cache exists to close
AUTOTUNE_CACHE="$(mktemp -t autotune_cache.XXXXXX.json)"
trap 'rm -f "$AUTOTUNE_CACHE"' EXIT
rm -f "$AUTOTUNE_CACHE"  # the CLI wants to create it atomically itself
python -m repro.core.autotune_cache --fast --cache "$AUTOTUNE_CACHE"
python -m repro.core.autotune_cache --fast --cache "$AUTOTUNE_CACHE" --verify-warm

echo "=== bench guards: heuristic regret + chain-speedup + mixed-precision + warm-start ==="
# per-run baseline path (mktemp, not a fixed /tmp name): concurrent CI runs
# on a shared runner must not clobber each other's baselines
BENCH_BASELINE="$(mktemp -t bench_baseline.XXXXXX.json)"
trap 'rm -f "$AUTOTUNE_CACHE" "$BENCH_BASELINE"' EXIT
git show HEAD:BENCH_gaunt.json > "$BENCH_BASELINE" 2>/dev/null || true
BENCH_BASELINE="$BENCH_BASELINE" python - <<'EOF'
import json, os, sys

# guard 1 — autotune cost model: where the heuristic pick disagrees with the
# measured winner, its measured regret must stay within tolerance
TOL = 1.5
fail = []
recs = json.load(open("BENCH_gaunt.json"))["records"]
for r in recs:
    ratio = r.get("heuristic_ratio")
    if ratio is not None and ratio > TOL:
        fail.append(f"{r['name']}: heuristic {r['heuristic']} is {ratio}x the "
                    f"measured winner {r['backend']} (> {TOL}x tolerance)")

# guard 2 — chain benchmarks: resident speedups must not regress > 20%
# against the committed baseline, nor fall below the absolute floor.
# Committed runs show > 1 everywhere; the floor sits below 1 because the
# baseline was measured on a different host and CPU microbenchmark noise
# across machines exceeds a few percent.  Both knobs are env-tunable for
# noisier runners (BENCH_GUARD_FLOOR / BENCH_GUARD_FRAC).
FLOOR = float(os.environ.get("BENCH_GUARD_FLOOR", "0.9"))
FRAC = float(os.environ.get("BENCH_GUARD_FRAC", "0.8"))
baseline = os.environ.get("BENCH_BASELINE", "")
if baseline and os.path.exists(baseline) and os.path.getsize(baseline):
    base = {r["name"]: r for r in json.load(open(baseline))["records"]}
else:
    base = {}
for r in recs:
    if not r["name"].startswith("engine_chain") or \
            r["name"].startswith("engine_chain_kernel"):
        continue
    s = r.get("speedup_vs_looped", 0.0)
    if s < FLOOR:
        fail.append(f"{r['name']}: resident path LOST to looped (x{s} < {FLOOR})")
    b = base.get(r["name"], {}).get("speedup_vs_looped")
    if b and s < FRAC * b:
        fail.append(f"{r['name']}: chain speedup regressed x{b} -> x{s} (>20%)")

# guard 3 — chain autotune: where the measured autotuner picked the
# collocation kernel, the pick must actually beat (>= KFLOOR x) the resident
# tree-conv on that workload — a kernel that wins the measurement but loses
# the bench means the autotune methodology regressed.  And the kernel must
# win SOMEWHERE: if no benchmarked chain workload selects a fused backend,
# the chain-autotune fold is dead weight.  Both knobs are env-tunable:
# BENCH_GUARD_KERNEL_FLOOR for the loss check, and
# BENCH_GUARD_REQUIRE_KERNEL_WIN=0 for hosts whose matmul/FFT balance makes
# tree the honest winner everywhere (that is a valid autotune outcome, not
# a regression).
KFLOOR = float(os.environ.get("BENCH_GUARD_KERNEL_FLOOR", "0.9"))
REQUIRE_WIN = os.environ.get("BENCH_GUARD_REQUIRE_KERNEL_WIN", "1") != "0"
kernel_recs = [r for r in recs if r["name"].startswith("engine_chain_kernel_")]
if kernel_recs:
    picked = [r for r in kernel_recs
              if r.get("backend", "").startswith("fused")]
    if not picked and REQUIRE_WIN:
        fail.append("engine_chain_kernel: the measured autotuner picked the "
                    "collocation kernel on NO benchmarked chain workload "
                    "(set BENCH_GUARD_REQUIRE_KERNEL_WIN=0 if tree honestly "
                    "wins everywhere on this host)")
    for r in picked:
        s = r.get("speedup_vs_tree", 0.0)
        if s < KFLOOR:
            fail.append(f"{r['name']}: autotuner picked {r['backend']} but it "
                        f"LOST to tree-conv (x{s} < {KFLOOR})")
# guard 4 — mixed precision: every engine_mixed_precision_* record must keep
# its bf16-vs-f32 relative error inside the documented budget (DESIGN.md
# §3.6; bf16 eps is 2^-8 ~ 3.9e-3, committed runs show err <= 4e-3, the
# default tolerance leaves ~10x headroom for input-dependent cancellation),
# AND wherever the measured autotuner kept a bfloat16 plan it must not LOSE
# to its f32 sibling on the bench re-measure.  bf16 is NOT required to win
# anywhere — on hosts that emulate bf16 (CPU) float32 everywhere is the
# honest autotune outcome; only a *losing* bf16 pick means the precision
# autotune methodology regressed.  The floor sits at 0.75, looser than
# guard 3's 0.9: kernel-vs-tree wins are x2-6 so 0.9 is far from the
# signal, but precision wins on an emulating host are marginal by nature
# (observed x0.8-1.4 run-to-run on the same workload) — the floor exists
# to catch a pick that is *clearly* wrong, not measurement jitter between
# the autotune timing and the bench re-timing.  Both knobs are env-tunable
# (BENCH_GUARD_BF16_TOL / BENCH_GUARD_BF16_FLOOR, modeled on guard 3).
BF16_TOL = float(os.environ.get("BENCH_GUARD_BF16_TOL", "0.05"))
BF16_FLOOR = float(os.environ.get("BENCH_GUARD_BF16_FLOOR", "0.75"))
for r in recs:
    if not r["name"].startswith("engine_mixed_precision_"):
        continue
    e = r.get("err")
    if e is not None and e > BF16_TOL:
        fail.append(f"{r['name']}: bf16 error {e} exceeds tolerance "
                    f"{BF16_TOL} (storage rounding should stay ~eps=3.9e-3; "
                    f"an err this large means accumulation dropped to bf16)")
    if r.get("auto_dtype") == "bfloat16":
        s = r.get("speedup_vs_f32", 0.0)
        if s < BF16_FLOOR:
            fail.append(f"{r['name']}: autotuner kept bfloat16 but it LOST "
                        f"to its f32 sibling (x{s} < {BF16_FLOOR})")

# guard 5 — persistent autotune: the warm engine in the cold-vs-warm
# record must have performed ZERO timing runs and selected identically to
# the cold one — a single warm timing run means the persisted table failed
# to cover the workload (broken serialization, fingerprint drift, or a
# selection path that stopped consulting the cache)
for r in recs:
    if not r["name"].startswith("engine_autotune_cache"):
        continue
    if r.get("warm_timing_runs", 0) != 0:
        fail.append(f"{r['name']}: warm engine ran "
                    f"{r['warm_timing_runs']} timing runs (must be 0 — the "
                    f"persisted cache did not cover the workload)")
    if not r.get("picks_match", False):
        fail.append(f"{r['name']}: warm engine selected differently from "
                    f"the cold one (persisted table is not faithful)")

# guard 6 — grid-resident gates (DESIGN.md §6.5): exactness first — the
# gate is affine on the sphere once its scalars are known, so grid-vs-SH
# disagreement is storage roundoff, NOT aliasing; err above tolerance means
# the fused pointwise stage or the quadrature projection broke.  Then
# policy honesty: where the measured gate policy (engine.select_gate)
# picked the grid gate, the bench re-measure must not show it losing to
# the SH epilogue; and the fused gate must win somewhere, else the gate
# fusion (and its autotune fold) is dead weight.  All knobs env-tunable,
# modeled on guards 3/4; BENCH_GUARD_REQUIRE_GATE_WIN=0 for hosts where
# the SH epilogue honestly wins everywhere.
GATE_TOL = float(os.environ.get("BENCH_GUARD_GATE_TOL", "1e-3"))
GATE_FLOOR = float(os.environ.get("BENCH_GUARD_GATE_FLOOR", "0.9"))
REQUIRE_GATE_WIN = os.environ.get("BENCH_GUARD_REQUIRE_GATE_WIN", "1") != "0"
gate_recs = [r for r in recs if r["name"].startswith("engine_grid_gate_")]
for r in gate_recs:
    e = r.get("err")
    if e is not None and e > GATE_TOL:
        fail.append(f"{r['name']}: grid-gate error {e} exceeds "
                    f"{GATE_TOL} (the affine gate is exact on the grid — "
                    f"an err this large means the fused stage broke)")
    if r.get("auto_policy") == "grid" and \
            r.get("speedup_vs_sh_gate", 0.0) < GATE_FLOOR:
        fail.append(f"{r['name']}: gate policy picked 'grid' but it LOST "
                    f"to the SH gate (x{r.get('speedup_vs_sh_gate')} < "
                    f"{GATE_FLOOR})")
if gate_recs and REQUIRE_GATE_WIN and not any(
        r.get("speedup_vs_sh_gate", 0.0) >= 1.0 for r in gate_recs):
    fail.append("engine_grid_gate: the fused grid gate beat the SH gate on "
                "NO benchmarked workload (set BENCH_GUARD_REQUIRE_GATE_WIN=0 "
                "if the SH epilogue honestly wins everywhere on this host)")

# guard 7 — serve scale-out (DESIGN.md §10): the bench record must EXIST
# (a silently-skipped serve job would let the serving layer rot unmeasured),
# open-loop p99 latency must stay under an env-tunable ceiling, nothing may
# be rejected at the smoke's low QPS, and the bucketed pools must beat the
# single-max_atoms baseline on throughput for the mixed-size workload —
# the whole point of size bucketing (committed runs show ~x2.7 on CPU; the
# floor sits at 1.0 because the win comes from padded-FLOP arithmetic, not
# microbenchmark noise).  BENCH_GUARD_SERVE_P99_MS / BENCH_GUARD_SERVE_FLOOR
# env-tunable; BENCH_GUARD_REQUIRE_SERVE_WIN=0 opts out of the win check on
# hosts whose scheduling jitter genuinely swamps the padding arithmetic.
SERVE_P99_MS = float(os.environ.get("BENCH_GUARD_SERVE_P99_MS", "500"))
SERVE_FLOOR = float(os.environ.get("BENCH_GUARD_SERVE_FLOOR", "1.0"))
REQUIRE_SERVE_WIN = os.environ.get("BENCH_GUARD_REQUIRE_SERVE_WIN", "1") != "0"
serve_recs = [r for r in recs if r["name"].startswith("serve_")]
if not serve_recs:
    fail.append("serve: BENCH_gaunt.json carries NO serve_* records — the "
                "load-generator bench did not run or did not record")
else:
    vs = [r for r in serve_recs if r["name"] == "serve_bucketed_vs_single"]
    if not vs:
        fail.append("serve: the serve_bucketed_vs_single record is missing")
    elif REQUIRE_SERVE_WIN and vs[0].get("speedup_vs_single", 0.0) < SERVE_FLOOR:
        fail.append(f"serve_bucketed_vs_single: bucketed pools LOST to the "
                    f"single-max_atoms baseline on throughput "
                    f"(x{vs[0].get('speedup_vs_single')} < {SERVE_FLOOR})")
    qps_recs = [r for r in serve_recs if r["name"].startswith("serve_qps")]
    if not qps_recs:
        fail.append("serve: no serve_qps* records — the QPS sweep is missing")
    for r in qps_recs:
        p99_ms = r.get("p99_us", 0.0) / 1e3
        if p99_ms > SERVE_P99_MS:
            fail.append(f"{r['name']}: p99 latency {p99_ms:.1f}ms exceeds "
                        f"the {SERVE_P99_MS}ms ceiling "
                        f"(BENCH_GUARD_SERVE_P99_MS)")
        if r.get("timing_runs") not in (None, 0):
            fail.append(f"{r['name']}: {r['timing_runs']} mid-serve autotune "
                        f"timing runs (serving must never time-measure)")

# guard 8 — chaos / fault tolerance (DESIGN.md §11): the serve_chaos_*
# records must EXIST (unmeasured recovery is asserted recovery), the lost-
# request count must be 0 at every injected fault rate (every request
# completed or structurally rejected — a lost request is a serving bug, not
# a tuning matter, so there is NO escape hatch for it), non-rejected results
# must match the fault-free run (retry idempotency), and recovery p99 must
# stay under an env-tunable ceiling (BENCH_GUARD_RECOVERY_P99_MS — the one
# knob here that is host-speed-dependent: recovery includes a re-staged
# evaluation, so slow runners may honestly exceed the default).
RECOVERY_P99_MS = float(os.environ.get("BENCH_GUARD_RECOVERY_P99_MS", "500"))
chaos_recs = [r for r in recs if r["name"].startswith("serve_chaos_")]
if not chaos_recs:
    fail.append("serve_chaos: BENCH_gaunt.json carries NO serve_chaos_* "
                "records — the chaos bench did not run or did not record")
for r in chaos_recs:
    if r.get("lost", 1) != 0:
        fail.append(f"{r['name']}: {r.get('lost')} requests LOST (every "
                    f"request must complete or reject structurally)")
    if r.get("results_match") is False:
        fail.append(f"{r['name']}: non-rejected results differ from the "
                    f"fault-free run (retry is not idempotent; max energy "
                    f"diff {r.get('max_energy_diff')})")
    p99 = r.get("recovery_p99_ms")
    if p99 is not None and p99 > RECOVERY_P99_MS:
        fail.append(f"{r['name']}: recovery p99 {p99}ms exceeds the "
                    f"{RECOVERY_P99_MS}ms ceiling "
                    f"(BENCH_GUARD_RECOVERY_P99_MS)")
if chaos_recs and not any(r["name"] == "serve_chaos_failover"
                          for r in chaos_recs):
    fail.append("serve_chaos: the serve_chaos_failover record is missing — "
                "replica failover is not being exercised")

if fail:
    print("BENCH GUARD FAILURES:")
    for f in fail:
        print(" -", f)
    sys.exit(1)
print("bench guards OK")
EOF
echo "CI OK"
