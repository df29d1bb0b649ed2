#!/usr/bin/env bash
# Tier-1 gate, runnable without TPU hardware: the full pytest suite plus a
# reduced lower+compile dry-run for one lm and one vlm arch, so ExecutionPlan
# or sharding regressions surface from a plain CPU container.
#
#     make check        (or: bash scripts/check.sh [extra pytest args])
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# CPU only, Pallas in interpret mode; the chip path is chip_smoke.py
export JAX_PLATFORMS=cpu

echo "== tier-1 pytest =="
# the two seed-era deselects (jamba hybrid decode drift, q4 decode top-1
# agreement) are fixed — the full suite runs with no exclusions
python -m pytest -x -q "$@"

echo "== docs lint (core docstrings + README quickstart smoke) =="
python scripts/docs_lint.py --docs

echo "== replint (lock discipline, donation, dispatch, host-sync, triples) =="
# AST analyzer over src/ — zero unsuppressed findings required; the JSON
# report lands next to the other check outputs (docs/LINTS.md)
mkdir -p /tmp/repro-check
python scripts/repro_lint.py --json /tmp/repro-check/replint.json

echo "== reduced dry-run: lm arch =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.dryrun --arch stablelm-1.6b --shape decode_32k \
    --reduced --out /tmp/repro-check/dryrun

echo "== reduced dry-run: vlm arch =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.dryrun --arch llava-onevision-0.5b \
    --shape decode_32k --reduced --out /tmp/repro-check/dryrun

echo "== backend lowering matrix: host | device | submesh =="
# the same reduced vlm graph must compile and run under every backend in
# the core/backends table (submesh on 8 placeholder devices), so no
# backend path rots without TPU hardware
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.dryrun_backends --arch llava-onevision-0.5b \
    --backends host,device,submesh

echo "== mixed-class TABM engine smoke: hi-res + thumbnail =="
# one high-resolution and one thumbnail request through ServingEngine on
# placeholder devices: classification at submit, per-class staging
# threads, class-sized ring commits, per-class drain (core/slot_classes)
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.smoke_classes

echo "== batched staging smoke: strided slab commit + grouped prefill =="
# eight queued same-class requests through the microbatching pipeline:
# multi-request produce_many slab commits, batch>1 grouped prefill with
# KVCache.insert_many, greedy tokens identical to the one-by-one oracle
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.smoke_classes --stage-batch 4

echo "== decode-cohort smoke: paged KV + mid-flight admit/retire =="
# five mixed-class requests against a 2-slot paged pool: continuous
# batching must retire and admit mid-flight while survivors decode in
# one batched cohort step, with tokens == the per-request oracle
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.smoke_classes --decode-cohort

echo "== disaggregated-fleet smoke: prefill fleet | pipe | decode fleet =="
# two-fleet serving with the decode fleet as a REAL subprocess over OS
# pipes: >=3 mixed-class requests cross as serialized RemotePrefill
# frames (slab + written KV blocks only); the driver asserts greedy
# tokens bit-identical to a single-process oracle and wire KV bytes
# under the whole-lane baseline (launch/serve_disagg.py)
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.serve_disagg --transport pipe --requests 3

echo "== fleet battery-simulation smoke: telemetry-priced devices =="
# >=100 simulated devices on a small pack traverse all three power
# states (per-device PMU under one PowerPolicy, modality profile priced
# from the modeled telemetry ledger) and report fleet tokens/s, J/token
# and a survival-hours histogram; asserts enforced by --smoke
BENCH_JSON="BENCH_$(python -c 'from repro.telemetry.writer import CURRENT_PR; print(CURRENT_PR)').json"
python -m repro.launch.fleet_sim --smoke --bench-json "$BENCH_JSON"

echo "== benchmark ledger + regression gate: $BENCH_JSON =="
# the versioned bench trajectory: fused cohort-decode (bit-identical
# pallas step; gates on the modeled HBM weight-traffic ratio and on
# cohort batching staying a real speedup) and the fused dequant-GEMM
# kernel (analytic traffic ratio), folded into the same BENCH_<pr>.json
# as the fleet metrics above, then regression-gated against the last
# committed baseline
python -m benchmarks.bench_decode --smoke --bench-json "$BENCH_JSON"
python -m benchmarks.bench_kernels --smoke --bench-json "$BENCH_JSON"
python scripts/bench_gate.py "$BENCH_JSON"

echo "OK: check passed"
