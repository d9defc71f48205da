#!/usr/bin/env bash
# Tier-1 CI: test suite + API smoke drivers.
# Usage: scripts/ci.sh [--fast]   (--fast skips the smoke drivers)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# forced host devices (repro.api.MeshSpec) need the CPU pinned as the
# platform; the Pallas kernels then run interpreted
export JAX_PLATFORMS=cpu

echo "=== tier-1 pytest ==="
python -m pytest -x -q

if [[ "${1:-}" != "--fast" ]]; then
  echo "=== smoke: models (repro.api.load_config) ==="
  python scripts/smoke_models.py

  echo "=== smoke: FHDP pipeline (repro.api.Session) ==="
  python scripts/smoke_pipeline.py

  echo "=== smoke: train launcher (Session CLI) ==="
  python -m repro.launch.train --strategy pipeline --devices 8 --steps 2

  echo "=== smoke: hierarchical FL over the comm fabric ==="
  python -m repro.launch.train --strategy hier_fl --devices 2 --mesh 2 \
      --topology "2@nano*2,agx*2" --codec int8 --steps 2

  echo "=== smoke: async event-time FL (clocked merge + migration) ==="
  python -m repro.launch.train --strategy async_hier_fl --devices 2 \
      --mesh 2 --topology "2@nano*2,agx*2" --codec int8 \
      --async-clock 0.3 --migrate-every 0.5 --compute-jitter 0.2 --steps 2

  echo "=== smoke: federated personalized distillation (LoRA uplinks) ==="
  python -m repro.launch.train --strategy distill_fl --arch flad-adllm \
      --shape 16x8 --devices 2 --mesh 2 --topology "2@nano*2,agx*2" \
      --codec int8 --steps 2 --distill-warmup 4

  echo "=== smoke: async FL migration example ==="
  python examples/async_fl_migration.py --rounds 3

  echo "=== smoke: traced async round (repro.obs example) ==="
  python examples/traced_async_round.py --rounds 2 \
      --out /tmp/ci_traced_async.json
  python scripts/validate_trace.py /tmp/ci_traced_async.json

  echo "=== smoke: traced async FL via the train launcher ==="
  python -m repro.launch.train --strategy async_hier_fl --devices 2 \
      --mesh 2 --topology "2@nano*2,agx*2" --codec int8 \
      --async-clock 0.3 --compute-jitter 0.2 --steps 2 \
      --trace /tmp/ci_async_trace.json --metrics /tmp/ci_async_metrics.json
  python scripts/validate_trace.py /tmp/ci_async_trace.json

  echo "=== smoke: serve launcher (Session.serve) ==="
  python -m repro.launch.serve --devices 2 --batch 2 --context 16 \
      --decode-steps 4 --requests 1

  echo "=== smoke: continuous-batching serve (paged KV tier) ==="
  python -m repro.launch.serve --devices 2 --scheduler continuous \
      --slots 2 --context 16 --requests 4 --block-size 8 --cache int8

  echo "=== smoke: chunked prefill + prefix cache (serve launcher) ==="
  python -m repro.launch.serve --devices 2 --scheduler continuous \
      --slots 2 --context 16 --requests 4 --block-size 8 \
      --prefill chunked --prefill-chunk 8 --prefix-cache

  echo "=== smoke: speculative decoding (draft-verify serve) ==="
  python -m repro.launch.serve --devices 2 --scheduler continuous \
      --slots 2 --context 16 --requests 4 --block-size 8 \
      --prefill chunked --prefill-chunk 8 --speculative --draft-k 4

  echo "=== smoke: traced continuous serve (repro.obs) ==="
  python -m repro.launch.serve --devices 2 --scheduler continuous \
      --slots 2 --context 16 --requests 4 --block-size 8 \
      --prefill chunked --prefill-chunk 8 --prefix-cache \
      --trace /tmp/ci_serve_trace.json
  python scripts/validate_trace.py /tmp/ci_serve_trace.json

  echo "=== smoke: benchmark registry listing ==="
  python benchmarks/run.py --list

  echo "=== smoke: SWIFT live repartition example (dry run) ==="
  python examples/swift_repartition.py --dry-run

  echo "=== bench: repartition latency (quick, scratch output) ==="
  # scratch path: never clobber the committed full-run perf artifacts
  python benchmarks/repartition_latency.py --quick \
      --out /tmp/BENCH_repartition.quick.json
  python scripts/validate_bench.py /tmp/BENCH_repartition.quick.json

  echo "=== bench: attention fwd+bwd (quick, scratch output) ==="
  python benchmarks/attention_bench.py --quick \
      --out /tmp/BENCH_attention.quick.json
  python scripts/validate_bench.py /tmp/BENCH_attention.quick.json

  echo "=== bench: comm fabric (quick, scratch output) ==="
  python benchmarks/comm_bench.py --quick --out /tmp/BENCH_comm.quick.json
  python scripts/validate_bench.py /tmp/BENCH_comm.quick.json

  echo "=== bench: async event-time engine (quick, scratch output) ==="
  python benchmarks/async_bench.py --quick \
      --out /tmp/BENCH_async.quick.json
  python scripts/validate_bench.py /tmp/BENCH_async.quick.json

  echo "=== bench: serving tier (quick, scratch output) ==="
  python benchmarks/serving_bench.py --quick \
      --out /tmp/BENCH_serving.quick.json
  python scripts/validate_bench.py /tmp/BENCH_serving.quick.json

  echo "=== bench: chunked prefill + prefix cache (quick, scratch) ==="
  python benchmarks/prefill_bench.py --quick \
      --out /tmp/BENCH_prefill.quick.json
  python scripts/validate_bench.py /tmp/BENCH_prefill.quick.json

  echo "=== bench: personalized distillation (quick, scratch output) ==="
  python benchmarks/distill_fl_bench.py --quick \
      --out /tmp/BENCH_distill.quick.json
  python scripts/validate_bench.py /tmp/BENCH_distill.quick.json

  echo "=== bench: speculative decoding (quick, scratch output) ==="
  python benchmarks/specdec_bench.py --quick \
      --out /tmp/BENCH_specdec.quick.json
  python scripts/validate_bench.py /tmp/BENCH_specdec.quick.json

  echo "=== validate committed perf-trajectory artifacts ==="
  python scripts/validate_bench.py BENCH_repartition.json
  python scripts/validate_bench.py BENCH_attention.json
  python scripts/validate_bench.py BENCH_comm.json
  python scripts/validate_bench.py BENCH_async.json
  python scripts/validate_bench.py BENCH_serving.json
  python scripts/validate_bench.py BENCH_prefill.json
  python scripts/validate_bench.py BENCH_distill.json
  python scripts/validate_bench.py BENCH_specdec.json
fi

echo "CI OK"
