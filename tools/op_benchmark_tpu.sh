#!/usr/bin/env bash
# Per-op TPU benchmark gate (reference: tools/test_op_benchmark.sh).
# Re-measures the standard op configs on the attached TPU and fails
# (exit 8) if any op regressed beyond the threshold vs the committed
# baseline in tools/op_baselines/tpu_v5e.
#
# Usage: tools/op_benchmark_tpu.sh [threshold]   (default 0.5)
set -euo pipefail
cd "$(dirname "$0")/.."
THRESHOLD="${1:-0.5}"
OUT="$(mktemp -d)/pr_logs"
# default repeat (10000 on tpu) MUST match the committed baselines:
# avg_us amortizes the launch round trip over the scan length
python tools/op_benchmark.py --platform tpu --output "$OUT"
python tools/check_op_benchmark_result.py \
    --develop_logs_dir tools/op_baselines/tpu_v5e \
    --pr_logs_dir "$OUT" --threshold "$THRESHOLD"
