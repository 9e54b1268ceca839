#!/usr/bin/env bash
# Hardware tuning sweep, for a machine with a TPU attached. Each leg is
# a fresh process run one after another (page size / slots are
# runtime-construction knobs, and a chip belongs to one process at a
# time). Legs append to $OUT as JSON lines; the headline config is the
# best tok/s leg.
#
# Usage: scripts/bench_sweep.sh [OUT]
set -u
OUT="${1:-bench_sweep_results.jsonl}"
cd "$(dirname "$0")/.."

leg() {
  local name="$1"; shift
  echo "# leg: $name ($*)" >&2
  local t0=$(date +%s)
  local line rc
  line=$(python bench.py "$@" 2>/dev/null | tail -1; exit "${PIPESTATUS[0]}")
  rc=$?
  local t1=$(date +%s)
  if [ -n "$line" ]; then
    echo "{\"leg\": \"$name\", \"wall_s\": $((t1 - t0)), \"rc\": $rc, \"result\": $line}" >> "$OUT"
    echo "$line" >&2
  else
    echo "{\"leg\": \"$name\", \"wall_s\": $((t1 - t0)), \"rc\": $rc, \"result\": null}" >> "$OUT"
  fi
}

# 1. Current defaults: chunk sweep inside one leg.
leg baseline           --slots 64  --page-size 32 --chunk 16 --sweep-chunks 8,32,64,128
# 2. Page-size neighbors.
leg page16             --slots 64  --page-size 16 --chunk 16
leg page64             --slots 64  --page-size 64 --chunk 16
# 3. Batch scaling: decode is weight-streaming bound, so tok/s should rise
#    with slots until attention/page reads dominate.
leg slots96            --slots 96  --page-size 32 --chunk 16 --sweep-chunks 32,64
leg slots128           --slots 128 --page-size 32 --chunk 16 --sweep-chunks 32,64,128
# 4. Pallas A/B: same shape, kernel off (env prefix passes through).
OLLAMAMQ_NO_PALLAS=1 leg slots128_jnp --slots 128 --page-size 32 --chunk 16 --sweep-chunks 32
# 5. Full-sampler leg (Ollama defaults) on the larger batch.
leg slots128_sampled   --slots 128 --page-size 32 --chunk 16 --sweep-chunks 32 --sampled

echo "sweep done -> $OUT" >&2
