#!/bin/bash
# The builder's proof of one cell, in one call on the chip: two sets of N runs
# with the same seeds in both, then (third argument 1) one traced run.
#   chiprun [--chips 4] -- bash benchmarks/prove.sh <workload> <N> [0|1]
# Stops at the first run that prints no result. Each run's output stays in
# the run's own directory under chiprun_out/ (nothing outside the checkout).
W=$1; N=$2; T=${3:-1}
SECONDS_=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
run() {  # set seed trace
  echo "RUN $1 $2 $3"
  D=chiprun_out/benchmarks/$W/prove_$1_seed$2_trace$3
  mkdir -p "$D"
  python3 benchmarks/run.py --workload "$W" --seed "$2" --seconds "$SECONDS_" --trace "$3" > "$D/stdout.txt" 2> "$D/stderr.txt"
  rc=$?
  grep -E '^\{"(correct|note": "(window|compiles|warm_up|trace|reference|memory|not_correct))' "$D/stdout.txt" | cut -c1-6000
  grep -E 'WARNING' "$D/stderr.txt" | cut -c1-400
  if ! tail -n 1 "$D/stdout.txt" | grep -q '^{"correct"'; then
    echo "RUN FAILED rc=$rc"; tail -n 25 "$D/stderr.txt"; exit 1
  fi
}
for S in A B; do
  for i in $(seq 1 $N); do run $S $((2147480000 + i * 7919)) 0; done
done
if [ "$T" = "1" ]; then run T 2147480001 1; fi
