#!/bin/bash
# Runs of one cell in a row, one process each, as the bounds and limits
# in PERF.md are measured:
#
#   benchmarks/chip/campaign.sh OUT TAG CELL SECONDS TRACE CONTROL SEED...
#
# Writes OUT/TAG_SEED.out and OUT/TAG_SEED.err for each seed, and prints
# one line a run (its exit code, the window's and the host's summary)
# followed by the start of its result line.
set -u
out=$1 tag=$2 cell=$3 secs=$4 trace=$5 control=$6
shift 6
mkdir -p "$out"
here=$(dirname "$0")
for seed in "$@"; do
  f=$out/${tag}_$seed
  python3 "$here/run.py" --workload "$cell" --seed "$seed" \
    --seconds "$secs" --trace "$trace" --control "$control" \
    > "$f.out" 2> "$f.err"
  rc=$?
  echo "$tag seed=$seed rc=$rc $(grep -m1 '^window' "$f.err")"
  grep -m1 '^host' "$f.err"
  grep '^control' "$f.err"
  tail -n 1 "$f.out" | cut -c1-700
done
exit 0
