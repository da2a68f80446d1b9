#!/bin/bash
# The contract's measurement of one cell: two sets of 6 runs on the same six
# seeds and three traced runs on three more, each run a process of its own.
#   chiprun --timeout 3600 -- bash benchmark/proof/full_sets.sh <cell> <run_seconds>
# Result lines go to chiprun_out/<cell>.sets.jsonl, one per run.
cell=$1; seconds=$2
mkdir -p chiprun_out
out=chiprun_out/$cell.sets.jsonl
: > $out
run() {  # set, seed, trace
  t0=$(date +%s)
  line=$(python3 benchmark/run.py --workload $cell --seed $2 --seconds $seconds --trace $3 2> chiprun_out/$cell.err.log | tail -1)
  rc=$?
  echo "{\"set\": \"$1\", \"seed\": $2, \"trace\": $3, \"rc\": $rc, \"wall_s\": $(( $(date +%s) - t0 )), \"line\": ${line:-null}}" >> $out
  echo "set $1 seed $2 trace $3 rc $rc wall $(( $(date +%s) - t0 )) s: $(echo "$line" | cut -c1-420)"
  [ -n "$line" ] || tail -20 chiprun_out/$cell.err.log
}
run traced 2200000033 1      # first: it compiles, and a fault shows early
for set in 1 2; do
  for seed in 2147483659 2390001217 2718281828 3141592653 3735928559 4000000007; do
    run $set $seed 0
  done
done
for seed in 2600000077 3300000011; do
  run traced $seed 1
done
