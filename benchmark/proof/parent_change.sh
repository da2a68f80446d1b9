#!/bin/bash
# One cell on the parent commit and on the change, in one call on one chip:
# parent, change, change, parent, each run a process of its own, the two
# sides of a pair on one seed.  The parent is a checkout unpacked under a
# directory of the repo that .gitignore lists (git archive <commit> | tar -x).
#   chiprun --timeout 3000 -- bash benchmark/proof/parent_change.sh <parent dir> <cell> <run_seconds> <seed 1> <seed 2> [<trace>]
# Result lines go to chiprun_out/<cell>.pairs.jsonl, one per run.
parent=$1; cell=$2; seconds=$3; seed1=$4; seed2=$5; trace=${6:-0}
root=$(pwd)
mkdir -p chiprun_out
out=$root/chiprun_out/$cell.pairs.jsonl
run() {  # side, dir, seed
  t0=$(date +%s)
  line=$(cd $2 && python3 benchmark/run.py --workload $cell --seed $3 --seconds $seconds --trace $trace 2> $root/chiprun_out/$cell.$1.err.log | tail -1)
  echo "{\"side\": \"$1\", \"seed\": $3, \"trace\": $trace, \"wall_s\": $(( $(date +%s) - t0 )), \"line\": ${line:-null}}" >> $out
  echo "$1 seed $3 trace $trace wall $(( $(date +%s) - t0 )) s: $(echo "$line" | cut -c1-1500)"
  [ -n "$line" ] || tail -20 $root/chiprun_out/$cell.$1.err.log
}
run parent $parent $seed1
run change . $seed1
run change . $seed2
run parent $parent $seed2
