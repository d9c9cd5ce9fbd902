#!/bin/bash
# the two sets of six runs of one cell (same seeds in both), each run a
# new process: bash benchmark/tools_sets.sh <cell> [seconds] [first seed] [sets]
cell=$1; seconds=${2:-45}; first=${3:-2300000000}; sets=${4:-A B}
mkdir -p chiprun_out/sets
out=chiprun_out/sets/$cell.jsonl
for set in $sets; do
  for i in 1 2 3 4 5 6; do
    seed=$((first + 104729 * i))
    log=chiprun_out/sets/${cell}_${set}_${i}.log
    t0=$(date +%s)
    python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 > $log 2>&1
    rc=$?
    wall=$(( $(date +%s) - t0 ))
    line=$(tail -n 1 $log)
    echo "{\"set\": \"$set\", \"seed\": $seed, \"rc\": $rc, \"wall_s\": $wall, \"result\": $line}" >> $out
    echo "$set $i seed $seed rc $rc wall $wall: $(echo $line | cut -c1-330)"
    grep -E "^reference|OVER|SLOW|^window|^request failed" $log | cut -c1-200
  done
done
