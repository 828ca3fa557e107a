#!/bin/bash
# How the spreads in PERF.md were measured (two sets A and B with the same seeds, then traced runs).
# usage: benchmark/measure_sets.sh <cell> <seconds> <nruns> <ntraced>  (from the checkout root)
cell=$1; secs=$2; n=$3; nt=$4
mkdir -p chiprun_out/sets
seeds=(2147483659 3123456789 4023456811 1098765433 3999999979 2500000003 2718281829 3141592653)
one() { # set index seed trace
  python3 benchmark/run.py --workload $cell --seed $3 --seconds $secs --trace $4 2> chiprun_out/sets/$cell.$1.$2.err | tail -1 > chiprun_out/sets/$cell.$1.$2.json
  local rc=${PIPESTATUS[0]}
  echo "$cell $1 $2 seed=$3 rc=$rc $(cut -c1-$5 chiprun_out/sets/$cell.$1.$2.json)"
}
for set in A B; do
  for i in $(seq 0 $((n-1))); do one $set $i ${seeds[$i]} 0 230; done
done
for i in $(seq 0 $((nt-1))); do one T $i $((1500000001 + 1000003 * i)) 1 700; done
