#!/bin/bash
# Interleaved runs of one benchmark cell on two checkouts, in one chip
# call: tools/ab_cell.sh <cell> <trace 0|1> <out-prefix> <order> <seed>...
# <order> is a string of P (the checkout $AB_PARENT names) and C (this
# tree, or $AB_CHANGE); every seed is run in that order. The last
# stdout line of each run lands in chiprun_out/<out-prefix>.jsonl, its
# whole stdout (the harness's '#' lines) beside it.
cell=$1; trace=$2; out=$3; order=$4; shift 4
root=$(pwd); mkdir -p "$root/chiprun_out"
for seed in "$@"; do
  for side in $(echo "$order" | grep -o .); do
    dir=$root; [ "$side" = P ] && dir=$root/${AB_PARENT:-.smoke_tree/parent}
    [ "$side" = C ] && [ -n "$AB_CHANGE" ] && dir=$root/$AB_CHANGE
    log=$root/chiprun_out/$out.$side.$seed
    (cd "$dir" && python3 benchmark/run.py --workload "$cell" --seed "$seed" \
       --seconds 51 --trace "$trace" >"$log.out" 2>"$log.err")
    line=$(tail -n 1 "$log.out")
    echo "{\"side\": \"$side\", \"seed\": $seed, \"result\": $line}" >> "$root/chiprun_out/$out.jsonl"
    echo "$side $seed $(echo "$line" | head -c 400)"
  done
done
