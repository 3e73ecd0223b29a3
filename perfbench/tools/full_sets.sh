#!/bin/bash
# Two sets of runs of one cell with the same seeds, as the bound rule asks:
#   bash perfbench/tools/full_sets.sh CELL SECONDS "SEED SEED ..."
cell=$1; secs=$2; seeds=$3
mkdir -p chiprun_out/sets
for set in ${SETS:-1 2}; do
  for s in $seeds; do
    python3 perfbench/run.py --workload "$cell" --seed "$s" --seconds "$secs" --trace 0 > "chiprun_out/sets/$cell-$set-$s.log" 2>&1
    echo "SET $set SEED $s RC=$? $(tail -n 1 chiprun_out/sets/$cell-$set-$s.log)"
    grep '"check"\|reference_s\|samples\|"requests"\|phase' "chiprun_out/sets/$cell-$set-$s.log" | sed "s/^/  $s: /" | cut -c1-700
  done
done
