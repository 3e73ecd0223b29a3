#!/bin/bash
# The parent commit against the working tree in one call, both on ONE compile
# cache, which the parent warms first (so the change's lowerings and cache
# hits say whether it runs the parent's programs):
#   mkdir -p .parent && git archive HEAD | tar -x -C .parent
#   bash perfbench/tools/ab.sh "CELL ..." SEED_UNTRACED SEED_TRACED [SECONDS]
# Per cell: parent, change (untraced, one seed), change, parent (traced, the
# other seed).  Logs under chiprun_out/ab/.
cells=$1; sa=$2; sb=$3; secs=${4:-45}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}
mkdir -p chiprun_out/ab
n=0
for cell in $cells; do
  for run in "parent $sa 0" "change $sa 0" "change $sb 1" "parent $sb 1"; do
    set -- $run; n=$((n + 1))
    log=$PWD/chiprun_out/ab/$(printf %02d $n)-$cell-$1-s$2-t$3.log
    dir=.; [ "$1" = parent ] && dir=.parent
    dbg=; [ "$1 $3" = "change 1" ] && dbg=$PWD/chiprun_out/ab/ctx
    (cd $dir && PB_DEBUG_DIR=$dbg python3 perfbench/run.py --workload "$cell" \
      --seed "$2" --seconds "$secs" --trace "$3" > "$log" 2>&1)
    echo "RUN $cell $1 seed=$2 trace=$3 RC=$? $(tail -n 1 "$log" | cut -c1-2500)"
    grep '"check"\|"lowerings"\|cache_hits\|roofline\|"phase": "engine built"\|first call done' "$log" \
      | sed "s/^/  /" | cut -c1-600
  done
done
