#!/bin/bash
# ISSUE 53 on the chip: a cell's start-up by phase, on the parent commit and
# on the change, run after run in ONE call on one machine. Every run is
# scripts/round_pacing_chip.py ... --trace 2 (benchmark.run with the program's
# start-up clock, the compile keys over the window and the tail printed beside
# its result line), so the parent reads under this PR's benchmark files too:
#   mkdir -p .parent && git archive <parent> | tar -x -C .parent
#   cp -r BENCHMARK.json benchmark tests scripts/round_pacing_chip.py -> .parent
#
#   scripts/startup_chip.sh cells <tag> <seed> <cell> [<cell> ...]
# a cell: the change's FIRST run (seed), the parent's (seed+1: where the
# machine sets no JAX_COMPILATION_CACHE_DIR the change's cache is copied to
# the parent's checkout first) and the change again (seed+1). A program that
# holds a Pallas kernel is another cache entry in another checkout (the
# kernel's serialized body carries source paths), so each side's first run
# compiles those and the third run is the warm one; with TRACE_START=1 a
# fourth run, the change with --trace-start (seed+2).
#
#   scripts/startup_chip.sh pair <tag> <seed> <cell> [<cell> ...]
# the parent and the change on one seed, once each (for a cell whose programs
# both checkouts have in the machine's cache by now).
#
#   scripts/startup_chip.sh aa <tag> <seed> <cell>
# the parent's FIRST run (seed) and the change's (seed); then the SAME tree
# in two checkouts, .proof/ (the committed files: git archive $(git
# write-tree) | tar -x -C .proof) as "parent" and the working tree as
# "change": .proof's own first run (seed+1), then two pairs in the driver's
# order, both sides warm (seed+2, seed+3), with a window of 10 s: these read
# the start.
# DIR=<checkout> runs the change from another copy than the working tree.
mode=$1; tag=$2; seed=$3; shift 3
mkdir -p chiprun_out/$tag
here=$(pwd)
change=${DIR:-.}
window=51
run() {   # name dir cell seed [extra]
  out=$here/chiprun_out/$tag/$3.$1.s$4
  t0=$(date +%s)
  (cd $2 && python3 scripts/round_pacing_chip.py --workload $3 --seed $4 \
     --seconds $window --trace ${TRACE:-2} $5 > $out.json 2> $out.log)
  echo "rc=$? $1 $3 seed=$4 wall=$(( $(date +%s) - t0 ))s $(head -c 2600 $out.json)"
  grep -E "start-up:|start programs|start capture|compile keys|program_kernels|engine built|trainer built|reference done|correctness done|window opens|NO RESULT|Error" \
     $out.log | cut -c1-1800
  grep -E "round_pacing|benchmark\]|Error|Traceback" $out.log | tail -n 400 > $out.err
  rm -f $out.log
}
share_cache() {   # from to: a checkout's compile cache, copied whole
  if [ -n "$JAX_COMPILATION_CACHE_DIR" ] || [ ! -d $1/.jax_cache ]; then
    echo "cache: JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR (one cache for every checkout: nothing to copy)"
    return
  fi
  mkdir -p $2/.jax_cache && cp -r $1/.jax_cache/. $2/.jax_cache/
  echo "cache: $(ls $1/.jax_cache | wc -l) files of $1 copied to $2"
}
if [ "$mode" = cells ]; then
  for cell in "$@"; do
    run change-cold $change $cell $seed
    share_cache $change .parent
    run parent .parent $cell $((seed + 1))
    run change $change $cell $((seed + 1))
    if [ -n "$TRACE_START" ]; then
      run change-trace-start $change $cell $((seed + 2)) --trace-start
    fi
  done
elif [ "$mode" = pair ]; then
  for cell in "$@"; do        # both sides warm by now: the driver's order,
    if [ "$ORDER" = cp ]; then  # or ORDER=cp the change first
      run change $change $cell $seed
      run parent .parent $cell $seed
    else
      run parent .parent $cell $seed
      run change $change $cell $seed
    fi
  done
else
  cell=$1
  run parent-cold .parent $cell $seed
  run change-cold $change $cell $seed
  share_cache $change .proof
  window=10         # what follows reads the start, not the window
  run aa-first .proof $cell $((seed + 1))
  for pair in 2 3; do
    run aa-parent .proof $cell $((seed + pair))
    run aa-change $change $cell $((seed + pair))
  done
fi
