#!/bin/bash
# The long-document cell on the chip, run after run in one call:
# scripts/solar_cell_chip.sh <tag> <trace> <seed> [...]
# scripts/exaone_cell_chip.sh with this cell's name: the same outputs under
# chiprun_out/<tag>/, the same DIR= (the parent under this PR's benchmark
# files has to fail at once) and TRAFFIC= (a sizing experiment).
# scripts/solar_cell_chip.sh pairs <tag> <trace> <seed> [...]
# runs every seed on BOTH sides, the parent commit (unpacked under .parent/:
# git archive <parent> | tar -x -C .parent) and the working tree (or DIR=),
# parent first for the odd pairs and last for the even ones; the two sides
# of a pair share their seed, outputs under chiprun_out/<tag>/parent|change/.
export WORKLOAD=solar-open2-250b.batch-longdoc
cell=$(dirname "$0")/exaone_cell_chip.sh
if [ "$1" != pairs ]; then exec bash "$cell" "$@"; fi
tag=$2; shift 2; n=0
while [ $# -ge 2 ]; do
  n=$((n + 1)); order="parent change"; [ $((n % 2)) = 0 ] && order="change parent"
  for side in $order; do
    dir=${DIR:-.}; [ $side = parent ] && dir=.parent
    echo "== pair $n $side"
    DIR=$dir bash "$cell" $tag/$side $1 $2
  done
  shift 2
done
