#!/bin/bash
# One accepted cell on the parent commit (unpacked under .parent/) and on the
# working tree, in one call on one machine, the same seed on both sides:
# scripts/cells_parent_change_chip.sh <tag> <cell> <seed> <trace> [order]
# order: "pc" (parent first, the default) or "cp". DIR=<checkout> runs the
# change from another copy than the working tree (the committed files:
# git archive $(git write-tree) | tar -x -C .proof).
tag=$1; cell=$2; seed=$3; trace=$4; order=${5:-pc}
mkdir -p chiprun_out/$tag
here=$(pwd)
run() {   # side dir
  out=$here/chiprun_out/$tag/$cell.$1.s$seed.t$trace
  (cd $2 && python3 -m benchmark.run --workload $cell --seed $seed \
     --seconds 51 --trace $trace > $out.json 2> $out.log)
  echo "rc=$? $1 $cell seed=$seed trace=$trace $(head -c 900 $out.json)"
  grep -E "compared|requests:|window opens|NO RESULT|Error" $out.log | tail -n 8
  tail -n 300 $out.log > $out.err; rm -f $out.log
}
change=${DIR:-.}
if [ "$order" = pc ]; then run parent .parent; run change $change; else run change $change; run parent .parent; fi
