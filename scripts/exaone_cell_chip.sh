#!/bin/bash
# The mixed-length cell on the chip, run after run in one call (they share
# the compile cache): scripts/exaone_cell_chip.sh <tag> <trace> <seed> [...]
# Each run's result line goes to chiprun_out/<tag>/<seed>.t<trace>.json and
# its log's tail to .err. DIR=<checkout> runs another checkout's files;
# TRAFFIC=<file> lays that traffic file over the cell's own first (in the
# machine's copy of the checkout, so the file lies outside chiprun_out/: a
# sizing experiment, nothing is committed). WORKLOAD=<cell> runs another
# cell (scripts/solar_cell_chip.sh, scripts/falconh1_cell_chip.sh).
# scripts/exaone_cell_chip.sh pairs <tag> <trace> <seed> [...]
# runs every seed on BOTH sides, the parent commit (unpacked under .parent/:
# git archive <parent> | tar -x -C .parent) and the working tree (or DIR=),
# parent first for the odd pairs and last for the even ones; the two sides
# of a pair share their seed, outputs under chiprun_out/<tag>/parent|change/.
if [ "$1" = pairs ]; then
  tag=$2; shift 2; n=0
  while [ $# -ge 2 ]; do
    n=$((n + 1)); order="parent change"; [ $((n % 2)) = 0 ] && order="change parent"
    for side in $order; do
      dir=${DIR:-.}; [ $side = parent ] && dir=.parent
      echo "== pair $n $side"
      DIR=$dir bash "$0" $tag/$side $1 $2
    done
    shift 2
  done
  exit 0
fi
cell=${WORKLOAD:-k-exaone-236b-a23b.batch-mixedlength}
tag=$1; shift
here=$(pwd); mkdir -p chiprun_out/$tag
if [ -n "$TRAFFIC" ]; then
  # chiprun leaves chiprun_out/ out of the machine's copy: a file there is
  # not laid and the cell would run its own sizes under the experiment's tag
  cp "$TRAFFIC" ${DIR:-.}/benchmark/traffic/${cell##*.}.json \
    || { echo "TRAFFIC=$TRAFFIC not laid" >&2; exit 2; }
  grep -E '"(pool|min|max)"' ${DIR:-.}/benchmark/traffic/${cell##*.}.json | tr -d '\n'; echo
fi
while [ $# -ge 2 ]; do
  trace=$1; seed=$2; shift 2
  out=$here/chiprun_out/$tag/$seed.t$trace
  t0=$(date +%s)
  (cd ${DIR:-.} && python3 -m benchmark.run --workload $cell \
    --seed $seed --seconds ${SECONDS_:-51} --trace $trace > $out.json 2> $out.log)
  echo "rc=$? seed=$seed trace=$trace took=$(( $(date +%s) - t0 ))s $(tail -c 2600 $out.json)"
  grep -E "compared|requests:|serve_tokens|setup_s|engine built|correctness done|NO RESULT|Error|metric |tail:" $out.log | tail -n 30
  tail -n 400 $out.log > $out.err; rm -f $out.log
  # the traced tail's modules and its forty heaviest ops
  cp ${DIR:-.}/benchmark_out/$cell/trace_summary.json \
    $out.summary.json 2>/dev/null
  # every token's arrival (KEEP_LOADGEN=1: a run far under its set is held
  # against it, PERF.md section 7)
  [ -n "$KEEP_LOADGEN" ] && gzip -c ${DIR:-.}/benchmark_out/$cell/loadgen.json \
    > $out.loadgen.json.gz 2>/dev/null
done
true
