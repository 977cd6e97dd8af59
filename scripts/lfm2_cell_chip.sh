#!/bin/bash
# The new cell on the chip, run after run in one call (they share the compile
# cache): scripts/lfm2_cell_chip.sh <tag> <trace> <seed> [<trace> <seed> ...]
# Each run's result line goes to chiprun_out/<tag>/<seed>.t<trace>.json and
# its log's tail to .err.
tag=$1; shift
mkdir -p chiprun_out/$tag
while [ $# -ge 2 ]; do
  trace=$1; seed=$2; shift 2
  out=chiprun_out/$tag/$seed.t$trace
  python3 -m benchmark.run --workload ${WORKLOAD:-lfm2-24b-a2b.batch-longanswer} \
    --seed $seed --seconds ${SECONDS_:-51} --trace $trace > $out.json 2> $out.log
  echo "rc=$? seed=$seed trace=$trace $(tail -c 1200 $out.json | head -c 1200)"
  grep -E "compared|requests:|serve_tokens|setup_s|engine built|NO RESULT|Error|metric " $out.log | tail -n 30
  tail -n 400 $out.log > $out.err; rm -f $out.log
done
