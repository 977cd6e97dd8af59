#!/bin/bash
# ISSUE 41 on the chip: serving cells through scripts/round_pacing_chip.py
# (benchmark.run with the tail's programs, its largest device ops and the
# window's counters printed, `prefill_row_programs_dispatched` and
# `prefill_programs_with_end` among them), run after run in ONE call, each run
# on the side named:
#   scripts/head_last_chip.sh <tag> <cell> <side> <trace> <seed> [<cell> <side> <trace> <seed> ...]
# side: "change" (the working tree), "proof" (.proof/: the committed files,
# `git archive $(git write-tree) | tar -x -C .proof`) or "parent" (.parent/,
# unpacked with `git archive <parent> | tar -x -C .parent`; it gets this
# tree's copy of the script, which reads a checkout without the counters
# too). Both sides of a
# pair share a seed; every other run gets its own. Result lines and log tails:
# chiprun_out/<tag>/.
tag=$1; shift 1
mkdir -p chiprun_out/$tag
here=$(pwd)
cp scripts/round_pacing_chip.py .parent/scripts/round_pacing_chip.py 2>/dev/null
n=0
while [ $# -ge 4 ]; do
  cell=$1; side=$2; trace=$3; seed=$4; shift 4; n=$((n + 1))
  dir=$here; [ "$side" = parent ] && dir=$here/.parent
  [ "$side" = proof ] && dir=$here/.proof
  out=$here/chiprun_out/$tag/$n.$cell.$side.s$seed.t$trace
  (cd $dir && python3 scripts/round_pacing_chip.py --workload $cell \
     --seed $seed --seconds 51 --trace $trace > $out.json 2> $out.log)
  echo "rc=$? run=$n $side $cell seed=$seed trace=$trace"
  python3 - $out.json <<'PY'
import json, sys
try:
    r = json.loads(open(sys.argv[1]).read().strip().splitlines()[-1])
except Exception as e:
    print("  no result line:", e); sys.exit(0)
keep = {k: r.get(k) for k in ("correct", "failed", "attempted")}
for k, v in (r.get("metrics") or {}).items():
    if k.startswith(("itl_", "serve_tokens", "setup_s", "step.", "kv.preempt",
                     "engine.decode_occ", "engine.sched_busy")):
        keep[k] = v["value"]
dev = r.get("device") or {}
for k in ("busy_s", "window_s", "memory_peak_bytes"):
    keep[k] = dev.get(k)
print("  " + json.dumps(keep))
ops = (r.get("breakdown") or {}).get("device_ops") or []
print("  device_ops: " + json.dumps([[n, round(s, 4)] for n, s in ops[:12]]))
PY
  grep -E "compared beside|requests:|window counters|tail program|tail chunk program|tail jit__lambda|NO RESULT|Error|compiled inside" $out.log | tail -n 18
  tail -n 300 $out.log > $out.err; rm -f $out.log
  # the traced tail's modules and its forty heaviest ops
  [ "$trace" != 0 ] && cp $dir/benchmark_out/$cell/trace_summary.json \
    $out.summary.json 2>/dev/null
  true
done
