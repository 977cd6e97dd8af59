#!/bin/bash
# ISSUE 39 on the chip: one serving cell through scripts/round_pacing_chip.py
# (benchmark.run with the tail's programs, the window's counters and
# `weights_relaid_bytes` printed), run after run in one call, each run on the
# side named: scripts/weight_layout_chip.sh <tag> <cell> <side> <trace> <seed> [<side> <trace> <seed> ...]
# side: "change" (the working tree) or "parent" (.parent/, unpacked with
# `git archive <parent> | tar -x -C .parent`; it gets this tree's copy of the
# script, which runs on a checkout without the mechanism too). The first run
# of a side compiles, the later ones load from the compile cache: a relaid
# weight has to survive both. Result lines and log tails: chiprun_out/<tag>/.
tag=$1; cell=$2; shift 2
mkdir -p chiprun_out/$tag
here=$(pwd)
cp scripts/round_pacing_chip.py .parent/scripts/round_pacing_chip.py 2>/dev/null
n=0
while [ $# -ge 3 ]; do
  side=$1; trace=$2; seed=$3; shift 3; n=$((n + 1))
  dir=$here; [ "$side" = parent ] && dir=$here/.parent
  out=$here/chiprun_out/$tag/$n.$side.s$seed.t$trace
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
    if k.startswith(("itl_", "serve_tokens", "setup_s", "step.", "client.ttft",
                     "engine.queue", "engine.decode_occ")):
        keep[k] = v["value"]
dev = r.get("device") or {}
for k in ("busy_s", "window_s", "memory_peak_bytes"):
    keep[k] = dev.get(k)
print("  " + json.dumps(keep))
ops = (r.get("breakdown") or {}).get("device_ops") or []
print("  device_ops: " + json.dumps([[n, round(s, 4)] for n, s in ops[:10]]))
PY
  grep -E "compared beside|requests:|weights_relaid_bytes|tail program|client itl|NO RESULT|Error|engine built|decode ladder|correctness done|window opens" $out.log | tail -n 18
  tail -n 300 $out.log > $out.err; rm -f $out.log
done
