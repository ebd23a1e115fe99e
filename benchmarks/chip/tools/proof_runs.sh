#!/bin/bash
# The readings that prove the first two cells, in one process after another
# on one machine with a chip, from the checkout's root:
#   bash benchmarks/chip/tools/proof_runs.sh [out_dir]
# A trace fixture; burst512 cold, warm and traced; chat's knee sweep, with
# its rate set from it; a traced chat run; control readings on three seeds
# a cell; then two sets of six runs a cell (the same seeds in both sets).
# Each step's output goes to <out_dir>/<step>.out and .err; a step is
# skipped once fewer than 150 of the script's 3,450 seconds remain.
set -u
OUT=${1:-chiprun_out/proof}; mkdir -p "$OUT"
export PYTHONUNBUFFERED=1
B=benchmarks/chip
T0=$(date +%s)
step() {
  local name=$1; shift
  local left=$(( 3450 - ($(date +%s) - T0) ))
  if [ $left -lt 150 ]; then echo "== $name skipped (time)"; return; fi
  local t0=$(date +%s)
  timeout $(( $1 < left ? $1 : left )) "${@:2}" > "$OUT/$name.out" 2> "$OUT/$name.err"
  local rc=$?
  echo "== $name rc=$rc secs=$(( $(date +%s) - t0 ))"
  tail -c 700 "$OUT/$name.out"; echo; tail -n 8 "$OUT/$name.err" | cut -c1-300
}
run() { step "$1" "$2" python3 $B/run.py --workload "$3" --seed "$4" --seconds 51 --trace "$5"; }
step fixture 240 python3 $B/tools/record_trace_fixture.py "$OUT/trace"
run b_cold 1300 gector-base.burst512 3000000001 0
run b_warm 300 gector-base.burst512 102 0
run b_trace 360 gector-base.burst512 103 1
step c_sweep 1500 python3 $B/tools/sweep.py --workload qwen2-0.5b.chat --seed 7 --seconds 15 --rates 4,6,8,10,12,15,18,22
python3 $B/tools/pick_rate.py "$OUT/c_sweep.out" $B/traffic/chat.json 1.5 | tee "$OUT/chat_rate.txt"
cp $B/traffic/chat.json "$OUT/chat.json"
run c_trace 400 qwen2-0.5b.chat 201 1
step b_ctrl 500 python3 $B/tools/control.py --workload gector-base.burst512 --seeds 111,112,113 --seconds 3
step c_ctrl 600 python3 $B/tools/control.py --workload qwen2-0.5b.chat --seeds 211,212,213 --seconds 10
for s in 1 2; do for seed in 4101 4102 4103 4104 4105 4106; do run b_set${s}_$seed 300 gector-base.burst512 $seed 0; done; done
for s in 1 2; do for seed in 5101 5102 5103 5104 5105 5106; do run c_set${s}_$seed 360 qwen2-0.5b.chat $seed 0; done; done
echo "total secs $(( $(date +%s) - T0 ))"
