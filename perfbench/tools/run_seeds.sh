#!/bin/bash
# run_seeds.sh "<cell> [<cell> ...]" <seconds> "<seed> [<seed> ...]" [trace=0] [extra run.py arguments...]
# For each seed, each cell in turn: cells of one configuration then share the
# seed's loaded cluster (perfbench/.cache). Output: chiprun_out/seeds/.
cells=$1; seconds=$2; seeds=$3; trace=${4:-0}; shift 4 2>/dev/null || shift $#
out=chiprun_out/seeds
mkdir -p $out
for seed in $seeds; do
  for cell in $cells; do
    log=$out/$cell.$seed.$trace.log
    start=$(date +%s)
    python3 -m perfbench.run --workload $cell --seed $seed --seconds $seconds --trace $trace "$@" > $log 2>&1
    rc=$?
    echo "rc=$rc wall=$(( $(date +%s) - start ))s" >> $log
    grep '^\[perfbench\] \(set-up\|window\|trace\|checked\|failed\)' $log | cut -c1-400
    if [ $rc -eq 0 ]; then
      line=$(grep '^{"correct"' $log | tail -n 1)
      echo "{\"cell\": \"$cell\", \"seed\": $seed, \"trace\": $trace, \"line\": $line}" >> $out/lines.jsonl
      echo "$cell seed=$seed trace=$trace: $line" | cut -c1-900
    else
      echo "$cell seed=$seed trace=$trace FAILED rc=$rc"; grep -v 'hugepage\|warnings.warn' $log | tail -n 15
    fi
    mkdir -p $out/roles/$cell.$seed.$trace
    cp perfbench_out/$cell/$seed/*.log perfbench_out/$cell/$seed/*.json $out/roles/$cell.$seed.$trace/ 2>/dev/null
  done
done
