#!/bin/bash
# Traced runs of benchmark/run.py in one chip call, with what the scope.* readers said:
#   chiprun -- bash experiments/scope_runs.sh <outdir> "<dir>:<cell>:<seed>" ...
# <dir> is a checkout inside the repo ("." or .bench_parent / .bench_change / .bench_overlay). For each run: the
# result line's scope.* metrics beside step.device_ms, attention.device_ms and device.idle_share, the reader's
# `scopes:` line (ms a step by group and pass, the mixed and unresolved shares, what the map cost the program),
# whether anything compiled inside the window, and experiments/step_ops_in_trace.py's listing of the median
# step (every operation with its scope and pass, by label, by scope) in chiprun_out/<outdir>/<tag>.ops.txt.
out=$1; shift
mkdir -p chiprun_out/$out
n=0
for spec in "$@"; do
  IFS=: read -r dir cell seed <<< "$spec"
  n=$((n+1)); tag=$(printf "%02d" $n)_${cell}_$(basename $dir | tr -d .)
  (cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds 45 --trace 1) \
    > chiprun_out/$out/$tag.out 2> chiprun_out/$out/$tag.err
  echo "== $tag seed=$seed rc=$? $(tail -n 1 chiprun_out/$out/$tag.out | python3 -c "
import json,sys
try:
    d=json.loads(sys.stdin.read()); print('correct',d['correct'],'failed',d['failed'],{k:v['value'] for k,v in d['metrics'].items() if k.startswith(('scope.','step.device_ms','attention.device_ms','device.idle','loop.step_gap','moe.share_device','moe.device','kda.device','conv.device','ssm.device'))})
except Exception as e: print('no result line',e)")"
  grep -h "scopes: " chiprun_out/$out/$tag.err | sed 's/^.*scopes: /   scopes /'
  grep -h "checks: " chiprun_out/$out/$tag.err | sed 's/^.*checks: /   checks /'
  grep -h "set-up parts" chiprun_out/$out/$tag.err | sed 's/^.*set-up parts: /   parts /'
  (cd $dir && python3 experiments/step_ops_in_trace.py .bench_work/$cell) > chiprun_out/$out/$tag.ops.txt 2>&1
  sed -n '/^== by scope/,$p' chiprun_out/$out/$tag.ops.txt | sed 's/^/   /'
done
echo "cache: $JAX_COMPILATION_CACHE_DIR $(du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null | cut -f1) MB"
