#!/bin/bash
# Runs of benchmark/run.py in one chip call, one line each, with the set-up
# parts and the start-up tree beside the result:
#   chiprun -- bash experiments/lifecycle_runs.sh <outdir> "<dir>:<cell>:<seed>:<trace>[:fresh]" ...
# <dir> is a checkout inside the repo ("." or .bench_parent / .bench_change /
# .bench_overlay); "fresh" gives the run an empty compile cache directory.
out=$1; shift
mkdir -p chiprun_out/$out
n=0
for spec in "$@"; do
  IFS=: read -r dir cell seed tr fresh <<< "$spec"
  n=$((n+1)); tag=$(printf "%02d" $n)_${cell}_$(basename $dir | tr -d .)_t${tr}${fresh:+_$fresh}
  env=()
  [ -n "$fresh" ] && env=(JAX_COMPILATION_CACHE_DIR=$(mktemp -d))
  (cd $dir && env "${env[@]}" python3 benchmark/run.py --workload $cell --seed $seed --seconds 45 --trace $tr) \
    > chiprun_out/$out/$tag.out 2> chiprun_out/$out/$tag.err
  echo "== $tag rc=$? $(tail -n 1 chiprun_out/$out/$tag.out | python3 -c "
import json,sys
try:
    d=json.loads(sys.stdin.read()); print('correct',d['correct'],'failed',d['failed'],{k:v['value'] for k,v in d['metrics'].items() if k.startswith(('lifecycle','setup','tok','round_tok','wire','loop.step_gap','device.idle'))})
except Exception as e: print('no result line',e)")"
  grep -h "set-up parts" chiprun_out/$out/$tag.err | sed 's/^.*set-up parts: /   parts /'
  grep -h "lifecycle tree" chiprun_out/$out/$tag.err | sed 's/^.*lifecycle tree: /   tree /'
  grep -h "end to end" chiprun_out/$out/$tag.err | sed 's/^.*end to end[^:]*: /   e2e /'
done
echo "cache: $JAX_COMPILATION_CACHE_DIR $(du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null | cut -f1) MB"
