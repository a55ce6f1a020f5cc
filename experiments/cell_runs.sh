#!/bin/bash
# Runs of benchmark/run.py in one chip call, the result line's numbers each:
#   chiprun -- bash experiments/cell_runs.sh <outdir> "<dir>:<cell>:<seed>:<trace>" ...
# <dir> is a checkout inside the repo ("." or .bench_parent / .bench_change /
# .bench_overlay). Every metric of the line is printed (experiments/lifecycle_runs.sh
# prints the set-up's), and a run that gives no line says how long it took to fail.
out=$1; shift
mkdir -p chiprun_out/$out
n=0
for spec in "$@"; do
  IFS=: read -r dir cell seed tr <<< "$spec"
  n=$((n+1)); tag=$(printf "%02d" $n)_${cell}_$(basename $dir | tr -d .)_t${tr}
  t0=$SECONDS
  (cd $dir && timeout 900 python3 benchmark/run.py --workload $cell --seed $seed --seconds 45 --trace $tr) \
    > chiprun_out/$out/$tag.out 2> chiprun_out/$out/$tag.err
  rc=$?
  echo "== $tag seed=$seed rc=$rc $((SECONDS - t0))s $(tail -n 1 chiprun_out/$out/$tag.out | python3 -c "
import json,sys
try:
    d=json.loads(sys.stdin.read()); print('correct',d['correct'],'failed',d['failed'],'peak',d['device'].get('memory_peak_bytes'),'busy',d['device'].get('busy_s'),'window',d['device'].get('window_s'),{k:v['value'] for k,v in d['metrics'].items()})
except Exception as e: print('no result line:', open('chiprun_out/$out/$tag.err').read().strip().splitlines()[-1][:300])")"
  grep -h "reference check\|checks:\|scopes:" chiprun_out/$out/$tag.err | sed 's/^\[bench [0-9:]*\] /   /' | cut -c1-700
done
echo "cache: $JAX_COMPILATION_CACHE_DIR $(du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null | cut -f1) MB"
