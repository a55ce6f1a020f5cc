#!/bin/bash
# Runs of benchmark cells in one chip call with the program's timeline of the chip's queue beside each result line:
#   chiprun -- bash experiments/timeline_runs.sh <outdir> "<dir>:<cell>:<seed>:<trace>[:<options of chip_timeline_run.py>]" ...
# <dir> is a checkout inside the repo ("." or .bench_parent / .bench_change / .bench_nohand). A checkout that has
# experiments/chip_timeline_run.py runs through it (chiprun_out/<outdir>/<tag>.timeline.json: every loop.steps and
# loop.chip_wait span, the chip summary); one that has not (the parent of PR 72) runs benchmark/run.py alone. After a
# traced run, experiments/step_done_in_trace.py says how far the watcher's stamp lies behind the step program's end.
out=$1; shift
mkdir -p chiprun_out/$out
n=0
for spec in "$@"; do
  IFS=: read -r dir cell seed tr opts <<< "$spec"
  n=$((n+1)); tag=$(printf "%02d" $n)_${cell}_$(basename $dir | tr -d .)_t${tr}
  t0=$SECONDS
  if [ -f $dir/experiments/chip_timeline_run.py ]; then
    (cd $dir && timeout 1200 python3 experiments/chip_timeline_run.py --out $out --tag $tag $opts -- --workload $cell --seed $seed --seconds 45 --trace $tr) \
      > chiprun_out/$out/$tag.out 2> chiprun_out/$out/$tag.err
    rc=$?
    # a checkout writes under its own chiprun_out: bring the file to the repo's
    [ "$dir" != "." ] && [ -f $dir/chiprun_out/$out/$tag.timeline.json ] && mv $dir/chiprun_out/$out/$tag.timeline.json chiprun_out/$out/
  else
    (cd $dir && timeout 1200 python3 benchmark/run.py --workload $cell --seed $seed --seconds 45 --trace $tr) \
      > chiprun_out/$out/$tag.out 2> chiprun_out/$out/$tag.err
    rc=$?
  fi
  echo "== $tag seed=$seed rc=$rc $((SECONDS - t0))s $(tail -n 1 chiprun_out/$out/$tag.out | python3 -c "
import json,sys
try:
    d=json.loads(sys.stdin.read()); print('correct',d['correct'],'failed',d['failed'],'busy',d['device'].get('busy_s'),'window',d['device'].get('window_s'),{k:v['value'] for k,v in d['metrics'].items() if not k.startswith(('scope.','lifecycle.','attention.','moe.','step.mfu'))}, 'idle_gaps', (d.get('breakdown') or {}).get('idle_gaps'))
except Exception as e: print('no result line:', open('chiprun_out/$out/$tag.err').read().strip().splitlines()[-1][:300])")"
  grep -h "^timeline \|window: \|checks:" chiprun_out/$out/$tag.err | sed 's/^\[bench [0-9:]*\] /   /' | cut -c1-900
  if [ "$tr" = 1 ] && [ -f $dir/experiments/step_done_in_trace.py ]; then
    (cd $dir && python3 experiments/step_done_in_trace.py .bench_work/$cell 2>&1 | tail -n 1 | sed 's/^/   step_done /')
  fi
done
echo "cache: $JAX_COMPILATION_CACHE_DIR $(du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null | cut -f1) MB"
