#!/bin/bash
# One cell, parent against change, on the same chip in one call: p c c p with a
# seed a pair, then a traced run of the sides named. The parent is unpacked in
# .bench_parent, the change (git archive of the tree to commit) in .bench_change.
#   chiprun -- bash experiments/side_by_side.sh <outdir> <cell> <seedA> <seedB> ["p c"] [order]
out=$1; cell=$2; a=$3; b=$4; traced=$5
mkdir -p chiprun_out/$out
run() { # side seed trace tag
  side=$1; seed=$2; tr=$3; tag=$4
  dir=.bench_change; [ $side = p ] && dir=.bench_parent
  (cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds 45 --trace $tr) \
    > chiprun_out/$out/${cell}_${tag}.out 2> chiprun_out/$out/${cell}_${tag}.err
  echo "$cell $tag side=$side seed=$seed trace=$tr rc=$? $(tail -n 1 chiprun_out/$out/${cell}_${tag}.out | python3 -c "
import json,sys
try:
    d=json.loads(sys.stdin.read()); print('correct',d['correct'],'failed',d['failed'],{k:v['value'] for k,v in d['metrics'].items()})
except Exception as e: print('no result line',e)")"
}
# A sixth argument gives another order, e.g. "pa pb tp ca cb tc" for a cell whose
# two steps do not fit the machine's compile cache together (each side cold once).
n=0
for step in ${6:-$([ "$a" != "-" ] && echo pa ca cb pb) $(for side in $traced; do echo t$side; done)}; do
  n=$((n+1))
  case $step in
    t?) run ${step#t} $((b+7)) 1 $step ;;
    ?a) run ${step%a} $a 0 $n${step%a} ;;
    ?b) run ${step%b} $b 0 $n${step%b} ;;
  esac
done
