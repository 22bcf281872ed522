#!/bin/bash
# PR 51: the final tree's committed files (_proof/change51 = git archive of the
# write-tree) on the new cell: one traced run and six more seeds.
OUT=$PWD/chiprun_out; mkdir -p $OUT
C=_proof/change51
date -u +%H:%M:%S
run() {  # seed trace tag
  (cd $C && timeout 900 python3 benchmark/run.py --workload vfsweep256-canneal --seed $1 --seconds 40 --trace $2) > $OUT/$3.log 2>&1
  echo "== $3 rc=$? $(date -u +%H:%M:%S)"; tail -1 $OUT/$3.log | cut -c1-700
}
run 3200000007 1 vf_final_t1
for s in 3200000101 3200000202 3200000303 3200000404 3200000505 3200000606; do run $s 0 vf_final_$s; done
date -u +%H:%M:%S
