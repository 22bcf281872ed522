#!/bin/bash
# PR 51: everything that needs the chip, in one call (44 chip-minutes):
#   chiprun --chips 1 --timeout 3500 -- bash _hand/vf51_all.sh
# The change runs from _proof/change51 (git archive of the write-tree: the
# committed files), the parent from _proof/parent51 (the parent commit + this
# PR's benchmark files).  Make both first, here (`_proof/` is git-ignored):
#   git add -A; rm -rf _proof/change51 _proof/parent51
#   mkdir -p _proof/change51 _proof/parent51
#   git archive $(git write-tree) | tar -x -C _proof/change51
#   git archive <parent commit> | tar -x -C _proof/parent51
#   cp BENCHMARK.json _proof/parent51/; cp -r benchmark/. _proof/parent51/benchmark/
OUT=$PWD/chiprun_out; mkdir -p $OUT
C=_proof/change51; P=_proof/parent51
echo CACHE=$JAX_COMPILATION_CACHE_DIR; date -u +%H:%M:%S
run() {  # dir cell seed trace tag
  (cd $1 && timeout 1500 python3 benchmark/run.py --workload $2 --seed $3 --seconds 40 --trace $4) > $OUT/$5.log 2>&1
  echo "== $5 rc=$? $(date -u +%H:%M:%S)"; tail -1 $OUT/$5.log | cut -c1-900
}
N=vfsweep256-canneal
run $C $N 0 0 vf_cold_t0
grep -E "^set-up|^first grid|^grids:|^engine iter|^slowest" $OUT/vf_cold_t0.log
run $C $N 2147483659 1 vf_warm_t1
grep -E "^set-up|^grids:|^traced slice|^scope |^metric |^lane-iter|^stream |^setup-trace traced|^scope trace" $OUT/vf_warm_t1.log | cut -c1-200
for s in 3000000011 3000000022 3000000033 3000000044 3000000055 3000000066; do run $C $N $s 0 vf_seed_$s; done
(cd $P && timeout 300 python3 benchmark/run.py --workload $N --seed 1 --seconds 40 --trace 0) > $OUT/vf_parent.log 2>&1; echo "== parent on the new cell rc=$? (must be non-zero, at once) $(date -u +%H:%M:%S)"; tail -3 $OUT/vf_parent.log | cut -c1-300
run $P campaign64-dram 3100000001 1 c64_parent_t1
run $C campaign64-dram 3100000001 1 c64_change_t1
run $C canneal1024-dvfs 3100000002 0 cd1024_change_t0
run $P canneal1024-dvfs 3100000002 0 cd1024_parent_t0
date -u +%H:%M:%S
