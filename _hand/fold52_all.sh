#!/bin/bash
# PR 52 on the chip, one chip a call:
#   chiprun --chips 1 --timeout 3500 -- bash _hand/fold52_all.sh A
#   chiprun --chips 1 --timeout 3500 -- bash _hand/fold52_all.sh B
# The change runs from _proof/change52 (git archive of the write-tree: the
# committed files), the parent from _proof/parent52 (git archive of
# a9eeb4d); this PR edits no benchmark file, so nothing is laid over.
#   rm -rf _proof/change52 _proof/parent52; mkdir -p _proof/change52 _proof/parent52
#   git add -A; git archive $(git write-tree) | tar -x -C _proof/change52
#   git archive a9eeb4df5c3792683382bc1184293869a0e0abf5 | tar -x -C _proof/parent52
# A: step 0 (_hand/fold52.py), then the claimed cell parent / change / change
#    (traced) / parent (traced) / change / parent, then one pair of
#    canneal1024-dvfs (staged, solo: the rule never fires) and of
#    campaign64-dram (served, not staged: the control), as the clock allows.
# B: four more pairs of the claimed cell (the side that runs first
#    alternating), then one pair each of memstress1024-coh, a2a1024-fftskel
#    and memstress1024-atac (staged, solo), as the clock allows.
T0=$(date +%s); LIMIT=${LIMIT:-3350}
OUT=$PWD/chiprun_out/pr52; mkdir -p $OUT
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache; fi
C=$PWD/_proof/change52; P=$PWD/_proof/parent52; N=vfsweep256-canneal
left() { echo $(( LIMIT - ( $(date +%s) - T0 ) )); }
run() { # dir cell seed trace tag need_s
  if [ $(left) -lt $6 ]; then echo "SKIP $5: $(left) s left, needs $6"; return 1; fi
  t0=$(date +%s)
  ( cd $1 && timeout $(( $(left) - 20 )) python3 benchmark/run.py --workload $2 --seed $3 --seconds 40 --trace $4 ) > $OUT/$5.out 2> $OUT/$5.err
  echo "== $5 rc=$? in $(( $(date +%s) - t0 )) s: $(tail -n 1 $OUT/$5.out | cut -c1-600)"
}
traced() { grep -E "^(scope |top |traced slice|unscoped )" $OUT/$1.out | cut -c1-200 | head -n ${2:-45}; }
date -u +%H:%M:%S
if [ "$1" = A ]; then
  timeout 400 python3 _hand/fold52.py > $OUT/fold52.out 2> $OUT/fold52.err
  echo "== step 0 rc=$? $(left) s left"; grep -E "^(device|entry|flush)" $OUT/fold52.out
  run $P $N 3520000101 0 A_vf_parent_1 700
  run $C $N 3520000101 0 A_vf_change_1 700
  run $C $N 3520000202 1 A_vf_change_t 400 && traced A_vf_change_t
  run $P $N 3520000202 1 A_vf_parent_t 400 && traced A_vf_parent_t 30
  run $C $N 3520000303 0 A_vf_change_3 250
  run $P $N 3520000303 0 A_vf_parent_3 250
  run $P canneal1024-dvfs 3520000404 0 A_cd_parent 700 && \
  run $C canneal1024-dvfs 3520000404 0 A_cd_change 650
  run $P campaign64-dram 3520000505 0 A_c64_parent 500 && \
  run $C campaign64-dram 3520000505 0 A_c64_change 200
else
  # four more pairs of the claimed cell, the side that runs first alternating
  run $C $N 3520001101 0 B_vf_change_1 700
  run $P $N 3520001101 0 B_vf_parent_1 250
  run $P $N 3520001202 0 B_vf_parent_2 250
  run $C $N 3520001202 0 B_vf_change_2 250
  run $C $N 3520001303 0 B_vf_change_3 250
  run $P $N 3520001303 0 B_vf_parent_3 250
  run $P $N 3520001404 0 B_vf_parent_4 250
  run $C $N 3520001404 0 B_vf_change_4 250
  run $P memstress1024-coh 3520001707 0 B_coh_parent 700 && \
  run $C memstress1024-coh 3520001707 0 B_coh_change 650
  run $P a2a1024-fftskel 3520001808 0 B_a2a_parent 700 && \
  run $C a2a1024-fftskel 3520001808 0 B_a2a_change 650
  run $P memstress1024-atac 3520001909 0 B_atac_parent 900 && \
  run $C memstress1024-atac 3520001909 0 B_atac_change 850
fi
date -u +%H:%M:%S
