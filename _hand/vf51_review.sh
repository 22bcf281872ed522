#!/bin/bash
# PR 51, after the review: `correct` now holds every envelope to the stored
# GOLDEN numbers (references/canneal-dvfs-256-vfsweep.json: `golden`) as well
# as to the cpu-backend digests.  One call (one chip):
#   chiprun --chips 1 --timeout 3500 -- bash _hand/vf51_review.sh
# The change runs from _proof/change51 (git archive of the write-tree: the
# committed files), the parent from _proof/parent51 (the parent commit + this
# PR's benchmark files), both made as _hand/vf51_all.sh's header says.
#   1. one traced run and six more seeds of the new cell (every envelope inside
#      the golden envelope, every digest the stored one);
#   2. the control (the shipped single DVFS domain) through the harness's own
#      comparison: must come out NOT correct, by the envelope as by the digest;
#   3. the parent on the new cell: must exit non-zero at once.
# `bash _hand/vf51_review.sh seeds <seed> ...` runs only those seeds of the
# new cell, untraced (a further set, to see how often the machine's stalls
# fall into a 3-grid window).
OUT=$PWD/chiprun_out; mkdir -p $OUT
C=_proof/change51; P=_proof/parent51; N=vfsweep256-canneal
echo CACHE=$JAX_COMPILATION_CACHE_DIR; date -u +%H:%M:%S
run() {  # seed trace tag
  (cd $C && timeout 900 python3 benchmark/run.py --workload $N --seed $1 --seconds 40 --trace $2) > $OUT/$3.log 2>&1
  echo "== $3 rc=$? $(date -u +%H:%M:%S)"; tail -1 $OUT/$3.log | cut -c1-700
}
if [ "$1" = seeds ]; then
  shift; for s in "$@"; do run $s 0 vf_rev_$s; grep -h "^grids:" $OUT/vf_rev_$s.log | cut -c1-200; done
  date -u +%H:%M:%S; exit 0
fi
run 3300000007 1 vf_rev_t1
grep -E "^check .*(golden|digest)|^metric (run_fetch_ms|home_side_busy_share|power_demux_ms|served_)" $OUT/vf_rev_t1.log | cut -c1-220
for s in 3300000101 3300000202 3300000303 3300000404 3300000505 3300000606; do run $s 0 vf_rev_$s; done
grep -h "^check envelopes outside" $OUT/vf_rev_33*.log
(cd $C && timeout 1500 python3 benchmark/control.py --workload $N --seed 3300000999 --seconds 40) > $OUT/vf_rev_control.log 2>&1
echo "== control rc=$? (0: it came out not correct) $(date -u +%H:%M:%S)"
grep -E "^check .*(golden|digest)|^control" $OUT/vf_rev_control.log | cut -c1-220
(cd $P && timeout 300 python3 benchmark/run.py --workload $N --seed 1 --seconds 40 --trace 0) > $OUT/vf_rev_parent.log 2>&1
echo "== parent on the new cell rc=$? (must be non-zero, at once) $(date -u +%H:%M:%S)"; tail -2 $OUT/vf_rev_parent.log | cut -c1-300
date -u +%H:%M:%S
