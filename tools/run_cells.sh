#!/bin/bash
# Benchmark cells of the parent and of this tree in one chip call, in the
# order given, so that both sides are measured on the same chip:
#   git archive HEAD | tar -x -C _parent        (both git-ignored)
#   chiprun --timeout 3000 -- bash tools/run_cells.sh <tag> <side:workload:seed:trace> ...
# side P runs in _parent/, C in this tree. Each run's whole output goes to
# chiprun_out/cells/<tag>_<side>_<workload>_<seed>_t<trace>.log; its
# [gaps] lines and its result line are echoed.
tag=$1; shift
root=$(cd "$(dirname "$0")/.." && pwd)
out=$root/chiprun_out/cells; mkdir -p "$out"
for spec in "$@"; do
  IFS=: read -r side wl seed trace <<< "$spec"
  dir=$root; [ "$side" = P ] && dir=$root/_parent
  log=$out/${tag}_${side}_${wl}_${seed}_t${trace}.log
  ( cd "$dir" && python3 benchmark/run.py --workload "$wl" --seed "$seed" --seconds 20 --trace "$trace" ) > "$log" 2>&1
  echo "== $side $wl seed=$seed trace=$trace rc=$?"
  grep -a "^\[gaps\]" "$log" | tail -3 | cut -c1-600
  tail -n 1 "$log" | cut -c1-6000
done
