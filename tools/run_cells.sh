#!/bin/bash
# Benchmark cells of the parent and of this tree in one chip call, in the
# order given, so that both sides are measured on the same chip:
#   git archive HEAD | tar -x -C _parent        (both git-ignored)
#   chiprun --timeout 3000 -- bash tools/run_cells.sh <tag> <side:workload:seed:trace[:keep]> ...
# side P runs in _parent/, C in this tree, O in _parent_ov/ (the parent with
# this tree's BENCHMARK.json and benchmark/ laid over it, as the driver runs
# a PR's new metrics on the parent; git-ignored too). Each run's whole output
# goes to chiprun_out/cells/<tag>_<side>_<workload>_<seed>_t<trace>.log; its
# [gaps] and [timers] lines and its result line are echoed. A fifth field
# keeps the traced run's .xplane.pb just long enough for tools/host_gaps.py
# to read it into the log's .gaps.txt. CELL_TIMEOUT=<seconds> in the
# environment ends a run that hangs (a parent tried on a cell it cannot run).
# A parent that cannot run a new cell is shown to fail cleanly by side O:
# O:dlrm1tb.train:<seed>:0 on PR 49's parent exits 1 in under a second of its
# own clock (the app finds no models.dlrm), with no table made.
tag=$1; shift
root=$(cd "$(dirname "$0")/.." && pwd)
out=$root/chiprun_out/cells; mkdir -p "$out"
for spec in "$@"; do
  IFS=: read -r side wl seed trace keep <<< "$spec"
  dir=$root; [ "$side" = P ] && dir=$root/_parent; [ "$side" = O ] && dir=$root/_parent_ov
  log=$out/${tag}_${side}_${wl}_${seed}_t${trace}.log
  ( cd "$dir" && ${CELL_TIMEOUT:+timeout $CELL_TIMEOUT} python3 benchmark/run.py --workload "$wl" --seed "$seed" --seconds 20 --trace "$trace" ${keep:+--keep-trace} ) > "$log" 2>&1
  echo "== $side $wl seed=$seed trace=$trace rc=$?"
  grep -a "^\[gaps\]\|^\[timers\]" "$log" | tail -4 | cut -c1-600
  tail -n 1 "$log" | cut -c1-6000
  if [ -n "$keep" ]; then
    JAX_PLATFORMS=cpu python3 "$root/tools/host_gaps.py" "$dir/.bench_work/$wl/trace" > "${log%.log}.gaps.txt" 2>&1
    rm -rf "$dir/.bench_work/$wl/trace"
  fi
done
