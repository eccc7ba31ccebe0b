#!/bin/bash
# By hand, on the chip: the two full sets of a cell (6 seeds, the same in both
# sets, every run its own process), then three more seeds with --trace 1.
#   bash benchmark/tests/full_sets.sh <workload> <seconds> <first seed> [extra run.py args]
# NO_TRACED=1 leaves the three traced runs out.
# Results go to chiprun_out/<workload>.<set>.<seed>.out; read them with
#   python benchmark/tests/spread.py chiprun_out <workload>
w=$1; secs=$2; s0=$3; shift 3
mkdir -p chiprun_out
for set in A B; do
  for i in 0 1 2 3 4 5; do
    seed=$((s0 + i * 1000003))
    python3 benchmark/run.py --workload $w --seed $seed --seconds $secs --trace 0 "$@" \
      > chiprun_out/$w.$set.$seed.out 2> chiprun_out/$w.$set.$seed.err
    echo "$w set $set seed $seed rc=$?"
  done
done
[ -n "$NO_TRACED" ] && exit 0
for i in 6 7 8; do
  seed=$((s0 + i * 1000003))
  python3 benchmark/run.py --workload $w --seed $seed --seconds $secs --trace 1 --with-control "$@" \
    > chiprun_out/$w.T.$seed.out 2> chiprun_out/$w.T.$seed.err
  echo "$w traced seed $seed rc=$?"
done
