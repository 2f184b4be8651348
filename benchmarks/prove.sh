#!/bin/sh
# N runs of one cell as N processes in ONE chip call, sharing the compile
# cache; every run's last line is kept.
#
#   benchmarks/prove.sh <cell> <seconds> "<seeds>" [sets] ["<trace seeds>"]
#
# Makes <sets> sets (default 1) of one --trace 0 run per seed, the same
# seeds in each set, then one --trace 1 run per trace seed. Each run's
# last stdout line goes to $OUT/<cell>.set<k>.seed<seed>.json (traced:
# .trace.seed<seed>.json) and the end of its stderr to the .err beside it.
# OUT defaults to chiprun_out/prove, the directory the chip tool brings back.
# Then benchmarks/summarize.py prints medians and spreads.
set -u
cell=$1; seconds=$2; seeds=$3; sets=${4:-1}; tseeds=${5:-}
out=${OUT:-chiprun_out/prove}
mkdir -p "$out"
cd "$(dirname "$0")/.."
one() { # name seed trace
  python3 benchmarks/run.py --workload "$cell" --seed "$2" --seconds "$seconds" \
    --trace "$3" > "$out/$1.out" 2> "$out/$1.log"
  rc=$?
  tail -n 1 "$out/$1.out" > "$out/$1.json"
  tail -c 6000 "$out/$1.log" > "$out/$1.err"
  rm -f "$out/$1.out" "$out/$1.log"
  echo "$1 rc=$rc $(cut -c1-400 "$out/$1.json")"
}
k=1
while [ "$k" -le "$sets" ]; do
  for s in $seeds; do one "$cell.set$k.seed$s" "$s" 0; done
  k=$((k + 1))
done
for s in $tseeds; do one "$cell.trace.seed$s" "$s" 1; done
python3 benchmarks/summarize.py "$out" "$cell"
