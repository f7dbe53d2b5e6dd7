#!/bin/bash
# Run one cell several times on the machine this is started on and keep every
# last line: usage  chip_runs.sh <cell> <seconds> <trace:0|1> <seed> [<seed> ...]
# Results go to chiprun_out/runs/<cell>.jsonl (one line per run, with the seed),
# stderr tails to chiprun_out/runs/<cell>.s<seed>.t<trace>.err
cell=$1; secs=$2; trace=$3; shift 3
mkdir -p chiprun_out/runs
for seed in "$@"; do
  t0=$(date +%s.%N)
  python3 benchmarks/run.py --workload "$cell" --seed "$seed" --seconds "$secs" --trace "$trace" \
      > chiprun_out/runs/last.out 2> chiprun_out/runs/last.err
  rc=$?
  t1=$(date +%s.%N)
  tail -c 30000 chiprun_out/runs/last.err > "chiprun_out/runs/$cell.s$seed.t$trace.err"
  line=$(tail -n 1 chiprun_out/runs/last.out)
  echo "{\"cell\":\"$cell\",\"seed\":$seed,\"trace\":$trace,\"rc\":$rc,\"wall_s\":$(awk "BEGIN{print $t1 - $t0}"),\"line\":${line:-null}}" \
      | tee -a "chiprun_out/runs/$cell.jsonl" | cut -c1-1200
  cp benchmarks/out/*.trace-summary.json benchmarks/out/*.metrics-after.json benchmarks/out/*.requests.jsonl chiprun_out/runs/ 2>/dev/null
done
