# The two full sets of a cell, as the bound's rule asks: 6 runs each, the same
# seeds in both, every run a new process; then traced runs on three more.
#   bash benchmark/proof/sets.sh <cell> <seconds> <first seed> [traced runs]
cd "$(dirname "$0")/../.."
mkdir -p chiprun_out
W=$1; T=$2; S0=$3; N=${4:-3}
for set in 1 2; do
  for i in 0 1 2 3 4 5; do
    python3 benchmark/run.py --workload $W --seed $((S0 + 104729 * i)) --seconds $T --trace 0 \
      > chiprun_out/last_run.out 2> chiprun_out/last_run.err
    echo "set $set seed $((S0 + 104729 * i)) rc=$?"
    grep -E "^(batch_ms|generator_lateness|setup_s=|compared [0-9])" chiprun_out/last_run.out | cut -c1-300
    tail -1 chiprun_out/last_run.out >> chiprun_out/set${set}_$W.jsonl
  done
done
for i in $(seq 1 $N); do
  python3 benchmark/run.py --workload $W --seed $((S0 + 7 + 15485863 * i)) --seconds $T --trace 1 \
    > chiprun_out/last_run.out 2> chiprun_out/last_run.err
  echo "traced seed $((S0 + 7 + 15485863 * i)) rc=$?"
  tail -1 chiprun_out/last_run.out >> chiprun_out/traced_$W.jsonl
done
python3 benchmark/proof/spread.py chiprun_out/set1_$W.jsonl chiprun_out/set2_$W.jsonl
cut -c1-1500 chiprun_out/traced_$W.jsonl
